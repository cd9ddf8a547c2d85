"""Bytes one chip of the dealt field-aware factorization machine has to
move (PR 32, configuration ``kdd12_ffm_ps4``), from its shapes alone.
Kept with the benchmark, beside ``costs_ffm.py``, whose counts are of an
undivided table: called with this configuration's ``num_features`` they
would reckon the whole table's 9.6 GB gradient for a kernel that writes a
quarter of it, and a share of the roofline over 100%."""

from __future__ import annotations

from cellbench.costs_ffm import ffm_adagrad_step_min_bytes


def ffm_ps_chip_step_min_bytes(num_fields: int, num_factors: int,
                               batch_size: int, max_nnz: int,
                               shards: int) -> int:
    """HBM bytes one exact AdaGrad step has to move on **one** of
    ``shards`` chips that share the table by rows and the batch by rows:
    ``ffm_adagrad_step_min_bytes`` of the chip's ``batch_size / shards``
    rows. A chip reads its part of the batch, and of the batch's
    ``batch_size * max_nnz`` slots it owns one in ``shards`` on average
    (the cyclic deal; ``table_shard_slot_skew`` says how evenly): for
    those it reads the table row, takes the gradient row, and reads and
    writes the row of ``W`` and of ``G``. What crosses the chips (slot
    ids, rows, cotangent rows) is interconnect traffic and is not
    counted, nor is the landing of it in HBM: the share of the roofline
    can only be lower for that."""
    return ffm_adagrad_step_min_bytes(num_fields, num_factors,
                                      batch_size // shards, max_nnz)
