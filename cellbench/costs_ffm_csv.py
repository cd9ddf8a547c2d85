"""Bytes one exact AdaGrad step of the field-aware factorization machine
has to move when its batch is a table of id columns (PR 48, configuration
``kdd12_ffm_csv``), from its shapes alone. Kept with the benchmark, beside
``costs_ffm.py``, whose count is of an ELL batch: called with this
configuration it would reckon 4 + 4 + 1 bytes a slot of batch (an index, a
value and a field byte) where the columns put 4 (the id; the field is the
column's position and the value is 1, neither crosses)."""

from __future__ import annotations


def ffm_csv_adagrad_step_min_bytes(num_fields: int, num_factors: int,
                                   batch_size: int, columns: int) -> int:
    """HBM bytes one exact AdaGrad step has to move, whatever the program
    does: per slot (``batch_size * columns`` of them, none padding) the six
    table rows of ``costs_ffm.ffm_adagrad_step_min_bytes`` (one gathered,
    one gradient row written, the rows of ``W`` and ``G`` read and
    written), each ``num_fields * num_factors`` float32; and the batch read
    once: an int32 id a slot, a float32 label and weight a row."""
    slots = batch_size * columns
    rows = 6 * slots * num_fields * num_factors * 4
    batch = slots * 4 + batch_size * 8
    return rows + batch
