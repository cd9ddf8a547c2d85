"""Bytes the field-aware factorization machine's algorithms need, from
their shapes alone (PR 26). Kept with the benchmark, beside ``costs.py``,
so that no PR that claims a gain can change the yardstick."""

from __future__ import annotations


def ffm_adagrad_step_min_bytes(num_fields: int, num_factors: int,
                               batch_size: int, max_nnz: int) -> int:
    """HBM bytes one exact AdaGrad step of an ELL field-aware
    factorization machine has to move, whatever the program does.

    Exact AdaGrad (accumulators with no decay) leaves a coordinate whose
    gradient is zero, and its accumulator, bit-identical, so only the rows
    the batch names have to move: per slot one table row of ``num_fields *
    num_factors`` float32 is gathered for the interaction, one gradient
    row is written, and the row of ``W`` and of ``G`` is read and written:
    six rows a slot. The batch is read once (int32 index, float32 value
    and one byte of field per slot, label and weight per row). A dense
    gradient and a sweep over the whole table are the program's choice and
    are not counted, so the share of the roofline starts low and can only
    rise as the step improves."""
    slots = batch_size * max_nnz
    rows = 6 * slots * num_fields * num_factors * 4
    batch = slots * (4 + 4 + 1) + batch_size * 8
    return rows + batch


def ffm_grad_scatter_kernel_bytes(num_features: int, num_fields: int,
                                  num_factors: int, batch_size: int,
                                  max_nnz: int) -> int:
    """HBM bytes the ``grad_scatter`` kernel has to move for one step: one
    write of the dense float32 gradient of the ``[num_features + 1,
    num_fields * num_factors]`` table, and one read of the sorted slots:
    their int32 ids and the payload, three bfloat16 parts of every column,
    the columns padded to the 16 rows of a bfloat16 tile."""
    width = num_fields * num_factors
    slots = batch_size * max_nnz
    gradient = (num_features + 1) * width * 4
    payload = 3 * (-(-width // 16) * 16) * slots * 2
    return gradient + payload + slots * 4
