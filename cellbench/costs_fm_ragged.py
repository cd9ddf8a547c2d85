"""Bytes the factorization machine's algorithms need on ragged batches,
from their shapes and the batch's real slot count alone (PR 37). Kept with
the benchmark, beside ``costs.py``, so that no PR that claims a gain can
change the yardstick. ``slots`` is the number of non-zeros a step holds,
not what the program pads it to."""

from __future__ import annotations


def fm_ragged_adam_step_min_bytes(table_rows: int, num_factors: int,
                                  batch_size: int, slots: float) -> float:
    """HBM bytes one exact dense-Adam step of a factorization machine has
    to move on a batch of ``slots`` non-zeros in ``batch_size`` rows,
    whatever the program does: ``costs.fm_adam_step_min_bytes`` with the
    real slots in the place of ``batch * max_nnz``. Six float32 passes over
    the ``table_rows * (num_factors + 1) + 1`` coordinates; the batch read
    once (int32 id, float32 value and int32 row a slot, label and weight a
    row); the touched rows gathered and their gradient rows scattered."""
    coords = table_rows * (num_factors + 1) + 1
    tables = 6 * 4 * coords
    batch = slots * 12 + batch_size * 8
    rows = 2 * slots * (num_factors + 1) * 4
    return tables + batch + rows


def table_gather_kernel_bytes(table_rows: int, num_factors: int,
                              batch_size: int, slots: float) -> float:
    """HBM bytes the ``table_gather`` kernel has to move for one step: one
    read of the float32 tables (``num_factors + 1`` columns), one read of
    the sorted int32 ids and one write of the sorted rows, the columns
    padded to the 16 rows of the kernel's output tile."""
    width = num_factors + 1
    return table_rows * width * 4 + slots * 4 + (-(-width // 16) * 16) \
        * slots * 4


def grad_scatter_adam_kernel_bytes(table_rows: int, num_factors: int,
                                   batch_size: int, slots: float) -> float:
    """HBM bytes the ``grad_scatter_adam`` kernel has to move for one
    step: the parameters and both moments of every table read once and
    written once (six float32 passes over ``num_factors + 1`` columns),
    and one read of the sorted slots: their int32 ids and the payload,
    three bfloat16 parts of every column, padded to 16 rows."""
    width = num_factors + 1
    payload = 3 * (-(-width // 16) * 16) * slots * 2
    return 6 * table_rows * width * 4 + payload + slots * 4
