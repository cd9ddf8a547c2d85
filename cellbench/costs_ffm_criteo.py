"""Bytes the field-aware factorization machine's pair terms have to move,
from their shapes alone (PR 55, configuration ``criteo_ffm``). Kept with the
benchmark, beside ``costs_ffm.py``, so that no PR that claims a gain can
change the yardstick."""

from __future__ import annotations


def ffm_pair_kernels_bytes(num_fields: int, num_factors: int,
                           batch_size: int, max_nnz: int) -> int:
    """HBM bytes the pair terms of one step have to move, forward and
    backward, whatever implements them: the gathered rows ``wg`` (``m * k``
    float32 a slot) read once for the forward and once more for the
    backward, whose residual they are, and their cotangent ``d wg`` written
    once; the slots' field (one byte) and value (float32) read by each
    pass. The pair tensor lives on the chip and is not counted, nor are the
    lanes a row is padded to where it crosses as a line: a share that starts
    low and can only rise as the kernels improve."""
    slots = batch_size * max_nnz
    rows = 3 * slots * num_fields * num_factors * 4
    return rows + 2 * slots * (1 + 4)
