"""Bytes a checkpoint's device copy has to move, from shapes alone
(PR 41). Kept with the benchmark, beside ``costs_ffm.py``."""

from __future__ import annotations


def table_bytes_as_laid_out(rows: int, width: int) -> int:
    """Bytes of a ``[rows, width]`` float32 table as the chip holds it: a
    narrow table lies lane-major, its rows along the 128 lanes and its
    columns padded to the 8 sublanes of a float32 tile (44 columns take
    48: ``PERF.md`` section 4)."""
    return (-(-rows // 128) * 128) * (-(-width // 8) * 8) * 4


def ffm_snapshot_copy_bytes(num_features: int, num_fields: int,
                            num_factors: int) -> int:
    """HBM bytes the snapshot of a field-aware FM's state has to move:
    ``W`` and ``G``, each read once and written once, as laid out. A copy
    can do no less, so the share of the roofline cannot pass 100%."""
    table = table_bytes_as_laid_out(num_features + 1,
                                    num_fields * num_factors)
    return 2 * 2 * table


def ffm_checkpoint_payload_bytes(num_features: int, num_fields: int,
                                 num_factors: int) -> int:
    """Bytes of ``W`` and ``G`` in a checkpoint's file: whole rows in
    their logical columns, no padding."""
    return 2 * (num_features + 1) * num_fields * num_factors * 4
