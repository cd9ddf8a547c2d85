"""Cold feed: the text is split, parsed and converted in every epoch."""

from __future__ import annotations


def open_feed(uri: str, work_dir: str, iter_kwargs: dict, params: dict):
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    return DeviceIter(create_parser(uri), **iter_kwargs)


def served(before: dict, after: dict) -> list:
    """Reasons why the window was not served by this tier (none: it was)."""
    bad = []
    if after["cache_state"] is not None or after["snapshot_state"] is not None:
        bad.append(f"a warm tier is armed: cache={after['cache_state']} "
                   f"snapshot={after['snapshot_state']}")
    for stage in ("parse", "convert"):
        if after["stage_busy"][stage] - before["stage_busy"][stage] <= 0:
            bad.append(f"no {stage} work in the window")
    return bad
