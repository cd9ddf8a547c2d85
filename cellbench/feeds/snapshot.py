"""Snapshot-warm feed: epochs stream the stored device-layout batches
(``DMLCSN01``), with no parse and no convert work."""

from __future__ import annotations

import os

from cellbench.feeds import _warm


def open_feed(uri: str, work_dir: str, iter_kwargs: dict, params: dict):
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    path = os.path.join(work_dir, "tier.snapshot")
    it = DeviceIter(create_parser(uri, snapshot=path), **iter_kwargs)
    _warm.build_pass(it)
    return it


def served(before: dict, after: dict) -> list:
    bad = []
    if after["snapshot_state"] != "warm":
        bad.append(f"snapshot_state is {after['snapshot_state']!r}, not 'warm'")
    if after["stage_busy"]["snapshot_read"] - before["stage_busy"]["snapshot_read"] <= 0:
        bad.append("no snapshot_read work in the window")
    for stage in ("read", "parse", "convert"):
        busy = after["stage_busy"][stage] - before["stage_busy"][stage]
        if busy > 0:
            bad.append(f"{stage} was busy {busy:.3f} s in a window the "
                       "snapshot should serve alone")
    return bad
