"""Block-cache-warm feed: epochs serve the parsed blocks back from the
parse-once cache, so split and parse rest and convert still works."""

from __future__ import annotations

import os

from cellbench.feeds import _warm


def open_feed(uri: str, work_dir: str, iter_kwargs: dict, params: dict):
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    path = os.path.join(work_dir, "tier.blockcache")
    it = DeviceIter(create_parser(uri, block_cache=path), **iter_kwargs)
    _warm.build_pass(it)
    return it


def served(before: dict, after: dict) -> list:
    bad = []
    if after["cache_state"] != "warm":
        bad.append(f"cache_state is {after['cache_state']!r}, not 'warm'")
    if after["stage_busy"]["cache_read"] - before["stage_busy"]["cache_read"] <= 0:
        bad.append("no cache_read work in the window")
    # no check on 'parse': DeviceIter books whatever is left of a supply
    # wait under that name, warm or cold, so it is never quite zero
    return bad
