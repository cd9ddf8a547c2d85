"""Block-cache feed in the epoch plan's order (PR 45): one untimed pass
writes the parse-once cache, and every epoch after it serves the cached
blocks in a fresh seeded order, each block's rows in a fresh order too.

The path is the documented one: ``create_parser(uri, block_cache=path,
shuffle_seed=S, shuffle_window=N)`` under ``DeviceIter``, everything else
on defaults. The plan's own cold pass (epoch 0) is sequential while it
writes the cache and trains nothing here (``_warm.build_pass``), so the
first epoch the harness steps, times and compares is epoch 1, a planned
one. ``S`` and ``N`` come from the learner plugin, under the key ``plan``
of its ``device_iter_kwargs()``; that dict is taken out before
``DeviceIter`` is built and is handed the iterator opened (``opened``).
"""

from __future__ import annotations

import os

from cellbench.feeds import _warm, block_cache


def open_feed(uri: str, work_dir: str, iter_kwargs: dict, params: dict):
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    kwargs = dict(iter_kwargs)
    if "plan" not in kwargs:
        raise ValueError("feed block_cache_plan: the learner plugin's "
                         "device_iter_kwargs() names no 'plan' "
                         "(shuffle_seed, shuffle_window)")
    plan = kwargs.pop("plan")
    path = os.path.join(work_dir, "tier.blockcache")
    it = DeviceIter(create_parser(uri, block_cache=path,
                                  shuffle_seed=plan["shuffle_seed"],
                                  shuffle_window=plan["shuffle_window"]),
                    **kwargs)
    _warm.build_pass(it)
    plan["opened"] = it
    return it


def served(before: dict, after: dict) -> list:
    if "plan" not in after:
        # a program from before the plan kept books (the parent of PR 45):
        # what it does say is whether a seed is armed
        bad = block_cache.served(before, after)
        if after.get("shuffle_seed") is None:
            bad.append("no shuffle_seed is armed")
        return bad
    bad = []
    if after["cache_state"] != "warm":
        bad.append(f"cache_state is {after['cache_state']!r}, not 'warm'")
    was, now = before.get("plan"), after["plan"]
    if not was or not now:
        return bad + ["the program reports no plan (stats()['plan'])"]
    if now["order"] != "plan":
        bad.append(f"the epoch is served in {now['order']!r} order")
    blocks = now["blocks"] - was["blocks"]
    rows = now["rows_permuted"] - was["rows_permuted"]
    if blocks <= 0 or rows <= 0:
        bad.append(f"{blocks} blocks served in plan order, {rows} rows "
                   "through the row permutation")
    # under a plan the reads and gathers run on the pool's threads, and
    # DeviceIter books the source's cache_read seconds only while its own
    # pull is blocked: a short window whose blocks were all read ahead has
    # none there, so the pool's own seconds count as the tier's work too
    work = (after["stage_busy"]["cache_read"]
            - before["stage_busy"]["cache_read"]
            + now["permute_seconds"] - was["permute_seconds"])
    if work <= 0:
        bad.append("no cache_read work in the window")
    return bad
