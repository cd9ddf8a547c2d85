"""Window delta of one state of the convert pool's workers
(``params["state"]``, a seconds counter of ``DeviceIter.stats()["pool"]``)
as a percentage of their seconds in all of ``params["of"]``: with
``window_wait_seconds`` of the four states that partition the workers'
wall time, the share of it they spent shut out by the ``convert_ahead``
window, waiting for the consumer. The workers' seconds in all four states
against ``convert_workers`` times the window go on an earlier line (a
pool's workers live from the epoch's first pull to the end of its source).
No value where the program has no such entry (a parent commit) or no pool
worked in the window."""

from cellbench.readers import _program as P


def read(ctx, params):
    start_all, stop_all = ctx.stats_start or {}, ctx.stats_end or {}
    start, stop = start_all.get("pool") or {}, stop_all.get("pool") or {}
    states = params["of"]
    if any(s not in start or s not in stop for s in states):
        return None
    delta = {s: stop[s] - start[s] for s in states}
    also = {k: stop.get(k, 0) - start.get(k, 0)
            for k in ("ready_wait_seconds", "items", "ring_misses")}
    P.log("convert pool workers' seconds in the window: " + ", ".join(
        f"{s[:-len('_seconds')]} {v:.4f}" for s, v in delta.items())
        + f"; finished batches lay {also['ready_wait_seconds']:.4f} s over "
        f"{also['items']} items; staging ring misses {also['ring_misses']}")
    total = sum(delta.values())
    workers = stop_all.get("convert_workers")
    span = (stop_all.get("now") or 0) - (start_all.get("now") or 0)
    if workers and span > 0:
        # a pool's workers leave when their epoch's source ends: where the
        # consumer runs far ahead of the device that is long before the
        # epoch does, and the feed's headroom shows here, not in the share
        P.log(f"the four states hold {total:.4f} s, "
              f"{100.0 * total / (workers * span):.1f}% of {workers} workers "
              f"x {span:.3f} s between the window's two stats()")
    return 100.0 * delta[params["state"]] / total if total > 0 else None
