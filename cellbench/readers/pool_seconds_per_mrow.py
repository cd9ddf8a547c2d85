"""Window delta of one seconds counter of ``DeviceIter.stats()["pool"]``
(what the convert pool's threads spent their time on, PR 35) per million
rows dispatched. No value where the program has no such entry (a parent
commit)."""


def read(ctx, params):
    start = (ctx.stats_start or {}).get("pool") or {}
    stop = (ctx.stats_end or {}).get("pool") or {}
    key = params["counter"]
    if not ctx.rows_dispatched or key not in start or key not in stop:
        return None
    return (stop[key] - start[key]) / (ctx.rows_dispatched / 1e6)
