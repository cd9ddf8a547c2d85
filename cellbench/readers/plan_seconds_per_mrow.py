"""Window delta of one seconds counter of ``DeviceIter.stats()["plan"]``
(what serving in the epoch plan's order cost, PR 45) per million rows
dispatched. No value where the program has no such entry (a parent
commit) or no plan is armed."""


def read(ctx, params):
    start = (ctx.stats_start or {}).get("plan") or {}
    stop = (ctx.stats_end or {}).get("plan") or {}
    key = params["counter"]
    if not ctx.rows_dispatched or key not in start or key not in stop:
        return None
    return (stop[key] - start[key]) / (ctx.rows_dispatched / 1e6)
