"""Busy seconds of the named pipeline stages (``DeviceIter.stats()
['stage_busy']``, window delta) per million rows dispatched."""


def read(ctx, params):
    if not ctx.rows_dispatched:
        return None
    busy = sum(ctx.stats_end["stage_busy"][s] - ctx.stats_start["stage_busy"][s]
               for s in params["stages"])
    return busy / (ctx.rows_dispatched / 1e6)
