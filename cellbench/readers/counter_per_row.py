"""Window delta of one ``DeviceIter.stats()`` counter per row dispatched."""


def read(ctx, params):
    if not ctx.rows_dispatched:
        return None
    key = params["counter"]
    return (ctx.stats_end[key] - ctx.stats_start[key]) / ctx.rows_dispatched
