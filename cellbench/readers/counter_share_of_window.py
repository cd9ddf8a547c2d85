"""Window delta of one ``DeviceIter.stats()`` seconds counter as a
percentage of the window."""


def read(ctx, params):
    key = params["counter"]
    return 100.0 * (ctx.stats_end[key] - ctx.stats_start[key]) / ctx.seconds  # the counters' deltas run to the deadline
