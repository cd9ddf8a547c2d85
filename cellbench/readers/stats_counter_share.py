"""Window delta of one ``DeviceIter.stats()`` counter as a share of the
window delta of another: ``params["counter"]`` over ``params["of"]``, each a
key of the stats or a path of keys into them (``["csv_cells", "hashed"]``).
No value where the program keeps no such counter (a parent commit) or the
window moved the denominator by nothing."""


def _at(stats, path):
    for key in [path] if isinstance(path, str) else path:
        if not isinstance(stats, dict) or key not in stats:
            return None
        stats = stats[key]
    return stats


def read(ctx, params):
    (a0, a1), (b0, b1) = (
        [_at(stats, params[key]) for stats in (ctx.stats_start, ctx.stats_end)]
        for key in ("counter", "of"))
    if a1 is None or b1 is None:
        return None
    # a label the window first counted under was 0 at its start
    a0, b0 = a0 or 0, b0 or 0
    return (a1 - a0) / (b1 - b0) if b1 > b0 else None
