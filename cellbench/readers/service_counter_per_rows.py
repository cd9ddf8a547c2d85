"""Window delta of one counter of ``DeviceIter.stats()["service"]`` (the
service client's books of the wire, and what the feed adds of the fleet's)
per ``params["per_rows"]`` rows dispatched (1 where the file gives none).
No value where the iterator's source is no service client, or the program
predates the entry or the counter."""


def read(ctx, params):
    start = (ctx.stats_start or {}).get("service") or {}
    end = (ctx.stats_end or {}).get("service") or {}
    key = params["counter"]
    if not ctx.rows_dispatched or key not in start or key not in end:
        return None
    return (end[key] - start[key]) / (
        ctx.rows_dispatched / params.get("per_rows", 1))
