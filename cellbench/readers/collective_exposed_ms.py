"""Milliseconds per execution of the jitted step in which a collective ran
on a chip and no other operation did, from the device plane of the
profiler trace."""


def read(ctx, params):
    if ctx.trace is None or ctx.trace["step"] is None:
        return None
    if ctx.trace["step"]["collective_s_per_execution"] <= 0:
        return None
    return 1e3 * ctx.trace["step"]["collective_exposed_s_per_execution"]
