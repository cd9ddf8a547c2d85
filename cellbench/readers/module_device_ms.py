"""Mean device-busy milliseconds per execution of the jitted step's
module, from the device plane of the profiler trace."""


def read(ctx, params):
    if ctx.trace is None or ctx.trace["step"] is None:
        return None
    return 1e3 * ctx.trace["step"]["device_s_per_execution"]
