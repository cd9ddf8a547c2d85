"""CPU seconds of the whole process (``time.process_time()``: every
thread of the feed, the runtime and the harness) per million rows
dispatched, from the window's start. A traced run reads it up to the
completion at which the profiler starts (half an epoch), so that the
profiler's own host work, which is several times a warm feed's, is not in
it; an untraced run up to the window's last completion."""


def read(ctx, params):
    if not ctx.host_cpu_rows:
        return None
    return ctx.host_cpu_s / (ctx.host_cpu_rows / 1e6)
