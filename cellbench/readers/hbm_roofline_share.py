"""The least time the chip's memory system could take for one step (the
bytes the algorithm needs, from the adapter's count in
``cellbench/costs.py``, over the peak HBM bandwidth in ``peaks.json``) as
a percentage of the step's measured device time. Bound: HBM bytes."""


def read(ctx, params):
    if ctx.trace is None or ctx.trace["step"] is None:
        return None
    least = ctx.adapter.step_min_bytes() / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / ctx.trace["step"]["device_s_per_execution"]
