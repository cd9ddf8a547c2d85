"""``hbm_roofline_share`` for a ragged step: the least time the chip's
memory system could take for one step (the bytes exact dense Adam needs at
the window's mean *real* slots a batch, from
``cellbench/costs_fm_ragged.py``, over the peak HBM bandwidth) as a
percentage of the step's measured device time. Bound: HBM bytes."""

from cellbench import costs_fm_ragged
from cellbench.readers import _ragged


def read(ctx, params):
    if ctx.trace is None or ctx.trace["step"] is None or ctx.peaks is None:
        return None
    sizes = _ragged.sizes(ctx)
    if sizes is None:
        return None
    least = costs_fm_ragged.fm_ragged_adam_step_min_bytes(*sizes) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / ctx.trace["step"]["device_s_per_execution"]
