"""The device copy a save dispatches (the module ``params["module"]``
names: ``jit_ckpt_snapshot``), from the device plane of the trace: its
device milliseconds an execution, or with ``params["bytes"]`` the least
time the chip's memory system could take for it (the bytes that function
of ``cellbench/costs_ckpt.py`` counts from the configuration's sizes
``params["sizes"]``, over the peak HBM bandwidth in ``peaks.json``) as a
percentage of that time. The time is the union of the device's operations
inside the module's executions, by ``trace_reduce.reduce_trace``'s rule.
No value where there is no trace or the traced window held no save."""

import re

from cellbench import costs_ckpt


def read(ctx, params):
    if ctx.trace is None:
        return None
    want = re.compile(params["module"])
    found = [m for name, m in ctx.trace["modules"].items()
             if want.search(name)]
    if not found:
        return None
    seconds = sum(m["device_s_per_execution"] * m["executions_per_chip"]
                  for m in found) / sum(m["executions_per_chip"]
                                        for m in found)
    if "bytes" not in params:
        return 1e3 * seconds
    if ctx.peaks is None or not seconds:
        return None
    config = ctx.adapter.config
    least = getattr(costs_ckpt, params["bytes"])(
        *(config[k] for k in params["sizes"])) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
