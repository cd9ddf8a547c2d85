"""The least time the chip's memory system could take for one named
operation of the step (the bytes it has to move, from the function of
``cellbench/costs_ffm.py`` that ``params["bytes"]`` names, called with the
configuration's sizes ``params["sizes"]``, over the peak HBM bandwidth in
``peaks.json``) as a percentage of that operation's measured device time a
step. The operation is found in the device plane of the trace by its HLO
instruction name (``params["op"]``, a pattern: a Pallas kernel keeps the
name its ``pallas_call`` gave it whatever XLA numbers around it); its time
is the union of its intervals inside the counted executions of the step
(the rule of ``step_device_ms``), mean over chips. No value where there is
no trace, or the step ran no such operation (the gradient's scatter on
XLA's own route). Bound: HBM bytes."""

import re

from cellbench import costs_ffm
from cellbench import trace_reduce as T
from cellbench.readers import _program as P


def read(ctx, params):
    path = P.find_trace(ctx)
    if not path or ctx.peaks is None:
        return None
    want = re.compile(params["op"])
    config = ctx.adapter.config
    ns = n = 0
    for dev in P.loaded(path)["devices"].values():
        _, execs = P.step_executions(dev, config["step_module"])
        if not execs:
            continue
        n += len(execs)
        ns += T.length(P.within(
            [(a, b) for name, a, b in dev["ops"]
             if want.search(name.split(" ")[0])], T.merge(execs)))
    if not n or not ns:
        return None
    least = getattr(costs_ffm, params["bytes"])(
        *(config[k] for k in params["sizes"])) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ns / n * 1e-9)
