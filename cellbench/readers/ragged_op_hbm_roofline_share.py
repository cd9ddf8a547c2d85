"""``op_hbm_roofline_share`` for a ragged step: the least time the chip's
memory system could take for one named kernel of the step (the bytes from
the function of ``cellbench/costs_fm_ragged.py`` that ``params["bytes"]``
names, at the window's mean real slots a batch) as a percentage of that
kernel's measured device time a step. The kernel is found by its HLO
instruction name (``params["op"]``), its time is the union of its
intervals inside the counted executions of the step. No value with no
trace, no such operation, or no ragged books. Bound: HBM bytes."""

import re

from cellbench import costs_fm_ragged
from cellbench import trace_reduce as T
from cellbench.readers import _program as P
from cellbench.readers import _ragged


def read(ctx, params):
    path = P.find_trace(ctx)
    sizes = _ragged.sizes(ctx)
    if not path or ctx.peaks is None or sizes is None:
        return None
    want = re.compile(params["op"])
    ns = n = 0
    for dev in P.loaded(path)["devices"].values():
        _, execs = P.step_executions(dev, ctx.adapter.config["step_module"])
        if not execs:
            continue
        n += len(execs)
        ns += T.length(P.within(
            [(a, b) for name, a, b in dev["ops"]
             if want.search(name.split(" ")[0])], T.merge(execs)))
    if not n or not ns:
        return None
    least = getattr(costs_fm_ragged, params["bytes"])(*sizes) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ns / n * 1e-9)
