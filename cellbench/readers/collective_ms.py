"""Milliseconds per execution of the jitted step in which an operation
that crosses the chips ran on a chip, exposed or not
(``allreduce_exposed_ms`` reads the exposed part of what
``trace_reduce.COLLECTIVE`` names), from the device plane of the profiler
trace; mean over chips. An operation counts if ``trace_reduce.COLLECTIVE``
names it (XLA's own names: ``all-gather.9``) or ``params["also"]`` matches
its instruction name (jax names an HLO all-to-all after its primitive,
``all_to_all.3``, which that pattern does not know). The time is the union
of those operations' intervals inside the counted executions (the rule of
``step_device_ms``). No value where there is no trace or the step ran no
such operation."""

import re

from cellbench import trace_reduce as T
from cellbench.readers import _program as P


def read(ctx, params):
    path = P.find_trace(ctx)
    if not path:
        return None
    also = re.compile(params["also"]) if params.get("also") else None
    config = ctx.adapter.config
    ns = n = 0
    for dev in P.loaded(path)["devices"].values():
        _, execs = P.step_executions(dev, config["step_module"])
        if not execs:
            continue
        n += len(execs)
        ns += T.length(P.within(
            [(a, b) for name, a, b in dev["ops"]
             if T.COLLECTIVE.match(name)
             or (also and also.search(name.split(" ")[0]))], T.merge(execs)))
    return ns / n * 1e-6 if n and ns else None
