"""What XLA's compile of the learner's step says it holds a chip (PR 50),
in GB: ``learner.step_memory()[params["kind"]]``, kept from the compile
``hlo_scopes()`` makes for the scope metrics (``temp``: the step's
temporaries, which ``memory_peak_bytes`` cannot tell from the tables). No
value where the learner keeps none (a parent commit)."""

import json

from cellbench.readers import _program as P


def read(ctx, params):
    sizes = getattr(getattr(ctx.adapter, "learner", None), "step_memory",
                    None)
    if sizes is None:
        return None
    if "step_memory" not in P._cache:
        P.counters_at_first_read()      # before hlo_scopes() compiles
        P._cache["step_memory"] = sizes()
        P.log("step memory a chip, by the compiler (bytes): "
              + json.dumps(P._cache["step_memory"]))
    value = P._cache["step_memory"].get(params["kind"])
    return None if value is None else value * 1e-9
