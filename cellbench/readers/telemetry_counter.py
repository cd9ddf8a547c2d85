"""One of the program's process-wide counters
(``dmlc_tpu.utils.telemetry.compile_counters()``), as it stood at the end
of the window: ``jit_compile_s`` reads ``jit_compile_seconds``, the
seconds the process spent in XLA backend compiles, persistent-cache
retrievals included."""

from cellbench.readers import _program as P


def read(ctx, params):
    counters = P.counters_at_first_read()
    if counters is None:
        return None
    return counters.get(params["counter"])
