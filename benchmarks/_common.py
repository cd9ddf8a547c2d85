"""Shared helpers for the piece benches (compile cache + timing + output)."""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TARGET_MB = float(os.environ.get("DMLC_BENCH_MB", "64"))
REPS = 3


# every benchmark script imports this module first: the one place that
# turns the persistent compile cache on for all of them (a no-op for a run
# pinned to the CPU backend — dmlc_tpu/utils/compile_cache.py)
from dmlc_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(metric: str, value: float, unit: str, baseline: float, **extra) -> None:
    """The ONE stdout JSON line (extra keys allowed after the required
    four, e.g. a secondary ratio)."""
    line = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / baseline, 3) if baseline else 0.0,
    }
    line.update({k: round(v, 3) if isinstance(v, float) else v
                 for k, v in extra.items()})
    print(json.dumps(line))


def timed_stats(fn, reps: int = REPS):
    """Time ``fn`` reps times -> (best, median, times).

    Ambient throughput on this shared host swings 2-4x run-to-run: best-of
    guards against infra slowness, but a single lucky rep can overstate
    steady state by the same factor — benchmarks report BOTH."""
    from statistics import median

    times = []
    for _ in range(reps):
        t0 = time.monotonic()
        fn()
        times.append(time.monotonic() - t0)
    return min(times), median(times), times


def traced_steps(model, feed, steps: int, pieces, **tag):
    """``steps`` of ``model.step`` over ``feed`` (in turn) under the
    profiler, for the one-chip benches of a learner's whole step: prints one
    JSON line (with ``tag``) per operation of ``jit_step`` that takes 0.1 ms
    a step or more, largest first, and returns ``(found, by_scope)``:
    ``cellbench.trace_reduce``'s reduction and the ms a step under each
    scope name of ``pieces`` (by ``model.hlo_scopes()``)."""
    import tempfile

    import jax

    from cellbench import trace_reduce

    trace_dir = tempfile.mkdtemp(prefix="traced_steps_",
                                 dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(trace_dir)
    for i in range(steps):
        loss = model.step(feed[i % len(feed)])
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    found = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir),
                                      "^jit_step$", top=1 << 16)
    scopes = model.hlo_scopes()
    by_scope = dict.fromkeys(pieces, 0.0)
    for name, seconds in found["device_ops"]:
        scope, ms = scopes.get(name.split(" ")[0], ""), seconds / steps * 1e3
        # (a conditional's own event spans the operations of its road)
        if not name.endswith(" conditional"):
            for piece in by_scope:
                if piece in scope:
                    by_scope[piece] += ms
        if ms >= 0.1:
            print(json.dumps({**tag, "op": name, "ms_a_step": round(ms, 3),
                              "scope": scope[-96:]}), flush=True)
    return found, {k: round(v, 3) for k, v in by_scope.items()}
