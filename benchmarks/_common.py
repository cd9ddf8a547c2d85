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
