"""Shared helpers for the benchmark suite (corpus synth + timing + output)."""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CACHE_DIR = os.path.join(REPO, ".bench_cache")
TARGET_MB = float(os.environ.get("DMLC_BENCH_MB", "64"))  # = bench.py
REPS = 3


# every benchmark script imports this module first: the one place that
# turns the persistent compile cache on for all of them (a no-op for a run
# pinned to the CPU backend — dmlc_tpu/utils/compile_cache.py)
from dmlc_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# canonical stage order for the ingest attribution table (it names the
# unaccounted share of pipeline bound, per stage).
# snapshot_read = warm device-native snapshot supply (mmap + crc of
# post-convert batches, docs/data.md snapshot section); device_decode =
# on-device span decode dispatch (docs/data.md three-tier decode table)
STAGE_ORDER = ("read", "cache_read", "snapshot_read", "parse", "convert",
               "dispatch", "device_decode", "transfer")


def attribution_line(stats: dict, extra_transfer: float = 0.0) -> dict:
    """DeviceIter.stats() -> the JSON ``attribution`` object.

    ``extra_transfer`` folds a caller-measured transfer residue (e.g.
    bench.py's final block_until_ready drain) into the transfer stage and
    the wall, so the table accounts for the async blind spot end to end.
    ``coverage`` is sum(stages)/wall — the fraction of wall the named
    stages explain (the rest is consumer self-time).
    """
    stages = dict(stats.get("stages") or {})
    stages["transfer"] = stages.get("transfer", 0.0) + extra_transfer
    wall = float(stats.get("wall_seconds") or 0.0) + extra_transfer
    out = {k: round(stages.get(k, 0.0), 4) for k in STAGE_ORDER}
    out["wall"] = round(wall, 4)
    covered = sum(stages.get(k, 0.0) for k in STAGE_ORDER)
    out["coverage"] = round(covered / wall, 3) if wall > 0 else 0.0
    return out


def attribution_table(attribution: dict) -> str:
    """Render the attribution object as the human-readable stderr table."""
    from dmlc_tpu.utils.timer import format_stage_table

    stages = {k: attribution.get(k, 0.0) for k in STAGE_ORDER}
    return format_stage_table(stages, attribution.get("wall", 0.0),
                              order=STAGE_ORDER)


def emit(metric: str, value: float, unit: str, baseline: float, **extra) -> None:
    """The ONE stdout JSON line, same schema as bench.py (extra keys allowed
    after the required four, e.g. a secondary ratio)."""
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


def rotated_times(fns, rounds: int = REPS):
    """Time N legs back-to-back per round with ROTATING order.

    Host speed drifts a few percent over seconds on this shared machine
    and a fixed order would bias whichever leg runs later — rotation
    cancels both. Returns one time-list per leg, aligned by round, for
    the caller's statistic of choice (min, median of ratios, ...)."""
    sinks = [[] for _ in fns]
    legs = list(zip(fns, sinks))
    for i in range(rounds):
        k = i % len(legs)
        for fn, out in legs[k:] + legs[:k]:
            t0 = time.monotonic()
            fn()
            out.append(time.monotonic() - t0)
    return sinks


def paired_times(fn_a, fn_b, pairs: int = REPS):
    """Two-leg form of :func:`rotated_times` (alternating order)."""
    times_a, times_b = rotated_times([fn_a, fn_b], rounds=pairs)
    return times_a, times_b


def synth_text(path: str, make_line, target_mb: float = TARGET_MB) -> str:
    """Write `make_line(i) -> str` rows until ~target_mb; cached on disk."""
    if os.path.exists(path) and os.path.getsize(path) >= target_mb * 0.95 * 2**20:
        return path
    os.makedirs(os.path.dirname(path), exist_ok=True)
    written, i = 0, 0
    with open(path, "w") as f:
        target = target_mb * 2**20
        while written < target:
            chunk = "".join(make_line(j) for j in range(i, i + 2000))
            f.write(chunk)
            written += len(chunk)
            i += 2000
    return path
