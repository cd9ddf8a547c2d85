"""What the readers of the program's own tracing share (PR 24): the
profiler trace this process wrote, loaded once with the program's span
prefix, and the program's compilation counters as they stood when the
first of these readers ran.

``run.Observed`` carries neither the trace's path nor the window's start,
so the trace is found on disk: the newest ``*.xplane.pb`` under
``cellbench/.cache/work/*/trace`` written since this process started.
Everything here returns ``None`` where the program has nothing to read
(a parent commit without the spans, an untraced run): a reader then
leaves its metric out.
"""

from __future__ import annotations

import glob
import os
import re

from cellbench import trace_reduce as T

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_PREFIX = "dmlc_tpu:"
_cache: dict = {}


def log(msg: str) -> None:
    print(f"[cellbench] {msg}", flush=True)


def _process_started() -> float:
    try:
        return os.stat(f"/proc/{os.getpid()}").st_mtime
    except OSError:
        return 0.0


def find_trace(ctx=None) -> str | None:
    """Path of the trace this run wrote, if it wrote one."""
    if ctx is not None and ctx.trace is None:
        return None          # untraced, or nothing ran on a device plane
    since = _process_started() - 1.0
    found = [p for p in glob.glob(os.path.join(
        HERE, ".cache", "work", "*", "trace", "plugins", "profile", "*",
        "*.xplane.pb")) if os.path.getmtime(p) >= since]
    return max(found, key=os.path.getmtime) if found else None


def loaded(path: str) -> dict:
    """``trace_reduce.load`` with the program's span prefix, once per
    trace."""
    key = ("load", path)
    if key not in _cache:
        _cache[key] = T.load(path, SPAN_PREFIX)
    return _cache[key]


def counters_at_first_read() -> dict | None:
    """``telemetry.compile_counters()`` as the first reader of this module
    found them: readers run after the window and nothing compiles inside
    it, so that is the process up to the window's end — before any reader
    (``hlo_scopes``) compiles on its own account."""
    if "counters" not in _cache:
        from dmlc_tpu.utils import telemetry

        read = getattr(telemetry, "compile_counters", None)
        _cache["counters"] = dict(read()) if read else None
    return _cache["counters"]


def within(intervals, merged):
    """The parts of ``intervals`` (merged first) inside ``merged``."""
    return T.subtract(intervals, T.gaps(merged, -1e30, 1e30))


def step_executions(dev: dict, pattern: str):
    """``(merged operations, [(start, end)] of the counted executions)`` on
    one chip, by ``trace_reduce.reduce_trace``'s rule: executions of the
    modules matching ``pattern`` that lie wholly inside the chip's
    operations, but for the first and the last whole module."""
    merged = T.merge((a, b) for _, a, b in dev["ops"])
    if not merged:
        return merged, []
    lo, hi = merged[0][0], merged[-1][1]
    whole = sorted((a, b, name) for name, a, b in dev["modules"]
                   if a >= lo and b <= hi)
    want = re.compile(pattern)
    return merged, [(a, b) for a, b, name in whole[1:-1]
                    if want.search(re.sub(r"\(\d+\)$", "", name))]


def idle_by_span(trace: dict) -> dict | None:
    """Device idle seconds (mean over chips) by the program span that
    covers them, and under none: every idle gap is cut at the spans' edges
    and each piece goes to the span ``trace_reduce._label`` picks (of the
    spans covering a piece the shortest, so the innermost). ``None`` if
    the trace holds no program span or no device operation."""
    spans = trace["host_spans"]
    devices = [d for d in trace["devices"].values() if d["ops"]]
    if not spans or not devices:
        return None
    lo = min(e[1] for d in devices for e in d["ops"])
    hi = max(e[2] for d in devices for e in d["ops"])
    edges = sorted({t for _, a, b in spans for t in (a, b)})
    out: dict = {}
    for dev in devices:
        merged = T.merge((a, b) for _, a, b in dev["ops"])
        for ga, gb in T.gaps(merged, lo, hi):
            cuts = [ga] + [t for t in edges if ga < t < gb] + [gb]
            near = [s for s in spans if s[1] < gb and s[2] > ga]
            for a, b in zip(cuts, cuts[1:]):
                name = T._label((a, b), near, SPAN_PREFIX)
                out[name] = out.get(name, 0.0) + (b - a)
    return {k: v / len(devices) * 1e-9
            for k, v in sorted(out.items(), key=lambda kv: -kv[1])}
