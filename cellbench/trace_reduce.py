"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: device busy and idle time, device time per jitted module and per
operation, the part of the collectives during which nothing else ran, and
the idle gaps labelled by what the host was doing.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. All
times come from the trace's own clock; intervals are half-open
``(start_ns, end_ns)`` pairs.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)[^ ]* ")
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


# ---- interval arithmetic ----

def merge(intervals):
    """Sorted, disjoint union of ``intervals``."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(merged) -> float:
    return float(sum(b - a for a, b in merged))


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, merged_other):
    """The parts of ``intervals`` (merged first) outside ``merged_other``."""
    out = []
    other = list(merged_other)
    for a, b in merge(intervals):
        cur = a
        for c, d in other:
            if d <= cur:
                continue
            if c >= b:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def gaps(merged, lo, hi):
    """The complement of ``merged`` inside ``[lo, hi)``."""
    return subtract([(lo, hi)], merged)


# ---- reading the planes ----

_HLO = re.compile(r"^%?(?P<name>[^ ]+) = (?P<type>\(?[a-z0-9]+\[[0-9,]*\])?"
                  r"[^ ]* ?(?P<rest>.*)$")


def short_name(name: str) -> str:
    """An operation's name as the trace prints it, cut to what tells it
    apart: ``%fusion.3 = f32[54686453,8]{0,1:T(8,128)} fusion(...),
    kind=kCustom, calls=...`` becomes ``fusion.3 f32[54686453,8] fusion
    kCustom``. Names that are not HLO text are kept (at most 96 bytes)."""
    m = _HLO.match(name)
    if not m or " = " not in name:
        return name[:96]
    rest = m.group("rest")
    opcode = re.match(r"(?:[^ ]+ )?([a-z][a-z0-9\-]*)\(", rest)
    kind = re.search(r"kind=(k[A-Za-z]+)", rest)
    parts = [m.group("name"), (m.group("type") or "").lstrip("("),
             opcode.group(1) if opcode else "", kind.group(1) if kind else ""]
    return " ".join(p for p in parts if p)[:96]


def _events(line, shorten=False):
    return [(short_name(e.name) if shorten else e.name, float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def load(path: str, span_prefix: str = "cellbench:") -> dict:
    """``{"devices": {chip: {"ops": [...], "modules": [...]}},
    "host_spans": [(name, start, end)]}`` with ``(name, start, end)``
    events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = {
                "ops": (_events(lines[OPS_LINE], shorten=True)
                        if OPS_LINE in lines else []),
                "modules": (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else []),
            }
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith(span_prefix)]
    return {"devices": devices, "host_spans": sorted(spans,
                                                     key=lambda s: s[1])}


def _label(gap, spans, span_prefix):
    """The host span that covers most of ``gap``; the shorter wins a tie."""
    best, best_cover, best_len = "(no host span)", 0.0, 0.0
    for name, a, b in spans:
        if a >= gap[1]:
            break
        cover = min(b, gap[1]) - max(a, gap[0])
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and b - a < best_len):
            best, best_cover, best_len = name[len(span_prefix):], cover, b - a
    return best


def reduce_trace(path: str, module_pattern: str = ".*", top: int = 10,
                 span_prefix: str = "cellbench:") -> dict:
    """Reduce one trace. Seconds throughout; per-chip quantities are
    averaged over the chips that ran an operation.

    ``window_s``  first device operation's start to the last one's end,
                  over all chips
    ``busy_s``    union of the device operations' intervals
    ``modules``   per jitted module (name without its program id):
                  executions per chip and device seconds per execution,
                  counting executions that lie wholly inside the window, but
                  for the first and last of them on each chip
    ``step``      the same for the modules matching ``module_pattern``,
                  with the collective seconds inside those executions and
                  the part of them during which no other operation ran
    ``collective_s`` / ``collective_exposed_s``  time in collectives, and
                  the part of it during which no other operation ran on
                  that chip
    ``device_ops``  the ``top`` operations by device seconds
    ``idle_gaps``   idle seconds by the host span that covers each gap
    """
    trace = load(path, span_prefix)
    devices = {k: d for k, d in trace["devices"].items() if d["ops"]}
    if not devices:
        raise ValueError(f"{path}: no operation ran on a TPU device plane")
    lo = min(e[1] for d in devices.values() for e in d["ops"])
    hi = max(e[2] for d in devices.values() for e in d["ops"])
    n = len(devices)
    busy = coll = exposed = 0.0
    op_time = defaultdict(float)
    mod_count, mod_time = defaultdict(int), defaultdict(float)
    mod_coll, mod_exposed = defaultdict(float), defaultdict(float)
    gap_time = defaultdict(float)
    want = re.compile(module_pattern)
    for dev in devices.values():
        merged = merge((a, b) for _, a, b in dev["ops"])
        busy += length(merged)
        c_iv = [(a, b) for name, a, b in dev["ops"] if COLLECTIVE.match(name)]
        o_iv = merge((a, b) for name, a, b in dev["ops"]
                     if not COLLECTIVE.match(name))
        c_merged, x_merged = merge(c_iv), subtract(c_iv, o_iv)
        coll += length(c_merged)
        exposed += length(x_merged)
        for name, a, b in dev["ops"]:
            op_time[name] += b - a
        # executions cut by an edge of the trace are left out, and so are
        # the first and the last that are whole: the profiler starts and
        # stops recording operations a little after and before the module
        # events around them
        whole = sorted((a, b, name) for name, a, b in dev["modules"]
                       if a >= lo and b <= hi)
        for a, b, name in whole[1:-1]:
            key = re.sub(r"\(\d+\)$", "", name)
            mod_count[key] += 1
            mod_time[key] += length(clip(merged, a, b))
            mod_coll[key] += length(clip(c_merged, a, b))
            mod_exposed[key] += length(clip(x_merged, a, b))
        for gap in gaps(merged, lo, hi):
            gap_time[_label(gap, trace["host_spans"], span_prefix)] += \
                gap[1] - gap[0]
    ns = 1e-9
    modules = {k: {"executions_per_chip": mod_count[k] / n,
                   "device_s_per_execution": mod_time[k] / mod_count[k] * ns}
               for k in mod_count}
    step_keys = [k for k in modules if want.search(k)]
    step_n = sum(mod_count[k] for k in step_keys)
    step = None
    if step_n:
        per = lambda d: sum(d[k] for k in step_keys) / step_n * ns  # noqa: E731
        step = {"executions_per_chip": step_n / n,
                "device_s_per_execution": per(mod_time),
                "collective_s_per_execution": per(mod_coll),
                "collective_exposed_s_per_execution": per(mod_exposed)}
    rank = lambda d: [[k, v / n * ns] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "chips": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n * ns,
        "modules": modules,
        "step": step,
        "collective_s": coll / n * ns,
        "collective_exposed_s": exposed / n * ns,
        "device_ops": rank(op_time),
        "idle_gaps": rank(gap_time),
        "host_span_names": sorted({s[0] for s in trace["host_spans"]}),
    }


def describe(path: str) -> str:
    """Planes, lines and event counts of a trace: what to look at before
    writing code against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for ln in plane.lines:
            ev = list(ln.events)
            names = sorted({e.name for e in ev})[:6]
            out.append(f"  LINE {ln.name!r}: {len(ev)} events, e.g. {names}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    p = sys.argv[1]
    p = p if p.endswith(".pb") else find_xplane(p)
    print(describe(p))
    print(json.dumps(reduce_trace(p, *sys.argv[2:3]), indent=1))
