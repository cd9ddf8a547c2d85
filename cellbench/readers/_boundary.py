"""What the readers of the epoch boundary share (PR 35): the boundaries
of the window, read from the program's own span ring
(``dmlc_tpu.utils.telemetry.spans_snapshot``: in process, no profiler),
where every span of a batch's life carries the batch's id as the labels
``epoch`` and ``batch``.

A boundary is one ``epoch_reset`` of the run's pipeline that starts after
``stats_start["now"]`` (``DeviceIter.stats()``'s reading of the ring's
clock at the window's start) and whose epoch's batch 0 was handed out
before ``stats_end["now"]``: the warm-up's and the verification epoch's
boundaries lie outside. Its phases, in milliseconds from the start of the
``epoch_reset``:

``first_put``  to the end of the ``dispatch`` of the epoch's batch 0:
               producer join and restart, first read / parse / recv, first
               merge, first convert, first put
``withheld``   from there to the end of the ``next`` that hands batch 0
               out: the puts ``DeviceIter._fill`` primes before it lets the
               first batch go

A boundary is whole if the ring still holds its ``epoch_reset`` and batch
0's ``dispatch`` and ``next``; where a ring has wrapped
(``spans_dropped``) the readers say so and read the whole ones. A program
without the labels or without ``stats()["now"]`` (a parent commit) has no
boundary: every reader here then returns ``None``.

With a trace at hand the ring's clock is tied to the profiler's by the
spans both hold (one ``next`` and one ``dispatch`` a batch), and the
device's idle gap at each traced boundary is printed by phase; no metric
hangs on that line.
"""

from __future__ import annotations

import statistics

from cellbench import trace_reduce as T
from cellbench.readers import _program as P

OFFSET_SPREAD_NS = 100e3    # a wider clock tie prints no idle by phase
LOGGED = 16                 # boundaries that get a line of their own
TIED_BY = ("next", "dispatch")  # the consumer's spans, one of each a batch
SOURCE_SPANS = ("read", "parse", "cache_read", "snapshot_read",
                "service_recv")


def end(span: dict) -> int:
    return span["start_ns"] + span["dur_ns"]


def window_spans(ctx):
    """``(spans of the run's pipeline inside the window, its start ns,
    its end ns)``, or ``None`` where the program's ``stats()`` has no
    ``now``."""
    start, stop = ((ctx.stats_start or {}).get("now"),
                   (ctx.stats_end or {}).get("now"))
    if start is None or stop is None:
        return None
    key = ("window", id(ctx))
    if key not in P._cache:
        from dmlc_tpu.utils import telemetry

        lo, hi = int(start * 1e9), int(stop * 1e9)
        spans = [s for s in telemetry.spans_snapshot(
            (ctx.stats_end or {}).get("pipeline")) if s["start_ns"] >= lo
            and end(s) <= hi]
        dropped = telemetry.spans_dropped()
        if dropped:
            P.log(f"span rings: {dropped} spans dropped so far (rings that "
                  "wrapped or retired with their threads); reading the "
                  "boundaries that are still whole")
        P._cache[key] = (spans, lo, hi)
    return P._cache[key]


def _of(spans, name, **labels):
    return [s for s in spans if s["name"] == name
            and all(s["labels"].get(k) == v for k, v in labels.items())]


def boundaries(ctx):
    """The whole boundaries of the window, oldest first, each a dict of
    its spans (``reset``, ``put``, ``hand``; ``first_batch``, ``start`` and
    the epoch's other early spans where the ring has them). ``None`` where
    the program gives none to read."""
    found = window_spans(ctx)
    if found is None:
        return None
    key = ("boundaries", id(ctx))
    if key in P._cache:
        return P._cache[key]
    spans = found[0]
    out = []
    for reset in _of(spans, "epoch_reset"):
        epoch = reset["labels"].get("epoch")
        if epoch is None:
            continue
        put = _of(spans, "dispatch", epoch=epoch, batch=0)
        hand = _of(spans, "next", epoch=epoch, batch=0)
        if len(put) != 1 or len(hand) != 1:
            continue    # cut by the window's end, or no longer whole
        out.append({"epoch": epoch, "reset": reset, "put": put[0],
                    "hand": hand[0],
                    "first_batch": _of(spans, "first_batch", epoch=epoch),
                    "start": _of(spans, "producer_start", epoch=epoch)})
    out.sort(key=lambda b: b["reset"]["start_ns"])
    for b in out[:LOGGED]:
        _log_boundary(b, spans)
    if len(out) > LOGGED:       # a tiny cell turns over hundreds of times
        P.log(f"... and {len(out) - LOGGED} boundaries more")
    if out:
        _log_accounts(out)
    P._cache[key] = out or None
    return P._cache[key]


def phase_ms(boundary: dict) -> dict:
    t0 = boundary["reset"]["start_ns"]
    return {"first_put": (end(boundary["put"]) - t0) * 1e-6,
            "withheld": (end(boundary["hand"]) - end(boundary["put"])) * 1e-6}


def _log_boundary(b: dict, spans) -> None:
    """One line a boundary: when each step of batch 0's way ended, in ms
    from the start of the ``epoch_reset``, and the puts primed after it."""
    t0, epoch = b["reset"]["start_ns"], b["epoch"]

    def at(ns):
        return f"{(ns - t0) * 1e-6:.3f}"

    steps = [("reset", end(b["reset"]))]
    if b["start"]:
        steps.append(("producer started", end(b["start"][0])))
    early = [s for s in spans if s["name"] in SOURCE_SPANS
             and t0 <= s["start_ns"] <= b["put"]["start_ns"]]
    if early:
        first = min(early, key=end)
        steps.append((f"first {first['name']}", end(first)))
    merges = _of(spans, "merge", epoch=epoch, batch=0)
    if merges:
        steps.append((f"merge ({sum(m['dur_ns'] for m in merges) * 1e-6:.3f}"
                      f" ms in {len(merges)})", max(map(end, merges))))
    for s in _of(spans, "convert", epoch=epoch, batch=0):
        steps.append((f"convert ({s['dur_ns'] * 1e-6:.3f} ms)", end(s)))
    steps.append((f"put 0 ({b['put']['dur_ns'] * 1e-6:.3f} ms)",
                  end(b["put"])))
    primed = sorted((s for s in _of(spans, "dispatch", epoch=epoch)
                     if end(b["put"]) < end(s) <= end(b["hand"])), key=end)
    steps += [(f"put {s['labels']['batch']}", end(s)) for s in primed]
    steps.append(("batch 0 handed out", end(b["hand"])))
    P.log(f"boundary of epoch {epoch}, ms from the reset's start: "
          + ", ".join(f"{what} {at(ns)}" for what, ns in steps))


def _log_accounts(found) -> None:
    """The two accounts of the boundary side by side:
    ``epoch_turnaround_ms`` sums two durations, the phases run from the
    reset's start to the hand-out, so they differ by the caller's own time
    between ``reset()`` and ``next()``."""
    both = [(sum(phase_ms(b).values()),
             (b["reset"]["dur_ns"] + b["first_batch"][0]["dur_ns"]) * 1e-6)
            for b in found if b["first_batch"]]
    if both:
        P.log("boundaries, first_put + withheld beside epoch_reset + "
              "first_batch (epoch_turnaround_ms's account), ms: "
              + ", ".join(f"{a:.3f} / {b:.3f}" for a, b in both[:LOGGED])
              + f"; medians {statistics.median(a for a, _ in both):.3f} / "
              f"{statistics.median(b for _, b in both):.3f}")


# ---- the ring's clock against the profiler's ----

def clock_offset(ring_starts: dict, trace_starts: dict, tight_ns=10e3):
    """``(median, spread, pairs)`` of ``trace start - ring start`` over the
    events both hold, in ns, or ``None``: each argument gives, by span
    name, the starts of the same spans on one of two clocks (the
    consumer's ``next`` and ``dispatch``: one of each a batch); either may
    hold events the other lacks. The offset is where the pairwise
    differences lie densest (within ``tight_ns``: the steps' own cadence
    jitters by more, so a pairing shifted by a step scatters); ``spread``
    is the distance between the 5th and the 95th percentile of the
    differences within a millisecond of it, one a matched pair."""
    diffs = sorted(t - r for name, starts in trace_starts.items()
                   for t in starts for r in ring_starts.get(name, ()))
    if not diffs:
        return None
    best, lo = (0, 0), 0
    for hi in range(len(diffs)):
        while diffs[hi] - diffs[lo] > tight_ns:
            lo += 1
        best = max(best, (hi - lo + 1, -lo))
    count, lo = best[0], -best[1]
    if count < 2:
        return None
    centre = statistics.median(diffs[lo:lo + count])
    pairs = sorted(d for d in diffs if abs(d - centre) <= 1e6)
    cut = max(0, len(pairs) // 20)
    spread = pairs[len(pairs) - 1 - cut] - pairs[cut]
    return statistics.median(pairs), spread, len(pairs)


def log_idle_by_phase(ctx) -> None:
    """Once a traced run: the offset between the ring's clock and the
    profiler's, and with it the device's idle gap at every traced boundary
    cut by batch phase. Printed only."""
    key = ("idle_by_phase", id(ctx))
    found = boundaries(ctx)
    if key in P._cache or not found:
        return
    P._cache[key] = True
    path = P.find_trace(ctx)
    trace = P.loaded(path) if path else None
    devices = [d for d in (trace or {"devices": {}})["devices"].values()
               if d["ops"]]
    if not devices:
        return
    tied = clock_offset(
        {name: [s["start_ns"] for s in window_spans(ctx)[0]
                if s["name"] == name] for name in TIED_BY},
        {name: [a for n, a, _ in trace["host_spans"]
                if n == P.SPAN_PREFIX + name] for name in TIED_BY})
    if tied is None:
        P.log("clock: the trace and the ring share no two `next` or "
              "`dispatch` spans")
        return
    offset, spread, pairs = tied
    P.log(f"clock: profiler - ring = {offset:.0f} ns (median of {pairs} "
          "`next` and `dispatch` spans on both, 5th to 95th percentile "
          f"{spread:.0f} ns)")
    if spread >= OFFSET_SPREAD_NS:
        P.log("clock: too wide a tie to cut the device's idle by phase")
        return
    lo = min(e[1] for d in devices for e in d["ops"])
    hi = max(e[2] for d in devices for e in d["ops"])
    for b in found:
        t0, put, hand = (b["reset"]["start_ns"] + offset,
                         end(b["put"]) + offset, end(b["hand"]) + offset)
        if not lo <= t0 <= hand <= hi:
            continue    # not in the traced part of the window
        phases = {"before batch 0's put": 0.0, "until its hand-out": 0.0,
                  "after": 0.0}
        for dev in devices:
            merged = T.merge((a, b_) for _, a, b_ in dev["ops"])
            for ga, gb in T.gaps(merged, lo, hi):
                if gb <= t0 or ga >= hand:
                    continue    # the gaps that touch the boundary, whole
                cuts = [ga, min(max(put, ga), gb), min(max(hand, ga), gb), gb]
                for what, (a, b_) in zip(phases, zip(cuts, cuts[1:])):
                    phases[what] += (b_ - a) / len(devices)
        P.log(f"boundary of epoch {b['epoch']}, the device's idle by batch "
              "phase, ms (mean over chips): " + ", ".join(
                  f"{what} {ns * 1e-6:.3f}" for what, ns in phases.items()))
