"""Run one cell and keep every window boundary's spans, the service
fleet's included:

    python3 -m cellbench.tools.boundary_trace OUT.json --workload W \
        --seed N --seconds S [--trace 0]

runs ``cellbench.run`` in this process (traced unless told otherwise);
where the cell's feed is a service fleet it pulls ``trace_dump`` from the
dispatcher and every worker before the fleet stops (their rings are on
``CLOCK_MONOTONIC``, which one host's processes share; the offset each
round trip estimates is printed). Then,
for every boundary of the window (``readers/_boundary``), it prints one
timeline: the trainer's spans from 400 ms before the ``epoch_reset`` to
batch 0's hand-out and the fleet's spans of the traces those belong to (a
part's grant, ``service_parse``, ``service_encode``, ``service_send``), in
ms from the reset's start; ``OUT.json`` keeps the same spans with the
window's two ``stats()["now"]``, and the run's trace is copied beside it
(``OUT.json.xplane.pb``; cut it with ``tools/trim_spans.py``): what
``cellbench/tests/recorded/boundary_ring.json`` was cut from. The
hand-reading tool behind PERF.md's account of ``kdd12_fm_service``'s
boundary; no metric reads it.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

LEAD_NS = 400_000_000
TRAIL_NS = 400_000_000      # kept after the hand-out, not printed
TRAINER = ("epoch_reset", "first_batch", "producer_start", "next", "dispatch",
           "merge", "convert", "service_locate", "service_connect",
           "service_recv", "service_drain", "service_decode", "read", "parse",
           "cache_read", "snapshot_read", "step_dispatch", "transfer")


def pull_fleet(address: str) -> list:
    """``trace_dump`` of the dispatcher and of every live worker, each
    with the clock offset its round trip estimates."""
    from dmlc_tpu.service import dispatcher as D
    from dmlc_tpu.service import worker as W

    peers = []

    def note(name, ask):
        t0 = time.monotonic()
        snap = ask().get("snapshot") or {}
        t1 = time.monotonic()
        offset = (t0 + t1) / 2 - float(snap.get("now", (t0 + t1) / 2))
        print(f"[boundary_trace] {name}: {len(snap.get('spans', []))} spans,"
              f" round trip {1e3 * (t1 - t0):.3f} ms, clock offset estimate "
              f"{1e6 * offset:.0f} us (applied: 0, one host)", flush=True)
        peers.append({"peer": name, "spans": snap.get("spans", [])})

    note("dispatcher", lambda: D.request(address, {"cmd": "trace_dump"}))
    workers = D.request(address, {"cmd": "status"}).get("workers") or {}
    for worker, info in sorted(workers.items()):
        if info.get("alive"):
            note(worker, lambda i=info: W.request(
                i["host"], i["port"], {"cmd": "trace_dump"}))
    return peers


def main(argv) -> int:
    out_path, rest = argv[0], argv[1:]
    from cellbench import run
    from cellbench.feeds import service as feed
    from cellbench.readers import _boundary as B
    from cellbench.readers import _program as P

    fleet_spans = []
    stop = feed.Fleet.stop

    def pulling_stop(self):
        if self.address and self.processes and not fleet_spans:
            try:
                fleet_spans.extend(pull_fleet(self.address))
            except (OSError, ValueError, KeyError) as exc:
                print(f"[boundary_trace] no fleet trace: {exc!r}", flush=True)
        stop(self)

    feed.Fleet.stop = pulling_stop
    seen = {}
    window = run.run_window

    def keeping(*args, **kwargs):
        seen["ctx"] = window(*args, **kwargs)
        return seen["ctx"]

    run.run_window = keeping
    rc = run.main(rest if "--trace" in rest else rest + ["--trace", "1"])
    ctx = seen.get("ctx")
    bounds = B.boundaries(ctx) if ctx is not None else None
    if not bounds:
        print("[boundary_trace] the run read no boundary", flush=True)
        return rc or 1
    from dmlc_tpu.utils import telemetry

    ring = [s for s in telemetry.spans_snapshot(
        (ctx.stats_end or {}).get("pipeline")) if s["name"] in TRAINER]
    kept = []
    for b in bounds:
        t0 = b["reset"]["start_ns"]
        handed = B.end(b["hand"])
        mine = [dict(s, peer="trainer") for s in ring
                if t0 - LEAD_NS <= s["start_ns"] <= handed + TRAIL_NS]
        traces = {s.get("trace_id") for s in mine} - {None}
        lo, hi = t0 - 20 * LEAD_NS, handed
        for peer in fleet_spans:
            mine += [dict(s, peer=peer["peer"]) for s in peer["spans"]
                     if lo <= s["start_ns"] <= hi
                     and (s.get("trace_id") in traces
                          or s["name"] == "service_rpc"
                          and s["start_ns"] >= t0 - LEAD_NS)]
        mine.sort(key=lambda s: s["start_ns"])
        kept.append({"epoch": b["epoch"], "phases_ms": B.phase_ms(b),
                     "reset_start_ns": t0, "spans": mine})
        print(f"[boundary_trace] ---- boundary of epoch {b['epoch']}: "
              + ", ".join(f"{k} {v:.3f} ms"
                          for k, v in B.phase_ms(b).items()), flush=True)
        for name in ("merge", "convert"):
            # the epoch's first batches, one after the other: how long the
            # serial stage and the convert take while the epoch is young
            by_batch = {}
            for s in mine:
                if (s["name"] == name
                        and s["labels"].get("epoch") == b["epoch"]):
                    batch = s["labels"]["batch"]
                    by_batch[batch] = by_batch.get(batch, 0) + s["dur_ns"]
            print(f"[boundary_trace] {name} ms by batch: " + ", ".join(
                f"{k}: {v * 1e-6:.1f}" for k, v in sorted(by_batch.items())),
                flush=True)
        for s in mine:
            if s["start_ns"] > handed:
                continue        # kept in OUT.json, summed above
            labels = {k: v for k, v in s["labels"].items() if k != "nbytes"}
            if s["name"] == "next" and labels.get("batch", 0) > 0 \
                    and s["start_ns"] > t0:
                continue
            print(f"[boundary_trace] {(s['start_ns'] - t0) * 1e-6:10.3f} "
                  f"+{s['dur_ns'] * 1e-6:9.3f}  {s['peer']:<12} "
                  f"{s['thread'][:18]:<18} {s['name']:<16} {labels} "
                  f"{s.get('trace_id', '')}", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"argv": rest, "pipeline": ctx.stats_end["pipeline"],
                   "now": [ctx.stats_start["now"], ctx.stats_end["now"]],
                   "boundaries": kept}, f)
    print(f"[boundary_trace] {out_path}: {len(kept)} boundaries", flush=True)
    trace = P.find_trace(ctx)
    if trace:
        shutil.copyfile(trace, out_path + ".xplane.pb")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
