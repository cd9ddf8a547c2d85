"""Milliseconds from the start of one program span to the end of the next
span of another name, summed as their two durations, from the program's
own span ring (``dmlc_tpu.utils.telemetry.spans_snapshot``: in process, no
profiler needed): the median over the pairs the ring holds of this run's
pipeline. ``epoch_turnaround_ms`` pairs every ``epoch_reset`` with the
``first_batch`` that follows it: what the device iterator costs a job at
an epoch boundary, producer join, restart and first batch."""

import statistics

from cellbench.readers import _program as P


def read(ctx, params):
    from dmlc_tpu.utils import telemetry

    pipeline = (ctx.stats_end or {}).get("pipeline")
    first, then = params["first"], params["then"]
    spans = [s for s in telemetry.spans_snapshot(pipeline)
             if s["name"] in (first, then)]
    pairs = [(a["dur_ns"] + b["dur_ns"]) * 1e-6
             for a, b in zip(spans, spans[1:])
             if a["name"] == first and b["name"] == then]
    if not pairs:
        return None
    P.log(f"{first} + {then}: {len(pairs)} pairs in the ring, ms: "
          + ", ".join(f"{p:.3f}" for p in pairs))
    return statistics.median(pairs)
