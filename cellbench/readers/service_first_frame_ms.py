"""Milliseconds from the start of an ``epoch_reset`` to the end of the new
epoch's first ``service_recv`` labeled ``first=1`` (the first frame of the
epoch's first part: what the trainer waits for the fleet at a boundary),
from the program's own span ring: the median over the window's boundaries
(``_boundary``). The log splits each into the ``service_locate`` and
``service_connect`` before it and the wait for the frame itself. No value
where the iterator's source is no service client or the program predates
the labels."""

import statistics

from cellbench.readers import _boundary as B
from cellbench.readers import _program as P


def read(ctx, params):
    found = B.boundaries(ctx)
    if not found:
        return None
    spans = B.window_spans(ctx)[0]
    firsts = sorted((s for s in spans if s["name"] == "service_recv"
                     and s["labels"].get("first")),
                    key=lambda s: s["start_ns"])
    values = []
    for b in found:
        t0 = b["reset"]["start_ns"]
        frame = next((s for s in firsts if s["start_ns"] >= t0
                      and B.end(s) <= B.end(b["hand"])), None)
        if frame is None:
            continue
        before = {name: sum(s["dur_ns"] for s in spans if s["name"] == name
                            and t0 <= s["start_ns"] <= frame["start_ns"])
                  for name in ("service_locate", "service_connect")}
        values.append((B.end(frame) - t0) * 1e-6)
        if len(values) > B.LOGGED:
            continue
        P.log(f"boundary of epoch {b['epoch']}: first frame of part "
              f"{frame['labels'].get('part')} after {values[-1]:.3f} ms ("
              + ", ".join(f"{k} {v * 1e-6:.3f}" for k, v in before.items())
              + f", the frame's own wait {frame['dur_ns'] * 1e-6:.3f})")
    return statistics.median(values) if values else None
