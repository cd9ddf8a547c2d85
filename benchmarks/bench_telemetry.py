"""What the program's tracing costs where it is written: nanoseconds of one
``telemetry.span`` (plain, and labelled as a batch's spans are), of one
``record_span``, and of one registry counter increment, each the median of
seven timings of 20,000 on this machine's host. With ``--profiler`` the
spans are timed once more inside a live ``jax.profiler`` session (what a
``--trace 1`` run pays). Host numbers: they need no chip and say nothing
about one.

    python3 benchmarks/bench_telemetry.py [--profiler]
"""

from __future__ import annotations

import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_tpu.utils import telemetry  # noqa: E402

N = 20_000


def ns_each(fn) -> float:
    runs = []
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(N):
            fn()
        runs.append((time.perf_counter() - t0) / N * 1e9)
    return statistics.median(runs)


def plain_span():
    with telemetry.span("bench_probe"):
        pass


def labelled_span():
    with telemetry.span("bench_probe", epoch=3, batch=17):
        pass


def main(argv) -> int:
    counter = telemetry.REGISTRY.counter("bench_probe_seconds",
                                         pipeline="bench")
    rows = [("span", plain_span),
            ("labelled span", labelled_span),
            ("record_span", lambda: telemetry.record_span(
                "bench_probe", 1.0, 0.001, epoch=3, batch=17)),
            ("counter increment", lambda: counter.inc(0.001)),
            ("time.monotonic() twice", lambda: (time.monotonic(),
                                                time.monotonic()))]
    had_jax = "jax.profiler" in sys.modules
    for what, fn in rows:
        print(f"{what}{'' if had_jax else ' (no jax imported)'}: "
              f"{ns_each(fn):.0f} ns", flush=True)
    import jax.profiler     # from here on a span carries a TraceAnnotation

    for what, fn in rows[:2]:
        print(f"{what}, annotation inert: {ns_each(fn):.0f} ns", flush=True)
    if "--profiler" in argv:
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                for what, fn in rows[:2]:
                    print(f"{what}, profiler on: {ns_each(fn):.0f} ns",
                          flush=True)
            finally:
                jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
