"""The readers of the epoch boundary (PR 35): on rings written here span by
span, on a ring and a trace recorded on the chip
(``recorded/boundary_ring.json`` and ``recorded/boundary.xplane.pb``: two
boundaries of ``kdd12_fm_text`` as ``tools/boundary_trace.py`` kept them,
the trace cut with ``tools/trim_spans.py``; the ``expect`` entry holds what
was read off them by hand), on a program that lacks what they read (the
parent commit), and through the whole harness at the tiny size on the
CPU."""

import json
import os
import types

import pytest

from cellbench import run as R
from cellbench.readers import _boundary as B
from cellbench.readers import _program as P
from dmlc_tpu.utils import telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
RING = os.path.join(HERE, "recorded", "boundary_ring.json")
TRACE = os.path.join(HERE, "recorded", "boundary.xplane.pb")
NEW = ("boundary_first_put_ms", "boundary_withheld_ms",
       "merge_busy_s_per_mrow", "feed_backpressure_share",
       "service_first_frame_ms")
POOL = {"window_wait_seconds": 0.0, "pull_wait_seconds": 0.0,
        "pull_seconds": 0.0, "work_seconds": 0.0, "ready_wait_seconds": 0.0,
        "merge_seconds": 0.0, "items": 0, "stall_seconds": 0.0,
        "ring_hits": 0, "ring_misses": 0}


def read(name, ctx):
    spec = R.load_json(R.HERE, "metrics", name + ".json")
    return R.plugin("readers", spec["reader"]).read(ctx, spec)


def ctx_for(monkeypatch, pipeline, now=(0.0, 1e9), trace=None, **more):
    P._cache.clear()
    monkeypatch.setattr(P, "find_trace", lambda ctx=None: trace)
    return types.SimpleNamespace(
        trace={} if trace else None, rows_dispatched=2_000_000,
        stats_start=dict({"pipeline": pipeline, "now": now[0]},
                         **more.get("start", {})),
        stats_end=dict({"pipeline": pipeline, "now": now[1]},
                       **more.get("end", {})))


def boundary(t, epoch, first_put=0.100, withheld=0.060, service=False):
    """One boundary's spans as the program writes them, from ``t`` on."""
    span = telemetry.record_span
    span("epoch_reset", t, 0.002, epoch=epoch)
    span("first_batch", t + 0.003, first_put + withheld - 0.003, epoch=epoch)
    span("producer_start", t + 0.004, 0.001, epoch=epoch)
    if service:
        span("service_locate", t + 0.006, 0.010, part=0, epoch=epoch)
        span("service_connect", t + 0.017, 0.001, part=0, epoch=epoch)
        span("service_recv", t + 0.018, 0.0002, part=0, epoch=epoch, hello=1)
        span("service_recv", t + 0.019, 0.021, part=0, epoch=epoch, first=1)
    else:
        span("parse", t + 0.006, 0.030)
    span("merge", t + 0.040, 0.020, epoch=epoch, batch=0)
    span("convert", t + 0.061, 0.030, epoch=epoch, batch=0)
    span("dispatch", t + first_put - 0.004, 0.004, epoch=epoch, batch=0)
    span("dispatch", t + first_put + 0.020, 0.004, epoch=epoch, batch=1)
    span("next", t + 0.0035, first_put + withheld - 0.0035, epoch=epoch,
         batch=0, waited_s=0.1)


def test_phases_are_medians_over_the_windows_boundaries(monkeypatch, capsys):
    pipe = "boundary-test-median"
    with telemetry.scope(pipe):
        boundary(90.0, 1, first_put=0.500)        # before the window: warm-up
        for i, (a, b) in enumerate(((0.100, 0.060), (0.110, 0.050),
                                    (0.180, 0.058))):
            boundary(100.0 + 5 * i, 2 + i, a, b, service=True)
        boundary(120.0, 5, first_put=0.300)       # after it: verification
    with telemetry.scope("another-pipe"):
        boundary(101.0, 2, first_put=0.900)
    ctx = ctx_for(monkeypatch, pipe, now=(99.0, 119.0))
    assert read("boundary_first_put_ms", ctx) == pytest.approx(110.0)
    assert read("boundary_withheld_ms", ctx) == pytest.approx(58.0)
    # start of the reset to the end of the part's first frame
    assert read("service_first_frame_ms", ctx) == pytest.approx(40.0)
    out = capsys.readouterr().out
    assert out.count("ms from the reset's start") == 3
    assert "first service_recv" in out and "put 1" in out
    # the two accounts side by side: the phases hold the caller's own
    # millisecond between reset() and next() too
    assert "medians 160.000 / 159.000" in out
    assert "service_locate 10.000, service_connect 1.000, the frame's own " \
        "wait 21.000" in out


def test_no_boundary_after_now_reads_nothing(monkeypatch):
    pipe = "boundary-test-none"
    with telemetry.scope(pipe):
        boundary(90.0, 1)
    ctx = ctx_for(monkeypatch, pipe, now=(99.0, 119.0))
    assert [read(n, ctx) for n in NEW[:2] + NEW[4:]] == [None, None, None]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_books_reads_nothing(name, monkeypatch):
    """The parent commit: ``stats()`` has no ``now`` and no ``pool``, its
    spans carry no batch, its client no ``first=1``."""
    pipe = "boundary-test-parent"
    with telemetry.scope(pipe):
        for t in (100.0, 105.0):
            telemetry.record_span("epoch_reset", t, 0.002)
            telemetry.record_span("first_batch", t + 0.003, 0.150)
            telemetry.record_span("dispatch", t + 0.1, 0.004)
            telemetry.record_span("next", t + 0.003, 0.150, waited_s=0.1)
            telemetry.record_span("service_recv", t + 0.02, 0.02)
    old = ctx_for(monkeypatch, pipe)
    del old.stats_start["now"], old.stats_end["now"]
    assert read(name, old) is None
    # and a program with the clock but unlabelled spans
    assert read(name, ctx_for(monkeypatch, pipe, now=(99.0, 119.0))) is None


def test_a_wrapped_ring_is_said_and_the_whole_boundaries_are_read(
        monkeypatch, capsys):
    pipe = "boundary-test-wrapped"
    with telemetry.scope(pipe):
        # the oldest boundary has lost its epoch_reset to the wrap
        telemetry.record_span("dispatch", 100.096, 0.004, epoch=2, batch=0)
        telemetry.record_span("next", 100.0035, 0.1565, epoch=2, batch=0)
        boundary(105.0, 3, 0.120, 0.040)
        boundary(110.0, 4, 0.130, 0.044)
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 8192)
    ctx = ctx_for(monkeypatch, pipe, now=(99.0, 119.0))
    assert read("boundary_first_put_ms", ctx) == pytest.approx(125.0)
    assert read("boundary_withheld_ms", ctx) == pytest.approx(42.0)
    out = capsys.readouterr().out
    assert "8192 spans dropped" in out and "still whole" in out
    assert out.count("ms from the reset's start") == 2


def test_pool_readers_take_the_windows_deltas(monkeypatch, capsys):
    start = dict(POOL, window_wait_seconds=10.0, pull_seconds=1.0,
                 work_seconds=2.0, merge_seconds=0.5, items=64)
    stop = dict(POOL, window_wait_seconds=40.0, pull_wait_seconds=2.0,
                pull_seconds=4.0, work_seconds=7.0, merge_seconds=0.9,
                items=448, ring_misses=3)
    ctx = ctx_for(monkeypatch, None, start={"pool": start},
                  end={"pool": stop})
    # 0.4 s of merge for 2 Mrows; the window shut 30 of the workers' 40 s
    assert read("merge_busy_s_per_mrow", ctx) == pytest.approx(0.2)
    assert read("feed_backpressure_share", ctx) == pytest.approx(75.0)
    out = capsys.readouterr().out
    assert "384 items" in out and "staging ring misses 3" in out
    # a pool that did not work in the window (a warm snapshot cell)
    idle = ctx_for(monkeypatch, None, start={"pool": dict(POOL)},
                   end={"pool": dict(POOL)})
    assert read("feed_backpressure_share", idle) is None
    assert read("merge_busy_s_per_mrow", idle) == 0.0


@pytest.mark.parametrize("jitter_ns,tied", [(2_000, True), (400_000, False)])
def test_the_clock_tie_and_its_refusal(jitter_ns, tied):
    """The profiler's clock is the ring's plus a constant; each side holds
    spans the other lacks (the pull that ends an epoch is on the profiler's
    timeline only, the trace covers part of the window)."""
    offset = 1_695_000_000_123_456_789
    ring = [1000_000_000 + 53_000_000 * i + (i * i * 7919) % 300_000
            for i in range(300)]     # a step's cadence, jittering by 0.3 ms
    trace = [r + offset + ((i * 104729) % (2 * jitter_ns)) - jitter_ns
             for i, r in enumerate(ring[100:160])]
    trace.insert(30, trace[29] + 1_234_567)      # an epoch's last pull
    median, spread, pairs = B.clock_offset(
        {"next": ring, "dispatch": [r + 400_000 for r in ring]},
        {"next": trace, "dispatch": [t + 400_000 for t in trace[:20]],
         "merge": [1, 2, 3]})
    if tied:
        assert pairs == 60 + 20
        assert abs(median - offset) < 3_000 and spread < 5_000
    else:
        assert spread >= B.OFFSET_SPREAD_NS
    assert B.clock_offset({}, {"next": trace}) is None
    assert B.clock_offset({"next": ring[:1]}, {"next": trace[:1]}) is None


@pytest.fixture
def recorded():
    if not (os.path.exists(RING) and os.path.exists(TRACE)):
        pytest.skip("no recorded ring in this checkout")
    with open(RING) as f:
        return json.load(f)


def recorded_ctx(recorded, monkeypatch, trace=TRACE):
    rows = [s for b in recorded["boundaries"] for s in b["spans"]
            if s["peer"] == "trainer"]
    rows = list({(s["tid"], s["start_ns"], s["name"]): s
                 for s in rows}.values())
    monkeypatch.setattr(
        telemetry, "spans_snapshot", lambda pipeline=None: sorted(
            (s for s in rows if s["pipeline"] == pipeline),
            key=lambda s: s["start_ns"]))
    return ctx_for(monkeypatch, recorded["pipeline"], now=recorded["now"],
                   trace=trace)


def test_phases_on_the_recorded_ring(recorded, monkeypatch, capsys):
    ctx = recorded_ctx(recorded, monkeypatch)
    expect = recorded["expect"]
    for name in NEW[:2]:
        assert read(name, ctx) == pytest.approx(expect[name], rel=1e-9), name
    out = capsys.readouterr().out
    assert out.count("ms from the reset's start") == len(
        recorded["boundaries"])
    # the clock's tie to the recorded trace, and the device's idle at the
    # traced boundary by batch phase
    assert "clock: profiler - ring = " in out
    assert out.count("the device's idle by batch phase") == 1
    line = next(ln for ln in out.splitlines() if "by batch phase" in ln)
    got = [float(part.rsplit(" ", 1)[1]) for part in
           line.split("chips): ")[1].split(", ")]
    assert got == pytest.approx(expect["idle_by_phase_ms"], abs=0.002)
    # most of the chip's idle at a boundary lies before batch 0 is put,
    # and the priming withholds the rest
    assert got[0] > got[1] > got[2] >= 0.0


def test_a_clock_too_wide_to_print_by_says_so(recorded, monkeypatch, capsys):
    ctx = recorded_ctx(recorded, monkeypatch)
    monkeypatch.setattr(B, "OFFSET_SPREAD_NS", 0.0)
    assert read("boundary_withheld_ms", ctx) is not None   # the metric stays
    out = capsys.readouterr().out
    assert "too wide a tie" in out and "by batch phase" not in out


def test_tiny_cells_read_all_five_on_the_cpu(monkeypatch, capsys):
    """The tiny text cell, traced, on the CPU backend, with this PR's
    entries of ``BENCHMARK.json`` mirrored onto the tiny cells in memory
    (``rehearsal.json`` is the benchmark's own): every reader finds its
    books, and a rehearsal prints none of them as a value."""
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    mirrored = [dict(m, workloads=[w.replace("kdd12_", "tiny_")
                                   for w in m["workloads"]])
                for m in bench["per_layer"] if m["name"] in NEW]
    assert len(mirrored) == len(NEW)
    real = R.load_json

    def load_json(*parts):
        found = real(*parts)
        if parts[-1] == "rehearsal.json":
            found["per_layer"] += mirrored
        return found

    monkeypatch.setattr(R, "load_json", load_json)
    P._cache.clear()
    seen = {}
    for name in NEW[:4]:
        spec = real(R.HERE, "metrics", name + ".json")
        module = R.plugin("readers", spec["reader"])
        monkeypatch.setattr(
            module, "read", lambda ctx, params, _read=module.read: seen
            .setdefault(params.get("phase") or params.get("counter")
                        or params["state"], _read(ctx, params)))
    assert R.main(["--workload", "tiny_fm_text", "--seed", "3", "--seconds",
                   "1", "--trace", "1", "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    for name in NEW[:4]:
        assert line["metrics"][name]["value"] is None
    assert "service_first_frame_ms" not in line["metrics"]
    # what the readers read before the rehearsal blanked it
    assert set(seen) == {"first_put", "withheld", "merge_seconds",
                         "window_wait_seconds"}
    assert all(v is not None and v >= 0 for v in seen.values()), seen
    assert seen["first_put"] > seen["withheld"] > 0
    assert "ms from the reset's start" in out
    assert "convert pool workers' seconds in the window" in out


def test_the_tiny_service_cell_reads_the_first_frame_on_the_cpu(
        monkeypatch, capsys):
    """The fifth metric's cell: the tiny mirror of ``kdd12_fm_service``
    through its process fleet, ``BENCHMARK.json`` mirrored in memory as
    ``test_service_cell.py`` does."""
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kdd12_", "tiny_"))
        if parts[-1] == "service_text_epochs.json":
            return dict(real(*parts), frame_store_bytes=real(
                R.HERE, "configs", "tiny_fm_svc.json")["service"][
                    "frame_store_bytes"])
        return real(*parts)

    monkeypatch.setattr(R, "load_json", load_json)
    P._cache.clear()
    assert R.main(["--workload", "tiny_fm_service", "--seed", "2147483999",
                   "--seconds", "1", "--trace", "1", "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    for name in NEW:
        assert line["metrics"][name]["value"] is None
    assert "first frame of part 0 after" in out
    assert "service_locate" in out and "the frame's own wait" in out
