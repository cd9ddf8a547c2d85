"""The program's tracing as ISSUE 24 left it: one span primitive on two
clocks (the thread's ring and the profiler's), spans at the epoch
boundary, named scopes in the learners' steps, and the compilation
counters; and what ISSUE 35 added: a batch's id on every span of its
life, the serial stage's ``merge`` span, what the convert pool waits for,
and the service client's ``recv`` by what it waits for. Everything runs
with no ``DMLC_TPU_*`` variable set."""

import contextlib
import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.models import FMLearner, LinearLearner, _loop
from dmlc_tpu.ops.sparse import EllBatch
from dmlc_tpu.utils import telemetry

SCOPES = ("fm_gather", "fm_interaction", "fm_loss", "fm_optimizer",
          "fm_sink")


@pytest.fixture(autouse=True)
def _no_knobs(monkeypatch):
    for key in [k for k in os.environ if k.startswith("DMLC_TPU_")]:
        monkeypatch.delenv(key)
    yield
    telemetry.set_scope(None)


def _host_events(trace_dir):
    """``[(name, duration_ns)]`` of the ``/host:CPU`` plane of the one
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(e.name, e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


# ---------------- A: one span primitive, two clocks ----------------

@pytest.mark.parametrize("where", ["caller", "worker"])
def test_span_writes_the_ring_and_the_profilers_host_plane(tmp_path, where):
    booked = []

    def work():
        with telemetry.span("tracing_probe_" + where, book=booked.append,
                            rows=3) as sp:
            time.sleep(0.005)
        booked.append(sp)

    jax.profiler.start_trace(str(tmp_path))
    try:
        if where == "worker":
            t = threading.Thread(target=work)
            t.start()
            t.join()
        else:
            work()
    finally:
        jax.profiler.stop_trace()
    dt, sp = booked
    assert dt == sp.dt >= 0.005
    (row,) = [s for s in telemetry.spans_snapshot()
              if s["name"] == "tracing_probe_" + where]
    # the ring, the caller and the stage counter got one (t0, dt)
    assert row["dur_ns"] == int(sp.dt * 1e9)
    assert row["start_ns"] == int(sp.t0 * 1e9)
    assert row["labels"] == {"rows": 3}
    (event,) = [e for e in _host_events(str(tmp_path))
                if e[0] == "dmlc_tpu:tracing_probe_" + where]
    assert event[1] == pytest.approx(sp.dt * 1e9, rel=0.2)


def test_span_exclude_and_profiler_only_forms():
    before = telemetry.span_counts().get("tracing_probe_x", 0)
    t0 = time.perf_counter()
    with telemetry.span("tracing_probe_x") as sp:
        time.sleep(0.004)
        sp.exclude(0.003)
    # what was excluded is off the span, however long the sleep overran
    assert 0.001 <= sp.dt <= time.perf_counter() - t0 - 0.003
    booked = []
    with telemetry.span("tracing_probe_x", book=booked.append) as quiet:
        quiet.skip_ring()     # decided inside the block: profiler and
    assert booked == [quiet.dt] and quiet.dt >= 0.0   # book only
    assert telemetry.span_counts()["tracing_probe_x"] - before == 1
    with telemetry.span("tracing_probe_x") as late:
        late.labels["rows"] = 7        # a label known only by then
    assert [s["labels"] for s in telemetry.spans_snapshot()
            if s["name"] == "tracing_probe_x"][-1] == {"rows": 7}
    before += 1
    # the ring-only form stays for what is timed without a block (the
    # service tier's sends and RPCs)
    telemetry.record_span("tracing_probe_x", quiet.t0, quiet.dt)
    assert telemetry.span_counts()["tracing_probe_x"] - before == 2


def test_span_is_inert_without_jax_imported(tmp_path):
    """A process that never imported jax can have no profiler session:
    the span records the ring and imports nothing."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from dmlc_tpu.utils import telemetry\n"
            "with telemetry.span('p') as sp:\n"
            "    pass\n"
            "assert telemetry.span_counts() == {'p': 1}, "
            "telemetry.span_counts()\n"
            "assert 'jax' not in sys.modules\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr


# ---------------- B: the epoch boundary ----------------

def _corpus(tmp_path, n=96, d=6):
    rng = np.random.default_rng(0)
    lines = [f"{i % 2} " + " ".join(f"{j}:{rng.normal():.4f}"
                                    for j in range(d)) for i in range(n)]
    p = tmp_path / "c.libsvm"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def test_epoch_boundary_spans_once_per_epoch_in_order(tmp_path):
    parser = create_parser(_corpus(tmp_path), 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense")
    epochs, per_epoch = 3, 6
    for _ in range(epochs):
        assert sum(1 for _ in it) == per_epoch
        it.reset()
    label = it.stats()["pipeline"]
    it.close()
    mine = telemetry.spans_snapshot(label)
    names = [s["name"] for s in mine]
    for once in ("epoch_reset", "first_batch"):
        assert names.count(once) == epochs, (once, names.count(once))
    assert names.count("next") == epochs * per_epoch
    order = [n for n in names
             if n in ("epoch_reset", "first_batch", "producer_start")]
    # the first reset() after an epoch's end shows a consumer that loops:
    # from then on an epoch's end starts the next one's producer, ahead of
    # the reset() that adopts it (the last one here is never pulled from)
    lazy = ["first_batch", "producer_start", "epoch_reset"]
    assert order == lazy + ["first_batch", "producer_start",
                            "producer_start", "epoch_reset",
                            "first_batch", "producer_start", "epoch_reset"]
    starts = [s for s in mine if s["name"] == "producer_start"]
    assert [s["labels"]["epoch"] for s in starts] == [0, 1, 2, 3]
    # a lazy producer_start and the first pull's own 'next' lie inside
    # first_batch; an adopted producer leaves the pull alone there
    firsts = [s for s in mine if s["name"] == "first_batch"]
    for outer, inside in zip(firsts, (["next", "producer_start"],
                                      ["next", "producer_start"], ["next"])):
        lo, hi = outer["start_ns"], outer["start_ns"] + outer["dur_ns"]
        inner = [s for s in mine if s["name"] in ("producer_start", "next")
                 and lo <= s["start_ns"] and
                 s["start_ns"] + s["dur_ns"] <= hi]
        assert sorted(s["name"] for s in inner) == inside
    assert all("waited_s" in s["labels"] for s in mine
               if s["name"] == "next")


def test_program_spans_reach_the_profiler_from_every_pipeline_thread(
        tmp_path, monkeypatch):
    monkeypatch.setenv("DMLC_TPU_NO_NATIVE_READER", "1")
    parser = create_parser(_corpus(tmp_path), 0, 1, "libsvm", threaded=False,
                           chunk_bytes=1024)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense",
                    convert_workers=2)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        n = sum(1 for _ in it)
        it.reset()
    finally:
        jax.profiler.stop_trace()
    busy = it.stats()["stage_busy"]
    pool = it.stats()["pool"]
    label = it.stats()["pipeline"]
    it.close()
    seen = {}
    for name, dur in _host_events(trace_dir):
        if name.startswith("dmlc_tpu:"):
            seen.setdefault(name[len("dmlc_tpu:"):], []).append(dur)
    # 'merge' is recorded on a pool thread, inside the serial stage
    assert {"read", "parse", "merge", "convert", "dispatch", "next",
            "first_batch", "producer_start", "epoch_reset"} <= set(seen), \
        sorted(seen)
    assert len(seen["dispatch"]) == n and len(seen["epoch_reset"]) == 1
    # the ring's durations still are the stage counters' (one story): the
    # serial stage's merge is a span of its own name whose seconds the
    # 'convert' counter holds (with what else the stage spent beyond its
    # source), and stats()['pool'] counts them alone
    ring = {}
    for s in telemetry.spans_snapshot(label):
        ring[s["name"]] = ring.get(s["name"], 0.0) + s["dur_ns"] * 1e-9
    for stage in ("read", "dispatch"):
        assert ring[stage] == pytest.approx(busy[stage], rel=0.05, abs=2e-3)
    assert ring["convert"] + ring["merge"] == pytest.approx(
        busy["convert"], rel=0.05, abs=2e-3)
    assert ring["convert"] < busy["convert"]
    assert ring["merge"] == pytest.approx(pool["merge_seconds"], abs=1e-6)


# ---------------- B2: a batch's id, the merge, the pool's waits ----------

def _libfm_corpus(tmp_path, rows=3000, fields=6, ids=400):
    rng = np.random.default_rng(1)
    p = tmp_path / "c.libfm"
    p.write_text("".join(
        f"{i % 2} " + " ".join(f"{f}:{int(rng.integers(0, ids))}:1"
                               for f in range(fields)) + "\n"
        for i in range(rows)))
    return str(p)


@contextlib.contextmanager
def _feed(kind, tmp_path):
    """A ``DeviceIter`` over ``kind``'s path, and whether a pool converts
    for it: the convert pool over a local parser, the warm snapshot feed,
    an in-process service fleet."""
    if kind == "snapshot":
        parser = create_parser(_corpus(tmp_path, n=160), 0, 1, "libsvm",
                               threaded=False,
                               snapshot=str(tmp_path / "c.snapshot"))
        it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense",
                        pack_aux=True)
        assert sum(1 for _ in it) == 10     # the cold pass writes it
        it.reset()
        yield it, False
        return
    corpus = _libfm_corpus(tmp_path)
    kwargs = dict(num_col=400, batch_size=256, layout="ell", max_nnz=6,
                  convert_workers=2)
    if kind == "pool":
        yield DeviceIter(create_parser(corpus, 0, 1, "libfm", threaded=False,
                                       chunk_bytes=8192), **kwargs), True
        return
    from dmlc_tpu.service import LocalFleet, ServiceParser

    fleet = LocalFleet(corpus, 4, num_workers=2, parser={
        "format": "libfm", "threaded": False, "chunk_bytes": 8192})
    try:
        yield DeviceIter(ServiceParser(fleet.address), **kwargs), True
    finally:
        fleet.close()


def _epochs(it, epochs=2):
    """Run ``epochs`` epochs; the pipeline's ring spans, the batches an
    epoch and the epoch label of the first one run here."""
    first = it._epoch
    for _ in range(epochs):
        per_epoch = sum(1 for _ in it)
        it.reset()
    return telemetry.spans_snapshot(it.stats()["pipeline"]), per_epoch, first


@pytest.mark.parametrize("kind", ["pool", "snapshot", "service"])
def test_every_span_of_a_batchs_life_carries_one_id(tmp_path, kind):
    with _feed(kind, tmp_path) as (it, converts):
        spans, per_epoch, first = _epochs(it)
        it.close()
    main = threading.get_ident()
    want = {"dispatch", "next"} | ({"merge", "convert"} if converts
                                   else set())
    for epoch in (first, first + 1):
        for batch in range(per_epoch):
            mine = [s for s in spans if s["labels"].get("epoch") == epoch
                    and s["labels"].get("batch") == batch
                    and s["name"] in want]
            names = [s["name"] for s in mine]
            # one put, one conversion and one hand-out a batch; the merge
            # may take several incoming blocks
            assert set(names) == want, (epoch, batch, names)
            for once in want - {"merge"}:
                assert names.count(once) == 1, (epoch, batch, names)
            by = {s["name"]: s for s in mine}
            assert by["dispatch"]["tid"] == by["next"]["tid"] == main
            if converts:      # across the pool's threads
                assert by["merge"]["tid"] != main
                assert by["convert"]["tid"] != main
            if kind == "service":   # the part's trace id stays beside it
                assert by["dispatch"].get("trace_id")
    # no id is given twice
    puts = [(s["labels"]["epoch"], s["labels"]["batch"]) for s in spans
            if s["name"] == "dispatch" and s["labels"]["epoch"] >= first]
    assert len(puts) == len(set(puts)) == 2 * per_epoch


@pytest.mark.parametrize("kind", ["pool", "snapshot", "service"])
def test_the_boundarys_phases_come_in_order_once_an_epoch(tmp_path, kind):
    with _feed(kind, tmp_path) as (it, converts):
        spans, _, first = _epochs(it, epochs=3)
        it.close()

    def end(s):
        return s["start_ns"] + s["dur_ns"]

    for epoch in (first + 1, first + 2):   # those a reset() opened here
        def one(name, **labels):
            found = [s for s in spans if s["name"] == name
                     and all(s["labels"].get(k) == v
                             for k, v in dict(labels, epoch=epoch).items())]
            assert len(found) == 1, (name, epoch, labels, len(found))
            return found[0]

        reset, start = one("epoch_reset"), one("producer_start")
        first_batch = one("first_batch")
        put, out = one("dispatch", batch=0), one("next", batch=0)
        assert reset["start_ns"] <= end(reset) <= first_batch["start_ns"]
        # the convert pool over a local source, once its consumer has been
        # seen to loop, starts at the END of the epoch before, ahead of the
        # reset(); every other producer inside the epoch's first pull
        ahead = kind == "pool" and epoch == first + 2
        if ahead:
            assert end(start) <= reset["start_ns"]
        else:
            assert first_batch["start_ns"] <= start["start_ns"]
        assert end(start) <= put["start_ns"] < end(put) <= end(out)
        assert end(out) <= end(first_batch)
        if converts:
            merges = [s for s in spans if s["name"] == "merge"
                      and s["labels"] == {"epoch": epoch, "batch": 0}]
            convert = one("convert", batch=0)
            assert start["start_ns"] <= merges[0]["start_ns"]
            assert end(merges[-1]) <= convert["start_ns"]
            assert end(convert) <= put["start_ns"]


@pytest.mark.parametrize("kind", ["source", "batches"])
def test_a_mid_epoch_restore_continues_the_batch_count(tmp_path, kind):
    parser = create_parser(_corpus(tmp_path, n=640), 0, 1, "libsvm",
                           threaded=False, chunk_bytes=4096)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense")
    for _ in range(12):
        next(it)
    state = it.state_dict()
    assert state["kind"] == "source"    # annotated blocks: a seek
    if kind == "batches":               # a source without them: a replay
        state = {"kind": "batches", "batches": state["batches"]}
    it.load_state(state)
    assert sum(1 for _ in it) == 28
    label = it.stats()["pipeline"]
    it.close()
    handed = [s["labels"]["batch"] for s in telemetry.spans_snapshot(label)
              if s["name"] == "next"]
    assert handed == list(range(40))


def test_natural_blocks_are_their_own_batches(tmp_path):
    parser = create_parser(_corpus(tmp_path), 0, 1, "libsvm", threaded=False,
                           chunk_bytes=1024)
    it = DeviceIter(parser, num_col=6, batch_size=None, layout="bcoo")
    n = sum(1 for _ in it)
    label = it.stats()["pipeline"]
    it.close()
    spans = telemetry.spans_snapshot(label)
    for name in ("convert", "dispatch", "next"):
        assert [s["labels"]["batch"] for s in spans
                if s["name"] == name] == list(range(n)), name


def test_stats_now_is_the_rings_clock(tmp_path):
    parser = create_parser(_corpus(tmp_path), 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense")
    next(it)
    now = it.stats()["now"]
    next(it)
    label = it.stats()["pipeline"]
    it.close()
    handed = [s for s in telemetry.spans_snapshot(label)
              if s["name"] == "next"]
    assert len(handed) == 2
    # the reading lies between the two hand-outs, on their clock
    assert (handed[0]["start_ns"] + handed[0]["dur_ns"] <= now * 1e9
            <= handed[1]["start_ns"])


class _PoolClock:
    """``OrderedWorkerPool._now`` for one pool: a time that only the test
    moves, with every thread's readings of it and the interval each thread
    closed last (the pool names it as it reads). The test moves the time
    only while every thread is held where its schedule wants it: which
    state an interval is booked to is the schedule's, and no scheduler's
    or sleep's."""

    def __init__(self):
        self.now = 0.0
        self.cond = threading.Condition()
        self.readings = {}
        self.closed = {}

    def __call__(self, closes: str) -> float:
        with self.cond:
            thread = threading.current_thread()
            self.readings.setdefault(thread, []).append(self.now)
            self.closed[thread] = closes
            self.cond.notify_all()
            return self.now

    def advance_when(self, seconds: float, held) -> None:
        """Move the time on once ``held()`` says every thread is where the
        schedule wants it (polled: the pool's own changes do not notify)."""
        deadline = time.monotonic() + 30
        with self.cond:
            while not held():
                assert time.monotonic() < deadline, "the schedule hangs"
                self.cond.wait(0.002)
            self.now += seconds


@pytest.mark.parametrize("slow", ["work_fn", "consumer"])
def test_the_pool_counts_what_its_workers_wait_for(monkeypatch, slow):
    from dmlc_tpu.io.threaded_iter import OrderedWorkerPool

    clock, tick = _PoolClock(), 0.01
    monkeypatch.setattr(OrderedWorkerPool, "_now",
                        lambda self, closes: clock(closes))
    workers, items, ahead = 2, 24, 2
    consumer = threading.current_thread()
    got, pulled = [], []

    def source():
        for i in range(items):
            pulled.append(i)
            yield i
        pulled.append(None)     # ran dry: the workers leave

    def consumer_waits():
        # it has read the clock for its wait (both workers are at work, so
        # the window let them by: every earlier item is handed over)
        return clock.closed.get(consumer) == "asked"

    def shut_out():
        # every worker waits at a shut window, or left at the stream's end
        if pulled[-1] is None:
            return not any(t.is_alive() for t in pool._threads)
        return len(pulled) - len(got) >= ahead and all(
            clock.closed.get(t) in ("start", "work") for t in pool._threads)

    # slow work: the workers work side by side, ``tick`` an item, while the
    # consumer waits for the first of the two
    together = threading.Barrier(
        workers, action=lambda: clock.advance_when(tick, consumer_waits))

    def work(item):
        if slow == "work_fn":
            together.wait(timeout=30)
        return item

    label = telemetry.new_pipeline_label("pool-probe")

    def read(metric, by):
        return telemetry.REGISTRY.sum_by(metric, by, pool="probe",
                                         pipeline=label)

    with telemetry.scope(label):
        pool = OrderedWorkerPool(source, work, num_workers=workers,
                                 max_ahead=ahead, counter_label="probe")
    while (item := pool.next()) is not None:
        got.append(item)
        if slow == "consumer":      # ``tick`` an item, the window shut
            clock.advance_when(tick, shut_out)
    for t in pool._threads:      # the workers leave at the stream's end
        t.join(timeout=10)
        assert not t.is_alive()
    wall = clock.now
    lifetimes = sum(clock.readings[t][-1] - clock.readings[t][0]
                    for t in pool._threads)
    pool.destroy()
    assert got == list(range(items))
    seconds = read(telemetry.POOL_SECONDS_METRIC, "state")
    four = sum(seconds[s] for s in ("window_wait", "pull_wait", "pull",
                                    "work"))
    # the four states are the workers' time, from a worker's first reading
    # of the clock to its last
    assert four == pytest.approx(lifetimes, rel=1e-9)
    share = seconds["window_wait"] / four
    if slow == "work_fn":       # the feed sets the pace: no back-pressure
        assert wall == pytest.approx(items / workers * tick)
        assert four == pytest.approx(workers * wall, rel=1e-9)
        assert share < 0.15 and seconds["work"] / four > 0.7
        assert pool.stall_seconds > 0.05
    else:                       # the consumer is behind: the window is shut
        assert wall == pytest.approx(items * tick)
        # (the source ran dry, and the workers left, when the window opened
        # for the item after the last)
        assert workers * (wall - 4 * tick) <= four <= workers * wall
        assert share > 0.7 and seconds["ready_wait"] > 0.1
    assert read(telemetry.POOL_EVENTS_METRIC, "kind") == {"items": items}


@pytest.mark.parametrize("what", ["pool", "ring"])
def test_device_iter_stats_carry_the_pools_books_over_epochs(tmp_path, what):
    parser = create_parser(_corpus(tmp_path), 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense",
                    convert_workers=2)
    zero = it.stats()["pool"]
    assert set(zero) == {
        "window_wait_seconds", "pull_wait_seconds", "pull_seconds",
        "work_seconds", "ready_wait_seconds", "merge_seconds", "items",
        "stall_seconds", "ring_hits", "ring_misses"}
    assert not any(zero.values())
    seen = []
    for _ in range(3):
        for n, _batch in enumerate(it, 1):
            if n == 6:
                # at the epoch's last hand-out: its end starts the next
                # epoch's pool (and ring), which would be the one read
                seen.append(it.stats())
        assert n == 6
        it.reset()
    it.close()
    pools = [s["pool"] for s in seen]
    if what == "pool":      # summed over the pools of successive epochs
        assert [p["items"] for p in pools] == [6, 12, 18]
        for key in ("pull_seconds", "work_seconds", "merge_seconds"):
            assert 0 < pools[0][key] < pools[1][key] < pools[2][key], key
        assert pools[2]["merge_seconds"] <= pools[2]["pull_seconds"]
        assert pools[2]["stall_seconds"] == seen[2]["host_stall_seconds"]
    else:                   # a ring lives one producer; its books stay
        live = [s["staging_ring"] for s in seen]
        assert all(r is not None for r in live)
        used = [p["ring_hits"] + p["ring_misses"] for p in pools]
        assert used[0] == live[0]["hits"] + live[0]["misses"]
        assert used[0] <= used[1] <= used[2]
        assert used[2] == sum(r["hits"] + r["misses"] for r in live)


def test_the_service_clients_recv_is_split_by_what_it_waits_for(tmp_path):
    from dmlc_tpu.service import LocalFleet, ServiceParser

    parts = 4
    fleet = LocalFleet(_libfm_corpus(tmp_path), parts, num_workers=2,
                       parser={"format": "libfm", "threaded": False,
                               "chunk_bytes": 8192})
    telemetry.reset_spans()
    try:
        client = ServiceParser(fleet.address)
        with telemetry.scope("split-probe"):
            for _ in range(2):
                client.before_first()
                while client.next_block() is not None:
                    pass
        stats = client.service_stats()
        client.close()
    finally:
        fleet.close()
    split = {k: stats[k + "_seconds"]
             for k in ("locate", "connect", "frame", "drain")}
    # (a part served whole has no trailing END to drain: its window stops
    # at the count the HELLO gave)
    assert all(split[k] > 0 for k in ("locate", "connect", "frame")), split
    # the four are what recv_seconds books, less the client's own steps
    # between them
    # (little at the size of the cells; here a part is a few blocks and
    # the machine may be busy with other tests)
    assert sum(split.values()) <= stats["recv_seconds"]
    assert sum(split.values()) >= 0.6 * stats["recv_seconds"] - 5e-3
    spans = telemetry.spans_snapshot("split-probe")
    ring = {}
    for s in spans:
        ring[s["name"]] = ring.get(s["name"], 0.0) + s["dur_ns"] * 1e-9
    for what, name in (("locate", "service_locate"),
                       ("connect", "service_connect"),
                       ("frame", "service_recv"),
                       ("drain", "service_drain")):
        assert ring.get(name, 0.0) == pytest.approx(split[what],
                                                    abs=1e-6), name
    # every wait names its part and epoch; a part's first frame says so,
    # and the waits of one part share the grant's trace id
    waits = [s for s in spans if s["name"].startswith("service_")
             and s["name"] != "service_decode"]
    assert all(set(s["labels"]) >= {"part", "epoch"} for s in waits)
    firsts = [s for s in waits if s["labels"].get("first")]
    assert [(s["labels"]["epoch"], s["labels"]["part"]) for s in firsts] == [
        (e, p) for e in (0, 1) for p in range(parts)]
    for epoch in (0, 1):
        for part in range(parts):
            ids = {s.get("trace_id") for s in waits
                   if s["labels"]["epoch"] == epoch
                   and s["labels"]["part"] == part}
            assert len(ids) == 1 and None not in ids, (epoch, part, ids)


def _ell_batch(b=32, k=4, d=50, seed=0):
    rng = np.random.default_rng(seed)
    return EllBatch(
        indices=jnp.asarray(rng.integers(0, d, (b, k)), jnp.int32),
        values=jnp.asarray(rng.normal(size=(b, k)), jnp.float32),
        label=jnp.asarray(rng.integers(0, 2, b), jnp.float32),
        weight=jnp.ones(b, jnp.float32))


def test_step_dispatch_and_epoch_sync_spans(tmp_path):
    model = LinearLearner(num_col=6, layout="dense")
    parser = create_parser(_corpus(tmp_path), 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=16,
                    layout="dense")
    before = telemetry.span_counts()
    model.fit_epoch(it)
    it.close()
    after = telemetry.span_counts()
    assert after["step_dispatch"] - before.get("step_dispatch", 0) == 6
    assert after["epoch_sync"] - before.get("epoch_sync", 0) == 1


# ---------------- C: named scopes in the step ----------------

def _fm(layout="ell", d=50, **kw):
    return FMLearner(num_col=d, num_factors=4, layout=layout, seed=1, **kw)


def _dense_batch(b=32, d=50, pad=1):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.normal(size=(b, d + pad)), jnp.float32),
            jnp.asarray(rng.integers(0, 2, b), jnp.float32),
            jnp.ones(b, jnp.float32))


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_name_is_in_the_fm_steps_lowered_text(scope):
    model = _fm()
    text = model._step.lower(model.params, model.opt_state,
                             _ell_batch()).as_text(debug_info=True)
    assert f"/{scope}" in text or f"({scope})" in text, scope


@pytest.mark.parametrize("scope", ["fm_gather", "fm_loss", "fm_optimizer",
                                   "fm_sink"])
def test_linear_learner_uses_the_same_scope_names(scope):
    model = LinearLearner(num_col=50, layout="ell")
    text = model._step.lower(model.params, model.opt_state,
                             _ell_batch()).as_text(debug_info=True)
    assert f"/{scope}" in text or f"({scope})" in text, scope


def test_the_scatter_is_named_as_the_transposed_gather():
    """The gradient comes from ``jax.value_and_grad``, so the scatter is
    written nowhere: the compiled step's scatter operations carry
    ``transpose(jvp(fm_gather))`` in their ``op_name``, and the forward
    gathers ``jvp(fm_gather)`` without it; that is what a device trace's
    ``tf_op`` stat shows (cellbench/readers/_program.op_names)."""
    model = _fm()
    text = model._step.lower(model.params, model.opt_state,
                             _ell_batch()).compile().as_text()
    names = dict(re.findall(
        r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?op_name=\"([^\"]*)\"", text,
        flags=re.M))
    # (the sink's ``.at[-1].set`` is a scatter too: ``fm_sink/scatter``,
    # and the scatter's combiner parameters are named plain "scatter-add")
    scatters = {k: v for k, v in names.items() if v.endswith("/scatter-add")}
    assert scatters, sorted(names.values())
    assert all("transpose(jvp(fm_gather))" in v for v in scatters.values())
    forward = [k for k, v in names.items()
               if "fm_gather" in v and "transpose(" not in v]
    assert forward and not set(forward) & set(scatters)
    assert all(any(s in v for v in names.values()) for s in SCOPES)


@pytest.mark.parametrize("what", ["sort", "kernel", "forward", "no_scatter"])
def test_the_kernel_route_keeps_the_transposed_gathers_name(
        monkeypatch, what):
    """ISSUE 25: on the kernel route the backward is a sort and a Pallas
    kernel in place of two scatter-adds; called inside the ``fm_gather``
    scope, their instructions still read ``transpose(jvp(fm_gather))`` in
    ``hlo_scopes()``, so ``fm_grad_scatter_device_ms`` counts them and
    ``fm_gather_device_ms`` does not. (An optimizer of the caller's own:
    the dense gradient exists.)"""
    import optax

    from dmlc_tpu.ops import grad_scatter as gs

    real = gs.grad_scatter_pallas
    monkeypatch.setattr(gs, "grad_scatter_pallas", lambda *a, **kw: real(
        *a, **dict(kw, interpret=True)))   # the CPU interprets the kernel
    monkeypatch.setattr(gs, "grad_scatter_route", lambda *a: "kernel")
    model = _fm(d=4999, optimizer=optax.adam(0.05))
    model.step(_ell_batch(d=5000))
    scopes = model.hlo_scopes()
    backward = {k: v for k, v in scopes.items()
                if "transpose(jvp(fm_gather))" in v}
    if what == "sort":
        sorts = [k for k, v in scopes.items() if v.endswith("/sort")]
        assert sorts and all(k in backward for k in sorts), sorts
    elif what == "kernel":
        kernel = [k for k, v in scopes.items() if "/grad_scatter/" in v]
        assert kernel and all(k in backward for k in kernel)
    elif what == "forward":
        gathers = [k for k, v in scopes.items()
                   if v.endswith("/gather") and "fm_gather" in v]
        forward = set(gathers) - set(backward)   # the two table gathers
        assert forward and set(gathers) & set(backward)   # and the permute
    else:
        assert not [v for v in scopes.values() if v.endswith("/scatter-add")]


@pytest.mark.parametrize("what", ["no_transposed_gather", "kernel",
                                  "permute_and_sort", "no_scatter",
                                  "interaction"])
def test_the_fused_route_reads_fm_optimizer(monkeypatch, what):
    """ISSUE 31: where the kernel finishes Adam itself no gradient is
    scattered, so nothing reads ``transpose(jvp(fm_gather))``
    (``fm_grad_scatter_device_ms`` reads 0); the sort of the slots (the
    forward is XLA's here), the permute of the cotangent rows and the
    kernel read ``fm_optimizer``, and the interaction's backward reads
    ``transpose(jvp(fm_interaction))`` as on the dense route."""
    from dmlc_tpu.ops import grad_scatter as gs

    real = gs.grad_scatter_pallas
    monkeypatch.setattr(gs, "grad_scatter_pallas", lambda *a, **kw: real(
        *a, **dict(kw, interpret=True)))   # the CPU interprets the kernel
    monkeypatch.setattr(gs, "grad_scatter_route", lambda *a: "kernel")
    model = _fm(d=4999)
    model.step(_ell_batch(d=5000))
    scopes = model.hlo_scopes()
    update = {k: v for k, v in scopes.items() if "/fm_optimizer/" in v}
    if what == "no_transposed_gather":
        assert not [v for v in scopes.values()
                    if "transpose(jvp(fm_gather))" in v]
        assert [v for v in scopes.values() if "/fm_gather/" in v]
    elif what == "kernel":
        kernel = [k for k, v in scopes.items() if "/grad_scatter_adam/" in v]
        assert kernel and all(k in update for k in kernel)
    elif what == "permute_and_sort":
        for op in ("/gather", "/sort"):
            named = [k for k, v in update.items() if v.endswith(op)]
            assert named, (op, sorted(update.values()))
    elif what == "no_scatter":
        assert not [v for v in scopes.values() if v.endswith("/scatter-add")]
    else:
        assert [v for v in scopes.values()
                if "transpose(jvp(fm_interaction))" in v]


@pytest.mark.parametrize("what", ["no_transposed_gather", "kernel",
                                  "permute_and_sort", "sink",
                                  "interaction"])
def test_the_fused_ffm_route_reads_ffm_optimizer(monkeypatch, what):
    """ISSUE 34: the field-aware FM's twin. Where the kernel finishes
    AdaGrad itself nothing reads ``transpose(jvp(ffm_gather))``
    (``ffm_grad_scatter_device_ms`` reads 0); the sort (the forward is
    XLA's here), the permute of the cotangent rows and the kernel, still
    named ``grad_scatter``, read ``ffm_optimizer``
    (``ffm_optimizer_device_ms`` holds them and the sink row)."""
    from dmlc_tpu.models import FFMLearner
    from dmlc_tpu.ops import grad_scatter as gs

    real = gs.grad_scatter_pallas
    monkeypatch.setattr(gs, "grad_scatter_pallas", lambda *a, **kw: real(
        *a, **dict(kw, interpret=True)))   # the CPU interprets the kernel
    monkeypatch.setattr(gs, "grad_scatter_route", lambda *a: "kernel")
    model = FFMLearner(4999, 3, 4)
    assert model.table_update_route(8 * 64) == ("fused", "adagrad")
    rng = np.random.default_rng(0)
    model.step(EllBatch(
        jnp.asarray(rng.integers(0, 4999, (64, 8)), jnp.int32),
        jnp.ones((64, 8), jnp.float32),
        jnp.asarray(rng.integers(0, 2, 64), jnp.float32),
        jnp.ones(64, jnp.float32),
        jnp.asarray(rng.integers(0, 3, (64, 8)), jnp.uint8)))
    scopes = model.hlo_scopes()
    update = {k: v for k, v in scopes.items() if "/ffm_optimizer/" in v}
    if what == "no_transposed_gather":
        assert not [v for v in scopes.values()
                    if "transpose(jvp(ffm_gather))" in v]
        assert [v for v in scopes.values() if "/ffm_gather/" in v]
    elif what == "kernel":
        kernel = [k for k, v in scopes.items() if "/grad_scatter/" in v]
        assert kernel and all(k in update for k in kernel)
        assert not [v for v in scopes.values() if "grad_scatter_adam" in v]
    elif what == "permute_and_sort":
        for op in ("/gather", "/sort"):
            named = [k for k, v in update.items() if v.endswith(op)]
            assert named, (op, sorted(update.values()))
    elif what == "sink":
        assert [v for v in scopes.values() if "/ffm_sink/" in v]
        assert not [v for v in scopes.values() if v.endswith("/scatter-add")]
    else:
        assert [v for v in scopes.values()
                if "transpose(jvp(ffm_interaction))" in v]


def _strip_metadata(hlo: str) -> str:
    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r"(?:\d+ .*\n)*", "\n", hlo)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)


@pytest.mark.parametrize("learner,layout", [
    ("fm", "ell"), ("fm", "dense"), ("fm", "bcoo"), ("linear", "ell"),
    ("linear", "dense")])
def test_scopes_change_metadata_only(monkeypatch, learner, layout):
    """The optimised HLO of the step, metadata stripped, is byte-identical
    with and without the named scopes."""
    def build():
        if learner == "fm":
            model = _fm(layout)
        else:
            model = LinearLearner(num_col=50, layout=layout)
        if layout == "ell":
            batch = _ell_batch()
        elif layout == "bcoo":
            from jax.experimental import sparse as jsparse

            x, y, w = _dense_batch(pad=0)
            batch = (jsparse.BCOO.fromdense(x, nse=32 * 50), y, w)
        else:
            batch = _dense_batch()
        return model._step.lower(model.params, model.opt_state,
                                 batch).compile().as_text()

    scoped = build()
    assert "fm_optimizer" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = build()
    assert "fm_optimizer" not in plain
    assert _strip_metadata(scoped) == _strip_metadata(plain)


def _host_learner_and_batch(kind):
    rng = np.random.default_rng(3)
    ell = EllBatch(indices=rng.integers(0, 12, (16, 4)).astype(np.int32),
                   values=rng.normal(size=(16, 4)).astype(np.float32),
                   label=rng.integers(0, 2, 16).astype(np.float32),
                   weight=np.ones(16, np.float32))
    dense = (rng.normal(size=(16, 8)),          # float64, as numpy makes it
             rng.integers(0, 2, 16).astype(np.float32),
             np.ones(16, np.float32))
    if kind == "fm_dense":
        return FMLearner(num_col=7, layout="dense"), dense
    if kind == "fm_ell":
        return FMLearner(num_col=12, num_factors=4, layout="ell"), ell
    if kind == "linear_dense":
        return LinearLearner(num_col=7, layout="dense"), dense
    if kind == "linear_ell":
        return LinearLearner(num_col=12, layout="ell"), ell
    from dmlc_tpu.models.als import AlsLearner

    return AlsLearner(num_users=16, num_items=12, num_factors=3), ell._replace(
        label=np.arange(16, dtype=np.float32))


@pytest.mark.parametrize("kind", ["fm_dense", "fm_ell", "linear_dense",
                                  "linear_ell", "als"])
def test_step_takes_a_batch_of_host_arrays(kind):
    """The tracing's bookkeeping in ``step()`` must not ask of a batch what
    only a jax array has: numpy leaves carry no ``.sharding``."""
    model, batch = _host_learner_and_batch(kind)
    first = float(model.step(batch))
    assert np.isfinite(first)
    assert np.isfinite(float(model.step(batch)))
    avals = jax.tree_util.tree_leaves(model._step_avals)
    assert avals and all(a.dtype != np.float64 for a in avals)
    # and hlo_scopes() compiles for those shapes
    scopes = model.hlo_scopes()
    assert scopes and "" in scopes.values()
    if kind != "als":
        assert any("fm_optimizer" in v for v in scopes.values())


def test_step_survives_a_leaf_the_bookkeeping_cannot_describe(monkeypatch):
    from dmlc_tpu.models import _loop

    def refuse(tree):
        raise TypeError("no shape")

    monkeypatch.setattr(_loop, "_abstract", refuse)
    model = _fm()
    assert np.isfinite(float(model.step(_ell_batch())))
    assert model.hlo_scopes() == {}


# ---------------- D: the compilation counters ----------------

def test_jit_compilations_rise_on_a_new_shape_and_not_on_a_repeat():
    model = _fm()
    read = telemetry.compile_counters
    c0 = read()
    model.step(_ell_batch(b=32))
    c1 = read()
    assert c1["jit_compilations"] > c0["jit_compilations"]
    assert c1["jit_compile_seconds"] > c0["jit_compile_seconds"]
    model.step(_ell_batch(b=32, seed=1))
    assert read()["jit_compilations"] == c1["jit_compilations"]
    model.step(_ell_batch(b=48))
    assert read()["jit_compilations"] > c1["jit_compilations"]
    by_fn = telemetry.REGISTRY.sum_by("jit_compilations", "fn")
    assert by_fn.get("jit(step)", 0) >= 2, by_fn


def test_compile_counters_are_in_the_prometheus_text_and_the_pod_snapshot():
    _fm().step(_ell_batch(b=8))
    telemetry.arm_compile_counters()        # idempotent: one listener set
    from jax._src import monitoring

    assert monitoring.get_event_duration_listeners().count(
        telemetry._on_compile_duration) == 1
    text = telemetry.render_prometheus()
    assert "dmlc_tpu_jit_compilations" in text
    assert "dmlc_tpu_jit_compile_seconds" in text
    snap = telemetry.pod_snapshot()["compile"]
    assert set(snap) == {"jit_compilations", "jit_compile_seconds",
                         "compile_cache_hits"}
    assert snap["jit_compilations"] >= 1


@pytest.mark.parametrize("meshed", [False, True])
def test_hlo_scopes_are_this_builds_even_when_the_executable_is_stale(
        monkeypatch, meshed):
    """The running executable may carry another build's names (the
    persistent cache keys no metadata). Stood in for here by building and
    stepping the learner with the scopes patched away: ``hlo_scopes()``
    builds the step anew and still gives the scoped names, with and
    without a mesh (where JAX's in-memory caches would answer for the same
    lowering)."""
    mesh = None
    batch = _ell_batch()
    with monkeypatch.context() as patched:
        patched.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
        if meshed:
            from dmlc_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(devices=jax.devices()[:4])
        model = FMLearner(num_col=50, num_factors=4, layout="ell", seed=1,
                          mesh=mesh)
        if meshed:
            batch = jax.tree_util.tree_map(jax.device_put, batch,
                                           model.batch_shardings())
        assert model.hlo_scopes() == {}   # no step yet: no shapes to compile
        model.step(batch)
        first = model._step_avals
        model.step(batch)
        assert model._step_avals is first
        running = model._step.lower(*first).compile().as_text()
        assert "fm_optimizer" not in running
    scopes = model.hlo_scopes()
    scatters = {k: v for k, v in scopes.items()
                if v.endswith("/scatter-add")}
    assert scatters and all("transpose(jvp(fm_gather))" in v
                            for v in scatters.values())
    assert all(any(s in v for v in scopes.values()) for s in SCOPES)
    # every instruction of the running step is there under its own name
    # (those XLA gave no op_name read ""), whatever the module is called
    names = [m["name"] for line in running.splitlines()
             if (m := _loop._HLO_INSTRUCTION.match(line))]
    assert len(names) > 20 and set(names) == set(scopes)
    assert all(v.startswith("jit(step)/") for v in scatters.values())
    # no configuration was touched on the way
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert model.hlo_scopes() == scopes   # remembered, and a copy


# ---------------- a save and a restore (PR 41) ----------------

CKPT_SPANS = ("ckpt_snapshot", "ckpt_drain", "ckpt_write", "ckpt_sync",
              "ckpt_publish", "ckpt_restore_read", "ckpt_restore_verify",
              "ckpt_restore_put")


def test_a_save_and_a_restore_write_their_spans_and_counters(
        tmp_path, monkeypatch):
    from dmlc_tpu.models import _checkpoint

    monkeypatch.setattr(_checkpoint, "CHUNK_BYTES", 1 << 10)
    model = _fm()
    before, was = telemetry.span_counts(), telemetry.checkpoint_counters()
    handle = model.save_async(str(tmp_path / "ck"), step=3)
    handle.wait()
    other = _fm()
    other.restore(str(tmp_path / "ck"))
    after, now = telemetry.span_counts(), telemetry.checkpoint_counters()
    grew = {name: after.get(name, 0) - before.get(name, 0)
            for name in CKPT_SPANS}
    assert grew["ckpt_snapshot"] == grew["ckpt_sync"] == \
        grew["ckpt_publish"] == 1
    assert grew["ckpt_drain"] == grew["ckpt_write"] > 9   # one a chunk
    assert grew["ckpt_restore_read"] == grew["ckpt_restore_verify"] \
        == grew["ckpt_drain"]
    assert grew["ckpt_restore_put"] >= 9
    spans = [s for s in telemetry.spans_snapshot()
             if s["name"] in CKPT_SPANS]
    snap = [s for s in spans if s["name"] == "ckpt_snapshot"][-1]
    # what a save holds the dispatching thread for is on that thread
    assert snap["thread"] == threading.current_thread().name
    assert snap["labels"] == {"step": 3}
    drains = [s for s in spans if s["name"] == "ckpt_drain"][-grew[
        "ckpt_drain"]:]
    assert {s["thread"] for s in drains} == {"dmlc-ckpt-saver"}
    assert [s["labels"]["chunk"] for s in drains] == list(range(len(drains)))
    assert now["ckpt_saves_total"]["ok"] == \
        was["ckpt_saves_total"].get("ok", 0) + 1
    assert now["ckpt_bytes_total"] - was["ckpt_bytes_total"] \
        == handle.nbytes > 0
    assert now["ckpt_saves_in_flight"] == 0
    stats = model.checkpoint_stats()
    assert {k: stats[k] for k in now} == now
    assert stats["last_save"]["step"] == 3 and stats["last_restore"] is None
    assert other.checkpoint_stats()["last_restore"]["bytes"] == handle.nbytes
    text = telemetry.render_prometheus()
    assert 'dmlc_tpu_ckpt_saves_total{result="ok"}' in text
    assert "dmlc_tpu_ckpt_bytes_total" in text


def test_the_snapshots_operations_read_the_scope_ckpt_snapshot(
        tmp_path, monkeypatch):
    from dmlc_tpu.models import _checkpoint

    monkeypatch.setattr(_checkpoint, "CHUNK_BYTES", 64)
    model = _fm()
    assert model.hlo_scopes("ckpt_snapshot") == {}      # before a save
    model.save(str(tmp_path / "ck"), step=0)
    scopes = model.hlo_scopes("ckpt_snapshot")
    # parameters read their argument's name; every operation the scope
    named = [op for op in scopes.values() if op.startswith("jit(")]
    assert named and all(
        op.startswith("jit(ckpt_snapshot)/ckpt_snapshot/") for op in named)
