"""Always-on pipeline telemetry (ISSUE 6): the span tracer + metrics
registry core, Chrome-trace export validated structurally against
``DeviceIter.stats()``, per-pipeline counter isolation between two
concurrent iterators, the structured stall diagnostic, pod-snapshot
merging, and the lint-metrics gate.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.io import faults, resilience
from dmlc_tpu.io.resilience import RetryPolicy
from dmlc_tpu.io.threaded_iter import OrderedWorkerPool, ThreadedIter
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError
from dmlc_tpu.utils.timer import StageMeter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
    monkeypatch.delenv("DMLC_FAULT_PLAN", raising=False)
    monkeypatch.delenv("DMLC_PIPELINE_STALL_TIMEOUT", raising=False)
    faults.reset()
    resilience.reset_counters()
    yield
    faults.reset()
    telemetry.set_scope(None)


def _libsvm_text(n=300, d=6, seed=0):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        feats = " ".join(f"{j}:{rng.normal():.5f}" for j in range(d))
        lines.append(f"{i % 2} {feats}")
    return ("\n".join(lines) + "\n").encode()


def _write(tmp_path, name, data):
    p = tmp_path / name
    p.write_bytes(data)
    return str(p)


# ---------------- registry core ----------------

class TestRegistry:
    def test_counter_gauge_info(self):
        reg = telemetry.MetricsRegistry()
        c = reg.counter("c", stage="parse")
        c.inc()
        c.inc(2.5)
        assert reg.counter("c", stage="parse") is c  # get-or-create
        assert c.value == pytest.approx(3.5)
        reg.gauge("g", x="1").set(7)
        assert reg.gauge("g", x="1").value == 7.0
        assert not hasattr(reg, "histogram")   # three kinds, no fourth
        reg.info("i", k="v").set({"a": 1})
        assert reg.info("i", k="v").value == {"a": 1}

    def test_label_scoping_and_sums(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("ev", event="retries", pipeline="a").inc(2)
        reg.counter("ev", event="retries", pipeline="b").inc(3)
        reg.counter("ev", event="fatal", pipeline="a").inc(1)
        assert reg.sum("ev") == 6.0
        assert reg.sum("ev", pipeline="a") == 3.0
        assert reg.sum_by("ev", "event") == {"retries": 5.0, "fatal": 1.0}
        assert reg.sum_by("ev", "event", pipeline="b") == {"retries": 3.0}
        rows = reg.snapshot(name="ev", pipeline="a")
        assert {tuple(sorted(r["labels"].items())) for r in rows} == {
            (("event", "fatal"), ("pipeline", "a")),
            (("event", "retries"), ("pipeline", "a")),
        }
        reg.clear("ev")
        assert reg.sum("ev") == 0.0

    def test_concurrent_increments_are_exact(self):
        reg = telemetry.MetricsRegistry()
        c = reg.counter("n")

        def work():
            for _ in range(5000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 20000.0

    def test_stage_meter_is_registry_backed(self):
        meter = StageMeter("read", "parse", metric="test_stage_seconds")
        meter.add("read", 0.25)
        meter.add("parse", 1.0)
        meter.add("extra", 0.5)  # dynamic stage, same behavior as before
        assert meter.seconds() == {"read": 0.25, "parse": 1.0, "extra": 0.5}
        assert meter.total() == pytest.approx(1.75)
        # the same numbers are visible through the registry — stats() and
        # the pod snapshot read ONE set of books
        assert telemetry.REGISTRY.sum(
            "test_stage_seconds", pipeline=meter.scope, stage="parse") == 1.0
        # independent meters never alias (auto-unique scope)
        other = StageMeter("read", metric="test_stage_seconds")
        other.add("read", 9.0)
        assert meter.seconds()["read"] == 0.25


# ---------------- scoping ----------------

class TestScoping:
    def test_scope_context_restores(self):
        assert telemetry.current_scope() is None
        with telemetry.scope("p1"):
            assert telemetry.current_scope() == "p1"
            with telemetry.scope("p2"):
                assert telemetry.current_scope() == "p2"
            assert telemetry.current_scope() == "p1"
        assert telemetry.current_scope() is None

    def test_scoped_target_inherits_creator_scope(self):
        seen = {}
        with telemetry.scope("creator"):
            target = telemetry.scoped_target(
                lambda: seen.setdefault("scope", telemetry.current_scope()))
        t = threading.Thread(target=target)
        t.start()
        t.join()
        assert seen["scope"] == "creator"

    def test_record_event_scoping_and_compat_api(self):
        resilience.record_event("retries")
        with telemetry.scope("pipe-a"):
            resilience.record_event("retries", 2)
        snap = resilience.counters_snapshot()
        assert snap["retries"] == 3  # process-wide: byte-compatible view
        assert set(resilience._Counters._KEYS) <= set(snap)
        assert resilience.counters_snapshot("pipe-a")["retries"] == 2
        assert resilience.counters_snapshot("")["retries"] == 1
        delta = resilience.counters_delta(
            {"retries": 1}, pipeline="pipe-a")
        assert delta["retries"] == 1
        resilience.reset_counters()
        assert resilience.counters_snapshot()["retries"] == 0

    def test_threaded_iter_producer_inherits_scope(self):
        seen = []

        def gen():
            seen.append(telemetry.current_scope())
            yield 1

        with telemetry.scope("owner"):
            it = ThreadedIter.from_factory(gen, max_capacity=2)
        assert it.next() == 1
        it.destroy()
        assert seen == ["owner"]

    def test_scope_adoption_on_first_pull(self):
        """A thread primitive built OUTSIDE any scope (e.g. the threaded
        input split, constructed with the parser before its DeviceIter
        exists) adopts the first scoped consumer's label mid-run."""
        events = []

        def gen():
            for i in range(20):
                events.append(telemetry.current_scope())
                yield i

        it = ThreadedIter.from_factory(gen, max_capacity=2)  # unscoped
        with telemetry.scope("late-owner"):
            out = [it.next() for _ in range(20)]
        it.destroy()
        assert out == list(range(20))
        # production after the first pull runs under the adopted label
        # (the eager prefetch before it may legitimately be unscoped)
        assert events[-1] == "late-owner"
        assert set(events) <= {None, "late-owner"}

    def test_threaded_split_scope_captured_at_construction(self, tmp_path):
        """Thread primitives the parser chain built BEFORE the DeviceIter
        existed (the threaded input split starts prefetching at parser
        construction) are stamped with the pipeline label AT ITERATOR
        CONSTRUCTION — not on the first pull — so even the initial
        prefetch window is scoped (the old adoption-window caveat is
        gone from docs/observability.md)."""
        from dmlc_tpu.data.device import DeviceIter
        from dmlc_tpu.data.parsers import create_parser

        p = tmp_path / "c.libsvm"
        p.write_text("".join(f"{i % 2} 0:1.0 1:2.0\n" for i in range(200)))
        parser = create_parser(str(p) + "?engine=python", 0, 1, "libsvm",
                               threaded=True, parse_workers=1)
        # the parse-ahead chain was built outside any scope: find its
        # primitives and prove they are unscoped now, scoped after init
        prims = []
        stack = [parser]
        while stack:
            obj = stack.pop()
            if obj is None:
                continue
            if hasattr(obj, "adopt_scope"):
                prims.append(obj)
            stack.extend(getattr(obj, n, None)
                         for n in ("source", "base", "_base", "_iter"))
        assert prims, "no thread primitive found in the parser chain"
        assert all(prim._scope is None for prim in prims)
        it = DeviceIter(parser, num_col=2, batch_size=32, layout="dense")
        assert all(prim._scope == it.pipeline_label for prim in prims)
        # and an already-scoped primitive is never re-labeled
        prims[0].adopt_scope("someone-else")
        assert prims[0]._scope == it.pipeline_label
        it.close()

    def test_worker_pool_workers_inherit_scope(self):
        seen = set()

        def work(item):
            seen.add(telemetry.current_scope())
            return item

        with telemetry.scope("owner"):
            pool = OrderedWorkerPool(lambda: iter(range(6)), work,
                                     num_workers=2)
        assert [pool.next() for _ in range(6)] == list(range(6))
        pool.destroy()
        assert seen == {"owner"}


# ---------------- span tracer ----------------

class TestSpanTracer:
    def test_ring_bounded_counts_preserved(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_TRACE_RING_SPANS", "64")
        ring = telemetry._SpanRing(1, "t", 64)
        for i in range(200):
            ring.record("parse", i, 1, None, None)
        assert len(ring.snapshot()) == 64
        assert ring.total == 200
        assert ring.counts["parse"] == 200
        # oldest-first and the oldest retained is #136
        assert ring.snapshot()[0][1] == 136
        ring.clear()
        assert ring.snapshot() == [] and ring.total == 0

    def test_record_span_carries_scope_and_labels(self):
        telemetry.reset_spans()
        with telemetry.scope("pipe-z"):
            telemetry.record_span("convert", 10.0, 0.25, rows=7)
        rows = telemetry.spans_snapshot(pipeline="pipe-z")
        assert len(rows) == 1
        s = rows[0]
        assert s["name"] == "convert"
        assert s["start_ns"] == 10_000_000_000
        assert s["dur_ns"] == 250_000_000
        assert s["labels"] == {"rows": 7}
        assert telemetry.span_counts().get("convert", 0) >= 1

    def test_chrome_export_structure(self, tmp_path):
        telemetry.reset_spans()
        telemetry.record_span("read", 1.0, 0.5)
        with telemetry.scope("pipe-q"):
            telemetry.record_span("parse", 1.5, 0.25)
        out = str(tmp_path / "trace.json")
        n = telemetry.export_chrome_trace(out)
        assert n == 2
        doc = json.loads(open(out).read())
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] == "ms"
        assert doc["otherData"]["telemetry_schema_version"] == \
            telemetry.SCHEMA_VERSION
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"read", "parse"}
        for e in xs:
            assert set(e) >= {"name", "cat", "ph", "pid", "tid", "ts", "dur"}
            assert e["ts"] >= 0 and e["dur"] >= 0
        parse = next(e for e in xs if e["name"] == "parse")
        assert parse["args"]["pipeline"] == "pipe-q"
        assert parse["dur"] == pytest.approx(250_000.0)  # us
        # metadata events name the process/threads (Perfetto niceties)
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in doc["traceEvents"])

    def test_chrome_export_is_the_one_peer_pod_export(self, tmp_path):
        """One writer of the Perfetto JSON: the process's own export is
        ``export_pod_trace`` of one peer named ``dmlc_tpu``."""
        telemetry.reset_spans()
        with telemetry.scope("pipe-one"):
            telemetry.record_span("read", 1.0, 0.5, rows=3)
        telemetry.record_span("parse", 2.0, 0.25)
        one, pod = str(tmp_path / "one.json"), str(tmp_path / "pod.json")
        assert telemetry.export_chrome_trace(one, pipeline="pipe-one") == 1
        assert telemetry.export_pod_trace(pod, [{
            "peer": "dmlc_tpu", "schema": telemetry.SCHEMA_VERSION,
            "spans": telemetry.spans_snapshot("pipe-one")}]) == 1
        a, b = (json.loads(open(p).read()) for p in (one, pod))
        assert a == b
        assert [e["args"]["name"] for e in a["traceEvents"]
                if e["ph"] == "M"] == ["dmlc_tpu",
                                       threading.current_thread().name]
        assert a["otherData"]["peers"] == ["dmlc_tpu"]
        assert a["otherData"]["spans_dropped"] == telemetry.spans_dropped()

    def test_trace_mode_parsing(self, monkeypatch):
        assert telemetry.trace_mode() == ("off", None)
        monkeypatch.setenv("DMLC_TPU_TRACE", "0")
        assert telemetry.trace_mode() == ("off", None)
        # the retired annotate switch: spans carry their profiler
        # annotation unconditionally, so "1" arms nothing
        monkeypatch.setenv("DMLC_TPU_TRACE", "1")
        assert telemetry.trace_mode() == ("off", None)
        assert not hasattr(telemetry, "profiler_annotation")
        monkeypatch.setenv("DMLC_TPU_TRACE", "chrome:/tmp/x.json")
        assert telemetry.trace_mode() == ("chrome", "/tmp/x.json")


# ---------------- the acceptance contract: trace vs stats() ----------------

def _close(span_sum, ref, what):
    tol = max(0.10 * max(ref, span_sum), 0.02)
    assert abs(span_sum - ref) <= tol, (
        f"{what}: span sum {span_sum:.4f}s vs stats {ref:.4f}s "
        f"(tolerance {tol:.4f}s)")


class TestTraceMatchesStats:
    def test_chrome_trace_covers_all_stages_and_matches_attribution(
            self, tmp_path, monkeypatch):
        """DMLC_TPU_TRACE=chrome:<path> on a cold+warm epoch pair writes a
        well-formed Chrome trace: all six stage names present, and the
        per-stage span sums reconcile with the stats() attribution within
        10% (the acceptance bar — spans and stage counters are fed from
        the same code sites, so disagreement means a bookkeeping hole)."""
        monkeypatch.setenv("DMLC_TPU_NO_NATIVE_READER", "1")
        trace_path = str(tmp_path / "ingest.trace.json")
        monkeypatch.setenv("DMLC_TPU_TRACE", f"chrome:{trace_path}")
        telemetry.reset_spans()
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=2000))
        cache = str(tmp_path / "corpus.blockcache")
        parser = create_parser(path, 0, 1, "libsvm", threaded=False,
                               block_cache=cache, chunk_bytes=8192)
        it = DeviceIter(parser, num_col=6, batch_size=256, layout="dense",
                        prefetch=2, convert_ahead=2, convert_workers=1,
                        transfer_sample=1, pack_aux=True)
        batches = 0
        for _ in it:          # cold epoch: read/parse (+ shadow write)
            batches += 1
        it.reset()
        for _ in it:          # warm epoch: cache_read
            batches += 1
        stats = it.stats()
        assert stats["cache_state"] == "warm"
        it.close()            # chrome mode -> dump on close

        doc = json.loads(open(trace_path).read())
        events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        for e in events:
            assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
            assert e["ts"] >= 0 and e["dur"] >= 0
        names = {e["name"] for e in events}
        assert set(telemetry.STAGES) <= names, names

        # this pipeline's spans only (another test's pipeline may share
        # the process), per-stage sums in seconds
        mine = [e for e in events
                if e.get("args", {}).get("pipeline") == stats["pipeline"]]
        sums = {}
        for e in mine:
            sums[e["name"]] = sums.get(e["name"], 0.0) + e["dur"] / 1e6

        # per-batch spans really are per batch: one dispatch per batch
        # delivered, one sampled transfer probe per batch (sample=1)
        ndisp = sum(1 for e in mine if e["name"] == "dispatch")
        assert ndisp == batches
        assert sum(1 for e in mine if e["name"] == "transfer") == \
            stats["transfer_samples"]

        busy = stats["stage_busy"]
        _close(sums.get("read", 0.0), busy["read"], "read")
        _close(sums.get("cache_read", 0.0), busy["cache_read"], "cache_read")
        _close(sums.get("convert", 0.0), busy["convert"], "convert")
        _close(sums.get("dispatch", 0.0), busy["dispatch"], "dispatch")
        _close(sums.get("transfer", 0.0), stats["stages"]["transfer"],
               "transfer")
        # busy 'parse' is measured around the whole supply pull, which in
        # a cold cache epoch includes the shadow-write — the write's own
        # spans account for that share, so parse reconciles NET of them
        _close(sums.get("parse", 0.0),
               max(0.0, busy["parse"] - sums.get("cache_write", 0.0)),
               "parse")

    def test_dump_trace_without_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_NO_NATIVE_READER", "1")
        telemetry.reset_spans()
        path = _write(tmp_path, "c.libsvm", _libsvm_text(n=200))
        parser = create_parser(path, 0, 1, "libsvm", threaded=False,
                               chunk_bytes=4096)
        it = DeviceIter(parser, num_col=6, batch_size=64, layout="dense",
                        convert_workers=1, transfer_sample=0)
        for _ in it:
            pass
        out = str(tmp_path / "direct.json")
        n = it.dump_trace(out)
        it.close()
        assert n > 0
        doc = json.loads(open(out).read())
        assert {"read", "parse", "convert", "dispatch"} <= {
            e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}


# ---------------- cross-pipeline isolation (satellite 1) ----------------

class TestPipelineIsolation:
    @staticmethod
    def _make(path, cache):
        parser = create_parser(path, 0, 1, "libsvm", threaded=False,
                               block_cache=cache, chunk_bytes=4096)
        return DeviceIter(parser, num_col=6, batch_size=128, layout="dense",
                          convert_workers=1, transfer_sample=0)

    def test_two_concurrent_iterators_keep_disjoint_counters(
            self, tmp_path, monkeypatch):
        """ISSUE 6 satellite: a fault injected into pipeline A's warm
        cache must show up in A's stats()['resilience'] ONLY — before the
        scoped registry, both iterators diffed the same process-wide
        totals and saw each other's events."""
        monkeypatch.setenv("DMLC_TPU_NO_NATIVE_READER", "1")
        pa = _write(tmp_path, "corpus_a.libsvm", _libsvm_text(seed=0))
        pb = _write(tmp_path, "corpus_b.libsvm", _libsvm_text(seed=1))
        cache_a = str(tmp_path / "a.blockcache")
        cache_b = str(tmp_path / "b.blockcache")
        it_a = self._make(pa, cache_a)
        it_b = self._make(pb, cache_b)
        try:
            for _ in it_a:  # cold: publish both caches
                pass
            for _ in it_b:
                pass
            it_a.reset()
            it_b.reset()
            # warm epochs INTERLEAVED while the corruption fault targets
            # only pipeline A's cache file
            with faults.inject("cache_read~a.blockcache@1=corrupt"):
                done_a = done_b = False
                while not (done_a and done_b):
                    if not done_a:
                        try:
                            next(it_a)
                        except StopIteration:
                            done_a = True
                    if not done_b:
                        try:
                            next(it_b)
                        except StopIteration:
                            done_b = True
            res_a = it_a.stats()["resilience"]
            res_b = it_b.stats()["resilience"]
            assert res_a["cache_corruptions"] == 1
            assert res_a["cache_rebuilds"] == 1
            # B saw NOTHING of A's fault — the contamination fix
            assert res_b["cache_corruptions"] == 0
            assert res_b["cache_rebuilds"] == 0
            assert all(v == 0 for v in res_b.values()), res_b
            # process-wide totals still aggregate both pipelines
            assert resilience.counters_snapshot()["cache_corruptions"] == 1
        finally:
            it_a.close()
            it_b.close()

    def test_stats_carries_pipeline_label(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_NO_NATIVE_READER", "1")
        path = _write(tmp_path, "c.libsvm", _libsvm_text(n=50))
        parser = create_parser(path, 0, 1, "libsvm", threaded=False)
        it = DeviceIter(parser, num_col=6, batch_size=32,
                        pipeline_label="train-input")
        try:
            next(iter(it))
            assert it.stats()["pipeline"] == "train-input"
        finally:
            it.close()


# ---------------- structured stall diagnostic (satellite 3) ----------------

class TestStallDiagnostic:
    def test_threaded_iter_publishes_structured_dict(self, monkeypatch):
        monkeypatch.setenv("DMLC_PIPELINE_STALL_TIMEOUT", "0.3")
        gate = threading.Event()

        def produce(cell):
            gate.wait(30)
            return False, None

        it = ThreadedIter(produce,
                          restart_policy=RetryPolicy(max_attempts=4))
        with pytest.raises(DMLCError, match="pipeline stalled"):
            it.next()
        gate.set()
        it.destroy()
        diag = telemetry.REGISTRY.info(
            telemetry.STALL_METRIC, component="ThreadedIter",
            label="", pipeline="").value
        assert diag is not None
        assert diag["component"] == "ThreadedIter"
        assert diag["timeout_seconds"] == pytest.approx(0.3)
        assert diag["producer_alive"] is True
        assert diag["queue_len"] == 0
        assert diag["last_producer_error"] is None
        assert diag["restart_budget"] == {
            "enabled": True, "used": 0, "limit": 3}

    def test_worker_pool_publishes_structured_dict(self, monkeypatch):
        monkeypatch.setenv("DMLC_PIPELINE_STALL_TIMEOUT", "0.3")
        gate = threading.Event()

        def work(item):
            gate.wait(30)
            return item

        pool = OrderedWorkerPool(lambda: iter(range(4)), work,
                                 num_workers=2, counter_label="parse")
        with pytest.raises(DMLCError, match="pipeline stalled"):
            pool.next()
        gate.set()
        pool.destroy()
        diag = telemetry.REGISTRY.info(
            telemetry.STALL_METRIC, component="OrderedWorkerPool",
            label="parse", pipeline="").value
        assert diag is not None
        assert diag["label"] == "parse"
        assert diag["workers"] == 2
        assert diag["waiting_for"] == 0
        assert diag["pulled"] >= 1
        assert diag["restart_budget"]["enabled"] is False
        assert diag["last_producer_error"] is None

    def test_stall_dict_carries_producer_error_and_budget_use(
            self, monkeypatch):
        monkeypatch.setenv("DMLC_PIPELINE_STALL_TIMEOUT", "0.3")
        gate = threading.Event()
        state = {"first": True}

        def gen():
            if state["first"]:
                state["first"] = False
                raise TimeoutError("flaky source")
            gate.wait(30)
            yield 1

        it = ThreadedIter.from_factory(
            gen, restart_policy=RetryPolicy(max_attempts=3,
                                            base_delay=0.001))
        with pytest.raises(DMLCError, match="pipeline stalled"):
            it.next()
        gate.set()
        it.destroy()
        diag = telemetry.REGISTRY.info(
            telemetry.STALL_METRIC, component="ThreadedIter",
            label="", pipeline="").value
        assert "TimeoutError" in diag["last_producer_error"]
        assert diag["restart_budget"] == {
            "enabled": True, "used": 1, "limit": 2}


# ---------------- pod snapshot + merge ----------------

class TestPodAggregation:
    def test_pod_snapshot_shape(self):
        telemetry.REGISTRY.counter(
            telemetry.STAGE_BUSY_METRIC, stage="parse",
            pipeline="snap-test").inc(2.0)
        with telemetry.scope("snap-test"):
            resilience.record_event("retries")
        telemetry.record_span("parse", 0.0, 0.5)
        snap = telemetry.pod_snapshot()
        assert snap["telemetry_schema_version"] == telemetry.SCHEMA_VERSION
        assert snap["stages"]["parse"] >= 2.0  # summed ACROSS pipelines
        assert snap["resilience"]["retries"] >= 1
        assert snap["spans"]["parse"] >= 1
        json.dumps(snap)  # must be wire-serializable

    def test_format_pod_table_merges_ranks(self):
        v = telemetry.SCHEMA_VERSION
        table = telemetry.format_pod_table({
            1: {"telemetry_schema_version": v,
                "stages": {"read": 0.5, "parse": 2.0},
                "resilience": {"retries": 2}},
            0: {"telemetry_schema_version": v,
                "stages": {"parse": 1.0, "transfer": 0.25},
                "resilience": {}},
        })
        lines = table.splitlines()
        assert lines[0].split()[:2] == ["rank", "read"]
        for stage in telemetry.STAGES:
            assert stage in lines[0]
        r0 = next(ln for ln in lines if ln.strip().startswith("0"))
        r1 = next(ln for ln in lines if ln.strip().startswith("1"))
        assert "1.000" in r0 and "2.000" in r1
        assert "{'retries': 2}" in r1
        assert "3.000" in lines[-1]  # parse sum row

    def test_format_pod_table_refuses_cross_schema(self):
        table = telemetry.format_pod_table({
            0: {"telemetry_schema_version": telemetry.SCHEMA_VERSION,
                "stages": {"parse": 1.0}},
            1: {"telemetry_schema_version": 999, "stages": {"parse": 9.0}},
        })
        assert "not merged" in table
        assert "9.000" not in table


# ---------------- lint-metrics gate (satellite 5) ----------------

class TestLintMetrics:
    def _scan(self):
        sys.path.insert(0, os.path.join(REPO, "bin"))
        try:
            import lint_metrics
        finally:
            sys.path.pop(0)
        return lint_metrics.scan_source

    def test_flags_adhoc_bookkeeping(self):
        scan = self._scan()
        bad = (
            "def f():\n"
            "    t0 = time.monotonic()\n"
            "    COUNTERS.bump('retries')\n"
            "    # time.monotonic() in a comment is fine\n"
        )
        offenders = scan(bad)
        assert [ln for ln, _ in offenders] == [2, 3]

    def test_sanctioned_calls_pass(self):
        scan = self._scan()
        good = (
            "def f():\n"
            "    t0 = get_time()\n"
            "    _resilience.record_event('retries')\n"
            "    telemetry.record_span('parse', t0, get_time() - t0)\n"
        )
        assert scan(good) == []

    def test_repo_is_clean(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "lint_metrics.py"),
             REPO],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
