"""Fleet-wide observability plane (ISSUE 19): cross-process trace
propagation (wire codec, dispatcher-rooted (job, part) traces, client
block stamping), merged pod timelines with per-peer clock offsets and
the cross-schema listed-not-merged contract, Prometheus text exposition
round-trips, the bounded metrics time-series ring, pipeline-scope
retirement under churn, the control-decision audit ledger across every
controller, and the lint-metrics RPC-span + METRICS-env gates.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from dmlc_tpu.data import autotune
from dmlc_tpu.io import resilience
from dmlc_tpu.service import autoscale as svc_autoscale
from dmlc_tpu.service import dispatcher as svc_dispatcher
from dmlc_tpu.service.client import ServiceParser
from dmlc_tpu.service.fleet import LocalFleet
from dmlc_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUM_PARTS = 3
CHUNK = 16 * 1024
PARSER_CFG = {"format": "libsvm", "threaded": False, "chunk_bytes": CHUNK}


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    for var in ("DMLC_TPU_TRACE", "DMLC_TPU_TRACE_CONTEXT",
                "DMLC_TPU_METRICS_HISTORY",
                "DMLC_TPU_METRICS_MAX_PIPELINES"):
        monkeypatch.delenv(var, raising=False)
    telemetry.set_trace(None)
    telemetry.set_trace_propagation(None)
    telemetry.reset_decisions()
    telemetry.reset_metrics_history()
    resilience.reset_counters()
    yield
    telemetry.set_trace(None)
    telemetry.set_trace_propagation(None)
    telemetry.reset_decisions()
    telemetry.reset_metrics_history()
    telemetry.set_scope(None)


@pytest.fixture
def corpus(tmp_path):
    rng = np.random.default_rng(0)
    path = tmp_path / "c.libsvm"
    with open(path, "w") as f:
        for i in range(3000):
            feats = " ".join(f"{j}:{rng.normal():.4f}" for j in range(6))
            f.write(f"{i % 2} {feats}\n")
    return str(path)


def _drain_service(address: str):
    parser = ServiceParser(address)
    out = []
    try:
        while (blk := parser.next_block()) is not None:
            out.append(blk)
    finally:
        parser.close()
    return out


def _wait_for(cond, timeout=20.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# trace context primitives

class TestTraceContext:
    def test_id_shapes(self):
        tids = {telemetry.new_trace_id() for _ in range(32)}
        sids = {telemetry.new_span_id() for _ in range(32)}
        assert len(tids) == 32 and len(sids) == 32
        assert all(len(t) == 16 and int(t, 16) >= 0 for t in tids)
        assert all(len(s) == 8 and int(s, 16) >= 0 for s in sids)

    def test_trace_scope_installs_and_restores(self):
        assert telemetry.current_trace() is None
        with telemetry.trace("aa" * 8, "bb" * 4):
            assert telemetry.current_trace() == ("aa" * 8, "bb" * 4)
            # a falsy trace id CLEARS the context for the inner block
            with telemetry.trace(None):
                assert telemetry.current_trace() is None
            assert telemetry.current_trace() == ("aa" * 8, "bb" * 4)
        assert telemetry.current_trace() is None

    def test_wire_codec_round_trip(self):
        with telemetry.trace("cc" * 8, "dd" * 4):
            wire = telemetry.trace_context_wire()
        assert wire == {"tid": "cc" * 8, "sid": "dd" * 4}
        assert telemetry.trace_context_from_wire(wire) == \
            ("cc" * 8, "dd" * 4)
        # explicit ctx wins over the (empty) thread-local
        assert telemetry.trace_context_wire(("ee" * 8, "")) == \
            {"tid": "ee" * 8, "sid": ""}

    def test_wire_codec_rejects_malformed(self):
        # observability never fails an RPC: garbage decodes to None
        for bad in (None, "x", 7, [], {}, {"tid": ""}, {"tid": 3},
                    {"sid": "aa"}, {"tid": None, "sid": "aa"}):
            assert telemetry.trace_context_from_wire(bad) is None
        # a non-string sid degrades to "" instead of failing
        assert telemetry.trace_context_from_wire(
            {"tid": "ff" * 8, "sid": 9}) == ("ff" * 8, "")
        # no installed context and no explicit one -> no wire key
        assert telemetry.trace_context_wire() is None

    def test_kill_switch_env_and_override(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_TRACE_CONTEXT", "0")
        assert not telemetry.trace_propagation_enabled()
        with telemetry.trace("aa" * 8, "bb" * 4):
            assert telemetry.trace_context_wire() is None
        assert telemetry.trace_context_from_wire(
            {"tid": "aa" * 8, "sid": ""}) is None
        # the in-process override (bench's baseline leg) beats the env
        telemetry.set_trace_propagation(True)
        assert telemetry.trace_propagation_enabled()
        telemetry.set_trace_propagation(None)
        assert not telemetry.trace_propagation_enabled()

    def test_record_span_inherits_thread_context(self):
        with telemetry.trace("ab" * 8, "cd" * 4):
            telemetry.record_span("obs_test_span", 1.0, 0.5)
        rows = [s for s in telemetry.spans_snapshot()
                if s["name"] == "obs_test_span"]
        assert rows
        assert rows[-1]["trace_id"] == "ab" * 8
        assert rows[-1]["parent_id"] == "cd" * 4
        # explicit ids win over the installed context
        with telemetry.trace("ab" * 8, "cd" * 4):
            telemetry.record_span("obs_test_span2", 1.0, 0.5,
                                  trace_id="ef" * 8, parent_id="01" * 4,
                                  span_id="23" * 4)
        row = [s for s in telemetry.spans_snapshot()
               if s["name"] == "obs_test_span2"][-1]
        assert row["trace_id"] == "ef" * 8
        assert row["parent_id"] == "01" * 4
        assert row["span_id"] == "23" * 4

    def test_untraced_span_rows_carry_no_trace_keys(self):
        telemetry.record_span("obs_plain_span", 1.0, 0.5)
        row = [s for s in telemetry.spans_snapshot()
               if s["name"] == "obs_plain_span"][-1]
        # v1-era consumers of the row shape see exactly the old keys
        assert "trace_id" not in row and "parent_id" not in row


# ---------------------------------------------------------------------------
# control-decision audit ledger

class TestDecisionLedger:
    def test_event_shape_and_counters(self):
        ev = telemetry.record_decision(
            "autotune", "grow", trigger={"knob": "parse_workers"},
            outcome="2 -> 3", pipeline="p0", step=7)
        assert ev["component"] == "autotune" and ev["action"] == "grow"
        assert ev["trigger"] == {"knob": "parse_workers"}
        assert ev["outcome"] == "2 -> 3"
        assert ev["pipeline"] == "p0" and ev["step"] == 7
        assert isinstance(ev["ts"], float)
        assert telemetry.decisions_total() == 1
        assert telemetry.decision_counts() == {"autotune.grow": 1}
        snap = telemetry.decisions_snapshot("autotune")
        assert len(snap) == 1 and snap[0]["action"] == "grow"
        assert telemetry.decisions_snapshot("store") == []

    def test_ring_bounded_total_monotonic(self):
        n = telemetry.DECISION_HISTORY_LIMIT + 16
        for i in range(n):
            telemetry.record_decision("autotune", "grow", step=i)
        assert telemetry.decisions_total() == n
        events = telemetry.decisions_snapshot()
        assert len(events) == telemetry.DECISION_HISTORY_LIMIT
        # oldest dropped, newest kept
        assert events[-1]["step"] == n - 1
        assert events[0]["step"] == 16
        # the registry shadow counter never loses ring drops
        assert telemetry.decision_counts()["autotune.grow"] == n

    def test_decision_inherits_trace_context(self):
        with telemetry.trace("aa" * 8, "bb" * 4):
            ev = telemetry.record_decision("dispatcher", "hedge")
        assert ev["trace_id"] == "aa" * 8
        ev2 = telemetry.record_decision("dispatcher", "hedge")
        assert "trace_id" not in ev2

    def test_reset_clears_ledger_and_shadow_counter(self):
        telemetry.record_decision("store", "evict")
        telemetry.reset_decisions()
        assert telemetry.decisions_total() == 0
        assert telemetry.decisions_snapshot() == []
        assert telemetry.decision_counts() == {}


# ---------------------------------------------------------------------------
# Prometheus text exposition

class TestPrometheus:
    def test_render_parse_round_trip_and_naming(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("stage_busy_seconds", stage="parse",
                    pipeline="p0").inc(2.5)
        reg.gauge("autotune_knob", knob="prefetch").set(4)
        reg.info("build", version="x").set({"a": 1})
        text = telemetry.render_prometheus(reg.snapshot())
        samples = telemetry.parse_prometheus_text(text)
        by_name = {}
        for name, labels, value in samples:
            by_name.setdefault(name, []).append((labels, value))
        # naming contract: dmlc_tpu_ prefix, counters +_total, info
        # skipped
        assert by_name["dmlc_tpu_stage_busy_seconds_total"] == \
            [({"stage": "parse", "pipeline": "p0"}, 2.5)]
        assert by_name["dmlc_tpu_autotune_knob"] == \
            [({"knob": "prefetch"}, 4.0)]
        assert set(by_name) == {"dmlc_tpu_stage_busy_seconds_total",
                                "dmlc_tpu_autotune_knob"}
        assert not any(n.startswith("dmlc_tpu_build") for n in by_name)
        # every sample block is typed, output deterministically sorted
        assert text.startswith("# TYPE ")
        assert text == telemetry.render_prometheus(reg.snapshot())

    def test_label_escaping_round_trips(self):
        reg = telemetry.MetricsRegistry()
        nasty = 'a"b\\c\nd'
        reg.counter("ev", event=nasty).inc(1)
        text = telemetry.render_prometheus(reg.snapshot())
        (name, labels, value), = telemetry.parse_prometheus_text(text)
        assert name == "dmlc_tpu_ev_total"
        assert labels == {"event": nasty}
        assert value == 1.0

    def test_empty_labels_dropped_from_exposition(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("ev", event="retries", pipeline="").inc(3)
        (name, labels, _), = telemetry.parse_prometheus_text(
            telemetry.render_prometheus(reg.snapshot()))
        assert labels == {"event": "retries"}

    def test_parser_rejects_malformed(self):
        for bad in ("dmlc_tpu_x", 'x{k="v} 1', "9bad 1", "x notanum"):
            with pytest.raises(ValueError):
                telemetry.parse_prometheus_text(bad)
        # comments and blank lines are fine
        assert telemetry.parse_prometheus_text("# TYPE x counter\n\n") \
            == []

    def test_live_registry_renders_parseable(self):
        telemetry.REGISTRY.counter(
            telemetry.DECISION_METRIC, component="t",
            action="probe").inc()
        samples = telemetry.parse_prometheus_text(
            telemetry.render_prometheus())
        assert any(n == "dmlc_tpu_decision_events_total"
                   and l.get("component") == "t"
                   for n, l, _ in samples)


# ---------------------------------------------------------------------------
# bounded metrics time-series ring

class TestMetricsHistory:
    def test_ring_bounded_by_knob(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_METRICS_HISTORY", "4")
        for i in range(10):
            sample = telemetry.sample_metrics_history(now=float(i))
        hist = telemetry.metrics_history()
        assert len(hist) == 4
        assert [s["ts"] for s in hist] == [6.0, 7.0, 8.0, 9.0]
        for key in ("input_wait_seconds", "job_wait_seconds",
                    "wire_bytes_raw", "wire_bytes_sent", "store_bytes",
                    "decisions"):
            assert key in sample

    def test_sample_tracks_decisions(self):
        before = telemetry.sample_metrics_history(now=0.0)
        telemetry.record_decision("autotune", "grow")
        after = telemetry.sample_metrics_history(now=1.0)
        assert after["decisions"] == before["decisions"] + 1


# ---------------------------------------------------------------------------
# pipeline-scope retirement under churn (ISSUE 19 satellite)

class TestScopeRetirement:
    def test_churn_is_bounded_and_books_preserved(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_METRICS_MAX_PIPELINES", "8")
        reg = telemetry.MetricsRegistry()
        churn = 24
        for i in range(churn):
            scope = f"pipe-{i:03d}"
            reg.counter("stage_busy_seconds", stage="parse",
                        pipeline=scope).inc(1.0)
            reg.counter("batch_rows", pipeline=scope).inc(10.0)
            reg.gauge("autotune_knob", knob="prefetch",
                      pipeline=scope).set(float(i))
        rows = reg.snapshot()
        live = {r["labels"]["pipeline"] for r in rows
                if r["labels"].get("pipeline")}
        assert len(live) <= 8, "registry grew past the scope bound"
        assert reg.retired_pipelines() == churn - 8
        # counters FOLD into the pipeline="" totals: process-wide sums
        # are unchanged by retirement
        assert reg.sum("stage_busy_seconds") == pytest.approx(churn)
        folded = [r for r in rows if r["name"] == "batch_rows"
                  and r["labels"].get("pipeline") == ""]
        assert folded and folded[0]["value"] == 10.0 * (churn - 8)
        # gauges are per-instance state, not tallies: retired scopes'
        # gauges drop instead of folding into a meaningless total
        gauge_scopes = {r["labels"].get("pipeline") for r in rows
                        if r["name"] == "autotune_knob"}
        assert "" not in gauge_scopes
        assert len(gauge_scopes) <= 8

    def test_recently_touched_scope_survives(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_METRICS_MAX_PIPELINES", "8")
        reg = telemetry.MetricsRegistry()
        reg.counter("ev", event="x", pipeline="keep-me").inc(1)
        for i in range(20):
            # a NEW metric under keep-me advances its LRU stamp
            reg.counter(f"ev{i}", event="x", pipeline="keep-me").inc(1)
            reg.counter("ev", event="x", pipeline=f"churn-{i}").inc(1)
        rows = reg.snapshot("ev", "counter")
        scopes = {r["labels"]["pipeline"] for r in rows
                  if r["labels"].get("pipeline")}
        assert "keep-me" in scopes


# ---------------------------------------------------------------------------
# merged pod timeline export

class TestTimelineExport:
    @staticmethod
    def _span(name="parse", tid=1, start_ns=1_000_000, dur_ns=500_000,
              **extra):
        row = {"name": name, "tid": tid, "thread": "worker-t",
               "start_ns": start_ns, "dur_ns": dur_ns, "pipeline": "",
               "labels": {}}
        row.update(extra)
        return row

    def test_cross_schema_peer_listed_not_merged(self, tmp_path):
        """ISSUE 19 satellite: a peer at another schema version shows
        up in the merged timeline as one loud annotation, never as
        merged spans."""
        path = str(tmp_path / "pod.json")
        ok = {"peer": "dispatcher", "schema": telemetry.SCHEMA_VERSION,
              "clock_offset_s": 0.0, "spans": [self._span()],
              "decisions": []}
        old = {"peer": "rank-9", "schema": 1, "clock_offset_s": 0.0,
               "spans": [self._span("stale", start_ns=5),
                         self._span("stale2", start_ns=6)],
               "decisions": [{"ts": 1.0, "component": "autotune",
                              "action": "grow"}]}
        written = telemetry.export_pod_trace(path, [ok, old])
        assert written == 1  # only the schema-matched peer's span
        with open(path) as f:
            doc = json.load(f)
        other = doc["otherData"]
        assert other["peers"] == ["dispatcher", "rank-9"]
        assert other["peers_not_merged"] == ["rank-9"]
        events = doc["traceEvents"]
        # the old peer is LISTED (named process + annotation) ...
        names = [e for e in events if e["ph"] == "M"
                 and e["name"] == "process_name"]
        assert [e["args"]["name"] for e in names] == \
            ["dispatcher", "rank-9"]
        mismatch = [e for e in events if e["name"] == "schema-mismatch"]
        assert len(mismatch) == 1 and mismatch[0]["ph"] == "i"
        assert mismatch[0]["args"]["schema"] == 1
        assert mismatch[0]["args"]["expected"] == \
            telemetry.SCHEMA_VERSION
        # ... but NOT merged: none of its spans or decisions render
        old_pid = names[1]["pid"]
        assert not any(e for e in events
                       if e["pid"] == old_pid and e["ph"] in ("X", "i")
                       and e["name"] != "schema-mismatch")

    def test_clock_offset_shifts_peer_events(self, tmp_path):
        path = str(tmp_path / "pod.json")
        peer = {"peer": "rank-1", "schema": telemetry.SCHEMA_VERSION,
                "clock_offset_s": 2.0,
                "spans": [self._span(start_ns=0)],
                "decisions": [{"ts": 1.0, "component": "dispatcher",
                               "action": "hedge"}]}
        telemetry.export_pod_trace(path, [peer])
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        span = next(e for e in events if e["ph"] == "X")
        assert span["ts"] == pytest.approx(2.0 * 1e6)  # microseconds
        inst = next(e for e in events
                    if e.get("cat") == "dmlc_tpu_decision")
        assert inst["ts"] == pytest.approx(3.0 * 1e6)
        assert inst["name"] == "dispatcher.hedge"

    def test_trace_ids_ride_into_event_args(self, tmp_path):
        path = str(tmp_path / "pod.json")
        peer = {"peer": "w", "schema": telemetry.SCHEMA_VERSION,
                "clock_offset_s": 0.0,
                "spans": [self._span(trace_id="aa" * 8,
                                     parent_id="bb" * 4,
                                     span_id="cc" * 4)],
                "decisions": []}
        telemetry.export_pod_trace(path, [peer])
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        span = next(e for e in events if e["ph"] == "X")
        assert span["args"]["trace_id"] == "aa" * 8
        assert span["args"]["parent_id"] == "bb" * 4
        assert span["args"]["span_id"] == "cc" * 4


# ---------------------------------------------------------------------------
# service plane end to end

def _crossproc_traces():
    """Traces that link a worker-side serve to a client-side receive."""
    worker_side = {"service_parse", "service_encode", "service_send"}
    client_side = {"service_recv", "service_decode"}
    by_tid = {}
    for s in telemetry.spans_snapshot():
        tid = s.get("trace_id")
        if tid:
            by_tid.setdefault(tid, set()).add(s["name"])
    return [t for t, names in by_tid.items()
            if names & worker_side and names & client_side]


class TestServicePlane:
    def test_trace_propagation_and_merged_timeline(self, corpus,
                                                   tmp_path):
        """The ISSUE 19 headline: a service epoch produces causally
        linked cross-process traces, and dump_trace merges every
        component into ONE Chrome/Perfetto timeline."""
        fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2,
                           parser=PARSER_CFG)
        try:
            blocks = _drain_service(fleet.address)
            assert blocks
            # the client stamps each block with its grant's trace ctx
            stamped = [getattr(b, "trace_ctx", None) for b in blocks]
            assert any(c is not None for c in stamped)
            tids = {c[0] for c in stamped if c is not None}
            assert all(len(t) == 16 for t in tids)
            # one (job, part) = one trace: distinct parts, distinct ids
            assert len(tids) == NUM_PARTS
            # at least one trace links serve-side and receive-side spans
            assert len(_crossproc_traces()) >= 1
            trace_path = str(tmp_path / "pod_timeline.json")
            written = fleet.dump_trace(trace_path)
            assert written > 0
            with open(trace_path) as f:
                doc = json.load(f)
            other = doc["otherData"]
            assert other["telemetry_schema_version"] == \
                telemetry.SCHEMA_VERSION
            assert other["peers_not_merged"] == []
            # LocalFleet is ONE process: co-located peers collapse to a
            # single timeline row instead of duplicating every span
            assert len(other["peers"]) == 1
            assert "dispatcher" in other["peers"][0]
            span_names = {e["name"] for e in doc["traceEvents"]
                          if e["ph"] == "X"}
            assert {"service_grant", "service_send",
                    "service_recv"} <= span_names
        finally:
            fleet.close()

    def test_propagation_disabled_strips_the_plane(self, corpus):
        # span rings are process-global: compare against the traces
        # already retained so a prior test's epoch can't false-fail this
        before = set(_crossproc_traces())
        telemetry.set_trace_propagation(False)
        fleet = LocalFleet(corpus, 2, num_workers=1, parser=PARSER_CFG)
        try:
            blocks = _drain_service(fleet.address)
            assert blocks
            assert all(getattr(b, "trace_ctx", None) is None
                       for b in blocks)
            assert set(_crossproc_traces()) == before
        finally:
            fleet.close()

    def test_observability_rpcs_on_dispatcher_and_worker(self, corpus):
        fleet = LocalFleet(corpus, 2, num_workers=1, parser=PARSER_CFG)
        try:
            _drain_service(fleet.address)
            telemetry.record_decision("autotune", "grow",
                                      trigger={"knob": "prefetch"})
            # dispatcher control-plane RPCs
            resp = svc_dispatcher.request(fleet.address,
                                          {"cmd": "trace_dump"})
            snap = resp["snapshot"]
            assert snap["peer"] == "dispatcher"
            assert snap["schema"] == telemetry.SCHEMA_VERSION
            assert snap["pid"] == os.getpid()
            assert isinstance(snap["now"], float)
            assert any(s["name"] == "service_grant"
                       for s in snap["spans"])
            resp = svc_dispatcher.request(fleet.address,
                                          {"cmd": "metrics_text"})
            assert resp["content_type"].startswith("text/plain")
            samples = telemetry.parse_prometheus_text(resp["text"])
            assert any(n == "dmlc_tpu_service_job_parts_total"
                       for n, _, _ in samples)
            resp = svc_dispatcher.request(
                fleet.address, {"cmd": "decisions",
                                "component": "autotune"})
            assert resp["total"] >= 1
            assert all(d["component"] == "autotune"
                       for d in resp["decisions"])
            # worker data-plane RPCs: one JSON line per request
            w = fleet.workers[0]
            for cmd, check_fn in (
                    ("trace_dump",
                     lambda r: r["snapshot"]["schema"] ==
                     telemetry.SCHEMA_VERSION),
                    ("metrics_text",
                     lambda r: telemetry.parse_prometheus_text(
                         r["text"]) is not None),
                    ("decisions", lambda r: r["total"] >= 1)):
                with socket.create_connection((w.host, w.port),
                                              timeout=10.0) as s:
                    with s.makefile("rwb") as f:
                        f.write(json.dumps({"cmd": cmd}).encode()
                                + b"\n")
                        f.flush()
                        reply = json.loads(f.readline())
                assert check_fn(reply), cmd
        finally:
            fleet.close()

    def test_drain_decision_recorded_exactly_once(self, corpus):
        """The chaos acceptance: a drain shows up exactly once in the
        decisions ledger with the trigger that fired it, and the drain
        completion exactly once behind it."""
        fleet = LocalFleet(corpus, 2, num_workers=2, parser=PARSER_CFG)
        try:
            _drain_service(fleet.address)
            w = fleet.drain_worker(0, deadline=5.0)
            _wait_for(lambda: not w.alive, what="drained worker exit")
            _wait_for(lambda: telemetry.decision_counts().get(
                "dispatcher.drain_complete", 0) >= 1,
                what="drain_complete decision")
            counts = telemetry.decision_counts()
            assert counts.get("dispatcher.drain") == 1
            assert counts.get("dispatcher.drain_complete") == 1
            drains = [d for d in
                      telemetry.decisions_snapshot("dispatcher")
                      if d["action"] == "drain"]
            assert len(drains) == 1
            assert drains[0]["trigger"]["deadline_s"] == \
                pytest.approx(5.0)
            assert drains[0]["worker"]
        finally:
            fleet.close()

    def test_dispatcher_journals_decisions(self, corpus, tmp_path):
        """Decision events ride the dispatcher journal (op: decision)
        and journal replay skips them without disturbing assignment
        state."""
        journal = str(tmp_path / "disp.journal")
        fleet = LocalFleet(corpus, 2, num_workers=2,
                           parser=PARSER_CFG, journal_path=journal)
        try:
            _drain_service(fleet.address)
            w = fleet.drain_worker(0, deadline=5.0)
            _wait_for(lambda: not w.alive, what="drained worker exit")
            with open(journal) as f:
                ops = [json.loads(line) for line in f if line.strip()]
            decisions = [o for o in ops if o.get("op") == "decision"]
            assert any(o.get("action") == "drain" for o in decisions)
            # replay tolerates (skips) decision lines: restart works
            fleet.restart_dispatcher()
            resp = svc_dispatcher.request(fleet.address,
                                          {"cmd": "status"})
            assert "error" not in resp
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# controller decisions reach the ledger

def _mk_tuner(store, names, **kw):
    built = []
    for n in names:
        def apply(v, n=n):
            store[n] = int(v)
            return True

        built.append(autotune.Knob(n, get=lambda n=n: store[n],
                                   apply=apply))
    kw.setdefault("scope", "obs-tuner")
    kw.setdefault("min_batches", 4)
    return autotune.AutoTuner(built, **kw)


def _win(wall=1.0, batches=100, wait_frac=0.5, **busy):
    return {"wall": wall, "batches": batches,
            "input_wait": wait_frac * wall, "busy": busy,
            "transfer_est": 0.0, "resilience_events": 0}


class TestControllerLedger:
    def test_autotuner_moves_reach_the_ledger(self, monkeypatch):
        # worker-knob caps default to this host's CPU count (1 in CI):
        # raise them so the growth path is exercisable
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PARSE_WORKERS", "6")
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",))
        tuner.step(_win(parse=0.8))           # grow 2 -> 3
        assert store["parse_workers"] == 3
        events = telemetry.decisions_snapshot("autotune")
        assert len(events) == 1
        ev = events[0]
        assert ev["action"] == "grow"
        assert ev["trigger"]["knob"] == "parse_workers"
        assert ev["trigger"]["from"] == 2 and ev["trigger"]["to"] == 3
        assert ev["pipeline"] == "obs-tuner"
        # a regressing window reverts — also a ledger event
        tuner.step(_win(batches=70, parse=0.8))
        counts = telemetry.decision_counts()
        assert counts["autotune.grow"] == 1
        assert counts["autotune.revert"] == 1

    def test_autotuner_holds_and_skips_stay_off_the_ledger(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",))
        tuner.step({"wall": 0.0, "batches": 0, "input_wait": 0.0,
                    "busy": {}, "transfer_est": 0.0,
                    "resilience_events": 0})            # skip
        tuner.step(_win(wait_frac=0.01, parse=0.5))     # steady
        assert telemetry.decisions_snapshot("autotune") == []

    def test_parse_tier_tuner_ledger(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PARSE_WORKERS", "6")
        tuner = autotune.ParseTierTuner(start=2)
        assert tuner.decide(efficiency=0.9) == 3        # saturated
        assert tuner.decide(efficiency=0.5) == 3        # in band: quiet
        assert tuner.decide(efficiency=0.1) == 2        # idle
        events = telemetry.decisions_snapshot("parse_tier_tuner")
        assert [e["action"] for e in events] == ["grow", "shrink"]
        assert events[0]["trigger"] == {"efficiency": 0.9, "workers": 2}
        assert events[0]["next_workers"] == 3

    def test_autoscaler_decisions_with_triggers(self, corpus):
        fleet = LocalFleet(corpus, NUM_PARTS, num_workers=1,
                           parser=PARSER_CFG)
        waits = {"default": 0.0}
        try:
            scaler = fleet.autoscale(source=lambda: dict(waits),
                                     min_workers=1, max_workers=2,
                                     interval=1.0, up_ticks=2,
                                     down_ticks=2, cooldown_ticks=0,
                                     start=False)
            t = 0.0
            scaler.step(now=t)  # priming
            for _ in range(2):  # 2 starved ticks -> grow
                t += 1.0
                waits["default"] += 1.0
                scaler.step(now=t)
            _wait_for(lambda: len(fleet.live_workers()) == 2,
                      what="autoscaler grow")
            for _ in range(2):  # 2 idle ticks -> shrink
                t += 1.0
                scaler.step(now=t)
            _wait_for(lambda: len(fleet.live_workers()) == 1,
                      what="autoscaler drain")
            events = telemetry.decisions_snapshot("autoscaler")
            actions = [e["action"] for e in events]
            assert actions.count(svc_autoscale.GROW) == 1
            assert actions.count(svc_autoscale.SHRINK) == 1
            # HOLD ticks are history, not ledger noise
            assert svc_autoscale.HOLD not in actions
            grow = events[actions.index(svc_autoscale.GROW)]
            assert grow["trigger"]["wait_fracs"]["default"] > 0
            # fleet_size is recorded post-action: the grown fleet
            assert grow["trigger"]["fleet_size"] == 2
            # control ticks sampled the metrics-history ring
            assert len(telemetry.metrics_history()) >= 4
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# lint gates (ISSUE 19 satellite)

class TestLintGates:
    LINT = os.path.join(REPO, "bin", "lint_metrics.py")

    def _run(self, root):
        return subprocess.run([sys.executable, self.LINT, str(root)],
                              capture_output=True, text=True)

    @staticmethod
    def _tree(root, dispatcher_text):
        svc = root / "dmlc_tpu" / "service"
        svc.mkdir(parents=True)
        (svc / "dispatcher.py").write_text(dispatcher_text)
        (svc / "worker.py").write_text(
            "_telemetry.record_span('service_rpc', t0, dt)\n")

    def test_rpc_handler_without_span_fails(self, tmp_path):
        self._tree(tmp_path, 'if cmd == "locate":\n    pass\n'
                             'if cmd == "poll":\n    pass\n'
                             '# if cmd == "commented": ignored\n')
        proc = self._run(tmp_path)
        assert proc.returncode == 1
        assert proc.stderr.count("service_rpc") == 2
        assert "'locate'" in proc.stderr and "'poll'" in proc.stderr

    def test_rpc_handler_with_span_passes(self, tmp_path):
        self._tree(tmp_path,
                   'if cmd == "locate":\n    pass\n'
                   "_telemetry.record_span('service_rpc', t0, dt)\n")
        proc = self._run(tmp_path)
        assert proc.returncode == 0, proc.stderr

    def test_metrics_env_read_flagged(self, tmp_path):
        pkg = tmp_path / "dmlc_tpu"
        pkg.mkdir()
        (pkg / "rogue.py").write_text(
            'import os\n'
            'x = os.environ.get("DMLC_TPU_METRICS_HISTORY", "9")\n')
        proc = self._run(tmp_path)
        assert proc.returncode == 1
        assert "DMLC_TPU_METRICS_HISTORY" not in proc.stdout
        assert "knobs.py" in proc.stderr

    def test_repo_rpc_modules_are_clean(self):
        proc = self._run(REPO)
        assert proc.returncode == 0, proc.stderr
