"""Tier-1 tests for the disaggregated RowBlock data service
(dmlc_tpu/service, docs/service.md): wire-format golden pins, dispatcher
split-assignment semantics, and the end-to-end acceptance run — a
1-dispatcher + 2-worker localhost fleet whose delivered stream is
byte-identical to local parsing, survives a worker killed mid-epoch with
exact resilience counters, and restores mid-epoch checkpoints into a
fresh service connection."""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.data.parsers import Parser, create_parser
from dmlc_tpu.data.row_block import RowBlock
from dmlc_tpu.io import resilience
from dmlc_tpu.io.uri import URISpec
from dmlc_tpu.service import LocalFleet, ServiceParser
from dmlc_tpu.service import dispatcher as svc_dispatcher
from dmlc_tpu.service import frame as svc_frame
from dmlc_tpu.utils.check import DMLCError

DATA_DIR = os.path.join(os.path.dirname(__file__), "data")
GOLDEN = os.path.join(DATA_DIR, "service_frame_v1.golden")

CHUNK = 16384
NUM_PARTS = 3
PARSER_CFG = {"format": "libsvm", "threaded": False, "chunk_bytes": CHUNK}


# ---------------------------------------------------------------------------
# helpers

def _golden_block() -> tuple:
    """The fixed (block, resume) pair the golden frame pins."""
    block = RowBlock(
        offset=np.array([0, 2, 3, 5], np.int64),
        label=np.array([1.0, 0.0, 1.0], np.float32),
        index=np.array([1, 5, 7, 0, 3], np.uint64),
        value=np.array([0.5, 1.5, 2.5, -1.0, 4.25], np.float32),
        weight=np.array([1.0, 2.0, 0.5], np.float32),
        qid=np.array([4, 4, 9], np.int64),
    )
    resume = {"kind": "split",
              "split": {"kind": "byte", "file": 0, "offset": 4242},
              "chunks": 3}
    return block, resume


def _write_corpus(path, rows: int = 6000, cols: int = 6, seed: int = 0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(rows):
            feats = " ".join(f"{j}:{rng.normal():.4f}" for j in range(cols))
            f.write(f"{i % 2} {feats}\n")
    return str(path)


def _local_blocks(path: str, num_parts: int = NUM_PARTS):
    """The single-host reference stream: parts looped in order with the
    exact parser config the dispatcher ships."""
    out = []
    for p in range(num_parts):
        parser = create_parser(path, p, num_parts, "libsvm",
                               threaded=False, chunk_bytes=CHUNK)
        while (blk := parser.next_block()) is not None:
            out.append(blk)
        parser.close()
    return out


def _drain(parser: Parser):
    out = []
    while (blk := parser.next_block()) is not None:
        out.append(blk)
    return out


def _assert_blocks_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a.offset, b.offset)
        np.testing.assert_array_equal(a.label, b.label)
        np.testing.assert_array_equal(a.index, b.index)
        assert a.index.dtype == b.index.dtype
        for name in ("value", "weight", "qid", "field"):
            va, vb = getattr(a, name), getattr(b, name)
            assert (va is None) == (vb is None), name
            if va is not None:
                np.testing.assert_array_equal(va, vb)
        # resume annotations must survive the wire byte-for-byte
        ra = json.dumps(getattr(a, "resume_state", None), sort_keys=True)
        rb = json.dumps(getattr(b, "resume_state", None), sort_keys=True)
        assert ra == rb


@pytest.fixture
def corpus(tmp_path):
    return _write_corpus(tmp_path / "c.libsvm")


@pytest.fixture
def fleet(corpus):
    fl = LocalFleet(corpus, NUM_PARTS, num_workers=2, parser=PARSER_CFG)
    yield fl
    fl.close()


# ---------------------------------------------------------------------------
# wire format

def test_frame_golden_bytes():
    """The v1 frame encoding is byte-pinned: any drift in the header,
    meta JSON normalization, segment order/alignment, or crc breaks here,
    never silently on the wire."""
    block, resume = _golden_block()
    frame = svc_frame.encode_block_frame(block, resume)
    with open(GOLDEN, "rb") as f:
        want = f.read()
    assert frame == want


def test_frame_golden_decodes():
    """Decode-of-golden parity: the pinned bytes rebuild the exact block
    and annotation."""
    block, resume = _golden_block()
    with open(GOLDEN, "rb") as f:
        raw = f.read()
    kind, meta, payload = svc_frame.decode_frame(raw)
    assert kind == svc_frame.KIND_BLOCK
    got = svc_frame.block_from_frame(meta, payload)
    _block = block
    _block.resume_state = json.loads(json.dumps(resume))
    _assert_blocks_equal([got], [_block])
    assert meta["rows"] == 3
    assert meta["num_col"] == 8


def test_frame_reuses_a_warm_blocks_cache_span(corpus, tmp_path):
    """A block served off a warm block cache carries its cache span
    (``block.encoded``: the mmap's bytes, written by the one Python
    encoder) and ``encode_block_frame`` ships that span as the payload
    with no re-encode: the frame is byte-identical to the one the same
    arrays give through ``write_segments``, and its payload is the
    cache file's own ``[pos, end)`` bytes."""
    from dmlc_tpu.data import create_parser

    cache = str(tmp_path / "c.blockcache")
    resume = {"kind": "blocks", "blocks": 1}
    p = create_parser(corpus, 0, 1, "libsvm", chunk_bytes=CHUNK,
                      block_cache=cache)
    try:
        while p.next_block() is not None:
            pass
        p.before_first()
        assert p.cache_state == "warm"
        block = p.next_block()
        enc = block.encoded
        assert enc is not None
        fast = svc_frame.encode_block_frame(block, resume)
        plain_block = RowBlock.from_segments(block.to_segments())
        assert getattr(plain_block, "encoded", None) is None
        plain = svc_frame.encode_block_frame(plain_block, resume)
        assert bytes(fast) == bytes(plain)
        kind, meta, payload = svc_frame.decode_frame(bytes(fast))
        assert kind == svc_frame.KIND_BLOCK and meta["rows"] == len(block)
        assert bytes(payload) == bytes(enc.data)
        with open(cache, "rb") as f:
            raw = f.read()
        at = raw.find(bytes(enc.data))
        assert at >= 0 and at % 64 == 0  # the file's own aligned span
        del block, enc, plain_block, payload
    finally:
        p.close()


def test_frame_roundtrip_optional_arrays():
    """Absent optional arrays (binary features, unweighted rows) stay
    absent through the wire — None never densifies to ones."""
    block = RowBlock(
        offset=np.array([0, 1, 3], np.int64),
        label=np.array([0.0, 1.0], np.float32),
        index=np.array([2, 0, 9], np.uint32),
    )
    kind, meta, payload = svc_frame.decode_frame(
        svc_frame.encode_block_frame(block, None))
    got = svc_frame.block_from_frame(meta, payload)
    assert got.value is None and got.weight is None and got.qid is None
    np.testing.assert_array_equal(got.index, block.index)
    assert got.index.dtype == np.uint32
    assert getattr(got, "resume_state", None) is None
    # control frames round-trip their meta
    kind, meta, _ = svc_frame.decode_frame(svc_frame.encode_end_frame(2, 17))
    assert kind == svc_frame.KIND_END and meta == {"blocks": 17, "part": 2}
    kind, meta, _ = svc_frame.decode_frame(svc_frame.encode_error_frame("x"))
    assert kind == svc_frame.KIND_ERROR and meta["error"] == "x"


def test_frame_crc_detects_corruption():
    """A flipped payload byte fails the trailing crc — and the error
    classifies retryable, so the client re-requests instead of dying."""
    block, resume = _golden_block()
    raw = bytearray(svc_frame.encode_block_frame(block, resume))
    raw[-20] ^= 0xFF  # payload byte (crc is the final 4)
    with pytest.raises(svc_frame.ServiceFrameError) as exc_info:
        svc_frame.decode_frame(bytes(raw))
    assert resilience.classify(exc_info.value) == resilience.RETRYABLE


# ---------------------------------------------------------------------------
# dispatcher split assignment

def test_dispatcher_fcfs_exactly_once_and_reissue(tmp_path):
    disp = svc_dispatcher.Dispatcher("dummy.libsvm", 4,
                                     parser={"format": "libsvm"},
                                     liveness_timeout=0)
    try:
        addr = disp.address
        cfg = svc_dispatcher.request(addr, {"cmd": "config"})
        # every response carries the monotonic generation token (1 for a
        # journal-less dispatcher's whole life — no restart can recover)
        # and a monotonic clock stamp (the peer-clock-offset estimate
        # behind merged pod timelines, docs/observability.md)
        assert isinstance(cfg.pop("now"), float)
        assert cfg == {"uri": "dummy.libsvm", "num_parts": 4,
                       "parser": {"format": "libsvm"}, "plan": {},
                       "snapshot": {}, "wire": 2, "gen": 1}
        # unregistered workers get no splits
        resp = svc_dispatcher.request(addr, {"cmd": "next_split",
                                             "worker": "ghost"})
        assert resp["part"] is None and resp.get("register")
        for w, port in (("a", 1111), ("b", 2222)):
            svc_dispatcher.request(addr, {"cmd": "register", "worker": w,
                                          "host": "127.0.0.1",
                                          "port": port})
        # first-come-first-served visitation, exactly once
        grants = []
        for w in ("a", "b", "a", "b"):
            grants.append((w, svc_dispatcher.request(
                addr, {"cmd": "next_split", "worker": w})["part"]))
        assert grants == [("a", 0), ("b", 1), ("a", 2), ("b", 3)]
        assert svc_dispatcher.request(
            addr, {"cmd": "next_split", "worker": "a"})["part"] is None
        loc = svc_dispatcher.request(addr, {"cmd": "locate", "part": 1})
        assert (loc["worker"], loc["port"]) == ("b", 2222)
        # a lost worker's parts re-issue at the FRONT, lowest first
        svc_dispatcher.request(addr, {"cmd": "report_lost", "worker": "b"})
        assert svc_dispatcher.request(
            addr, {"cmd": "locate", "part": 1}).get("wait")
        assert svc_dispatcher.request(
            addr, {"cmd": "next_split", "worker": "a"})["part"] == 1
        assert svc_dispatcher.request(
            addr, {"cmd": "next_split", "worker": "a"})["part"] == 3
        # the dead worker must re-register before it can own parts again
        resp = svc_dispatcher.request(addr, {"cmd": "next_split",
                                             "worker": "b"})
        assert resp["part"] is None and resp.get("register")
    finally:
        disp.close()


def test_dispatcher_stale_heartbeat_reissues(tmp_path):
    disp = svc_dispatcher.Dispatcher("dummy", 1, liveness_timeout=0.2)
    try:
        addr = disp.address
        svc_dispatcher.request(addr, {"cmd": "register", "worker": "a",
                                      "host": "h", "port": 1})
        assert svc_dispatcher.request(
            addr, {"cmd": "next_split", "worker": "a"})["part"] == 0
        time.sleep(0.4)  # no heartbeats: the locate reaps the stale owner
        assert svc_dispatcher.request(
            addr, {"cmd": "locate", "part": 0}).get("wait")
    finally:
        disp.close()


# ---------------------------------------------------------------------------
# end to end

def test_service_stream_byte_identical(corpus, fleet):
    local = _local_blocks(corpus)
    sp = ServiceParser(fleet.address)
    got = _drain(sp)
    _assert_blocks_equal(got, local)
    assert sp.bytes_read > 0
    stages = sp.stage_seconds()
    assert stages["read"] > 0.0
    # second epoch re-serves from the worker frame stores, identically
    sp.before_first()
    _assert_blocks_equal(_drain(sp), local)
    sp.close()


def test_clean_run_leaves_control_plane_counters_at_zero(corpus):
    """A healthy fleet's epochs touch none of the recovery machinery: the
    control-plane quartet and the elastic-membership sextet are all in
    the resilience books and all read zero, so a nonzero one in a run's
    stats means a restart, a preemption or a hedge really happened."""
    quiet = ("dispatcher_restarts", "worker_reregistrations",
             "parts_reclaimed", "control_plane_retries", "worker_drains",
             "drain_handoffs", "preemption_notices", "speculative_reissues",
             "speculative_wins", "worker_joins")
    base = resilience.counters_snapshot()
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2, parser=PARSER_CFG)
    try:
        sp = ServiceParser(fleet.address)
        first = _drain(sp)
        sp.before_first()
        assert len(_drain(sp)) == len(first) > 0
        sp.close()
    finally:
        fleet.close()
    delta = resilience.counters_delta(base)
    assert [k for k in quiet if k not in delta] == []
    assert {k: delta[k] for k in quiet if delta[k]} == {}
    assert delta["service_giveups"] == 0
    assert delta["service_parts_parsed"] == NUM_PARTS


def test_service_worker_killed_mid_epoch(corpus):
    """The acceptance run: 2 workers, one killed mid-epoch while the
    client streams from it — the epoch stays byte-identical to local
    parsing, with EXACTLY one service_retries and one service_failovers
    (the resume landed on the surviving worker), and a mid-epoch client
    checkpoint taken before the kill restores into a fresh service
    connection."""
    local = _local_blocks(corpus, 4)
    fleet = LocalFleet(corpus, 4, num_workers=2, parser=PARSER_CFG)
    try:
        sp = ServiceParser(fleet.address)
        base = resilience.counters_snapshot()
        got = [sp.next_block() for _ in range(7)]
        state = sp.state_dict()  # mid-epoch checkpoint, pre-kill
        # kill the owner of the LAST part: its frames cannot already sit
        # in the client's TCP buffer (killing the current sender can be
        # invisible when the whole part was already buffered), so exactly
        # one fault is observed — either the live stream breaking or the
        # dead listener refusing the part-3 connection
        deadline = time.time() + 5.0
        while time.time() < deadline:
            status = svc_dispatcher.request(fleet.address, {"cmd": "status"})
            if "3" in status["assigned"]:
                break
            time.sleep(0.02)
        victim = next(i for i, w in enumerate(fleet.workers)
                      if w.worker_id == status["assigned"]["3"])
        fleet.kill_worker(victim)
        got.extend(_drain(sp))
        sp.close()
        _assert_blocks_equal(got, local)
        delta = resilience.counters_delta(base)
        assert delta["service_retries"] == 1
        assert delta["service_failovers"] == 1
        assert delta["service_giveups"] == 0
        # checkpoint -> FRESH client over a fresh connection: the stream
        # resumes at the exact block, served by the surviving worker
        sp2 = ServiceParser(fleet.address)
        sp2.load_state(state)
        rest = _drain(sp2)
        sp2.close()
        _assert_blocks_equal(rest, local[7:])
    finally:
        fleet.close()


def test_service_all_workers_dead_gives_up(corpus):
    fleet = LocalFleet(corpus, 2, num_workers=1, parser=PARSER_CFG)
    try:
        sp = ServiceParser(
            fleet.address,
            retry_policy=resilience.RetryPolicy(
                max_attempts=2, base_delay=0.01, max_delay=0.02,
                attempt_timeout=0.5))
        base = resilience.counters_snapshot()
        assert sp.next_block() is not None
        fleet.kill_worker(0)
        with pytest.raises(DMLCError):
            _drain(sp)
        delta = resilience.counters_delta(base)
        assert delta["service_giveups"] == 1
        assert delta["service_retries"] >= 1
        sp.close()
    finally:
        fleet.close()


def test_torn_frame_soft_retry_before_report_lost(corpus, fleet,
                                                  monkeypatch):
    """One torn frame (crc blip) re-requests the exact block from the
    SAME owner — report_lost (which re-queues the worker's whole share)
    only fires on a repeat from that owner. Asserted on the report_lost
    request itself: a blamed worker legitimately re-registers within its
    poll interval, so dispatcher 'alive' state is racy to observe."""
    reported = []
    orig_request = svc_dispatcher.request

    def recording(address, req, **kw):
        if req.get("cmd") == "report_lost":
            reported.append(req["worker"])
        return orig_request(address, req, **kw)

    monkeypatch.setattr(svc_dispatcher, "request", recording)
    sp = ServiceParser(fleet.address)
    assert sp.next_block() is not None
    pos = sp._pos
    sp._on_stream_fault(svc_frame.ServiceFrameError("crc mismatch"))
    assert reported == []  # NOT blamed for one blip
    blk = sp.next_block()  # resumes at the exact block, same owner
    assert blk is not None and sp._pos == pos + 1
    # a repeat torn frame from the same owner escalates to report_lost
    owner = sp._owner
    sp._soft_retry_owner = owner
    sp._on_stream_fault(svc_frame.ServiceFrameError("crc mismatch again"))
    assert reported == [owner]
    sp.close()


def test_service_feeds_device_iter(corpus, fleet):
    """ServiceParser is a drop-in DeviceIter source: batches match a
    local pipeline fed the same blocks, stats attribute the service
    supply under read/parse, and a mid-epoch DeviceIter checkpoint
    (annotation-kind state) restores into a fresh service client via the
    workers' annotation index."""
    local = _local_blocks(corpus)

    class _ListParser(Parser):
        def __init__(self, blocks):
            self._blocks, self._i = blocks, 0

        def next_block(self):
            if self._i >= len(self._blocks):
                return None
            self._i += 1
            return self._blocks[self._i - 1]

        def before_first(self):
            self._i = 0

    it_local = DeviceIter(_ListParser(local), num_col=6, batch_size=64,
                          layout="dense")
    want = [(np.asarray(x), np.asarray(y), np.asarray(w))
            for x, y, w in it_local]
    it_local.close()

    it = DeviceIter(ServiceParser(fleet.address), num_col=6, batch_size=64,
                    layout="dense")
    got = [(np.asarray(x), np.asarray(y), np.asarray(w)) for x, y, w in it]
    assert len(got) == len(want)
    for (xa, ya, wa), (xb, yb, wb) in zip(got, want):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
        np.testing.assert_array_equal(wa, wb)
    stats = it.stats()
    assert stats["stages"]["read"] >= 0.0
    it.close()

    # DeviceIter checkpoint -> fresh client + fresh DeviceIter
    it2 = DeviceIter(ServiceParser(fleet.address), num_col=6, batch_size=64,
                     layout="dense")
    for _ in range(9):
        next(it2)
    state = it2.state_dict()
    assert state["kind"] == "source"  # byte-exact annotation state
    it2.close()
    it3 = DeviceIter(ServiceParser(fleet.address), num_col=6, batch_size=64,
                     layout="dense")
    it3.load_state(state)
    rest = [(np.asarray(x), np.asarray(y), np.asarray(w))
            for x, y, w in it3]
    assert len(rest) == len(want) - 9
    for (xa, ya, wa), (xb, yb, wb) in zip(rest, want[9:]):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
    it3.close()


def test_service_parser_annotation_state_restore(corpus, fleet):
    """A parser-chain checkpoint (kind='split' annotation) taken against
    LOCAL parsing restores into a service client at the exact block —
    the service analog of BlockCacheIter's stored-annotation match."""
    local = _local_blocks(corpus)
    # the annotation of block k marks the position after it: a local
    # parser checkpointed there resumes at k+1
    k = 4
    annot = dict(local[k].resume_state)
    sp = ServiceParser(fleet.address)
    sp.load_state(annot)
    rest = _drain(sp)
    _assert_blocks_equal(rest, local[k + 1:])
    # and epoch-start states rewind cleanly
    sp.load_state({"kind": "split", "split": {}, "chunks": 0})
    assert len(_drain(sp)) == len(local)
    sp.close()


def test_service_uri_suffix_and_factories(corpus, fleet):
    spec = URISpec(f"{corpus}#service=127.0.0.1:9999")
    assert spec.service == "127.0.0.1:9999"
    assert spec.cache_file is None and spec.block_cache is None
    with pytest.raises(DMLCError):
        URISpec(f"{corpus}#service=")
    local = _local_blocks(corpus)
    # create_parser routes the suffix to a ServiceParser
    parser = create_parser(f"{corpus}#service={fleet.address}")
    assert isinstance(parser, ServiceParser)
    _assert_blocks_equal(_drain(parser), local)
    parser.close()
    # create_row_block_iter(service=...) drains the same stream
    from dmlc_tpu.data.iterators import create_row_block_iter

    it = create_row_block_iter(corpus, service=fleet.address, silent=True)
    big = it.next_block()
    assert len(big) == sum(len(b) for b in local)
    it.close()


def test_service_worker_block_cache(corpus, tmp_path):
    """Workers run the existing BlockCacheIter stack when the dispatcher
    config carries block_cache: the stream stays byte-identical and the
    partition-qualified caches are published on disk."""
    cache = str(tmp_path / "svc.blockcache")
    cfg = dict(PARSER_CFG, block_cache=cache)
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2, parser=cfg)
    try:
        sp = ServiceParser(fleet.address)
        _assert_blocks_equal(_drain(sp), local)
        sp.close()
        published = [p for p in range(NUM_PARTS) if os.path.exists(
            f"{cache}.split{NUM_PARTS}.part{p}")]
        assert published == list(range(NUM_PARTS))
    finally:
        fleet.close()


def test_service_tracker_fleet_pod_metrics(corpus):
    """Tracker-launched fleet: workers fetch ranks over the rabit
    protocol and their telemetry (incl. service_* span counts) flows
    through the PR-6 `metrics` command into the tracker's pod table."""
    fleet = LocalFleet(corpus, 2, num_workers=2, parser=PARSER_CFG,
                       tracker=True, heartbeat_interval=0.2)
    try:
        assert sorted(w.rank for w in fleet.workers) == [0, 1]
        assert sorted(w.worker_id for w in fleet.workers) == ["rank0",
                                                              "rank1"]
        sp = ServiceParser(fleet.address)
        n = len(_drain(sp))
        assert n > 0
        sp.close()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            pod = fleet.tracker.pod_metrics()
            spans = (pod.get(0) or {}).get("spans") or {}
            if sorted(pod) == [0, 1] and spans.get("service_encode"):
                break
            time.sleep(0.05)
        pod = fleet.tracker.pod_metrics()
        assert sorted(pod) == [0, 1]
        spans = pod[0].get("spans") or {}
        assert spans.get("service_encode", 0) > 0
        assert spans.get("service_send", 0) > 0
        table = fleet.tracker.format_pod_table()
        assert "rank" in table
    finally:
        fleet.close()


def test_lint_gates_cover_service_dir():
    """make lint-metrics / lint-retry / lint-store scan dmlc_tpu/service:
    the subsystem keeps its bookkeeping on the telemetry layer, its
    backoff on the shared RetryPolicy, and its dispatcher journal on the
    store's AppendJournal substrate (a hand-rolled .tmp publish or
    ad-hoc counter beside the journal fails the gates)."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    svc = os.path.join(root, "dmlc_tpu", "service")
    for tool in ("lint_metrics", "lint_retry", "lint_store"):
        spec = importlib.util.spec_from_file_location(
            tool, os.path.join(root, "bin", f"{tool}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for name in sorted(os.listdir(svc)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(svc, name), encoding="utf-8") as f:
                offenders = mod.scan_source(f.read())
            assert not offenders, (tool, name, offenders)
