"""Tier-1 suite for the data plane's stream (docs/service.md "The
stream"): the wire frame golden pin (the stored frame's stays pinned
separately), per-segment compression round-trips (byte-identical raw
payloads, dtype break-even decisions, measured ratio ledger),
torn/corrupt wire frames classifying retryable, the one stream protocol
(a CSR stream's digest in both directions and a snapshot epoch's frames
against PR 56's tree, an open that offers no ``"wire": 2``, a first
frame that is neither HELLO nor ERROR), pipelined fetch failover with
exact resilience counters for block and snapshot streams, the co-located
mmap fast path (byte-identity with pins held through a mid-epoch
eviction squeeze), and the knob/autotuner seams
(``service_pipeline_depth``, ``DMLC_TPU_WIRE_COMPRESSION``)."""

from __future__ import annotations

import hashlib
import json
import os
import socket
import struct
import time
import types

import numpy as np
import pytest

from dmlc_tpu.data.device import pack_dense_batches
from dmlc_tpu.data.parsers import create_parser
from dmlc_tpu.data.row_block import RowBlock
from dmlc_tpu.io import resilience
from dmlc_tpu.service import LocalFleet, ServiceParser
from dmlc_tpu.service import client as svc_client
from dmlc_tpu.service import dispatcher as svc_dispatcher
from dmlc_tpu.service import frame as svc_frame
from dmlc_tpu.service import worker as svc_worker
from dmlc_tpu.utils import knobs as _knobs
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError

from tests.test_service import (  # noqa: F401  (corpus fixture)
    CHUNK,
    NUM_PARTS,
    PARSER_CFG,
    _assert_blocks_equal,
    _drain,
    _local_blocks,
    corpus,
)

DATA_DIR = os.path.join(os.path.dirname(__file__), "data")
GOLDEN_V2 = os.path.join(DATA_DIR, "service_frame_v2.golden")


# ---------------------------------------------------------------------------
# helpers

def _golden_v2_block() -> tuple:
    """The fixed (block, resume) pair the v2 golden pins — large enough
    that the integer segments clear the compression break-even floor."""
    rows, nnz = 32, 256
    off = np.linspace(0, nnz, rows + 1).astype(np.int64)
    off[-1] = nnz
    block = RowBlock(
        offset=off,
        label=(np.arange(rows, dtype=np.float32) % 2),
        index=(np.arange(nnz, dtype=np.uint64) * 7) % 997,
        value=(np.arange(nnz, dtype=np.float32) * 0.25 - 8.0),
    )
    resume = {"kind": "split",
              "split": {"kind": "byte", "file": 0, "offset": 123},
              "chunks": 7}
    return block, resume


def _golden_v2_frame() -> bytes:
    block, resume = _golden_v2_block()
    v1 = svc_frame.encode_block_frame(block, resume)
    _, meta, payload = svc_frame.decode_frame(v1)
    v2 = svc_frame.encode_block_frame_v2(meta, payload, "zlib")
    assert v2 is not None
    return v2


# ---------------------------------------------------------------------------
# wire format: golden pins and codec round-trips

def test_frame_v2_golden_bytes():
    """The v2 frame encoding is byte-pinned: header (version 2), meta
    normalization (codec / wire map / raw_len keys), zlib output, and
    crc all drift-proof."""
    with open(GOLDEN_V2, "rb") as f:
        want = f.read()
    assert _golden_v2_frame() == want


def test_frame_v2_golden_decodes_to_v1_payload():
    """Decode-of-golden parity: the pinned v2 bytes inflate to the EXACT
    raw v1 segment payload and rebuild the exact block + annotation."""
    block, resume = _golden_v2_block()
    v1 = svc_frame.encode_block_frame(block, resume)
    _, meta1, payload1 = svc_frame.decode_frame(v1)
    with open(GOLDEN_V2, "rb") as f:
        kind, meta2, payload2 = svc_frame.decode_frame(f.read())
    assert kind == svc_frame.KIND_BLOCK
    assert bytes(payload2) == bytes(payload1)
    got = svc_frame.block_from_frame(meta2, payload2)
    np.testing.assert_array_equal(got.offset, block.offset)
    np.testing.assert_array_equal(got.index, block.index)
    np.testing.assert_array_equal(got.value, block.value)
    assert json.dumps(meta2["resume"], sort_keys=True) == \
        json.dumps(resume, sort_keys=True)


def test_frame_v2_identity_reframe_zero_copy():
    """The identity v2 path rewrites ONLY the header version byte: the
    body (meta+payload+crc) is the stored v1 frame's bytes, untouched —
    what lets the worker hand mmap'd spans to a vectored send."""
    block, resume = _golden_v2_block()
    v1 = svc_frame.encode_block_frame(block, resume)
    header, body = svc_frame.reframe_v2(v1)
    assert bytes(body) == v1[svc_frame.HEADER_LEN:]
    frame = bytes(header) + bytes(body)
    kind, meta, payload = svc_frame.decode_frame(frame)
    _, meta1, payload1 = svc_frame.decode_frame(v1)
    assert kind == svc_frame.KIND_BLOCK
    assert bytes(payload) == bytes(payload1)
    assert meta == meta1


def test_compression_break_even_per_dtype():
    """Per-segment dtype decisions: integer segments (offsets/indices)
    compress, float values ship raw, tiny segments never compress —
    and the measured ratio ledger records what each dtype actually did."""
    v2 = _golden_v2_frame()
    _, meta, _ = svc_frame.decode_frame(v2)
    wire = meta["wire"]
    # offset (<i8) and index (<u8) compressed; value/label (<f4) raw
    enc_by_name = {name: bool(enc) for name, (_w, _l, enc) in wire.items()}
    assert enc_by_name["offset"] and enc_by_name["index"]
    assert not enc_by_name["value"] and not enc_by_name["label"]
    ratios = svc_frame.wire_dtype_ratios()
    assert ratios["<i8"] < 1.0 and ratios["<u8"] < 1.0
    assert ratios["<f4"] == 1.0


def test_compression_roundtrip_every_codec_available():
    """Round-trip byte-identity through every codec this process has
    (zstd is import-gated — an absent module simply doesn't register,
    never crash)."""
    block, resume = _golden_v2_block()
    v1 = svc_frame.encode_block_frame(block, resume)
    _, meta, payload = svc_frame.decode_frame(v1)
    assert "zlib" in svc_frame.WIRE_CODECS  # stdlib floor, always there
    for codec in svc_frame.WIRE_CODECS:
        v2 = svc_frame.encode_block_frame_v2(meta, payload, codec)
        assert v2 is not None and len(v2) < len(v1)
        _, m2, p2 = svc_frame.decode_frame(v2)
        assert bytes(p2) == bytes(payload)
        assert m2["codec"] == codec


def test_incompressible_block_encodes_identity():
    """A block whose segments all sit under the break-even floor (or
    don't pay for the codec) returns None: the caller ships the
    reframed v1 bytes instead of a bigger 'compressed' frame."""
    block = RowBlock(
        offset=np.array([0, 2, 3, 5], np.int64),
        label=np.array([1.0, 0.0, 1.0], np.float32),
        index=np.array([1, 5, 7, 0, 3], np.uint64),
        value=np.array([0.5, 1.5, 2.5, -1.0, 4.25], np.float32),
    )
    v1 = svc_frame.encode_block_frame(block, None)
    _, meta, payload = svc_frame.decode_frame(v1)
    assert svc_frame.encode_block_frame_v2(meta, payload, "zlib") is None


def test_torn_and_corrupt_v2_frames_classify_retryable():
    """A truncated v2 frame and a crc byte-flip both raise
    ServiceFrameError, and the shared classifier calls it RETRYABLE —
    the client heals by re-requesting the exact block."""
    v2 = _golden_v2_frame()
    with pytest.raises(svc_frame.ServiceFrameError) as torn:
        svc_frame.decode_frame(v2[: len(v2) // 2])
    assert resilience.classify(torn.value) == resilience.RETRYABLE
    flipped = bytearray(v2)
    flipped[svc_frame.HEADER_LEN + 40] ^= 0xFF
    with pytest.raises(svc_frame.ServiceFrameError) as crc:
        svc_frame.decode_frame(bytes(flipped))
    assert resilience.classify(crc.value) == resilience.RETRYABLE


def test_negotiate_codec_preference_and_fallbacks():
    have = set(svc_frame.WIRE_CODECS)
    # both ends agree on the preferred available codec
    assert svc_frame.negotiate_codec(have) in have
    assert svc_frame.negotiate_codec(["zlib"]) == "zlib"
    # no overlap / unknown peer codecs -> identity, never an error
    assert svc_frame.negotiate_codec([]) is None
    assert svc_frame.negotiate_codec(["snappy", "brotli"]) is None


def test_wire_compression_knob_validated(monkeypatch):
    assert _knobs.wire_compression() == "auto"
    monkeypatch.setenv("DMLC_TPU_WIRE_COMPRESSION", "off")
    assert _knobs.wire_compression() == "off"
    assert _knobs.wire_compression("zlib") == "zlib"
    monkeypatch.setenv("DMLC_TPU_WIRE_COMPRESSION", "gzip9")
    with pytest.raises(DMLCError, match="wire compression"):
        _knobs.wire_compression()


def test_pipeline_depth_knob_row_and_resize(monkeypatch):
    assert _knobs.resolve("service_pipeline_depth") == 4
    monkeypatch.setenv("DMLC_TPU_SERVICE_PIPELINE_DEPTH", "16")
    assert _knobs.resolve("service_pipeline_depth") == 16
    monkeypatch.setenv("DMLC_TPU_SERVICE_PIPELINE_DEPTH", "0")
    with pytest.raises(DMLCError):
        _knobs.resolve("service_pipeline_depth")


# ---------------------------------------------------------------------------
# the one stream protocol: its open, its refusals, its bytes

class _Tee:
    """A client socket that keeps what crossed it, each way."""

    def __init__(self, sock, log):
        self._sock, self._log = sock, log

    def sendall(self, data):
        self._log["sent"] += bytes(data)
        return self._sock.sendall(data)

    def recv_into(self, buf, nbytes=0):
        got = self._sock.recv_into(buf, nbytes)
        self._log["recv"] += bytes(buf[:got])
        return got

    def __getattr__(self, name):
        return getattr(self._sock, name)


@pytest.fixture
def wire_log(monkeypatch):
    """Every stream the client module opens, recorded in both directions,
    with whatever varies by host or run pinned: the trainer's host name,
    the codecs it accepts (zlib alone, also the worker's choice), no
    trace ids on the request lines."""
    logs = []

    def create_connection(addr, timeout=None):
        logs.append({"sent": bytearray(), "recv": bytearray()})
        return _Tee(socket.create_connection(addr, timeout=timeout),
                    logs[-1])

    names = {n: getattr(socket, n) for n in dir(socket)
             if not n.startswith("__")}
    names.update(create_connection=create_connection,
                 gethostname=lambda: "trainer-host")
    monkeypatch.setattr(svc_client, "socket", types.SimpleNamespace(**names))
    monkeypatch.setattr(svc_client, "WIRE_CODECS",
                        {"zlib": svc_frame.WIRE_CODECS["zlib"]})
    monkeypatch.setenv("DMLC_TPU_WIRE_COMPRESSION", "zlib")
    telemetry.set_trace_propagation(False)
    yield logs
    telemetry.set_trace_propagation(None)


def _frames_of(raw) -> list:
    """``(kind, frame bytes)`` of every frame in a recorded direction."""
    raw, out, at = bytes(raw), [], 0
    while at < len(raw):
        _magic, _version, kind, meta_len, payload_len = struct.unpack_from(
            svc_frame._HEADER_FMT, raw, at)
        end = (at + svc_frame.HEADER_LEN + meta_len + payload_len
               + svc_frame._CRC_LEN)
        out.append((kind, raw[at:end]))
        at = end
    return out


def _wait_parsed(fleet, parts: int = NUM_PARTS) -> None:
    """Until every part is parsed: a HELLO then carries its block count,
    so what the client sends depends on nothing but what it received."""
    deadline = time.time() + 30.0
    while time.time() < deadline:
        status = svc_dispatcher.request(fleet.address, {"cmd": "status"})
        if status["completed"] == list(range(parts)):
            return
        time.sleep(0.02)
    raise AssertionError("the fleet never finished parsing")


# sha256 of each direction of the stream below as PR 56's tree
# (23576de) wrote it, recorded there by this very fixture: the request
# line, the HELLO, every fetch line and every frame of three parts on
# one reused connection
PARENT_CSR_STREAM = {
    "sent": (1504, "fda3716713da02721e7e7e73430c6e77"
                   "b90194db00498d89324423d8f8e98a83"),
    "recv": (191968, "06bdeff253fac9c368ec2fbd8ef49ff6"
                     "5f768e3ecfa553e8b80313841c82183c"),
}
# and of the 189 SNAPSHOT frames that tree PUSHED for the same corpus
# packed to 32 rows of float32, in order
PARENT_SNAPSHOT_FRAMES = (189, "0527a8c40aa315ba2a068543159fa125"
                               "205066ca555f5ee4cf7954bb90cf203f")


def test_a_csr_stream_is_the_parents_byte_for_byte_both_ways(
        corpus, wire_log):
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=1,
                       parser=PARSER_CFG)
    try:
        _wait_parsed(fleet)
        sp = ServiceParser(fleet.address)
        _assert_blocks_equal(_drain(sp), local)
        stats = sp.service_stats()
        sp.close()
    finally:
        fleet.close()
    assert len(wire_log) == 1 and stats["wire_version"] == 2
    (log,) = wire_log
    assert log["sent"].startswith(
        b'{"cmd": "stream", "part": 0, "start": 0, "job": "default", '
        b'"wire": 2, "accept": ["zlib"], "host": "trainer-host"}\n'
        b'{"block": 0, "part": 0, "job": "default"}\n')
    assert [k for k, _ in _frames_of(log["recv"])][:2] == [
        svc_frame.KIND_HELLO, svc_frame.KIND_BLOCK]
    for way, (nbytes, digest) in PARENT_CSR_STREAM.items():
        assert len(log[way]) == nbytes, way
        assert hashlib.sha256(log[way]).hexdigest() == digest, way


SNAP_GEOMETRY = {"batch_size": 32, "num_col": 6, "x_dtype": "float32"}


def test_a_snapshot_epoch_fetches_the_frames_the_parent_pushed(
        corpus, wire_log):
    """Snapshot streams ride the fetch plane: HELLO with the part's
    batch count and no codec, then one fetch line a frame, and the
    frames are the pushed ones bit for bit, in order."""
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=1,
                       parser=PARSER_CFG, snapshot=SNAP_GEOMETRY)
    try:
        _wait_parsed(fleet)
        raw0, sent0 = (telemetry.REGISTRY.counter(m, job="default").value
                       for m in (telemetry.SERVICE_WIRE_RAW_METRIC,
                                 telemetry.SERVICE_WIRE_SENT_METRIC))
        sp = ServiceParser(fleet.address)
        got = _drain(sp)
        stats = sp.service_stats()
        sp.close()
        raw, sent = (telemetry.REGISTRY.counter(m, job="default").value
                     for m in (telemetry.SERVICE_WIRE_RAW_METRIC,
                               telemetry.SERVICE_WIRE_SENT_METRIC))
    finally:
        fleet.close()
    assert stats["wire_version"] == 2 and stats["retries"] == 0
    assert len(wire_log) == 1  # the three parts shared one connection
    (log,) = wire_log
    assert log["sent"].startswith(
        b'{"cmd": "stream", "part": 0, "start": 0, "job": "default", '
        b'"wire": 2, "accept": ["zlib"], "host": "trainer-host", '
        b'"snapshot": true}\n{"block": 0, "part": 0, "job": "default"}\n')
    frames = _frames_of(log["recv"])
    kind, hello, _ = svc_frame.decode_frame(frames[0][1])
    assert kind == svc_frame.KIND_HELLO
    assert hello == {"wire": 2, "codec": None, "blocks": 63}
    packed = [raw_frame for kind, raw_frame in frames
              if kind == svc_frame.KIND_SNAPSHOT]
    count, digest = PARENT_SNAPSHOT_FRAMES
    assert len(packed) == len(got) == count
    assert hashlib.sha256(b"".join(packed)).hexdigest() == digest
    # the compression ledger counts a snapshot frame as shipped: raw = sent
    assert raw - raw0 == sent - sent0 == sum(len(f) for f in packed)


@pytest.mark.parametrize("req", [
    {"cmd": "stream", "part": 0, "start": 0, "job": "default"},
    {"cmd": "stream", "part": 0, "start": 0, "job": "default", "wire": 1},
    {"cmd": "stream", "part": 0, "start": 0, "job": "default",
     "snapshot": True},
], ids=["no_wire", "wire_1", "snapshot_no_wire"])
def test_a_stream_request_without_wire_2_gets_one_error_frame(corpus, req):
    """No push plane is left to fall back to: the answer is one ERROR
    frame that says why (not ``evicted``, not ``draining``: the client
    is at fault, no part moved), no block, and the connection closes."""
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=1,
                       parser=PARSER_CFG, snapshot=SNAP_GEOMETRY)
    try:
        _wait_parsed(fleet)
        worker = fleet.workers[0]
        with socket.create_connection((worker.host, worker.port),
                                      timeout=10.0) as sock:
            sock.sendall(json.dumps(req).encode() + b"\n")
            kind, meta, payload = svc_frame.recv_frame(sock)
            assert sock.recv(1) == b""  # closed: nothing was pushed
    finally:
        fleet.close()
    assert kind == svc_frame.KIND_ERROR and not payload
    assert set(meta) == {"error"} and "wire 2" in meta["error"]


@pytest.mark.parametrize("first", ["end", "block"])
def test_a_first_frame_neither_hello_nor_error_is_healed_by_a_retry(
        corpus, monkeypatch, first):
    """A stream whose first frame is anything but HELLO or ERROR broke
    the protocol: the client says so as it does of a torn frame, asks
    the same worker again, and the epoch is byte-identical."""
    local = _local_blocks(corpus)
    hello = svc_worker.encode_hello_frame
    wrong = {"end": svc_frame.encode_end_frame(0, 0),
             "block": svc_frame.encode_block_frame(*_golden_v2_block())}
    answers = [wrong[first]]
    monkeypatch.setattr(
        svc_worker, "encode_hello_frame",
        lambda meta: answers.pop() if answers else hello(meta))
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=1,
                       parser=PARSER_CFG)
    try:
        sp = ServiceParser(fleet.address)
        base = resilience.counters_snapshot()
        with pytest.raises(svc_frame.ServiceFrameError,
                           match="neither HELLO nor ERROR"):
            sp._ensure_stream()
        answers.append(wrong[first])
        got = _drain(sp)
        stats = sp.service_stats()
        sp.close()
    finally:
        fleet.close()
    _assert_blocks_equal(got, local)
    assert not answers
    delta = resilience.counters_delta(base)
    assert delta["service_retries"] == stats["retries"] == 1
    assert delta["service_failovers"] == delta["service_giveups"] == 0
    assert stats["wire_version"] == 2


def test_v2_transport_byte_identical_with_wire_ledger(corpus):
    """The v2 acceptance core: a pipelined, compressed epoch is
    byte-identical to local parsing and the compression-ratio ledger
    (service_wire_bytes_raw/sent, job-labeled) measured a real
    reduction (integer segments compress on this corpus)."""
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2,
                       parser=PARSER_CFG)
    try:
        raw0 = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_RAW_METRIC, job="default").value
        sent0 = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_SENT_METRIC, job="default").value
        sp = ServiceParser(fleet.address)
        got = _drain(sp)
        sp.close()
        _assert_blocks_equal(got, local)
        raw = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_RAW_METRIC, job="default").value - raw0
        sent = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_SENT_METRIC,
            job="default").value - sent0
        assert raw > 0
        assert 0 < sent < raw  # compressed: strictly fewer wire bytes
    finally:
        fleet.close()


def test_wire_compression_off_ships_identity(corpus, monkeypatch):
    """``DMLC_TPU_WIRE_COMPRESSION=off`` pins the negotiated codec to
    identity: the ledger's sent bytes match raw (vectored reframe only),
    and the stream stays byte-identical."""
    monkeypatch.setenv("DMLC_TPU_WIRE_COMPRESSION", "off")
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2,
                       parser=PARSER_CFG)
    try:
        raw0 = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_RAW_METRIC, job="default").value
        sent0 = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_SENT_METRIC, job="default").value
        sp = ServiceParser(fleet.address)
        got = _drain(sp)
        assert sp._codec is None
        sp.close()
        _assert_blocks_equal(got, local)
        raw = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_RAW_METRIC, job="default").value - raw0
        sent = telemetry.REGISTRY.counter(
            telemetry.SERVICE_WIRE_SENT_METRIC,
            job="default").value - sent0
        assert raw > 0 and sent == raw
    finally:
        fleet.close()


def _packed_batches(path: str, num_parts: int, geometry: dict) -> list:
    """What a snapshot-mode epoch delivers, from local parsing: each
    part's blocks packed to the geometry on their own (a part's tail is
    padded, never joined to the next part's head)."""
    out = []
    for p in range(num_parts):
        parser = create_parser(path, p, num_parts, "libsvm",
                               threaded=False, chunk_bytes=CHUNK)
        out.extend(packed for packed, _resume in pack_dense_batches(
            _drain(parser), geometry["batch_size"], geometry["num_col"]))
        parser.close()
    return out


@pytest.mark.parametrize("geometry", [
    None, {"batch_size": 256, "num_col": 6, "x_dtype": "float32"}],
    ids=["csr", "snapshot"])
def test_kill_worker_mid_pipelined_stream_exact_counters(
        corpus, monkeypatch, geometry):
    """Failover under a deep in-flight window: a worker killed while the
    client has 8 pipelined fetches outstanding costs EXACTLY one
    service_retries and one service_failovers — the reconnect
    re-negotiates and re-issues the window from the exact block cursor,
    and the epoch stays byte-identical to local parsing. A snapshot
    stream is the same exchange: its cursor counts packed batches."""
    monkeypatch.setenv("DMLC_TPU_SERVICE_PIPELINE_DEPTH", "8")
    if geometry is None:
        local, same = _local_blocks(corpus, 4), _assert_blocks_equal
    else:
        local = _packed_batches(corpus, 4, geometry)

        def same(got, want):
            assert len(got) == len(want)
            for g, w in zip(got, want):
                assert g.packed and g.x.tobytes() == w.tobytes()

    fleet = LocalFleet(corpus, 4, num_workers=2, parser=PARSER_CFG,
                       snapshot=geometry)
    try:
        sp = ServiceParser(fleet.address)
        assert sp.pipeline_depth == 8
        base = resilience.counters_snapshot()
        got = [sp.next_block() for _ in range(7)]
        state = sp.state_dict()
        assert (state["part"], state["block"]) == (1, 1)  # mid-part
        # kill the owner of the LAST part (its frames cannot already sit
        # in the client's TCP buffer), same scheme as the acceptance run
        # of tests/test_service.py
        deadline = time.time() + 5.0
        while time.time() < deadline:
            status = svc_dispatcher.request(fleet.address,
                                            {"cmd": "status"})
            if "3" in status["assigned"]:
                break
            time.sleep(0.02)
        victim = next(i for i, w in enumerate(fleet.workers)
                      if w.worker_id == status["assigned"]["3"])
        fleet.kill_worker(victim)
        got.extend(_drain(sp))
        assert sp.service_stats()["wire_version"] == 2
        sp.close()
        same(got, local)
        delta = resilience.counters_delta(base)
        assert delta["service_retries"] == 1
        assert delta["service_failovers"] == 1
        assert delta["service_giveups"] == 0
        # mid-epoch checkpoint restores into a fresh pipelined client
        sp2 = ServiceParser(fleet.address)
        sp2.load_state(state)
        rest = _drain(sp2)
        sp2.close()
        same(rest, local[7:])
    finally:
        fleet.close()


def test_resize_pipeline_depth_contract(corpus):
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=1,
                       parser=PARSER_CFG)
    try:
        sp = ServiceParser(fleet.address)
        assert sp.resize_pipeline_depth(8) is True
        assert sp.pipeline_depth == 8
        assert sp.resize_pipeline_depth(8) is False  # no-op
        assert sp.resize_pipeline_depth(0) is False  # below floor
        assert sp.pipeline_depth == 8
        sp.close()
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# co-located mmap fast path

def test_fastpath_byte_identity_through_eviction_squeeze(
        corpus, tmp_path, monkeypatch):
    """The zero-copy local fast path: a co-located client's second epoch
    serves EVERY block off the published block caches (no TCP), stays
    byte-identical — and a starvation-level byte budget armed mid-epoch
    evicts nothing while the client's reader pin holds. Once the fleet
    and client are gone the same budget pass evicts the artifacts,
    proving the pins were the protection."""
    from dmlc_tpu.store import reset_stores, store_for

    share = str(tmp_path / "share")
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2,
                       parser=PARSER_CFG, share_dir=share)
    cached = []
    try:
        sp = ServiceParser(fleet.address)
        _assert_blocks_equal(_drain(sp), local)
        cached = sorted(n for n in os.listdir(share) if ".part" in n)
        assert len(cached) == NUM_PARTS
        # epoch 2: every part is complete and published -> all mmap
        sp.before_first()
        fp0 = sp.fastpath_blocks  # the ledger is cumulative across epochs
        got = [sp.next_block() for _ in range(3)]
        assert sp.fastpath_blocks - fp0 >= 3  # the map is live mid-part
        # mid-epoch eviction squeeze: 1-byte budget + fresh store pass
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1")
        reset_stores()
        st = store_for(os.path.join(share, cached[0]))
        live = [e for e in st.entries() if not e["evicted"]]
        assert sorted(e["path"] for e in live) == cached
        assert all(e["pinned"] for e in live)
        got.extend(_drain(sp))
        _assert_blocks_equal(got, local)
        assert sp.fastpath_blocks - fp0 == len(local)  # zero TCP epoch
        sp.close()
    finally:
        fleet.close()
    # every pin dropped: the same budget pass now evicts the artifacts
    reset_stores()
    store_for(os.path.join(share, cached[0]))
    assert not [n for n in os.listdir(share) if ".part" in n]
    reset_stores()  # do not leak the budget-armed store to later tests


def test_fastpath_checkpoint_restore_exact_block(corpus, tmp_path):
    """A mid-epoch (part, block) checkpoint taken off the fast path
    restores into a FRESH client byte-identically — the fast path keeps
    the same cursor contract as the wire."""
    share = str(tmp_path / "share")
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2,
                       parser=PARSER_CFG, share_dir=share)
    try:
        sp = ServiceParser(fleet.address)
        _assert_blocks_equal(_drain(sp), local)  # publish the caches
        sp.before_first()
        first = [sp.next_block() for _ in range(7)]
        state = sp.state_dict()
        sp.close()
        sp2 = ServiceParser(fleet.address)
        sp2.load_state(state)
        rest = _drain(sp2)
        sp2.close()
        _assert_blocks_equal(first + rest, local)
    finally:
        fleet.close()
