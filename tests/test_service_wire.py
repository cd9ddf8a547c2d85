"""Tier-1 suite for the wire v2 data plane (docs/service.md Wire v2):
the v2 frame golden pin (v1 stays pinned separately), per-segment
compression round-trips (byte-identical raw payloads, dtype break-even
decisions, measured ratio ledger), torn/corrupt v2 frames classifying
retryable, the stream-open version negotiation matrix in both
directions, pipelined fetch failover with exact resilience counters,
the co-located mmap fast path (byte-identity with pins held through a
mid-epoch eviction squeeze), and the knob/autotuner seams
(``service_pipeline_depth``, ``DMLC_TPU_WIRE_COMPRESSION``)."""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from dmlc_tpu.data.row_block import RowBlock
from dmlc_tpu.io import resilience
from dmlc_tpu.service import LocalFleet, ServiceParser
from dmlc_tpu.service import dispatcher as svc_dispatcher
from dmlc_tpu.service import frame as svc_frame
from dmlc_tpu.service import worker as svc_worker
from dmlc_tpu.utils import knobs as _knobs
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError

from tests.test_service import (  # noqa: F401  (corpus fixture)
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
# negotiation matrix (both directions) and the transport end to end

def test_v2_client_v1_worker_falls_back(corpus, monkeypatch):
    """An old worker ignores the v2 offer keys and pushes v1 frames from
    ``start``: the client's handshake peek sees a data frame instead of
    a HELLO, stashes it, and the epoch is byte-identical on the v1
    plane."""
    monkeypatch.setattr(
        svc_worker.ParseWorker, "_serve_stream_v2",
        lambda self, conn, rfile, job, part, accept, host:
            self._serve_stream(conn, job, part, 0))
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2,
                       parser=PARSER_CFG)
    try:
        sp = ServiceParser(fleet.address)
        got = _drain(sp)
        assert sp._wire == 1 and sp.fastpath_blocks == 0
        sp.close()
        _assert_blocks_equal(got, local)
    finally:
        fleet.close()


def test_v1_client_v2_worker_serves_v1(corpus):
    """An old client sends no ``wire`` offer: the v2 worker dispatches
    the plain v1 push stream and the epoch is byte-identical."""
    local = _local_blocks(corpus)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2,
                       parser=PARSER_CFG)
    try:
        sp = ServiceParser(fleet.address)
        sp._offer_wire = 1  # the compat escape hatch IS the old client
        got = _drain(sp)
        sp.close()
        _assert_blocks_equal(got, local)
    finally:
        fleet.close()


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


def test_kill_worker_mid_pipelined_stream_exact_counters(
        corpus, monkeypatch):
    """Failover under a deep in-flight window: a worker killed while the
    client has 8 pipelined fetches outstanding costs EXACTLY one
    service_retries and one service_failovers — the reconnect
    re-negotiates and re-issues the window from the exact block cursor,
    and the epoch stays byte-identical to local parsing."""
    monkeypatch.setenv("DMLC_TPU_SERVICE_PIPELINE_DEPTH", "8")
    local = _local_blocks(corpus, 4)
    fleet = LocalFleet(corpus, 4, num_workers=2, parser=PARSER_CFG)
    try:
        sp = ServiceParser(fleet.address)
        assert sp.pipeline_depth == 8
        base = resilience.counters_snapshot()
        got = [sp.next_block() for _ in range(7)]
        state = sp.state_dict()
        # kill the owner of the LAST part (its frames cannot already sit
        # in the client's TCP buffer), same scheme as the v1 acceptance
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
        sp.close()
        _assert_blocks_equal(got, local)
        delta = resilience.counters_delta(base)
        assert delta["service_retries"] == 1
        assert delta["service_failovers"] == 1
        assert delta["service_giveups"] == 0
        # mid-epoch checkpoint restores into a fresh pipelined client
        sp2 = ServiceParser(fleet.address)
        sp2.load_state(state)
        rest = _drain(sp2)
        sp2.close()
        _assert_blocks_equal(rest, local[7:])
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
