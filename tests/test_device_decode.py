"""Device-side decode (ISSUE 18): raw container spans -> batches in HBM.

Covers the three layers of the tier: the ops/device_decode primitives
(span slicing + bitcast widening parity against host ``np.frombuffer``
views, the Pallas byte-plane kernel under ``interpret=True``, the
quantize/dequant pair), the DeviceIter integration (``device_decode=True``
warm epochs with EXACTLY zero host convert busy, byte-identical batches,
cross-mode checkpoints, the env knob), the service wire (snapshot frame
payloads device-decoding on the trainer), and the lint gate that keeps
per-batch host decode off the warm serve path."""

import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dmlc_tpu.data import create_parser  # noqa: E402
from dmlc_tpu.data.device import DeviceIter  # noqa: E402
from dmlc_tpu.ops import device_decode as dd  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUM_COL = 6
BATCH = 64


# ---------------- ops/device_decode primitives ----------------


def _span_of(arrays):
    """Pack named numpy arrays into one contiguous little-endian u8 span
    plus its layout tuple — exactly what a container batch's footer
    describes, built by hand so the parity tests own both sides."""
    buf, layout, off = [], [], 0
    for name, a in arrays.items():
        raw = np.ascontiguousarray(a).tobytes()
        layout.append((name, a.dtype.name, off, len(raw), a.shape))
        buf.append(raw)
        off += len(raw)
    return np.frombuffer(b"".join(buf), dtype=np.uint8), tuple(layout)


class TestSpanDecode:
    def test_parity_all_dtypes(self):
        """decode_span must be byte-identical to the host np.frombuffer
        views for every segment dtype the containers store: f32 2-D,
        bf16 2-D, int8, int32 indices, uint8 passthrough, f32 1-D aux."""
        rng = np.random.default_rng(0)
        arrays = {
            "x32": rng.normal(size=(16, 6)).astype(np.float32),
            "x16": rng.normal(size=(8, 4)).astype(np.float32).astype(
                jnp.bfloat16),
            "q": rng.integers(-127, 127, size=(16, 6)).astype(np.int8),
            "idx": rng.integers(0, 99, size=(4, 3)).astype(np.int32),
            "raw": rng.integers(0, 255, size=32).astype(np.uint8),
            "y": rng.normal(size=16).astype(np.float32),
        }
        span, layout = _span_of(arrays)
        segs = dd.decode_span(jnp.asarray(span), layout, use_pallas=False)
        assert set(segs) == set(arrays)
        for name, want in arrays.items():
            got = np.asarray(segs[name])
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(got, np.asarray(want))

    def test_pallas_interpret_matches_xla_route(self):
        """The byte-plane kernel (interpret mode) and the XLA bitcast
        route must produce identical slabs — f32 and bf16."""
        rng = np.random.default_rng(1)
        arrays = {
            "a32": rng.normal(size=(32, 12)).astype(np.float32),
            "a16": rng.normal(size=(16, 8)).astype(np.float32).astype(
                jnp.bfloat16),
        }
        span, layout = _span_of(arrays)
        xla = dd.decode_span(jnp.asarray(span), layout, use_pallas=False)
        pal = dd.decode_span(jnp.asarray(span), layout, use_pallas=True,
                             interpret=True)
        for name in arrays:
            np.testing.assert_array_equal(np.asarray(pal[name]),
                                          np.asarray(xla[name]))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_widen_span_pallas_interpret_parity(self, dtype):
        rng = np.random.default_rng(2)
        rows, cols = 24, 10
        want = rng.normal(size=(rows, cols)).astype(np.float32)
        if dtype == "bfloat16":
            want = np.asarray(want.astype(jnp.bfloat16))
        raw = np.frombuffer(np.ascontiguousarray(want).tobytes(),
                            dtype=np.uint8)
        got = dd.widen_span_pallas(jnp.asarray(raw), rows, cols, dtype,
                                   interpret=True)
        assert str(got.dtype) == dtype
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_hardware_eligibility_gate(self):
        """pallas_decode_eligible mirrors the Mosaic tile constraints:
        f32/bf16 only, cols % 128 == 0, rows a multiple of 32."""
        assert dd.pallas_decode_eligible(256, 640, "float32")
        assert dd.pallas_decode_eligible(32, 128, "bfloat16")
        assert not dd.pallas_decode_eligible(200, 640, "float32")  # rows
        assert not dd.pallas_decode_eligible(256, 100, "float32")  # cols
        assert not dd.pallas_decode_eligible(256, 640, "int8")
        assert not dd.pallas_decode_eligible(256, 640, "int32")
        # the tile picker only ever returns 32-multiples (or 0)
        assert dd._pick_block_r(512, 128, 4) == 512
        assert dd._pick_block_r(96, 128, 4) == 32
        assert dd._pick_block_r(100, 128, 4) == 0

    def test_row_tile_never_exceeds_the_vmem_budget(self):
        """The tile is bounded by BYTES, not rows: at any geometry the
        picked tile's double-buffered planes and output (plus the f32
        temporary) fit the scoped-VMEM limit, and the geometry that
        walked past it (block_r=512 at cols=4096: 32 MiB against 16) now
        gets a smaller tile instead."""
        from dmlc_tpu.ops.pallas_sparse import SCOPED_VMEM_BYTES

        for itemsize in (2, 4):
            for cols in (128, 640, 1024, 4096, 8192, 16384, 1 << 17):
                for rows in (32, 96, 512, 2048, 16384):
                    br = dd._pick_block_r(rows, cols, itemsize)
                    if br == 0:
                        # nothing fits: even the smallest tile is too big
                        assert (dd._block_vmem_bytes(32, cols, itemsize)
                                > SCOPED_VMEM_BYTES) or rows % 32
                        continue
                    assert br % 32 == 0 and rows % br == 0
                    io = br * cols * 2 * itemsize  # k planes + the output
                    assert 2 * io <= SCOPED_VMEM_BYTES
                    assert (dd._block_vmem_bytes(br, cols, itemsize)
                            <= SCOPED_VMEM_BYTES)
        assert dd._block_vmem_bytes(512, 4096, 4) > SCOPED_VMEM_BYTES
        assert dd._pick_block_r(2048, 4096, 4) == 128
        assert dd._pick_block_r(2048, 4096, 2) == 256
        # a caller's smaller budget is honoured
        assert dd._pick_block_r(2048, 4096, 4, vmem_budget=4 << 20) == 32
        # too wide for any tile: ineligible, so decode_span goes to XLA
        assert not dd.pallas_decode_eligible(64, 1 << 17, "float32")
        assert dd.span_route(
            (("a0", "<f4", 0, 64 * (1 << 17) * 4, (64, 1 << 17)),),
            use_pallas=True) == "xla"

    def test_span_route_reports_what_decode_takes(self):
        eligible = (("a0", "<f4", 0, 32 * 128 * 4, (32, 128)),
                    ("a1", "<f4", 32 * 128 * 4, 32 * 4, (32,)))
        higgs = (("a0", "<f4", 0, 64 * 30 * 4, (64, 30)),)
        assert dd.span_route(eligible, use_pallas=True) == "pallas"
        assert dd.span_route(eligible, use_pallas=False) == "xla"
        assert dd.span_route(higgs, use_pallas=True) == "xla"
        # default: the hardware gate (closed on the CPU backend)
        assert dd.span_route(eligible) == "xla"

    def test_quantize_dequant_roundtrip(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(64, 5)).astype(np.float32)
        x[:, 2] = 0.0  # zero column: scale pins to 1.0, dequant exact
        q, scale = dd.quantize_int8(x)
        assert q.dtype == np.int8 and scale.dtype == np.float32
        assert scale[2] == 1.0
        back = np.asarray(dd.dequant_q8(jnp.asarray(q), jnp.asarray(scale)))
        step = np.abs(x).max(axis=0) / 127.0 + 1e-12
        assert np.all(np.abs(x - back) <= step * 0.51 + 1e-6)
        np.testing.assert_array_equal(back[:, 2], 0.0)

    def test_snapshot_quantize_delegates_here(self):
        """io/snapshot.py's quantize_int8 is a thin wrapper over THIS
        module (the single sanctioned dtype path) — same outputs."""
        from dmlc_tpu.io.snapshot import quantize_int8 as snap_quant

        rng = np.random.default_rng(4)
        x = rng.normal(size=(16, 3)).astype(np.float32)
        qa, sa = dd.quantize_int8(x)
        qb, sb = snap_quant(x)
        np.testing.assert_array_equal(qa, qb)
        np.testing.assert_array_equal(sa, sb)

    def test_q8_span_decodes_on_device(self):
        """An int8 snapshot batch span (q + per-column scale) dequants on
        device to exactly what the host path produces."""
        rng = np.random.default_rng(5)
        x = rng.normal(size=(32, 6)).astype(np.float32)
        q, scale = dd.quantize_int8(x)
        span, layout = _span_of({"q": q, "scale": scale})
        segs = dd.decode_span(jnp.asarray(span), layout)
        dev = np.asarray(dd.dequant_q8(segs["q"], segs["scale"]))
        np.testing.assert_array_equal(dev, q.astype(np.float32) * scale)


# ---------------- DeviceIter integration ----------------


def _corpus(tmp_path, n=512):
    rng = np.random.default_rng(7)
    path = tmp_path / "c.libsvm"
    with open(path, "w") as f:
        for i in range(n):
            feats = " ".join(
                f"{j}:{rng.standard_normal():.6f}" for j in range(NUM_COL))
            f.write(f"{i % 2} {feats}\n")
    return str(path)


def _make_iter(corpus, snap=None, **kw):
    parser = create_parser(corpus, 0, 1, "libsvm", threaded=True,
                           snapshot=snap)
    kw.setdefault("num_col", NUM_COL)
    kw.setdefault("batch_size", BATCH)
    kw.setdefault("layout", "dense")
    kw.setdefault("pack_aux", True)
    return DeviceIter(parser, **kw)


def _drain(it):
    return [np.asarray(b.packed) for b in it]


class TestDeviceDecodePipeline:
    def test_warm_epoch_zero_host_decode_byte_identical(self, tmp_path):
        """ACCEPTANCE: a snapshot-warm epoch with device_decode=True does
        zero per-batch host numpy decode (convert busy EXACTLY 0, the
        work shows up as the 'device_decode' stage instead) and yields
        batches byte-identical to the host-decode warm path."""
        corpus = _corpus(tmp_path)
        snap = str(tmp_path / "c.snapshot")
        it = _make_iter(corpus, snap=snap)
        cold = _drain(it)
        it.close()
        host = _make_iter(corpus, snap=snap)  # host-decode warm baseline
        warm_host = _drain(host)
        host.close()
        dev = _make_iter(corpus, snap=snap, device_decode=True)
        warm_dev = _drain(dev)
        s = dev.stats()
        dev.close()
        assert s["snapshot_state"] == "warm"
        assert s["device_decode"] is True
        assert s["stage_busy"]["convert"] == 0.0
        assert s["stage_busy"]["device_decode"] > 0.0
        assert s["device_decode_bytes"] > 0
        assert "device_decode" in s["stages"]
        assert len(warm_dev) == len(cold) == -(-512 // BATCH)
        for a, b, c in zip(cold, warm_host, warm_dev):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)

    def test_q8_snapshot_device_matches_host_exactly(self, tmp_path):
        """int8 snapshots: the on-device q*scale dequant must be VALUE
        EXACT against the host dequant (same fused multiply on the same
        bytes), not merely within quantization error."""
        corpus = _corpus(tmp_path)
        snap = str(tmp_path / "q.snapshot")
        it = _make_iter(corpus, snap=snap, snapshot_quant="int8")
        _drain(it)
        it.close()
        host = _make_iter(corpus, snap=snap, snapshot_quant="int8")
        warm_host = _drain(host)
        assert host.stats()["snapshot_state"] == "warm"
        host.close()
        dev = _make_iter(corpus, snap=snap, snapshot_quant="int8",
                         device_decode=True)
        warm_dev = _drain(dev)
        s = dev.stats()
        dev.close()
        assert s["snapshot_state"] == "warm"
        assert s["stage_busy"]["convert"] == 0.0
        assert s["device_decode_bytes"] > 0
        for a, b in zip(warm_host, warm_dev):
            np.testing.assert_array_equal(a, b)

    def test_checkpoint_swaps_host_and_device_decode(self, tmp_path):
        """ACCEPTANCE: mid-epoch checkpoints restore byte-identically in
        BOTH directions across the decode-mode boundary — device-decode
        state into a host-decode pipeline and vice versa."""
        corpus = _corpus(tmp_path)
        snap = str(tmp_path / "c.snapshot")
        it = _make_iter(corpus, snap=snap)
        full = _drain(it)
        it.close()
        # warm device-decode pipeline -> 3 batches -> checkpoint
        it_dev = _make_iter(corpus, snap=snap, device_decode=True)
        for _ in range(3):
            next(it_dev)
        state = it_dev.state_dict()
        it_dev.close()
        it_host = _make_iter(corpus, snap=snap)
        it_host.load_state(state)
        rest = _drain(it_host)
        it_host.close()
        assert len(rest) == len(full) - 3
        for a, b in zip(rest, full[3:]):
            np.testing.assert_array_equal(a, b)
        # the reverse: host-decode state -> device-decode pipeline
        it_host2 = _make_iter(corpus, snap=snap)
        for _ in range(2):
            next(it_host2)
        state2 = it_host2.state_dict()
        it_host2.close()
        it_dev2 = _make_iter(corpus, snap=snap, device_decode=True)
        it_dev2.load_state(state2)
        rest2 = _drain(it_dev2)
        s = it_dev2.stats()
        it_dev2.close()
        assert s["snapshot_state"] == "warm"
        assert s["stage_busy"]["convert"] == 0.0
        assert len(rest2) == len(full) - 2
        for a, b in zip(rest2, full[2:]):
            np.testing.assert_array_equal(a, b)

    def test_env_knob_arms_the_tier(self, tmp_path, monkeypatch):
        corpus = _corpus(tmp_path, n=128)
        snap = str(tmp_path / "c.snapshot")
        monkeypatch.setenv("DMLC_TPU_DEVICE_DECODE", "1")
        it = _make_iter(corpus, snap=snap)
        assert it.device_decode is True
        _drain(it)
        it.reset()
        warm = _drain(it)
        s = it.stats()
        it.close()
        assert s["snapshot_state"] == "warm"
        assert s["device_decode"] is True and s["device_decode_bytes"] > 0
        assert len(warm) == -(-128 // BATCH)
        # explicit ctor argument beats the env
        monkeypatch.setenv("DMLC_TPU_DEVICE_DECODE", "1")
        it2 = _make_iter(corpus, snap=snap, device_decode=False)
        assert it2.device_decode is False
        it2.close()


# ---------------- service wire (snapshot frame payload = span) ----------


class TestServiceDeviceDecode:
    def test_wire_span_decodes_byte_identical(self, tmp_path):
        """A snapshot frame's payload IS the device-decodable span: the
        client attaches it to the block, and a device_decode=True
        DeviceIter over the wire yields batches byte-identical to the
        host-decode client with zero trainer-side convert busy."""
        from dmlc_tpu.service import LocalFleet, ServiceParser

        corpus = _corpus(tmp_path, n=300)
        geom = {"batch_size": 32, "num_col": NUM_COL,
                "x_dtype": "float32"}
        fleet = LocalFleet(corpus, 2, num_workers=2,
                           parser={"format": "libsvm"}, snapshot=geom)
        try:
            probe = ServiceParser(fleet.address)
            block = probe.next_block()
            assert block is not None and block.device_span is not None
            raw, layout, skind = block.device_span
            assert raw.dtype == np.uint8 and skind == "dense_packed"
            assert layout and layout[0][2] == 0  # payload-relative offsets
            probe.close()
            host = DeviceIter(ServiceParser(fleet.address),
                              num_col=NUM_COL, batch_size=32,
                              layout="dense", pack_aux=True)
            want = _drain(host)
            host.close()
            dev = DeviceIter(ServiceParser(fleet.address),
                             num_col=NUM_COL, batch_size=32,
                             layout="dense", pack_aux=True,
                             device_decode=True)
            got = _drain(dev)
            s = dev.stats()
            dev.close()
            assert s["stage_busy"]["device_decode"] > 0.0
            assert s["device_decode_bytes"] > 0
            assert len(got) == len(want) and len(want) >= 300 // 32
            key = lambda a: a.tobytes()  # noqa: E731
            assert sorted(key(a) for a in got) == sorted(
                key(a) for a in want)
        finally:
            fleet.close()


# ---------------- lint gate (satellite: decode stays sanctioned) -------


class TestLintDecodeGate:
    def _mod(self):
        sys.path.insert(0, os.path.join(REPO, "bin"))
        try:
            import lint_metrics
        finally:
            sys.path.pop(0)
        return lint_metrics

    def test_scan_decode_flags_host_decode(self):
        scan = self._mod().scan_decode
        bad = (
            "def f(buf):\n"
            "    x = np.frombuffer(buf, dtype=np.float32)\n"
            "    return x.astype(np.float64)\n"
            "    # np.frombuffer( in a comment is fine\n"
        )
        assert [ln for ln, _ in scan(bad)] == [2, 3]
        assert scan("segs = decode_span(d, layout)\n") == []

    def test_device_decode_env_read_flagged(self):
        scan = self._mod().scan_source
        bad = "v = os.environ.get('DMLC_TPU_DEVICE_DECODE')\n"
        assert len(scan(bad)) == 1

    def test_decode_scope_covers_warm_serve_path(self):
        lm = self._mod()
        rels = {str(p) for p in lm.DECODE_SCOPE}
        assert os.path.join("dmlc_tpu", "io", "snapshot.py") in rels
        assert os.path.join("dmlc_tpu", "data", "device.py") in rels
        sanctioned = {str(p) for p in lm.DECODE_MODULES}
        assert os.path.join("dmlc_tpu", "ops", "device_decode.py") \
            in sanctioned

    def test_repo_is_clean(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "lint_metrics.py"),
             REPO],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
