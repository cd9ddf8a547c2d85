"""Parse-once columnar block cache (ISSUE 5): on-disk format (golden-
pinned), cold-vs-warm byte-identical parity across formats, checkpoint/
resume mid-warm-epoch, corruption healing with exact resilience counters,
and the hardened chunk cache (CRC frames + versioned header) underneath.
"""

import json
import os
import struct

import numpy as np
import pytest

from dmlc_tpu.data import BlockCacheIter, create_parser, create_row_block_iter
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.data.row_block import RowBlock
from dmlc_tpu.io import faults, resilience
from dmlc_tpu.io.block_cache import (
    BLOCK_CACHE_MAGIC,
    BlockCacheReader,
    BlockCacheWriter,
    open_block_cache,
    source_signature,
)
from dmlc_tpu.io.cached_split import CHUNK_CACHE_MAGIC
from dmlc_tpu.io.input_split import create_input_split
from dmlc_tpu.io.uri import URISpec
from dmlc_tpu.utils.check import CacheCorruptionError, DMLCError


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    monkeypatch.delenv("DMLC_TPU_BLOCK_CACHE", raising=False)
    monkeypatch.delenv("DMLC_FAULT_PLAN", raising=False)
    faults.reset()
    resilience.reset_counters()
    yield
    faults.reset()


# ---------------- corpora ----------------

def _libsvm_text(n=300, d=6, qid=False, weight=False, seed=0):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        label = f"{i % 2}:{rng.random():.3f}" if weight else f"{i % 2}"
        q = f" qid:{i // 10}" if qid else ""
        feats = " ".join(f"{j}:{rng.normal():.5f}" for j in range(d))
        lines.append(f"{label}{q} {feats}")
    return ("\n".join(lines) + "\n").encode()


def _libfm_text(n=300, d=5, seed=1):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        feats = " ".join(f"{j % 3}:{j}:{rng.normal():.5f}" for j in range(d))
        lines.append(f"{i % 2} {feats}")
    return ("\n".join(lines) + "\n").encode()


def _csv_text(n=300, d=5, seed=2):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        cells = ",".join(f"{rng.normal():.5f}" for _ in range(d))
        lines.append(f"{i % 2},{cells}")
    return ("\n".join(lines) + "\n").encode()


def _write(tmp_path, name, data):
    p = tmp_path / name
    p.write_bytes(data)
    return str(p)


def _drain_arrays(parser):
    """Concatenated epoch output, every array a RowBlock carries, in
    delivery order — the byte-identity comparator."""
    out = {}

    def add(key, arr):
        if arr is not None:
            out.setdefault(key, []).append(np.asarray(arr))

    while (b := parser.next_block()) is not None:
        add("label", b.label)
        add("index", b.index)
        add("value", b.value)
        add("weight", b.weight)
        add("qid", b.qid)
        add("field", b.field)
        add("nnz", np.diff(np.asarray(b.offset)))
    return {k: np.concatenate(v) for k, v in out.items()}


def _assert_same(a, b):
    assert set(a) == set(b), (sorted(a), sorted(b))
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def _golden_blocks():
    """The exact fixture tests/data/blockcache_v1.golden was written
    from — rewriting it must reproduce the committed bytes."""
    return [
        (dict(
            offset=np.array([0, 2, 3], np.int64),
            label=np.array([1.0, 0.0], np.float32),
            weight=np.array([0.5, 2.0], np.float32),
            qid=np.array([1, 2], np.int64),
            field=np.array([0, 1, 2], np.uint64),
            index=np.array([3, 7, 9], np.uint64),
            value=np.array([0.25, -1.5, 3.0], np.float32),
        ), 2, 10, {"kind": "split", "chunks": 1,
                   "split": {"kind": "byte", "offset_curr": 64}}),
        (dict(
            offset=np.array([0, 1], np.int64),
            label=np.array([1.0], np.float32),
            index=np.array([0], np.uint32),
        ), 1, 1, None),
    ]


GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "data", "blockcache_v1.golden")


# ---------------- format ----------------

class TestFormat:
    def test_roundtrip_zero_copy(self, tmp_path):
        path = str(tmp_path / "c.blockcache")
        w = BlockCacheWriter(path, signature={"s": 1})
        for segments, rows, num_col, resume in _golden_blocks():
            w.add_block(segments, rows=rows, num_col=num_col, resume=resume)
        w.finish()
        assert not os.path.exists(path + ".tmp")  # atomic publish
        r = BlockCacheReader(path, signature={"s": 1})
        assert r.num_blocks == 2 and r.num_col == 10 and r.rows == 3
        for i, (segments, rows, _, resume) in enumerate(_golden_blocks()):
            got = r.load_segments(i)
            assert set(got) == {k for k, v in segments.items()
                                if v is not None}
            for name, arr in segments.items():
                if arr is None:
                    continue
                np.testing.assert_array_equal(got[name], arr)
                assert got[name].dtype == arr.dtype
                # mmap-backed views are read-only (zero-copy contract)
                assert not got[name].flags.writeable
            assert r.block_rows(i) == rows
            assert r.resume(i) == (json.loads(json.dumps(resume))
                                   if resume is not None else None)
        blk = RowBlock.from_segments(r.load_segments(0), hold=r.hold)
        assert len(blk) == 2 and blk.num_nonzero == 3

    def test_golden_layout_pinned(self, tmp_path):
        """The v1 layout is frozen: rewriting the golden fixture must be
        byte-identical to the committed file, and the committed file must
        decode exactly — an accidental format change fails both ways."""
        rebuilt = str(tmp_path / "rebuilt.golden")
        w = BlockCacheWriter(rebuilt,
                             signature={"pinned": "blockcache-v1-golden"})
        for segments, rows, num_col, resume in _golden_blocks():
            w.add_block(segments, rows=rows, num_col=num_col, resume=resume)
        w.finish()
        with open(GOLDEN, "rb") as f:
            want = f.read()
        with open(rebuilt, "rb") as f:
            got = f.read()
        assert got == want, "on-disk block-cache layout drifted from v1"
        r = BlockCacheReader(GOLDEN)
        assert r.signature == {"pinned": "blockcache-v1-golden"}
        seg0 = r.load_segments(0)
        np.testing.assert_array_equal(seg0["value"],
                                      np.array([0.25, -1.5, 3.0], np.float32))
        np.testing.assert_array_equal(seg0["qid"], np.array([1, 2], np.int64))
        seg1 = r.load_segments(1)
        assert seg1["index"].dtype == np.dtype(np.uint32)
        assert want[:8] == BLOCK_CACHE_MAGIC and want[-8:] == BLOCK_CACHE_MAGIC

    def test_signature_mismatch_self_invalidates(self, tmp_path):
        path = str(tmp_path / "c.blockcache")
        w = BlockCacheWriter(path, signature={"files": [["a", 1, 2]]})
        w.add_block(_golden_blocks()[1][0], rows=1, num_col=1)
        w.finish()
        base = resilience.counters_snapshot()
        assert open_block_cache(path, {"files": [["a", 1, 3]]}) is None
        assert not os.path.exists(path)  # stale cache dropped
        assert resilience.counters_delta(base)["cache_invalidations"] == 1
        # matching signature on a fresh cache opens fine
        w = BlockCacheWriter(path, signature={"files": [["a", 1, 3]]})
        w.add_block(_golden_blocks()[1][0], rows=1, num_col=1)
        w.finish()
        r = open_block_cache(path, {"files": [["a", 1, 3]]})
        assert r is not None and r.num_blocks == 1

    def test_truncated_cache_invalidates(self, tmp_path):
        path = str(tmp_path / "c.blockcache")
        w = BlockCacheWriter(path, signature={})
        w.add_block(_golden_blocks()[1][0], rows=1, num_col=1)
        w.finish()
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[:-10])  # tail magic gone
        assert open_block_cache(path) is None
        assert not os.path.exists(path)

    def test_crc_detects_bit_flip(self, tmp_path):
        path = str(tmp_path / "c.blockcache")
        w = BlockCacheWriter(path, signature={})
        w.add_block(_golden_blocks()[0][0], rows=2, num_col=10)
        w.finish()
        data = bytearray(open(path, "rb").read())
        data[70] ^= 0xFF  # inside block 0's first segment
        with open(path, "wb") as f:
            f.write(bytes(data))
        r = BlockCacheReader(path)  # footer is intact: open succeeds
        with pytest.raises(CacheCorruptionError):
            r.load_segments(0)
        assert resilience.classify(CacheCorruptionError("x")) == "retryable"

    def test_abort_drops_tmp(self, tmp_path):
        path = str(tmp_path / "c.blockcache")
        w = BlockCacheWriter(path, signature={})
        w.add_block(_golden_blocks()[1][0], rows=1, num_col=1)
        w.abort()
        assert not os.path.exists(path) and not os.path.exists(path + ".tmp")


# ---------------- cold/warm parity ----------------

    def test_block_encoded_is_the_files_own_span(self, tmp_path):
        """``block_encoded()`` on a cache the (one, Python) writer built
        hands out the mmap's own bytes — the file's ``[pos, end)`` span,
        the footer entry's crc, span-relative offsets that re-materialise
        every segment — not a re-encoded copy."""
        import zlib

        from dmlc_tpu.io.block_cache import EncodedSegments, read_segments

        path = str(tmp_path / "c.blockcache")
        w = BlockCacheWriter(path, signature={"s": 1})
        for segments, rows, num_col, resume in _golden_blocks():
            w.add_block(segments, rows=rows, num_col=num_col, resume=resume)
        w.finish()
        raw = open(path, "rb").read()
        r = BlockCacheReader(path, signature={"s": 1})
        for i, (segments, rows, _, _) in enumerate(_golden_blocks()):
            enc = r.block_encoded(i)
            assert isinstance(enc, EncodedSegments)
            entry = r._blocks[i]
            assert bytes(enc.data) == raw[entry["pos"]:entry["end"]]
            assert enc.data.nbytes == entry["end"] - entry["pos"]
            assert enc.crc == entry["crc"] == zlib.crc32(enc.data)
            assert enc.rows == rows and enc.num_col == r.num_col
            assert enc.hold is r.hold  # a view pinned by the mmap
            got = read_segments(enc.data, enc.arrays)
            want = r.load_segments(i)
            assert set(got) == set(want)
            for name in want:
                np.testing.assert_array_equal(got[name], want[name])
            del enc, got, want  # release the mmap views before close
        r.close()

    def test_io_layer_imports_nothing_from_the_data_layer(self):
        """The format lives under ``io/`` and the pipeline that uses it
        under ``data/``: no source file of ``dmlc_tpu/io`` may import
        ``dmlc_tpu.data`` (``EncodedSegments`` lived there until PR 28
        and ``block_encoded()`` imported it upwards). The package's
        ``__init__`` may re-export, so the sources are read, not the
        modules imported."""
        import ast

        import dmlc_tpu.io as io_pkg

        io_dir = os.path.dirname(os.path.abspath(io_pkg.__file__))
        sources = sorted(f for f in os.listdir(io_dir) if f.endswith(".py"))
        assert "block_cache.py" in sources
        offenders = []
        for name in sources:
            with open(os.path.join(io_dir, name)) as fh:
                tree = ast.parse(fh.read(), name)
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.level == 0:
                    mods = [node.module or ""]
                    if node.module == "dmlc_tpu":
                        mods += [f"dmlc_tpu.{a.name}" for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.level == 2:
                    # ``from ..data import x`` inside dmlc_tpu/io/
                    mods = [f"dmlc_tpu.{node.module or ''}"]
                offenders += [(name, node.lineno, m) for m in mods
                              if m == "dmlc_tpu.data"
                              or m.startswith("dmlc_tpu.data.")]
        assert offenders == []


class TestColdWarmParity:
    @pytest.mark.parametrize("fmt,data,uri_args", [
        ("libsvm", _libsvm_text(), ""),
        ("libsvm", _libsvm_text(qid=True), ""),
        ("libsvm", _libsvm_text(weight=True), ""),
        ("libfm", _libfm_text(), ""),
        ("csv", _csv_text(), "?label_column=0"),
    ])
    def test_cold_warm_byte_identical(self, tmp_path, fmt, data, uri_args):
        path = _write(tmp_path, f"corpus.{fmt}", data)
        cache = str(tmp_path / "c.blockcache")
        uri = path + uri_args
        ref = create_parser(uri, 0, 1, fmt, chunk_bytes=4096)
        want = _drain_arrays(ref)
        ref.close()
        parser = create_parser(uri, 0, 1, fmt, chunk_bytes=4096,
                               block_cache=cache)
        assert parser.cache_state == "cold"
        _assert_same(_drain_arrays(parser), want)   # cold epoch: tee-through
        assert os.path.exists(cache)                # published at stream end
        parser.before_first()
        assert parser.cache_state == "warm"
        _assert_same(_drain_arrays(parser), want)   # warm epoch: from mmap
        parser.close()
        # a FRESH warm pass never constructs the parser chain
        def boom():
            raise AssertionError("parser factory invoked on a warm pass")
        sig = source_signature(path, 0, 1, format=fmt,
                               args=dict(URISpec(uri).args),
                               index_dtype="<u8", chunk_bytes=4096,
                               split={})
        warm = BlockCacheIter(boom, cache, signature=sig)
        assert warm.cache_state == "warm"
        _assert_same(_drain_arrays(warm), want)
        warm.close()

    def test_warm_served_blocks_tee_to_the_same_cache_bytes(self, tmp_path):
        """A warm-served block carries its cache span (``block.encoded``,
        for the service wire). The cold tee has one encoder and ignores
        it: a cache teed from such blocks goes through ``add_block`` and
        is the first cache's bytes, block spans and footer alike."""
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=600))
        first, second = (str(tmp_path / n) for n in ("a.bc", "b.bc"))
        sig = {"s": 1}

        def parse():
            return create_parser(path, 0, 1, "libsvm", chunk_bytes=2048)

        cold = BlockCacheIter(parse, first, signature=sig)
        want = _drain_arrays(cold)
        cold.close()
        warm = BlockCacheIter(parse, first, signature=sig)
        assert warm.cache_state == "warm"
        probe = warm.next_block()
        assert probe.encoded is not None
        del probe
        warm.before_first()
        nested = BlockCacheIter(warm, second, signature=sig)
        assert nested.cache_state == "cold"
        _assert_same(_drain_arrays(nested), want)
        nested.close()
        assert open(first, "rb").read() == open(second, "rb").read()

    def test_multi_partition_parity(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=400))
        cache = str(tmp_path / "c.blockcache")
        for part in (0, 1):
            ref = create_parser(path, part, 2, "libsvm", chunk_bytes=2048)
            want = _drain_arrays(ref)
            ref.close()
            parser = create_parser(path, part, 2, "libsvm",
                                   chunk_bytes=2048, block_cache=cache)
            _assert_same(_drain_arrays(parser), want)
            parser.before_first()
            assert parser.cache_state == "warm"
            _assert_same(_drain_arrays(parser), want)
            parser.close()
            # partition-qualified cache files never collide
            assert os.path.exists(f"{cache}.split2.part{part}")

    def test_source_drift_invalidates(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=100))
        cache = str(tmp_path / "c.blockcache")
        parser = create_parser(path, 0, 1, "libsvm", block_cache=cache)
        _drain_arrays(parser)
        parser.close()
        # rewrite the corpus: size+mtime drift must force a re-parse
        data2 = _libsvm_text(n=120, seed=5)
        _write(tmp_path, "corpus.libsvm", data2)
        ref = create_parser(path, 0, 1, "libsvm")
        want = _drain_arrays(ref)
        ref.close()
        base = resilience.counters_snapshot()
        parser = create_parser(path, 0, 1, "libsvm", block_cache=cache)
        assert parser.cache_state == "cold"  # stale cache self-invalidated
        _assert_same(_drain_arrays(parser), want)
        parser.before_first()
        assert parser.cache_state == "warm"  # rebuilt for the new source
        _assert_same(_drain_arrays(parser), want)
        parser.close()
        assert resilience.counters_delta(base)["cache_invalidations"] == 1

    def test_chunk_bytes_drift_invalidates(self, tmp_path):
        """Block grouping config is part of the signature: the heal and
        count-based resume paths skip re-parsed blocks by INDEX, which is
        only sound when re-parse grouping matches the cached grouping — a
        cache built under one chunk_bytes must not serve warm under
        another."""
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=600))
        cache = str(tmp_path / "c.blockcache")
        parser = create_parser(path, 0, 1, "libsvm", chunk_bytes=2048,
                               block_cache=cache)
        _drain_arrays(parser)
        parser.close()
        ref = create_parser(path, 0, 1, "libsvm", chunk_bytes=8192)
        want = _drain_arrays(ref)
        ref.close()
        base = resilience.counters_snapshot()
        parser = create_parser(path, 0, 1, "libsvm", chunk_bytes=8192,
                               block_cache=cache)
        assert parser.cache_state == "cold"  # grouping drift: invalidated
        # ...and a corruption mid-warm under the REBUILT grouping heals
        # into a byte-identical stream (the index-skip is sound again)
        _drain_arrays(parser)
        parser.before_first()
        assert parser.cache_state == "warm"
        with faults.inject("cache_read@2=corrupt"):
            _assert_same(_drain_arrays(parser), want)
        parser.close()
        assert resilience.counters_delta(base)["cache_invalidations"] == 1

    def test_shuffle_maps_to_plan_with_deprecation(self, tmp_path):
        # the old hard rejection is gone: legacy shuffle decorator args +
        # block_cache now map onto the shuffle-native epoch plan with a
        # one-release DeprecationWarning (docs/data.md)
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=50))
        with pytest.warns(DeprecationWarning, match="epoch plan"):
            parser = create_parser(path, 0, 1, "libsvm", num_shuffle_parts=2,
                                   seed=9, block_cache=str(tmp_path / "c.bc"))
        try:
            assert parser.plan_state is not None
            assert parser.plan_state["shuffle_seed"] == 9
        finally:
            parser.close()

    def test_uri_suffix_and_env_dir(self, tmp_path, monkeypatch):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=100))
        # `#blockcache=<path>` suffix, mirroring `#cachefile`
        spec = URISpec(f"{path}?format=libsvm#blockcache=/x/c.bc")
        assert spec.block_cache == "/x/c.bc" and spec.cache_file is None
        assert spec.args == {"format": "libsvm"}
        cache = str(tmp_path / "via_uri.blockcache")
        parser = create_parser(f"{path}#blockcache={cache}", 0, 1, "libsvm")
        _drain_arrays(parser)
        parser.close()
        assert os.path.exists(cache)
        # DMLC_TPU_BLOCK_CACHE directory: auto-named per URI+args
        env_dir = tmp_path / "bc_dir"
        monkeypatch.setenv("DMLC_TPU_BLOCK_CACHE", str(env_dir))
        parser = create_parser(path, 0, 1, "libsvm")
        assert parser.cache_state == "cold"
        _drain_arrays(parser)
        parser.close()
        named = [f for f in os.listdir(env_dir) if f.endswith(".blockcache")]
        assert len(named) == 1
        parser = create_parser(path, 0, 1, "libsvm")
        assert parser.cache_state == "warm"
        parser.close()

    def test_create_row_block_iter_block_cache(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=150))
        cache = str(tmp_path / "c.blockcache")
        it = create_row_block_iter(path, 0, 1, "libsvm", silent=True,
                                   block_cache=cache)
        blk_cold = it.next_block()
        assert it.next_block() is None and os.path.exists(cache)
        it2 = create_row_block_iter(path, 0, 1, "libsvm", silent=True,
                                    block_cache=cache)
        blk_warm = it2.next_block()
        np.testing.assert_array_equal(blk_cold.label, blk_warm.label)
        np.testing.assert_array_equal(blk_cold.index, blk_warm.index)
        np.testing.assert_array_equal(blk_cold.value, blk_warm.value)


# ---------------- DeviceIter integration ----------------

def _device_batches(it, limit=None):
    out = []
    for b in it:
        out.append(np.asarray(b[0]))
        if limit and len(out) >= limit:
            break
    return out


class TestDeviceIter:
    def test_cache_state_and_stage(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=600))
        cache = str(tmp_path / "c.blockcache")
        parser = create_parser(path, 0, 1, "libsvm", chunk_bytes=4096,
                               block_cache=cache)
        it = DeviceIter(parser, num_col=6, batch_size=128, layout="dense",
                        prefetch=2)
        cold = _device_batches(it)
        stats = it.stats()
        assert stats["cache_state"] == "cold"
        assert "cache_read" in stats["stages"]
        it.reset()
        warm = _device_batches(it)
        stats = it.stats()
        assert stats["cache_state"] == "warm"
        assert stats["stage_busy"]["cache_read"] > 0.0
        assert len(cold) == len(warm)
        for a, b in zip(cold, warm):
            np.testing.assert_array_equal(a, b)
        it.close()

    def test_cold_and_warm_epochs_record_their_stage_spans(self, tmp_path):
        """What the tracer must show for a cold and a warm epoch (a hole
        here reads as a quiet pipeline on a trace): the cold pass
        records parse, convert, dispatch and one ``cache_write`` a
        block, no ``cache_read``; the warm pass one ``cache_read`` a
        block — the cache's exact block count — and no ``cache_write``."""
        from dmlc_tpu.utils import telemetry

        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=600))
        cache = str(tmp_path / "c.blockcache")
        parser = create_parser(path, 0, 1, "libsvm", chunk_bytes=4096,
                               block_cache=cache)
        it = DeviceIter(parser, num_col=6, batch_size=128, layout="dense",
                        prefetch=2)

        def epoch(last=None):
            before = after = telemetry.span_counts()
            n = 0
            for _ in it:
                n += 1
                if n == last:
                    # read at the last hand-out: a looping consumer's
                    # epoch ends by starting the next one's reads
                    after = telemetry.span_counts()
            if last is None:
                after = telemetry.span_counts()
            return n, {k: after[k] - before.get(k, 0) for k in after
                       if after[k] - before.get(k, 0)}

        batches, cold = epoch()
        it.reset()
        _, warm = epoch(last=batches)
        it.close()
        reader = open_block_cache(cache)
        blocks = reader.num_blocks
        reader.close()
        assert blocks > 1
        assert cold["cache_write"] == blocks and "cache_read" not in cold
        assert warm["cache_read"] == blocks and "cache_write" not in warm
        assert cold["parse"] >= blocks
        for spans in (cold, warm):
            assert spans["dispatch"] == batches
            assert spans["convert"] >= batches

    def test_checkpoint_resume_mid_warm_epoch(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=900))
        cache = str(tmp_path / "c.blockcache")
        uri = path + "?engine=python"  # annotated blocks: byte-exact states

        def make_iter():
            parser = create_parser(uri, 0, 1, "libsvm", chunk_bytes=2048,
                                   block_cache=cache)
            return DeviceIter(parser, num_col=6, batch_size=128,
                              layout="dense", prefetch=2, pack_aux=False)

        it = make_iter()
        _device_batches(it)            # cold epoch publishes the cache
        it.reset()
        warm_all = _device_batches(it)  # uninterrupted warm reference
        it.reset()
        _device_batches(it, limit=2)
        state = it.state_dict()
        assert state["kind"] == "source"  # byte-exact, identically to cold
        it.close()
        it2 = make_iter()
        assert it2.source.cache_state == "warm"
        it2.load_state(state)
        tail = _device_batches(it2)
        assert len(tail) == len(warm_all) - 2
        for a, b in zip(tail, warm_all[2:]):
            np.testing.assert_array_equal(a, b)
        it2.close()

    def test_cold_state_restores_into_warm_pipeline(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=900))
        cache = str(tmp_path / "c.blockcache")
        uri = path + "?engine=python"

        def make_iter():
            parser = create_parser(uri, 0, 1, "libsvm", chunk_bytes=2048,
                                   block_cache=cache)
            return DeviceIter(parser, num_col=6, batch_size=128,
                              layout="dense", prefetch=2, pack_aux=False)

        it = make_iter()
        head = _device_batches(it, limit=2)
        cold_state = it.state_dict()     # taken mid-COLD-epoch
        rest = _device_batches(it)       # finish the epoch: cache publishes
        it.close()
        it2 = make_iter()                # fresh pipeline is warm now
        assert it2.source.cache_state == "warm"
        it2.load_state(cold_state)       # cold state restores warm
        tail = _device_batches(it2)
        assert len(tail) == len(rest)
        for a, b in zip(tail, rest):
            np.testing.assert_array_equal(a, b)
        it2.close()


# ---------------- corruption healing ----------------

class TestCorruptionHeals:
    def test_fault_plan_corrupt_segment_heals(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=600))
        cache = str(tmp_path / "c.blockcache")
        parser = create_parser(path, 0, 1, "libsvm", chunk_bytes=2048,
                               block_cache=cache)
        want = _drain_arrays(parser)
        parser.before_first()
        assert parser.cache_state == "warm"
        base = resilience.counters_snapshot()
        with faults.inject("cache_read@2=corrupt") as plan:
            healed = _drain_arrays(parser)
        assert plan.fired() == 1
        _assert_same(healed, want)  # byte-identical through the heal
        delta = {k: v for k, v in resilience.counters_delta(base).items()
                 if v}
        assert delta == {"cache_corruptions": 1, "cache_rebuilds": 1}
        # the heal REWROTE the cache: the next epoch is warm and clean
        parser.before_first()
        assert parser.cache_state == "warm"
        _assert_same(_drain_arrays(parser), want)
        parser.close()

    def test_on_disk_bit_flip_heals(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=600))
        cache = str(tmp_path / "c.blockcache")
        parser = create_parser(path, 0, 1, "libsvm", chunk_bytes=2048,
                               block_cache=cache)
        want = _drain_arrays(parser)
        parser.close()
        data = bytearray(open(cache, "rb").read())
        data[80] ^= 0x55  # inside the first block's segments
        with open(cache, "wb") as f:
            f.write(bytes(data))
        base = resilience.counters_snapshot()
        parser = create_parser(path, 0, 1, "libsvm", chunk_bytes=2048,
                               block_cache=cache)
        assert parser.cache_state == "warm"  # footer intact: opens warm
        _assert_same(_drain_arrays(parser), want)
        delta = resilience.counters_delta(base)
        assert delta["cache_corruptions"] == 1
        assert delta["cache_rebuilds"] == 1
        parser.close()


# ---------------- chunk-cache hardening (CachedInputSplit) ----------------

def _records(split):
    out = []
    while (rec := split.next_record()) is not None:
        out.append(bytes(rec))
    return out


class TestChunkCacheCrc:
    def test_crc_framed_roundtrip(self, tmp_path):
        path = _write(tmp_path, "corpus.txt",
                      b"".join(b"line %d\n" % i for i in range(500)))
        cache = str(tmp_path / "chunks.cache")
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=4096)
        want = _records(split)
        split.close()
        assert open(cache, "rb").read(8) == CHUNK_CACHE_MAGIC
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=4096)
        assert _records(split) == want
        split.close()

    def test_legacy_headerless_cache_invalidates_cleanly(self, tmp_path):
        path = _write(tmp_path, "corpus.txt",
                      b"".join(b"line %d\n" % i for i in range(200)))
        cache = str(tmp_path / "chunks.cache")
        # fabricate a v0 cache: raw [u64 size][bytes] frames, no header
        payload = b"not the real corpus\n"
        with open(cache, "wb") as f:
            f.write(struct.pack("<Q", len(payload)))
            f.write(payload)
        base = resilience.counters_snapshot()
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=4096)
        recs = _records(split)
        split.close()
        # the legacy cache was dropped and rebuilt from SOURCE, not served
        assert recs[0] == b"line 0" and len(recs) == 200
        assert resilience.counters_delta(base)["cache_invalidations"] == 1
        assert open(cache, "rb").read(8) == CHUNK_CACHE_MAGIC

    def test_frame_corruption_heals_via_reread(self, tmp_path):
        path = _write(tmp_path, "corpus.txt",
                      b"".join(b"line %d\n" % i for i in range(2000)))
        cache = str(tmp_path / "chunks.cache")
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=2048)
        want = _records(split)
        split.close()
        data = bytearray(open(cache, "rb").read())
        data[len(data) // 2] ^= 0xFF  # flip a byte mid-file
        with open(cache, "wb") as f:
            f.write(bytes(data))
        base = resilience.counters_snapshot()
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=2048)
        healed = _records(split)
        split.close()
        assert healed == want  # unbroken record stream through the heal
        delta = resilience.counters_delta(base)
        assert delta["cache_corruptions"] == 1
        assert delta["cache_rebuilds"] == 1
        # the cache was rewritten: a third pass is clean
        base = resilience.counters_snapshot()
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=2048)
        assert _records(split) == want
        split.close()
        assert resilience.counters_delta(base)["cache_corruptions"] == 0

    def test_heal_resumes_by_bytes_across_chunk_bytes_drift(self, tmp_path):
        """The heal skips BYTES, not frames: a cache built under one
        chunk_bytes must heal correctly when the split is reopened with
        another (frame groupings differ, the byte stream does not)."""
        path = _write(tmp_path, "corpus.txt",
                      b"".join(b"line %d\n" % i for i in range(2000)))
        cache = str(tmp_path / "chunks.cache")
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=2048)
        want = _records(split)
        split.close()
        base = resilience.counters_snapshot()
        with faults.inject("cache_read@3=corrupt"):
            split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                       chunk_bytes=8192)  # drifted grouping
            healed = _records(split)
            split.close()
        assert healed == want  # record stream unbroken despite the drift
        assert resilience.counters_delta(base)["cache_corruptions"] == 1

    def test_fault_plan_injects_chunk_cache_corruption(self, tmp_path):
        path = _write(tmp_path, "corpus.txt",
                      b"".join(b"line %d\n" % i for i in range(1000)))
        cache = str(tmp_path / "chunks.cache")
        split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                   chunk_bytes=2048)
        want = _records(split)
        split.close()
        base = resilience.counters_snapshot()
        with faults.inject("cache_read@2=corrupt"):
            split = create_input_split(f"{path}#{cache}", 0, 1, "text",
                                       chunk_bytes=2048)
            healed = _records(split)
            split.close()
        assert healed == want
        assert resilience.counters_delta(base)["cache_corruptions"] == 1


# ---------------- guard rails ----------------

class TestGuards:
    def test_reset_partition_rejected(self, tmp_path):
        path = _write(tmp_path, "corpus.libsvm", _libsvm_text(n=50))
        parser = create_parser(path, 0, 1, "libsvm",
                               block_cache=str(tmp_path / "c.bc"))
        with pytest.raises(DMLCError):
            parser.reset_partition(1, 2)
        parser.close()

    def test_empty_blockcache_fragment_rejected(self):
        with pytest.raises(DMLCError):
            URISpec("path#blockcache=")

    def test_corrupt_error_class_in_fault_grammar(self):
        plan = faults.FaultPlan("cache_read@1=corrupt")
        err = plan.check("cache_read", "/some/cache")
        assert isinstance(err, CacheCorruptionError)
