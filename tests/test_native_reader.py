"""Tests for the fully-native streaming reader (native/src/reader.cc +
dmlc_tpu/data/native_parser.py).

Strategy mirrors SURVEY.md §4: partition-correctness is tested by looping
every part_index in one process over a tempdir corpus and comparing
record-for-record against the Python engine (which itself mirrors
input_split_base.cc). The Python engine is the reference here — the two
implementations must agree bit-for-bit on every partitioning.
"""

import http.server
import os
import threading

import numpy as np
import pytest

from dmlc_tpu import native
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.native_parser import (
    NativeFeedParser,
    NativeStreamParser,
    native_reader_eligible,
)
from dmlc_tpu.data.row_block import DenseBlock, RowBlock
from dmlc_tpu.utils.check import DMLCError

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native core unavailable")


def _rows_of(parser):
    out = []
    for blk in parser:
        assert isinstance(blk, RowBlock)
        for i in range(len(blk)):
            r = blk[i]
            vals = (tuple(float(v) for v in r.value)
                    if r.value is not None else ("binary",) * len(r.index))
            qid = int(r.qid) if r.qid is not None else None
            out.append((float(r.label), tuple(int(x) for x in r.index), vals, qid))
    parser.close()
    return out


def _py_parser(uri, part, nparts, fmt, args=None):
    q = "&".join(f"{k}={v}" for k, v in (args or {}).items())
    full = f"{uri}?{q}" if q else uri
    os.environ["DMLC_TPU_NO_NATIVE_READER"] = "1"
    try:
        return create_parser(full, part, nparts, fmt, threaded=False)
    finally:
        del os.environ["DMLC_TPU_NO_NATIVE_READER"]


@pytest.fixture
def corpus(tmp_path):
    """Three files with the boundary traps: NOEOL join, blank lines,
    comments, CRLF."""
    a = tmp_path / "a.txt"
    a.write_bytes(b"1 0:1.5 2:2.5\n0 1:3.0\n\n1 4:0.25\n")
    b = tmp_path / "b.txt"
    b.write_bytes(b"1 0:7.0")  # no trailing newline (PR#385 case)
    c = tmp_path / "c.txt"
    c.write_bytes(b"# comment only\r\n0 2:9.0\r\n1 0:1 1:2\n0 3:4\n")
    return ";".join(str(p) for p in (a, b, c))


class TestLibsvmAB:
    @pytest.mark.parametrize("nparts", [1, 2, 3, 4, 7])
    def test_partitions_match_python_engine(self, corpus, nparts):
        ref, nat = [], []
        for p in range(nparts):
            ref += _rows_of(_py_parser(corpus, p, nparts, "libsvm"))
            nat += _rows_of(NativeStreamParser(corpus, {}, p, nparts, "libsvm"))
        assert ref == nat
        assert len(ref) == 7

    def test_no_loss_no_duplication(self, corpus):
        whole = _rows_of(NativeStreamParser(corpus, {}, 0, 1, "libsvm"))
        for nparts in (2, 3, 5):
            parts = []
            for p in range(nparts):
                parts += _rows_of(
                    NativeStreamParser(corpus, {}, p, nparts, "libsvm"))
            assert parts == whole

    def test_epoch_reset(self, corpus):
        parser = NativeStreamParser(corpus, {}, 0, 2, "libsvm")
        first = _collect_epoch(parser)
        parser.before_first()
        second = _collect_epoch(parser)
        parser.close()
        assert first == second and len(first) > 0

    def test_bytes_read_counter(self, corpus):
        parser = NativeStreamParser(corpus, {}, 0, 1, "libsvm")
        for _ in parser:
            pass
        assert parser.bytes_read > 0
        parser.close()


def _collect_epoch(parser):
    out = []
    while True:
        blk = parser.next_block()
        if blk is None:
            return out
        for i in range(len(blk)):
            r = blk[i]
            out.append((float(r.label), tuple(int(x) for x in r.index)))


class TestDensePath:
    def test_dense_blocks(self, tmp_path):
        f = tmp_path / "d.libsvm"
        f.write_text("1 0:1.0 2:3.0\n0 1:2.0\n")
        parser = NativeStreamParser(str(f), {}, 0, 1, "libsvm")
        assert parser.set_emit_dense(4)
        blk = parser.next_block()
        assert isinstance(blk, DenseBlock)
        np.testing.assert_allclose(
            np.asarray(blk.x), [[1, 0, 3, 0], [0, 2, 0, 0]])
        np.testing.assert_allclose(np.asarray(blk.label), [1, 0])
        parser.close()

    def test_qid_downgrades_to_csr_midstream(self, tmp_path):
        f = tmp_path / "q.libsvm"
        f.write_text("1 qid:7 0:1.0\n0 qid:8 1:2.0\n")
        parser = NativeStreamParser(str(f), {}, 0, 1, "libsvm")
        assert parser.set_emit_dense(4)
        blk = parser.next_block()
        # dense scanner cannot express qid: native downgrade to CSR
        assert isinstance(blk, RowBlock)
        assert blk.qid is not None
        assert [int(q) for q in blk.qid] == [7, 8]
        parser.close()


class TestCsvAndLibfm:
    def test_csv_matches_python(self, tmp_path):
        f = tmp_path / "t.csv"
        f.write_text("1.0,2.0,3.0\n4.0,5.0,6.0\n7.5,8.5,9.5\n")
        ref = _rows_of(_py_parser(str(f), 0, 1, "csv", {"label_column": "0"}))
        nat = _rows_of(NativeStreamParser(
            str(f), {"label_column": "0"}, 0, 1, "csv"))
        assert ref == nat

    def test_csv_dense(self, tmp_path):
        f = tmp_path / "t.csv"
        f.write_text("1.0,2.0,3.0\n4.0,5.0,6.0\n")
        parser = NativeStreamParser(str(f), {"label_column": "0"}, 0, 1, "csv")
        assert parser.set_emit_dense(2)
        blk = parser.next_block()
        assert isinstance(blk, DenseBlock)
        np.testing.assert_allclose(np.asarray(blk.x), [[2, 3], [5, 6]])
        np.testing.assert_allclose(np.asarray(blk.label), [1, 4])
        parser.close()

    def test_libfm_matches_python(self, tmp_path):
        f = tmp_path / "t.libfm"
        f.write_text("1 0:3:1.5 1:7:2.5\n0 2:1:0.5\n")
        ref = _rows_of(_py_parser(str(f), 0, 1, "libfm"))
        nat = _rows_of(NativeStreamParser(str(f), {}, 0, 1, "libfm"))
        assert ref == nat

    def test_libfm_has_fields(self, tmp_path):
        f = tmp_path / "t.libfm"
        f.write_text("1 0:3:1.5 1:7:2.5\n")
        parser = NativeStreamParser(str(f), {}, 0, 1, "libfm")
        blk = parser.next_block()
        assert blk.field is not None
        assert [int(x) for x in blk.field[0:2]] == [0, 1]
        parser.close()


class TestErrorsAndRouting:
    def test_malformed_input_raises(self, tmp_path):
        f = tmp_path / "bad.libsvm"
        f.write_text("1 0:1.0\n0 not$valid\n")
        parser = NativeStreamParser(str(f), {}, 0, 1, "libsvm")
        with pytest.raises(DMLCError):
            while parser.next_block() is not None:
                pass
        parser.close()

    def test_create_parser_routes_native(self, tmp_path):
        f = tmp_path / "r.libsvm"
        f.write_text("1 0:1.0\n")
        p = create_parser(str(f), 0, 1, "libsvm", threaded=True)
        try:
            assert isinstance(p, NativeStreamParser)
        finally:
            p.close()

    def test_cachefile_not_routed_native(self, tmp_path):
        f = tmp_path / "r.libsvm"
        f.write_text("1 0:1.0\n")
        cache = tmp_path / "cache.bin"
        assert not native_reader_eligible(
            f"{f}#{cache}", "libsvm", True, {})

    def test_indexing_mode_heuristic(self, tmp_path):
        # all indices >= 1 with mode=-1: sklearn-style shift to 0-based
        f = tmp_path / "one.libsvm"
        f.write_text("1 1:1.0 3:3.0\n0 2:2.0\n")
        nat = _rows_of(NativeStreamParser(
            str(f), {"indexing_mode": "-1"}, 0, 1, "libsvm"))
        ref = _rows_of(_py_parser(str(f), 0, 1, "libsvm",
                                  {"indexing_mode": "-1"}))
        assert nat == ref
        assert nat[0][1] == (0, 2)

    def test_partition_args_validated(self, tmp_path):
        # num_parts=0 once SIGFPE'd in the native byte-range divide; out-of
        # -range parts silently yielded an empty stream
        f = tmp_path / "v.libsvm"
        f.write_text("1 0:1.0\n")
        for part, nparts in ((0, 0), (3, 2), (-1, 2)):
            with pytest.raises(DMLCError):
                create_parser(str(f), part, nparts, "libsvm")

    def test_error_then_before_first_no_hang(self, tmp_path, monkeypatch):
        # buffered path: a reader whose source vanishes mid-stream must raise
        # on next() and keep raising (not deadlock) after before_first()
        import os

        monkeypatch.setenv("DMLC_TPU_NO_MMAP", "1")
        f = tmp_path / "gone.libsvm"
        f.write_text("1 0:1.0\n" * 100)
        from dmlc_tpu.native import FMT_LIBSVM, Reader

        r = Reader([str(f)], [600], 0, 1, FMT_LIBSVM)
        assert r.next() is not None
        os.remove(str(f))
        for _ in range(2):
            r.before_first()
            with pytest.raises(DMLCError):
                while r.next() is not None:
                    pass
        r.close()

    def test_mmap_path_snapshots_across_unlink(self, tmp_path):
        # mmap path (single-file partition): the mapping pins the inode, so
        # deleting the source mid-stream still serves every epoch — snapshot
        # semantics, immune to file replacement during training
        import os

        f = tmp_path / "snap.libsvm"
        f.write_text("1 0:1.0\n" * 100)
        size = os.path.getsize(str(f))
        from dmlc_tpu.native import FMT_LIBSVM, Reader

        r = Reader([str(f)], [size], 0, 1, FMT_LIBSVM)
        assert r.next() is not None
        os.remove(str(f))
        for _ in range(2):
            r.before_first()
            rows = 0
            while (out := r.next()) is not None:
                rows += len(out[1]["label"])
            assert rows == 100
        r.close()

    def test_qid_downgrade_uses_flag(self, tmp_path):
        # qid rows make the dense scanner raise NeedsCsrError (explicit flag,
        # not error-string matching) and the parser fall back to CSR blocks
        from dmlc_tpu import native as nat

        with pytest.raises(nat.NeedsCsrError):
            nat.parse_libsvm_dense(b"1 qid:3 0:1.0\n", 4)
        f = tmp_path / "q.libsvm"
        f.write_text("1 qid:3 0:1.0\n0 qid:4 1:2.0\n")
        p = create_parser(str(f), 0, 1, "libsvm", threaded=True)
        if hasattr(p, "set_emit_dense"):
            p.set_emit_dense(4)
        blocks = list(p)
        p.close()
        qids = [int(q) for b in blocks for q in b.qid]
        assert qids == [3, 4]

    def test_batch_repack_error_after_clean_rows(self, tmp_path):
        # rows parsed before an error chunk must be delivered BEFORE the
        # error surfaces, matching non-batch ordering
        import numpy as np

        f = tmp_path / "err.libsvm"
        good = "".join(f"1 0:{i}.5\n" for i in range(2000))  # several chunks
        f.write_text(good + "0 bad$token\n")

        def rows_before_error(batch_rows):
            p = NativeStreamParser(str(f), {}, 0, 1, "libsvm",
                                   chunk_bytes=4096)
            p.set_emit_dense(4, batch_rows=batch_rows)
            rows = 0
            with pytest.raises(DMLCError):
                while True:
                    blk = p.next_block()
                    if blk is None:
                        break
                    rows += len(blk)
            p.close()
            return rows

        plain = rows_before_error(0)
        batched = rows_before_error(64)
        assert plain > 0
        assert batched == plain  # same rows delivered ahead of the raise

    def test_csv_batch_repack_matches_python(self, tmp_path):
        # csv -> dense with label/weight split in C++ and batch-aligned
        # blocks must equal the python conversion row-for-row
        import numpy as np

        f = tmp_path / "c.csv"
        rows = 500
        with open(f, "w") as fh:
            for i in range(rows):
                fh.write(f"{i % 2},{i * 0.5},{-i}.25,{i % 7}\n")

        def collect(use_native):
            p = create_parser(str(f) + "?format=csv&label_column=0",
                              0, 1, threaded=use_native, chunk_bytes=2048)
            ok = p.set_emit_dense(3, batch_rows=64) if use_native else \
                p.set_emit_dense(3)
            xs, ys = [], []
            for blk in p:
                xs.append(np.asarray(blk.x))
                ys.append(np.asarray(blk.label))
            p.close()
            return np.concatenate(xs), np.concatenate(ys)

        xn, yn = collect(True)
        xp, yp = collect(False)
        np.testing.assert_allclose(xn, xp, rtol=1e-6)
        np.testing.assert_allclose(yn, yp)
        assert xn.shape == (rows, 3)
        # full batches are exactly 64 rows until the tail
        p = create_parser(str(f) + "?format=csv&label_column=0", 0, 1,
                          threaded=True, chunk_bytes=2048)
        p.set_emit_dense(3, batch_rows=64)
        sizes = [len(b) for b in p]
        p.close()
        assert set(sizes[:-1]) == {64} and sizes[-1] <= 64


class TestNativeCsvSplit:
    """The zero-copy CSV split path (reader.cc FMT_CSV_SPLIT): when label/
    weight columns are configured and no dense repack is requested, the
    native merge pass splits them from the packed feature cells, and the
    RowBlock wrap adds no copies — A/B'd row-for-row vs the Python engine
    (csv_parser.h:120-146 semantics)."""

    @staticmethod
    def _collect(uri, threaded):
        import numpy as np

        p = create_parser(uri, 0, 1, threaded=threaded, chunk_bytes=2048)
        vals, labels, weights = [], [], []
        for blk in p:
            vals.append(np.asarray(blk.value))
            labels.append(np.asarray(blk.label))
            weights.append(None if blk.weight is None
                           else np.asarray(blk.weight))
        p.close()
        w = (None if all(x is None for x in weights)
             else np.concatenate([x for x in weights if x is not None]))
        return np.concatenate(vals), np.concatenate(labels), w

    @pytest.mark.parametrize("cols", ["label_column=0",
                                      "label_column=2&weight_column=5",
                                      "label_column=5"])
    def test_split_rowblocks_match_python_engine(self, tmp_path, cols):
        import numpy as np

        f = tmp_path / "s.csv"
        rng = np.random.default_rng(7)
        with open(f, "w") as fh:
            for i in range(400):
                fh.write(",".join(f"{v:.5f}" for v in rng.normal(size=6)) + "\n")
        uri = str(f) + "?format=csv&" + cols
        vn, yn, wn = self._collect(uri, threaded=True)
        vp, yp, wp = self._collect(uri + "&engine=python", threaded=False)
        np.testing.assert_allclose(vn, vp, rtol=1e-6)
        np.testing.assert_allclose(yn, yp, rtol=1e-6)
        if wp is None:
            assert wn is None
        else:
            np.testing.assert_allclose(wn, wp, rtol=1e-6)

    def test_split_out_of_range_label_errors(self, tmp_path):
        f = tmp_path / "bad.csv"
        f.write_text("1,2,3\n4,5,6\n")
        p = create_parser(str(f) + "?format=csv&label_column=9", 0, 1,
                          threaded=True)
        with pytest.raises(DMLCError):
            list(p)
        p.close()


class TestNativeRecordIO:
    """Native recordio split vs the Python engine, row-for-row
    (reader.cc format 4/5 + recordio.cc vs io/input_split.py
    RecordIOSplitter)."""

    @staticmethod
    def _write_corpus(tmp_path, nfiles=3, per_file=40):
        import struct
        from dmlc_tpu.io.recordio import RECORDIO_MAGIC, RecordIOWriter

        rng = np.random.default_rng(3)
        paths, recs = [], []
        for p in range(nfiles):
            path = str(tmp_path / f"part{p}.rec")
            paths.append(path)
            with open(path, "wb") as f:
                w = RecordIOWriter(f)
                for i in range(per_file):
                    if i % 7 == 0:
                        # aligned magic collision -> multi-part record
                        rec = (rng.bytes(8)
                               + struct.pack("<I", RECORDIO_MAGIC)
                               + rng.bytes(12 + (i % 5)))
                    else:
                        rec = rng.bytes(int(rng.integers(1, 5000)))
                    recs.append(rec)
                    w.write_record(rec)
        return ";".join(paths), recs

    def test_routes_to_native_and_matches_python(self, tmp_path):
        from dmlc_tpu import native
        from dmlc_tpu.io.input_split import create_input_split
        from dmlc_tpu.io.native_recordio import NativeRecordIOSplit

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        uri, truth = self._write_corpus(tmp_path)
        s = create_input_split(uri, 0, 1, "recordio")
        assert isinstance(s, NativeRecordIOSplit)
        got = []
        while (r := s.next_record()) is not None:
            got.append(bytes(r))
        s.close()
        assert got == truth
        for nparts in (2, 5):
            nat, py = [], []
            for k in range(nparts):
                sn = create_input_split(uri, k, nparts, "recordio")
                while (r := sn.next_record()) is not None:
                    nat.append(bytes(r))
                sn.close()
                sp = create_input_split(uri + "?engine=python", k, nparts,
                                        "recordio")
                while (r := sp.next_record()) is not None:
                    py.append(bytes(r))
                sp.close()
            assert nat == truth
            assert py == truth

    def test_chunk_mode_reframes_and_epoch_reset(self, tmp_path):
        from dmlc_tpu import native
        from dmlc_tpu.io.input_split import create_input_split
        from dmlc_tpu.io.recordio import RecordIOChunkReader

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        uri, truth = self._write_corpus(tmp_path)
        s = create_input_split(uri, 0, 1, "recordio", chunk_bytes=8192)
        recs = []
        while (c := s.next_chunk()) is not None:
            recs.extend(bytes(r) for r in RecordIOChunkReader(c))
        s.close()
        assert recs == truth
        s = create_input_split(uri, 0, 1, "recordio")
        n1 = sum(1 for _ in iter(s.next_record, None))
        s.before_first()
        n2 = sum(1 for _ in iter(s.next_record, None))
        s.close()
        assert n1 == n2 == len(truth)

    def test_recordio_extract_rejects_garbage(self):
        from dmlc_tpu import native

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        import pytest
        from dmlc_tpu.utils.check import DMLCError

        with pytest.raises(DMLCError):
            native.recordio_extract(b"definitely not recordio data")


class TestNativeIndexedRecordIO:
    """Native indexed-recordio (reader.cc IndexedReader) vs the Python
    engine: record-count partitioning row-for-row, shuffled epochs with
    deterministic seeds, mid-epoch resume."""

    @staticmethod
    def _write_indexed(tmp_path, n=103):
        records = [f"sample-{i:03d}".encode() * (i % 5 + 1) for i in range(n)]
        data_p = str(tmp_path / "d.rec")
        idx_p = str(tmp_path / "d.idx")
        with open(data_p, "wb") as df, open(idx_p, "wb") as xf:
            from dmlc_tpu.io import write_indexed_recordio

            write_indexed_recordio(df, xf, records)
        return data_p, idx_p, records

    def test_routes_native_and_matches_python(self, tmp_path):
        from dmlc_tpu import native
        from dmlc_tpu.io.input_split import create_input_split
        from dmlc_tpu.io.native_recordio import NativeIndexedRecordIOSplit

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        data_p, idx_p, records = self._write_indexed(tmp_path)
        for nparts in (1, 2, 4):
            nat, py = [], []
            for part in range(nparts):
                s = create_input_split(data_p, part, nparts,
                                       "indexed_recordio", index_uri=idx_p)
                assert isinstance(s, NativeIndexedRecordIOSplit)
                nat.extend(bytes(r) for r in s.iter_records())
                s.close()
                sp = create_input_split(data_p + "?engine=python", part,
                                        nparts, "indexed_recordio",
                                        index_uri=idx_p, threaded=False)
                py.extend(bytes(r) for r in sp.iter_records())
                sp.close()
            assert nat == records
            assert py == records

    def test_shuffle_epochs_and_determinism(self, tmp_path):
        from dmlc_tpu import native
        from dmlc_tpu.io.input_split import create_input_split

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        data_p, idx_p, records = self._write_indexed(tmp_path, n=64)

        def make():
            return create_input_split(data_p, 0, 1, "indexed_recordio",
                                      index_uri=idx_p, shuffle=True, seed=7)

        s = make()
        e1 = [bytes(r) for r in s.iter_records()]
        s.before_first()
        e2 = [bytes(r) for r in s.iter_records()]
        s.close()
        assert sorted(e1) == sorted(records)  # full coverage
        assert sorted(e2) == sorted(records)
        assert e1 != records                  # actually shuffled
        assert e1 != e2                       # reshuffled per epoch
        s2 = make()                           # same seed -> same sequence
        assert [bytes(r) for r in s2.iter_records()] == e1
        s2.close()

    def test_shuffled_partitions_cover_all_records(self, tmp_path):
        from dmlc_tpu import native
        from dmlc_tpu.io.input_split import create_input_split

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        data_p, idx_p, records = self._write_indexed(tmp_path, n=50)
        got = []
        for part in range(3):
            s = create_input_split(data_p, part, 3, "indexed_recordio",
                                   index_uri=idx_p, shuffle=True, seed=3)
            got.extend(bytes(r) for r in s.iter_records())
            s.close()
        assert sorted(got) == sorted(records)

    def test_resume_mid_epoch_under_shuffle(self, tmp_path):
        from dmlc_tpu import native
        from dmlc_tpu.io.input_split import create_input_split

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        data_p, idx_p, _ = self._write_indexed(tmp_path, n=60)

        def make():
            return create_input_split(data_p, 0, 1, "indexed_recordio",
                                      index_uri=idx_p, shuffle=True, seed=5)

        s = make()
        list(s.iter_records())   # epoch 0
        s.before_first()         # epoch 1 permutation drawn
        for _ in range(10):
            s.next_record()
        state = s.state_dict()
        want = [bytes(s.next_record()) for _ in range(5)]
        s.close()
        s2 = make()
        s2.load_state(state)
        got = [bytes(s2.next_record()) for _ in range(5)]
        s2.close()
        assert got == want

    def test_resume_skips_prefix_without_io(self, tmp_path):
        """Native skip: resuming deep into an epoch must not read the
        consumed prefix (dmlc_indexed_reader_skip = rng replay + seek)."""
        from dmlc_tpu import native
        from dmlc_tpu.io.input_split import create_input_split

        if not native.available():
            import pytest
            pytest.skip("native core unavailable")
        data_p, idx_p, _ = self._write_indexed(tmp_path, n=200)
        total = __import__("os").path.getsize(data_p)

        def make():
            return create_input_split(data_p, 0, 1, "indexed_recordio",
                                      index_uri=idx_p, shuffle=True, seed=5,
                                      batch_size=10)

        s = make()
        for _ in range(150):
            s.next_record()
        state = s.state_dict()
        want = [bytes(s.next_record()) for _ in range(10)]
        s.close()
        s2 = make()
        s2.load_state(state)
        got = [bytes(s2.next_record()) for _ in range(10)]
        # only the suffix (plus bounded prefetch) was read — not the
        # 150-record prefix
        assert s2.bytes_read < total // 2, (s2.bytes_read, total)
        s2.close()
        assert got == want


class TestNativeCooEmit:
    """set_emit_coo: the native parse emits device-ready COO blocks (int32
    coords, bucket padding with OOB sentinels, all-ones value elision) —
    must agree entry-for-entry with the Python CSR -> block_to_bcoo_host
    convert path it replaces (ops/sparse.py)."""

    NUM_COL = 1_000_000

    def _libfm_corpus(self, tmp_path, n=400, unit=True):
        p = tmp_path / "c.libfm"
        lines = []
        for i in range(n):
            val = "1" if unit else f"{(i % 7) + 0.5:.1f}"
            feats = " ".join(
                f"{j}:{(i * 2654435761 + j * 40503) % self.NUM_COL}:{val}"
                for j in range(6))
            lines.append(f"{i % 2} {feats}")
        p.write_text("\n".join(lines) + "\n")
        return str(p)

    def _native_coo_blocks(self, uri, fmt, num_col, **coo_kw):
        parser = create_parser(uri, 0, 1, fmt, threaded=True)
        assert isinstance(parser, NativeStreamParser)
        assert parser.set_emit_coo(num_col, **coo_kw)
        blocks = []
        while True:
            b = parser.next_block()
            if b is None:
                break
            blocks.append(b)
        parser.close()
        return blocks

    def _python_ref(self, path, fmt, num_col):
        from dmlc_tpu.ops.sparse import block_to_bcoo_host

        parser = _py_parser(path, 0, 1, fmt)
        coords, values, labels, weights = [], [], [], []
        for blk in parser:
            c, v, l, w, _ = block_to_bcoo_host(blk, num_col)
            coords.append(c)
            values.append(v if v is not None
                          else np.ones(len(c), np.float32))
            labels.append(l)
            weights.append(w)
        parser.close()
        return (np.concatenate(coords), np.concatenate(values),
                np.concatenate(labels), np.concatenate(weights))

    @staticmethod
    def _concat_real(blocks):
        """Strip bucket padding and re-base row ids across blocks."""
        from dmlc_tpu.data.row_block import CooBlock

        coords, values, labels, weights = [], [], [], []
        base = 0
        for b in blocks:
            assert isinstance(b, CooBlock)
            c = b.coords[:b.nnz].astype(np.int64)
            c[:, 0] += base
            base += b.n_rows
            coords.append(c)
            values.append(np.ones(b.nnz, np.float32) if b.values is None
                          else np.asarray(b.values[:b.nnz]))
            labels.append(b.label[:b.n_rows])
            weights.append(b.weight[:b.n_rows])
        return (np.concatenate(coords), np.concatenate(values),
                np.concatenate(labels), np.concatenate(weights))

    def test_libfm_matches_python_convert(self, tmp_path):
        path = self._libfm_corpus(tmp_path)
        blocks = self._native_coo_blocks(
            path + "?format=libfm", "libfm", self.NUM_COL,
            row_bucket=128, nnz_bucket=512, elide_unit=True)
        rc, rv, rl, rw = self._python_ref(path, "libfm", self.NUM_COL)
        nc, nv, nl, nw = self._concat_real(blocks)
        assert (nc == rc).all()
        assert (nv == rv).all()
        assert (nl == rl).all()
        assert (nw == rw).all()

    def test_unit_values_elided_and_padded_shapes(self, tmp_path):
        path = self._libfm_corpus(tmp_path, unit=True)
        blocks = self._native_coo_blocks(
            path + "?format=libfm", "libfm", self.NUM_COL,
            row_bucket=128, nnz_bucket=512, elide_unit=True)
        for b in blocks:
            assert b.values is None  # ":1" corpus -> elided
            assert b.coords.dtype == np.int32
            assert b.coords.shape[0] % 512 == 0
            assert len(b.label) % 128 == 0
            assert b.shape == (len(b.label), self.NUM_COL)
            # padding is OOB (rows_padded, num_col) — masked by BCOO ops
            pad = b.coords[b.nnz:]
            if len(pad):
                assert (pad[:, 0] == len(b.label)).all()
                assert (pad[:, 1] == self.NUM_COL).all()
            # pad rows are zero-weight
            assert (np.asarray(b.weight[b.n_rows:]) == 0).all()

    def test_non_unit_values_not_elided(self, tmp_path):
        path = self._libfm_corpus(tmp_path, unit=False)
        blocks = self._native_coo_blocks(
            path + "?format=libfm", "libfm", self.NUM_COL,
            row_bucket=128, nnz_bucket=512, elide_unit=True)
        rc, rv, rl, rw = self._python_ref(path, "libfm", self.NUM_COL)
        nc, nv, nl, nw = self._concat_real(blocks)
        assert any(b.values is not None for b in blocks)
        for b in blocks:
            if b.values is not None:  # padding slots carry zero values
                assert (np.asarray(b.values[b.nnz:]) == 0).all()
        assert (nv == rv).all()
        assert (nc == rc).all()

    def test_libsvm_weights_and_indexing_heuristic(self, tmp_path):
        # 1-based indices everywhere -> heuristic shifts to 0-based
        # (libsvm_parser.h:159-168); weights ride the label:weight syntax
        p = tmp_path / "w.libsvm"
        p.write_text("".join(
            f"{i % 2}:{0.5 + i} {1 + (i * 37) % 50}:2.5 {1 + (i * 53) % 50 + 50}:1\n"
            for i in range(200)))
        blocks = self._native_coo_blocks(
            str(p), "libsvm", 101, row_bucket=64, nnz_bucket=64,
            elide_unit=True)
        rc, rv, rl, rw = self._python_ref(str(p), "libsvm", 101)
        nc, nv, nl, nw = self._concat_real(blocks)
        assert (nc == rc).all()
        assert (nv == rv).all()
        assert (nw == rw).all()
        assert nc[:, 1].min() >= 0 and nc[:, 1].max() <= 100

    def test_deviceiter_routes_native_coo(self, tmp_path):
        from dmlc_tpu.data.device import DeviceIter

        path = self._libfm_corpus(tmp_path)
        parser = create_parser(path + "?format=libfm", 0, 1, threaded=True)
        it = DeviceIter(parser, num_col=self.NUM_COL, batch_size=None,
                        layout="bcoo", elide_unit_values=True)
        total_rows = 0
        for mat, y, w in it:
            assert mat.shape[1] == self.NUM_COL
            total_rows += int(w.sum())  # pad rows are zero-weight
        it.close()
        assert total_rows == 400

    def test_csr_wire_matches_pair_wire(self, tmp_path):
        """csr_wire emit (cols + row_ptr, half the coordinate bytes) must
        carry exactly the information of the (row, col) pair emit: a host
        prefix-sum rebuild reproduces the pair coords entry-for-entry,
        OOB pad tail included (native/src/api.h CooResult csr_wire docs)."""
        path = self._libfm_corpus(tmp_path)
        kw = dict(row_bucket=128, nnz_bucket=512, elide_unit=True)
        pair = self._native_coo_blocks(
            path + "?format=libfm", "libfm", self.NUM_COL, **kw)
        csr = self._native_coo_blocks(
            path + "?format=libfm", "libfm", self.NUM_COL,
            csr_wire=True, **kw)
        assert len(pair) == len(csr) and len(csr) > 0
        for bp, bc in zip(pair, csr):
            assert bc.row_ptr is not None and bc.coords.ndim == 1
            rp = np.asarray(bc.row_ptr)
            rows_padded = len(bc.label)
            assert rp.shape == (rows_padded + 1,)
            assert rp[0] == 0 and (np.diff(rp) >= 0).all()
            # pad rows (and the end sentinel) all point at the real nnz
            assert (rp[bc.n_rows:] == bc.nnz).all()
            # row id of entry j = #{i >= 1 : rp[i] <= j}
            incr = np.zeros(len(bc.coords) + 1, np.int64)
            np.add.at(incr, rp[1:], 1)
            rows = np.cumsum(incr)[:len(bc.coords)]
            assert (rows == bp.coords[:, 0]).all()
            assert (bc.coords == bp.coords[:, 1]).all()
            assert (np.asarray(bc.label) == np.asarray(bp.label)).all()
            assert (np.asarray(bc.weight) == np.asarray(bp.weight)).all()

    def test_csr_wire_device_rebuild_semantics(self, tmp_path):
        """The jitted consumer rebuild (data/device._csr_coords_impl) must
        reproduce the pair-wire coords exactly — real entries map to their
        rows, pad entries land on the OOB row rows_padded."""
        import jax.numpy as jnp

        from dmlc_tpu.data.device import _csr_coords_impl

        path = self._libfm_corpus(tmp_path)
        kw = dict(row_bucket=128, nnz_bucket=512, elide_unit=True)
        pair = self._native_coo_blocks(
            path + "?format=libfm", "libfm", self.NUM_COL, **kw)
        csr = self._native_coo_blocks(
            path + "?format=libfm", "libfm", self.NUM_COL,
            csr_wire=True, **kw)
        for bp, bc in zip(pair, csr):
            got = np.asarray(_csr_coords_impl(
                jnp.asarray(bc.coords), jnp.asarray(np.asarray(bc.row_ptr))))
            assert (got == bp.coords).all()

    def test_deviceiter_csr_wire_todense_equal(self, tmp_path):
        """End-to-end: the default (csr_wire) BCOO pipeline and the pair
        wire densify to the same matrices, labels, and weights."""
        from dmlc_tpu.data.device import DeviceIter

        num_col = 512
        p = tmp_path / "small.libfm"
        p.write_text("".join(
            f"{i % 2} " + " ".join(
                f"{j}:{(i * 97 + j * 31) % num_col}:1" for j in range(5))
            + "\n" for i in range(300)))

        def batches(csr_wire):
            parser = create_parser(str(p) + "?format=libfm", 0, 1,
                                   threaded=True)
            it = DeviceIter(parser, num_col=num_col, batch_size=None,
                            layout="bcoo", elide_unit_values=True,
                            csr_wire=csr_wire)
            out = [(np.asarray(mat.todense()), np.asarray(y), np.asarray(w))
                   for mat, y, w in it]
            it.close()
            return out

        a, b = batches(True), batches(False)
        assert len(a) == len(b) and len(a) > 0
        for (xa, ya, wa), (xb, yb, wb) in zip(a, b):
            assert (xa == xb).all()
            assert (ya == yb).all()
            assert (wa == wb).all()

    def test_feeder_coo_path(self, tmp_path):
        """Push-mode (remote) pipeline speaks COO too."""
        path = self._libfm_corpus(tmp_path)
        with open(path, "rb") as fh:
            data = fh.read()
        f = native.Feeder(native.FMT_LIBFM_COO, num_col=self.NUM_COL,
                          row_bucket=128, nnz_bucket=512, elide_unit=True)
        f.push(data)
        f.finish()
        blocks = []
        while True:
            out = f.next()
            if out is None:
                break
            fmt, d = out
            assert fmt == native.FMT_LIBFM_COO
            blocks.append(d)
        f.close()
        assert blocks
        assert sum(b["n_rows"] for b in blocks) == 400
        assert all(b["values"] is None for b in blocks)


class TestPackedAux:
    """pack_aux: batch repack emits ONE [B, num_col + 2] array with label/
    weight as trailing columns (api.h DenseResult packed_aux) — must match
    the split emit column-for-column, f32 and bf16, libsvm and csv."""

    def _corpus(self, tmp_path, weighted=True):
        f = tmp_path / "p.libsvm"
        w = lambda i: f":{0.5 + (i % 3)}" if weighted else ""
        f.write_text("".join(
            f"{i % 2}{w(i)} 0:{i}.5 2:{(i * 7) % 50}\n" for i in range(500)))
        return str(f)

    def _collect(self, path, fmt, num_col, pack, dtype="float32", **pk):
        p = create_parser(path, 0, 1, fmt, threaded=True, chunk_bytes=2048)
        assert p.set_emit_dense(num_col, batch_rows=64, dtype=dtype,
                                pack_aux=pack)
        blocks = []
        while True:
            b = p.next_block()
            if b is None:
                break
            blocks.append(b)
        p.close()
        return blocks

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_libsvm_packed_matches_split(self, tmp_path, dtype):
        path = self._corpus(tmp_path)
        packed = self._collect(path, "libsvm", 4, True, dtype)
        split = self._collect(path, "libsvm", 4, False, dtype)
        assert len(packed) == len(split) > 1
        for bp, bs in zip(packed, split):
            assert bp.packed and not bs.packed
            assert bp.x.shape == (len(bs), 6)  # num_col + 2
            f32 = lambda a: np.asarray(a, np.float32)
            np.testing.assert_array_equal(f32(bp.x[:, :4]), f32(bs.x))
            np.testing.assert_array_equal(f32(bp.x[:, 4]), f32(bs.label))
            np.testing.assert_array_equal(f32(bp.x[:, 5]), f32(bs.weight))
            # the label/weight attrs alias the packed columns
            np.testing.assert_array_equal(f32(bp.label), f32(bp.x[:, 4]))
        # tail block is partial but still packed-width
        assert len(packed[-1]) == 500 % 64
        assert packed[-1].x.shape[1] == 6

    def test_unweighted_rows_pack_unit_weight(self, tmp_path):
        path = self._corpus(tmp_path, weighted=False)
        packed = self._collect(path, "libsvm", 4, True)
        assert all((np.asarray(b.x[:, 5]) == 1.0).all() for b in packed)

    def test_csv_packed_matches_split(self, tmp_path):
        f = tmp_path / "p.csv"
        f.write_text("".join(
            f"{i % 2},{i * 0.5},{-i}.25,{(i % 5) + 0.5}\n"
            for i in range(300)))
        uri = str(f) + "?format=csv&label_column=0&weight_column=3"
        packed = self._collect(uri, "csv", 2, True)
        split = self._collect(uri, "csv", 2, False)
        for bp, bs in zip(packed, split):
            assert bp.packed
            np.testing.assert_array_equal(
                np.asarray(bp.x[:, :2]), np.asarray(bs.x))
            np.testing.assert_array_equal(
                np.asarray(bp.x[:, 2]), np.asarray(bs.label))
            np.testing.assert_array_equal(
                np.asarray(bp.x[:, 3]), np.asarray(bs.weight))


# ---------------- the engine parity matrix ----------------
# Every text engine emits byte-identical blocks by contract (the block
# cache's signature leaves the engine out because of it). The Python
# engine (``engine="python"``: numpy all the way down) is the referee;
# the two native engines — NativeStreamParser for local corpora, what
# every text cell of the benchmark runs, and NativeFeedParser for remote
# ones — are held to it over one matrix of corpora.

def _libsvm_text(n=300, d=6, qid=False, weight=False, seed=0, binary=False,
                 eol="\n", terminated=True):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        label = f"{i % 2}:{rng.random():.3f}" if weight else f"{i % 2}"
        q = f" qid:{i // 10}" if qid else ""
        if binary:
            feats = " ".join(f"{j}" for j in range(1, d + 1))
        else:
            feats = " ".join(f"{j}:{rng.normal():.5f}" for j in range(d))
        lines.append(f"{label}{q} {feats}")
    return (eol.join(lines) + (eol if terminated else "")).encode()


def _libfm_text(n=300, d=5, seed=1):
    rng = np.random.default_rng(seed)
    return ("\n".join(
        f"{i % 2} " + " ".join(f"{j % 3}:{j}:{rng.normal():.5f}"
                               for j in range(d))
        for i in range(n)) + "\n").encode()


def _csv_text(n=300, d=5, seed=2):
    rng = np.random.default_rng(seed)
    return ("\n".join(
        f"{i % 2}," + ",".join(f"{rng.normal():.5f}" for _ in range(d))
        for i in range(n)) + "\n").encode()


_CRLF_NOTERM = _libsvm_text(eol="\r\n", terminated=False)

# name -> (format, corpus bytes, URI args)
PARITY_MATRIX = {
    "libsvm": ("libsvm", _libsvm_text(), ""),
    "libsvm-qid": ("libsvm", _libsvm_text(qid=True), ""),
    "libsvm-weight": ("libsvm", _libsvm_text(weight=True), ""),
    "libsvm-binary": ("libsvm", _libsvm_text(binary=True), ""),
    "libsvm-auto-index": ("libsvm", _libsvm_text(d=3, seed=7),
                          "?indexing_mode=-1"),
    "libsvm-one-based": ("libsvm", _libsvm_text(d=3, seed=8),
                         "?indexing_mode=1"),
    "libsvm-crlf-noterm": ("libsvm", _CRLF_NOTERM, ""),
    "libfm": ("libfm", _libfm_text(), ""),
    "libfm-auto-index": ("libfm", _libfm_text(seed=5), "?indexing_mode=-1"),
    "csv-label": ("csv", _csv_text(), "?label_column=0"),
    "csv-label-weight": ("csv", _csv_text(seed=9),
                         "?label_column=0&weight_column=1"),
    "csv-no-label": ("csv", _csv_text(seed=11), ""),
}
# the corpora no earlier suite of this file holds the stream reader to
# (plain libsvm, qid, indexing_mode=-1, plain libfm and the label-column
# csv are TestLibsvmAB / TestCsvAndLibfm / TestErrorsAndRouting's)
STREAM_LACKS = ["libsvm-weight", "libsvm-binary", "libsvm-one-based",
                "libsvm-crlf-noterm", "libfm-auto-index",
                "csv-label-weight", "csv-no-label"]


def _drain_arrays(parser):
    """Concatenated epoch output, every plane a RowBlock carries, in
    delivery order — the byte-identity comparator."""
    out = {}
    while (b := parser.next_block()) is not None:
        planes = {"label": b.label, "index": b.index, "value": b.value,
                  "weight": b.weight, "qid": b.qid, "field": b.field,
                  # offsets are chunk-relative; compare per-row nnz
                  "nnz": np.diff(np.asarray(b.offset))}
        for key, arr in planes.items():
            if arr is not None:
                out.setdefault(key, []).append(np.asarray(arr))
    return {k: np.concatenate(v) for k, v in out.items()}


def _assert_same(a, b):
    assert set(a) == set(b), (sorted(a), sorted(b))
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def _epoch(uri, fmt, engine, want=None, part=0, nparts=1):
    p = create_parser(uri, part, nparts, fmt, threaded=True,
                      parse_workers=1, engine=engine, chunk_bytes=2048)
    try:
        if want is not None:
            assert type(p) is want, type(p)
        return _drain_arrays(p)
    finally:
        p.close()


class _RangeFiles(http.server.BaseHTTPRequestHandler):
    """HEAD + ranged GET over an in-memory file table: the least a
    remote corpus needs (io/http_filesys.py reads by Range)."""
    files: dict = {}

    def log_message(self, *a):
        pass

    def _data(self):
        data = self.files.get(self.path.split("?", 1)[0])
        if data is None:
            self.send_response(404)
            self.end_headers()
        return data

    def do_HEAD(self):
        data = self._data()
        if data is not None:
            self.send_response(200)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()

    def do_GET(self):
        data = self._data()
        if data is None:
            return
        lo, hi = self.headers.get("Range", "bytes=0-").split("=")[1].split("-")
        if int(lo) >= len(data):
            self.send_response(416)
            self.end_headers()
            return
        chunk = data[int(lo):int(hi) + 1] if hi else data[int(lo):]
        self.send_response(206 if "Range" in self.headers else 200)
        self.send_header("Content-Length", str(len(chunk)))
        self.end_headers()
        self.wfile.write(chunk)


@pytest.fixture()
def http_files(monkeypatch):
    """``serve(name, data) -> url`` on a loopback server; reads go in
    2 KiB range requests so every corpus takes several."""
    from dmlc_tpu.io import http_filesys

    monkeypatch.setattr(http_filesys, "_BLOCK", 2048)
    _RangeFiles.files = {}
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _RangeFiles)
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def serve(name, data):
        _RangeFiles.files["/" + name] = data
        return f"http://127.0.0.1:{server.server_address[1]}/{name}"

    yield serve
    server.shutdown()
    server.server_close()


@pytest.fixture()
def corpus_at(tmp_path, request):
    """``corpus_at(where, name, data) -> uri``: the same bytes as a local
    file (the stream reader's side) or behind HTTP (the feeder's; the
    server starts only where a test asks for that side)."""
    def put(where, name, data):
        if where == "http":
            return request.getfixturevalue("http_files")(name, data)
        (tmp_path / name).write_bytes(data)
        return str(tmp_path / name)
    return put


# which native engine serves which side, through engine="native"
_NATIVE_ENGINE = {"local": NativeStreamParser, "http": NativeFeedParser}


class TestEngineParityMatrix:
    @pytest.mark.parametrize("name", STREAM_LACKS)
    def test_stream_engine_matches_python(self, tmp_path, name):
        fmt, data, uri_args = PARITY_MATRIX[name]
        (tmp_path / f"c.{fmt}").write_bytes(data)
        uri = str(tmp_path / f"c.{fmt}") + uri_args
        _assert_same(_epoch(uri, fmt, "native", want=NativeStreamParser),
                     _epoch(uri, fmt, "python"))

    @pytest.mark.parametrize("name", list(PARITY_MATRIX))
    def test_feed_engine_matches_python(self, http_files, name):
        """The default engine of every remote corpus (what a TPU-VM reads
        from a bucket), over the whole matrix."""
        fmt, data, uri_args = PARITY_MATRIX[name]
        uri = http_files(f"c.{fmt}", data) + uri_args
        _assert_same(_epoch(uri, fmt, "native", want=NativeFeedParser),
                     _epoch(uri, fmt, "python"))

    @pytest.mark.parametrize("nparts", [2, 3, 5])
    @pytest.mark.parametrize("where", ["local", "http"])
    def test_crlf_noterm_partition_boundaries(self, corpus_at, where,
                                              nparts):
        """CRLF records and no final newline under each part count's own
        boundary layout: the C++ reader's byte-range adjustment (local)
        and the Python split feeding the C++ chunker (remote) both land
        where the Python engine does, part by part."""
        uri = corpus_at(where, "crlf.libsvm",
                        _libsvm_text(n=120, d=3, eol="\r\n",
                                     terminated=False))
        for part in range(nparts):
            _assert_same(
                _epoch(uri, "libsvm", "native", want=_NATIVE_ENGINE[where],
                       part=part, nparts=nparts),
                _epoch(uri, "libsvm", "python", part=part, nparts=nparts))


class TestCrossEngineResume:
    @pytest.mark.parametrize("where", ["local", "http"])
    def test_native_checkpoint_resumes_in_python_engine(self, corpus_at,
                                                        where):
        """A mid-stream checkpoint of a native engine (a block count:
        chunk grouping is deterministic and the same in every engine)
        restores into the Python engine, which replays the remainder
        byte-identically."""
        uri = corpus_at(where, "ck.libsvm", _libsvm_text(n=500, d=4))

        def parser(engine):
            return create_parser(uri, 0, 1, "libsvm", threaded=True,
                                 parse_workers=1, engine=engine,
                                 chunk_bytes=2048)

        ref = _epoch(uri, "libsvm", "python")
        src = parser("native")
        try:
            assert type(src) is _NATIVE_ENGINE[where]
            head = [np.asarray(src.next_block().label) for _ in range(2)]
            state = src.state_dict()
        finally:
            src.close()
        assert state["kind"] == "blocks" and state["blocks"] == 2
        dst = parser("python")
        try:
            dst.load_state(state)
            tail = _drain_arrays(dst)
        finally:
            dst.close()
        np.testing.assert_array_equal(
            np.concatenate(head + [tail["label"]]), ref["label"])

    def test_python_seek_state_refused_by_native_engine(self, tmp_path):
        """The other direction is not a replay: the Python engine
        checkpoints a byte-exact split position (``kind='split'``), which
        the native reader cannot seek to — it refuses the state loudly
        rather than restarting the epoch from row 0."""
        f = tmp_path / "ck.libsvm"
        f.write_bytes(_libsvm_text(n=500, d=4))
        src = create_parser(str(f), 0, 1, "libsvm", threaded=True,
                            parse_workers=1, engine="python",
                            chunk_bytes=2048)
        try:
            assert src.next_block() is not None
            state = src.state_dict()
        finally:
            src.close()
        assert state["kind"] == "split"
        dst = create_parser(str(f), 0, 1, "libsvm", engine="native",
                            chunk_bytes=2048)
        try:
            with pytest.raises(DMLCError, match="incompatible resume state"):
                dst.load_state(state)
        finally:
            dst.close()


class TestEngineKnob:
    @pytest.fixture(autouse=True)
    def _no_engine_env(self, monkeypatch):
        monkeypatch.delenv("DMLC_TPU_PARSE_ENGINE", raising=False)
        monkeypatch.delenv("DMLC_TPU_NO_NATIVE_READER", raising=False)

    @staticmethod
    def _is_python_engine(p):
        from dmlc_tpu.data.parsers import TextParserBase

        base = p
        while not isinstance(base, TextParserBase):
            base = base.base
        return base._native is False  # _pin_python_scanner's mark

    def test_env_routes_engine(self, tmp_path, monkeypatch):
        f = tmp_path / "env.libsvm"
        f.write_bytes(_libsvm_text(n=50, d=3))
        monkeypatch.setenv("DMLC_TPU_PARSE_ENGINE", "python")
        p = create_parser(str(f), 0, 1, "libsvm")
        try:
            assert self._is_python_engine(p)
        finally:
            p.close()
        monkeypatch.setenv("DMLC_TPU_PARSE_ENGINE", "native")
        p = create_parser(str(f), 0, 1, "libsvm")
        try:
            assert type(p) is NativeStreamParser
        finally:
            p.close()

    def test_uri_arg_routes_engine_and_argument_wins(self, tmp_path,
                                                     monkeypatch):
        """``?engine=`` routes, outranks the environment, and is itself
        outranked by ``create_parser(engine=)``."""
        f = tmp_path / "uri.libsvm"
        f.write_bytes(_libsvm_text(n=50, d=3))
        monkeypatch.setenv("DMLC_TPU_PARSE_ENGINE", "native")
        p = create_parser(str(f) + "?engine=python", 0, 1, "libsvm")
        try:
            assert self._is_python_engine(p)
        finally:
            p.close()
        monkeypatch.setenv("DMLC_TPU_PARSE_ENGINE", "python")
        p = create_parser(str(f), 0, 1, "libsvm", engine="native")
        try:
            assert type(p) is NativeStreamParser
        finally:
            p.close()

    @pytest.mark.parametrize("engine,via", [
        ("turbo", "env"), ("native-batch", "env"),
        ("native-batch", "uri"), ("native-batch", "argument")])
    def test_unknown_engine_rejected_loudly(self, tmp_path, monkeypatch,
                                            engine, via):
        """Input validation: a misspelt engine fails the run, it does not
        fall through to ``auto`` — and so does the chunk-batch engine
        PR 28 deleted, by its old name, on each of the three ways in."""
        f = tmp_path / "bad.libsvm"
        f.write_bytes(_libsvm_text(n=10, d=2))
        uri, kw = str(f), {}
        if via == "env":
            monkeypatch.setenv("DMLC_TPU_PARSE_ENGINE", engine)
        elif via == "uri":
            uri += f"?engine={engine}"
        else:
            kw["engine"] = engine
        with pytest.raises(DMLCError, match="parse engine"):
            create_parser(uri, 0, 1, "libsvm", **kw)

    @pytest.mark.parametrize("why", ["threaded=False",
                                     "DMLC_TPU_NO_NATIVE_READER"])
    def test_ineligible_native_falls_back_loudly(self, tmp_path,
                                                 monkeypatch, caplog, why):
        """``engine="native"`` on a configuration the fused reader cannot
        serve runs the Python engine and says so: a knob that silently
        ran another path would lie."""
        import logging

        f = tmp_path / "fb.libsvm"
        f.write_bytes(_libsvm_text(n=40, d=3))
        threaded = True
        if why == "threaded=False":
            threaded = False
        else:
            monkeypatch.setenv("DMLC_TPU_NO_NATIVE_READER", "1")
        with caplog.at_level(logging.WARNING):
            p = create_parser(str(f), 0, 1, "libsvm", threaded=threaded,
                              engine="native", chunk_bytes=4096)
        try:
            assert type(p) is not NativeStreamParser
            assert "engine=native unavailable" in caplog.text
            assert p.next_block() is not None  # the stream still serves
        finally:
            p.close()

    def test_engine_outside_cache_signature(self, tmp_path):
        """One cache serves every engine: a cache built under
        ``?engine=python`` opens warm under ``engine="native"`` (the
        selector is stripped from the signature) and serves the native
        engine's own stream."""
        f = tmp_path / "sig.libsvm"
        f.write_bytes(_libsvm_text(n=120, d=3))
        cache = str(tmp_path / "sig.bc")
        p = create_parser(str(f) + "?engine=python", 0, 1, "libsvm",
                          chunk_bytes=4096, block_cache=cache)
        try:
            assert p.cache_state == "cold"
            while p.next_block() is not None:
                pass
            p.before_first()
            assert p.cache_state == "warm"
        finally:
            p.close()
        q = create_parser(str(f), 0, 1, "libsvm", engine="native",
                          chunk_bytes=4096, block_cache=cache)
        try:
            assert q.cache_state == "warm"  # no invalidation, no rebuild
            _assert_same(_drain_arrays(q),
                         _epoch(str(f), "libsvm", "native"))
        finally:
            q.close()
