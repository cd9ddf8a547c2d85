"""IO layer tests: URI, filesystems, RecordIO, ThreadedIter, InputSplit.

Follows the reference test strategy (SURVEY.md §4): sharding correctness is
tested by looping every part_index in-process over tempdir/in-memory corpora
(unittest_inputsplit.cc pattern), parse/pipeline failure injection mirrors
unittest_threaditer_exc_handling.cc.
"""

import io
import os
import struct

import pytest

from dmlc_tpu.io import (
    URI, URISpec, MemoryFileSystem, RecordIOChunkReader, RecordIOReader,
    RecordIOWriter, RECORDIO_MAGIC, ThreadedIter, create_input_split,
    get_filesystem, open_stream,
)
from dmlc_tpu.io.input_split import LineSplitter, ShuffledInputSplit
from dmlc_tpu.utils.check import DMLCError


# ---------------- URI ----------------

def test_uri_parse():
    u = URI("hdfs://namenode:9000/path/file.txt")
    assert u.protocol == "hdfs://"
    assert u.host == "namenode:9000"
    assert u.name == "/path/file.txt"
    local = URI("/tmp/x.txt")
    assert local.protocol == "file://" and local.name == "/tmp/x.txt"


def test_urispec():
    s = URISpec("s3://b/key?format=libsvm&clabel=0#cachefile", 2, 4)
    assert s.uri == "s3://b/key"
    assert s.args == {"format": "libsvm", "clabel": "0"}
    assert s.cache_file == "cachefile.split4.part2"
    s1 = URISpec("path#cache", 0, 1)
    assert s1.cache_file == "cache"  # single part: no suffix (uri_spec.h:50)
    s2 = URISpec("plain/path")
    assert s2.cache_file is None and s2.args == {}
    with pytest.raises(DMLCError):
        URISpec("a#b#c")


# ---------------- filesystems ----------------

def test_local_fs(tmp_path):
    p = tmp_path / "data.txt"
    p.write_bytes(b"hello")
    fs = get_filesystem(str(p))
    info = fs.get_path_info(URI(str(p)))
    assert info.size == 5 and info.type == "file"
    listing = fs.list_directory(URI(str(tmp_path)))
    assert any(i.path.name.endswith("data.txt") for i in listing)
    with open_stream(str(p)) as f:
        assert f.read() == b"hello"
    assert open_stream(str(tmp_path / "missing.txt"), "r", allow_null=True) is None
    with pytest.raises(DMLCError):
        open_stream(str(tmp_path / "missing.txt"))


def test_mem_fs():
    MemoryFileSystem.reset()
    with open_stream("mem://bucket/a.txt", "w") as f:
        f.write(b"abc")
    with open_stream("mem://bucket/sub/b.txt", "w") as f:
        f.write(b"defg")
    fs = get_filesystem("mem://bucket/a.txt")
    assert fs.get_path_info(URI("mem://bucket/a.txt")).size == 3
    names = {i.path.raw for i in fs.list_directory(URI("mem://bucket"))}
    assert "mem://bucket/a.txt" in names
    rec = fs.list_directory_recursive(URI("mem://bucket"))
    assert sum(i.size for i in rec) == 7
    with open_stream("mem://bucket/a.txt") as f:
        assert f.read() == b"abc"


def test_unknown_protocol():
    with pytest.raises(DMLCError):
        get_filesystem("zz://x/y")


# ---------------- recordio ----------------

def test_recordio_roundtrip():
    buf = io.BytesIO()
    writer = RecordIOWriter(buf)
    records = [b"hello", b"", b"world!!", b"x" * 1000]
    for r in records:
        writer.write_record(r)
    buf.seek(0)
    out = list(RecordIOReader(buf))
    assert out == records


def test_recordio_golden_layout():
    # format spec recordio.h:17-45: [magic][lrec][data][pad]
    buf = io.BytesIO()
    RecordIOWriter(buf).write_record(b"abcde")
    raw = buf.getvalue()
    magic, lrec = struct.unpack_from("<II", raw, 0)
    assert magic == RECORDIO_MAGIC == 0xCED7230A
    assert lrec >> 29 == 0 and lrec & ((1 << 29) - 1) == 5
    assert raw[8:13] == b"abcde" and raw[13:16] == b"\x00\x00\x00"
    assert len(raw) == 16


def test_recordio_magic_escape():
    # payload containing the magic at an aligned cell must be escaped
    magic_bytes = struct.pack("<I", RECORDIO_MAGIC)
    payloads = [
        magic_bytes,                        # exactly magic
        b"abcd" + magic_bytes + b"efgh",    # aligned mid-payload
        magic_bytes * 3,                    # consecutive magics
        b"ab" + magic_bytes + b"cd",        # UNaligned: no escape needed
    ]
    buf = io.BytesIO()
    writer = RecordIOWriter(buf)
    for p in payloads:
        writer.write_record(p)
    assert writer.except_counter >= 5
    buf.seek(0)
    assert list(RecordIOReader(buf)) == payloads


def test_recordio_chunk_reader_parts():
    buf = io.BytesIO()
    writer = RecordIOWriter(buf)
    records = [f"rec{i}".encode() * (i % 7 + 1) for i in range(100)]
    for r in records:
        writer.write_record(r)
    chunk = buf.getvalue()
    for nparts in (1, 2, 3, 8):
        got = []
        for part in range(nparts):
            got.extend(bytes(r) for r in RecordIOChunkReader(chunk, part, nparts))
        assert got == records, f"nparts={nparts}"


# ---------------- ThreadedIter ----------------

def test_threaded_iter_order_and_recycle():
    it = ThreadedIter.from_factory(lambda: iter(range(100)), max_capacity=4)
    got = []
    while True:
        v = it.next()
        if v is None:
            break
        got.append(v)
        it.recycle(v)
    assert got == list(range(100))
    it.destroy()


def test_threaded_iter_before_first():
    it = ThreadedIter.from_factory(lambda: iter(range(10)), max_capacity=2)
    assert it.next() == 0
    assert it.next() == 1
    it.before_first()  # epoch reset mid-stream (threadediter.h:210-235)
    got = list(it)
    assert got == list(range(10))
    it.before_first()
    assert list(it) == list(range(10))
    it.destroy()


def test_threaded_iter_stall_watchdog(monkeypatch):
    """DMLC_PIPELINE_STALL_TIMEOUT: a live-but-wedged producer (hung device
    transfer) raises a diagnosable error instead of blocking
    the consumer forever. Off by default."""
    import threading as _threading

    release = _threading.Event()

    def gen():
        yield 1
        release.wait(30)  # wedge until the test releases us
        yield 2

    it = ThreadedIter.from_factory(lambda: gen(), max_capacity=1)
    assert it.next() == 1
    monkeypatch.setenv("DMLC_PIPELINE_STALL_TIMEOUT", "0.3")
    with pytest.raises(DMLCError, match="pipeline stalled.*alive but blocked"):
        it.next()
    # un-wedge: with the watchdog off again the stream continues normally
    monkeypatch.delenv("DMLC_PIPELINE_STALL_TIMEOUT")
    release.set()
    assert it.next() == 2
    it.destroy()


def test_threaded_iter_exception_propagation():
    # mirror unittest_threaditer_exc_handling.cc:25-60
    def gen():
        for i in range(50):
            if i == 20:
                raise DMLCError("injected producer failure")
            yield i

    it = ThreadedIter.from_factory(gen, max_capacity=4)
    got = []
    with pytest.raises(DMLCError, match="injected"):
        while True:
            v = it.next()
            if v is None:
                break
            got.append(v)
    assert got == list(range(20))
    it.destroy()


def test_threaded_iter_exception_in_before_first():
    state = {"n": 0}

    def factory():
        state["n"] += 1
        if state["n"] == 2:
            raise ValueError("reset failure")
        return iter(range(3))

    it = ThreadedIter.from_factory(factory, max_capacity=2)
    assert list(it) == [0, 1, 2]
    with pytest.raises(ValueError, match="reset failure"):
        it.before_first()
    it.destroy()


# ---------------- InputSplit: line ----------------

def _write_corpus(tmp_path, contents):
    paths = []
    for i, data in enumerate(contents):
        p = tmp_path / f"part{i:02d}.txt"
        p.write_bytes(data)
        paths.append(str(p))
    return ";".join(paths)


def _collect_all_parts(uri, num_parts, type_="text", threaded=False, **kw):
    per_part = []
    for part in range(num_parts):
        split = create_input_split(uri, part, num_parts, type_, threaded=threaded, **kw)
        per_part.append([bytes(r) for r in split.iter_records()])
        split.close()
    return per_part


LINES = [f"line-{i:04d} value:{i * 3}".encode() for i in range(500)]


@pytest.mark.parametrize("num_parts", [1, 2, 3, 5, 8])
def test_line_split_no_loss_no_dup(tmp_path, num_parts):
    # 3 files, all ending with newline
    third = len(LINES) // 3
    contents = [
        b"\n".join(LINES[:third]) + b"\n",
        b"\n".join(LINES[third:2 * third]) + b"\n",
        b"\n".join(LINES[2 * third:]) + b"\n",
    ]
    uri = _write_corpus(tmp_path, contents)
    parts = _collect_all_parts(uri, num_parts)
    merged = [r for p in parts for r in p]
    assert merged == LINES, f"num_parts={num_parts}"


@pytest.mark.parametrize("num_parts", [1, 2, 4, 7])
def test_line_split_noeol_files(tmp_path, num_parts):
    # files WITHOUT trailing newline: the PR#385/PR#452 cases
    third = len(LINES) // 3
    contents = [
        b"\n".join(LINES[:third]),            # NOEOL
        b"\n".join(LINES[third:2 * third]),   # NOEOL
        b"\n".join(LINES[2 * third:]),        # NOEOL
    ]
    uri = _write_corpus(tmp_path, contents)
    parts = _collect_all_parts(uri, num_parts)
    merged = [r for p in parts for r in p]
    assert merged == LINES, f"num_parts={num_parts}"


def test_line_split_crlf_and_blank_lines(tmp_path):
    data = b"a\r\nb\n\n\nc\r\rd\ne"
    p = tmp_path / "f.txt"
    p.write_bytes(data)
    parts = _collect_all_parts(str(p), 1)
    assert parts[0] == [b"a", b"b", b"c", b"d", b"e"]


def test_line_split_record_larger_than_chunk(tmp_path):
    # force the buffer-doubling path (Chunk::Load, input_split_base.cc:260-277)
    big = b"x" * 5000
    data = b"\n".join([b"small", big, b"tail"]) + b"\n"
    p = tmp_path / "f.txt"
    p.write_bytes(data)
    for num_parts in (1, 2):
        got = []
        for part in range(num_parts):
            split = create_input_split(
                str(p), part, num_parts, "text", threaded=False, chunk_bytes=64
            )
            got.extend(bytes(r) for r in split.iter_records())
            split.close()
        assert got == [b"small", big, b"tail"]


def test_line_split_before_first_epoch(tmp_path):
    uri = _write_corpus(tmp_path, [b"\n".join(LINES[:50]) + b"\n"])
    split = create_input_split(uri, 0, 1, "text", threaded=False)
    first = [bytes(r) for r in split.iter_records()]
    split.before_first()
    second = [bytes(r) for r in split.iter_records()]
    assert first == second == LINES[:50]
    split.close()


def test_line_split_on_memfs():
    MemoryFileSystem.reset()
    with open_stream("mem://c/a.txt", "w") as f:
        f.write(b"\n".join(LINES[:100]))
    with open_stream("mem://c/b.txt", "w") as f:
        f.write(b"\n".join(LINES[100:200]))
    uri = "mem://c/a.txt;mem://c/b.txt"
    parts = _collect_all_parts(uri, 3)
    merged = [r for p in parts for r in p]
    assert merged == LINES[:200]


def test_line_split_directory_expansion(tmp_path):
    d = tmp_path / "corpus"
    d.mkdir()
    (d / "a.txt").write_bytes(b"1\n2\n")
    (d / "b.txt").write_bytes(b"3\n4\n")
    parts = _collect_all_parts(str(d), 1)
    assert parts[0] == [b"1", b"2", b"3", b"4"]


def test_line_split_regex_expansion(tmp_path):
    (tmp_path / "data-0.txt").write_bytes(b"a\n")
    (tmp_path / "data-1.txt").write_bytes(b"b\n")
    (tmp_path / "other.log").write_bytes(b"z\n")
    pattern = str(tmp_path / "data-.*\\.txt")
    parts = _collect_all_parts(pattern, 1)
    assert parts[0] == [b"a", b"b"]


def test_threaded_input_split_matches(tmp_path):
    uri = _write_corpus(tmp_path, [b"\n".join(LINES) + b"\n"])
    for num_parts in (1, 3):
        got = []
        for part in range(num_parts):
            split = create_input_split(uri, part, num_parts, "text", threaded=True)
            got.extend(bytes(r) for r in split.iter_records())
            split.close()
        assert got == LINES


def test_threaded_input_split_epoch_reset(tmp_path):
    uri = _write_corpus(tmp_path, [b"\n".join(LINES[:30]) + b"\n"])
    split = create_input_split(uri, 0, 1, "text", threaded=True)
    a = [bytes(r) for r in split.iter_records()]
    split.before_first()
    b = [bytes(r) for r in split.iter_records()]
    assert a == b == LINES[:30]
    split.close()


# ---------------- InputSplit: recordio ----------------

def _write_rec_files(tmp_path, records, nfiles):
    per = (len(records) + nfiles - 1) // nfiles
    paths = []
    for i in range(nfiles):
        p = tmp_path / f"data{i}.rec"
        with open(p, "wb") as f:
            w = RecordIOWriter(f)
            for r in records[i * per:(i + 1) * per]:
                w.write_record(r)
        paths.append(str(p))
    return ";".join(paths)


@pytest.mark.parametrize("num_parts", [1, 2, 5])
def test_recordio_split(tmp_path, num_parts):
    magic_bytes = struct.pack("<I", RECORDIO_MAGIC)
    records = [os.urandom(i % 50 + 1) for i in range(200)]
    records[17] = magic_bytes + b"embedded"      # escape path exercised
    records[42] = b"abcd" + magic_bytes
    uri = _write_rec_files(tmp_path, records, 3)
    parts = _collect_all_parts(uri, num_parts, "recordio")
    merged = [r for p in parts for r in p]
    assert merged == records, f"num_parts={num_parts}"


def test_recordio_split_small_chunks(tmp_path):
    records = [os.urandom(40) for _ in range(100)]
    uri = _write_rec_files(tmp_path, records, 1)
    split = create_input_split(uri, 0, 1, "recordio", threaded=False, chunk_bytes=64)
    got = [bytes(r) for r in split.iter_records()]
    assert got == records
    split.close()


# ---------------- InputSplit: indexed recordio ----------------

def _write_indexed(tmp_path, records):
    data_p = tmp_path / "data.rec"
    idx_p = tmp_path / "data.idx"
    with open(data_p, "wb") as df, open(idx_p, "wb") as xf:
        from dmlc_tpu.io import write_indexed_recordio

        write_indexed_recordio(df, xf, records)
    return str(data_p), str(idx_p)


@pytest.mark.parametrize("num_parts", [1, 2, 4])
def test_indexed_recordio_split(tmp_path, num_parts):
    records = [f"sample-{i:03d}".encode() * (i % 5 + 1) for i in range(103)]
    data_uri, idx_uri = _write_indexed(tmp_path, records)
    got = []
    for part in range(num_parts):
        split = create_input_split(
            data_uri, part, num_parts, "indexed_recordio",
            index_uri=idx_uri, threaded=False,
        )
        got.extend(bytes(r) for r in split.iter_records())
        split.close()
    assert got == records


def test_indexed_recordio_shuffle(tmp_path):
    records = [f"r{i:03d}".encode() for i in range(64)]
    data_uri, idx_uri = _write_indexed(tmp_path, records)
    split = create_input_split(
        data_uri, 0, 1, "indexed_recordio",
        index_uri=idx_uri, shuffle=True, seed=7, threaded=False,
    )
    epoch1 = [bytes(r) for r in split.iter_records()]
    split.before_first()
    epoch2 = [bytes(r) for r in split.iter_records()]
    split.close()
    assert sorted(epoch1) == sorted(records)  # coverage
    assert sorted(epoch2) == sorted(records)
    assert epoch1 != records                  # actually shuffled
    assert epoch1 != epoch2                   # reshuffled each epoch

    # determinism under the same seed
    split_b = create_input_split(
        data_uri, 0, 1, "indexed_recordio",
        index_uri=idx_uri, shuffle=True, seed=7, threaded=False,
    )
    assert [bytes(r) for r in split_b.iter_records()] == epoch1
    split_b.close()


def test_indexed_recordio_batches(tmp_path):
    records = [os.urandom(16) for _ in range(40)]
    data_uri, idx_uri = _write_indexed(tmp_path, records)
    split = create_input_split(
        data_uri, 0, 1, "indexed_recordio",
        index_uri=idx_uri, batch_size=7, threaded=False,
    )
    # batch api returns whole-record chunks of <= batch_size records
    total = []
    nchunks = 0
    while True:
        chunk = split.next_chunk()
        if chunk is None:
            break
        nchunks += 1
        total.extend(bytes(r) for r in split.records_in_chunk(chunk))
    split.close()
    assert total == records
    assert nchunks == (40 + 6) // 7


# ---------------- shuffled chunk split ----------------

def test_shuffled_input_split_coverage(tmp_path):
    uri = _write_corpus(tmp_path, [b"\n".join(LINES) + b"\n"])
    got = []
    for part in range(2):
        split = create_input_split(
            uri, part, 2, "text", num_shuffle_parts=4, seed=3, threaded=False
        )
        got.extend(bytes(r) for r in split.iter_records())
        split.close()
    assert sorted(got) == sorted(LINES)
    assert got != LINES  # order was shuffled at chunk level


# ---------------- partition edge cases ----------------

def test_more_parts_than_records(tmp_path):
    p = tmp_path / "tiny.txt"
    p.write_bytes(b"only-one-line\n")
    parts = _collect_all_parts(str(p), 8)
    merged = [r for pt in parts for r in pt]
    assert merged == [b"only-one-line"]


def test_empty_files_skipped(tmp_path):
    (tmp_path / "a.txt").write_bytes(b"x\n")
    (tmp_path / "empty.txt").write_bytes(b"")
    uri = str(tmp_path / "a.txt") + ";" + str(tmp_path / "empty.txt")
    parts = _collect_all_parts(uri, 2)
    merged = [r for pt in parts for r in pt]
    assert merged == [b"x"]


# ---------------- reset_partition reuse (regression: review findings) ----------------

def test_reset_partition_reuse_no_stale_state(tmp_path):
    # one split object reused across partitions, including empty ones
    p = tmp_path / "r.txt"
    p.write_bytes(b"a\nb\nc\n")
    split = create_input_split(str(p), 0, 1, "text", threaded=False)
    assert bytes(split.next_record()) == b"a"  # mid-iteration
    split.reset_partition(7, 8)  # empty byte range
    assert split.next_record() is None
    split.reset_partition(0, 1)
    assert [bytes(r) for r in split.iter_records()] == [b"a", b"b", b"c"]
    split.close()


def test_indexed_reset_partition_empty_after_use(tmp_path):
    records = [f"r{i}".encode() for i in range(8)]
    data_uri, idx_uri = _write_indexed(tmp_path, records)
    split = create_input_split(
        data_uri, 0, 1, "indexed_recordio", index_uri=idx_uri,
        shuffle=True, seed=1, threaded=False,
    )
    assert split.next_record() is not None  # partially consumed
    split.reset_partition(10, 16)  # out-of-range -> empty
    assert split.next_record() is None
    split.reset_partition(0, 1)
    assert sorted(bytes(r) for r in split.iter_records()) == sorted(records)
    split.close()


def test_single_file_split_chunk_then_record():
    import dmlc_tpu.io.input_split as isp
    import tempfile, os as _os
    with tempfile.NamedTemporaryFile("wb", suffix=".txt", delete=False) as f:
        f.write(b"x\ny\n")
        path = f.name
    try:
        s = isp.SingleFileSplit(path)
        chunk = s.next_chunk()
        assert bytes(chunk) == b"x\ny\n"
        assert s.next_record() is None  # chunk consumed the stream
        s.before_first()
        assert bytes(s.next_record()) == b"x"
    finally:
        _os.unlink(path)


def test_single_file_split_streams_in_bounded_chunks():
    """A file larger than chunk_bytes is served in multiple record-aligned
    chunks with no dropped/duplicated/split records (single_file_split.h
    buffers incrementally; slurping the whole file would OOM on stdin)."""
    import dmlc_tpu.io.input_split as isp
    import tempfile, os as _os
    lines = [f"row-{i:05d}" for i in range(500)]
    with tempfile.NamedTemporaryFile("wb", suffix=".txt", delete=False) as f:
        f.write(("\n".join(lines) + "\n").encode())
        path = f.name
    try:
        s = isp.SingleFileSplit(path, chunk_bytes=4096)
        got = []
        while (rec := s.next_record()) is not None:
            got.append(bytes(rec).decode())
        assert got == lines
        # chunk interface: multiple chunks, all record-aligned, re-parseable
        s.before_first()
        chunks = []
        while (c := s.next_chunk()) is not None:
            assert len(c) <= 8192
            chunks.append(bytes(c))
        assert len(chunks) > 1
        reparsed = b"".join(chunks).decode().splitlines()
        assert reparsed == lines
        s.close()
    finally:
        _os.unlink(path)


def test_memfile_double_close():
    MemoryFileSystem.reset()
    f = open_stream("mem://b/x.txt", "w")
    f.write(b"hi")
    f.close()
    f.close()  # idempotent
    with open_stream("mem://b/x.txt") as g:
        assert g.read() == b"hi"


# ---------------- chunk-boundary regression pins (ISSUE 14 satellite) ----
#
# CRLF line endings and a final record with no trailing newline, at EXACT
# partition boundaries: the stream engine (LineSplitter) and the
# zero-copy mmap engine (MmapLineSplit) must deliver identical record
# streams for every (partition, chunk-budget) combination — including
# boundaries that land between the '\r' and '\n' of a CRLF pair and a
# partition whose final record is unterminated. An exhaustive sweep
# (every nparts up to len(corpus)+1 places a raw boundary at every byte)
# verified the current handling correct; these tests pin it so the SIMD
# batch path — whose chunk/tail handling is new code over the same
# splits — can never silently regress it.

_BOUNDARY_CORPORA = {
    "lf_term": b"a 1:1\nbb 2:2\nccc 3:3\nd 4:4\n",
    "lf_noterm": b"a 1:1\nbb 2:2\nccc 3:3\nd 4:4",
    "crlf_term": b"a 1:1\r\nbb 2:2\r\nccc 3:3\r\nd 4:4\r\n",
    "crlf_noterm": b"a 1:1\r\nbb 2:2\r\nccc 3:3\r\nd 4:4",
    "cr_only_noterm": b"a 1:1\rbb 2:2\rccc 3:3\rd 4:4",
    "blank_runs": b"a 1:1\n\n\r\n\nbb 2:2\r\n\r\nccc 3:3",
}


def _split_records(split):
    out = []
    while (r := split.next_record()) is not None:
        out.append(bytes(r))
    return out


@pytest.mark.parametrize("name", sorted(_BOUNDARY_CORPORA))
def test_mmap_split_boundary_parity_exhaustive(tmp_path, name):
    """Every partition boundary position x several chunk budgets:
    MmapLineSplit records == LineSplitter records, and the union over
    all parts is exactly the corpus's lines (nothing lost or doubled at
    a CRLF straddle or an unterminated tail)."""
    import re

    from dmlc_tpu.io.filesystem import get_filesystem
    from dmlc_tpu.io.input_split import MmapLineSplit

    data = _BOUNDARY_CORPORA[name]
    p = tmp_path / f"{name}.txt"
    p.write_bytes(data)
    fs = get_filesystem(str(p))
    want_lines = [l for l in re.split(rb"[\r\n]+", data) if l]
    for nparts in range(1, len(data) + 2):
        union = []
        for part in range(nparts):
            per_engine = {}
            for label, cls in (("stream", LineSplitter),
                               ("mmap", MmapLineSplit)):
                for cb in (1, 3, 7, len(data), 4096):
                    s = cls(fs, str(p))
                    s._chunk_bytes = cb
                    s.reset_partition(part, nparts)
                    recs = _split_records(s)
                    s.close()
                    prev = per_engine.setdefault(label, recs)
                    assert recs == prev, (name, nparts, part, label, cb)
            assert per_engine["mmap"] == per_engine["stream"], (
                name, nparts, part)
            union.extend(per_engine["mmap"])
        assert union == want_lines, (name, nparts)


def test_mmap_split_unterminated_tail_resume(tmp_path):
    """Checkpoint/restore across the unterminated-final-record chunk:
    states taken after every chunk (including the tail) restore
    byte-identically into a fresh MmapLineSplit AND cross-engine from a
    LineSplitter state."""
    from dmlc_tpu.io.filesystem import get_filesystem
    from dmlc_tpu.io.input_split import MmapLineSplit

    data = _BOUNDARY_CORPORA["crlf_noterm"]
    p = tmp_path / "resume.txt"
    p.write_bytes(data)
    fs = get_filesystem(str(p))

    def chunks_from(split):
        out = []
        while (c := split.next_chunk()) is not None:
            out.append(bytes(c))
        return out

    base = MmapLineSplit(fs, str(p))
    base._chunk_bytes = 8
    base.reset_partition(0, 1)
    full = chunks_from(base)
    base.close()
    assert len(full) >= 2  # the sweep must cross the unterminated tail
    for k in range(len(full) + 1):
        for src_cls in (MmapLineSplit, LineSplitter):
            s = src_cls(fs, str(p))
            s._chunk_bytes = 8
            s.reset_partition(0, 1)
            for _ in range(k):
                s.next_chunk()
            state = s.state_dict()
            s.close()
            r = MmapLineSplit(fs, str(p))
            r._chunk_bytes = 8
            r.reset_partition(0, 1)
            r.load_state(state)
            tail = b"".join(chunks_from(r))
            r.close()
            # chunk grouping may differ across engines on the appended
            # final newline; the delivered BYTES must not
            want = b"".join(full[k:])
            assert tail.replace(b"\n", b"").replace(b"\r", b"") == \
                want.replace(b"\n", b"").replace(b"\r", b""), (src_cls, k)
