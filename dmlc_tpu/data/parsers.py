"""ML text-format parsers: libsvm, csv, libfm.

Equivalent of reference src/data/{parser.h,text_parser.h,libsvm_parser.h,
csv_parser.h,libfm_parser.h} + the factory/registry in src/data.cc.

Parsing strategy: the reference splits each chunk across OS threads and runs
a char-by-char scanner (text_parser.h:110-146). The Python engine instead
parses a whole chunk with vectorized numpy string conversion (one C-level
``split`` + one ``astype`` per chunk); the C++ native core
(:mod:`dmlc_tpu.native`) supplies the multi-threaded scanner for the hot
path. Both emit identical RowBlocks (tested against each other).

Semantics matched to the reference:
- libsvm: ``label[:weight] [qid:N] idx[:val]...``; ``#`` comments
  (libsvm_parser.h:67-84); missing values mean binary features; 1-based
  index heuristic à la sklearn when indexing_mode=-1 (libsvm_parser.h:159-168).
- csv: dense rows, synthetic indices 0..k (csv_parser.h:120-121);
  ``label_column``/``weight_column``/single-char ``delimiter`` params.
- libfm: ``label field:idx:val...``; indexing_mode applies to both field and
  index (libfm_parser.h:130-143).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import warnings
from typing import Dict, Iterator, Optional

import numpy as np

from dmlc_tpu.data.row_block import DenseBlock, RowBlock
from dmlc_tpu.io import resilience as _resilience
from dmlc_tpu.io.input_split import (
    DEFAULT_CHUNK_BYTES,
    InputSplit,
    create_input_split,
    create_mmap_text_split,
)
from dmlc_tpu.io.threaded_iter import OrderedWorkerPool, ThreadedIter
from dmlc_tpu.io.uri import URISpec
from dmlc_tpu.utils import knobs as _knobs
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import (CacheCorruptionError, DMLCError, check,
                                  get_logger)
from dmlc_tpu.utils.params import Parameter, field
from dmlc_tpu.utils.registry import Registry

PARSER_REGISTRY: Registry = Registry.get("parser")


class Parser:
    """Single-pass RowBlock iterator — analog of dmlc::Parser (data.h:293-320)."""

    def next_block(self) -> Optional[RowBlock]:
        raise NotImplementedError

    def before_first(self) -> None:
        raise NotImplementedError

    @property
    def bytes_read(self) -> int:
        return 0

    def __iter__(self) -> Iterator[RowBlock]:
        while True:
            blk = self.next_block()
            if blk is None:
                return
            yield blk

    def close(self) -> None:
        pass


# ---------------- param structs ----------------

class LibSVMParserParam(Parameter):
    """libsvm_parser.h:24-39."""
    format = field(str, default="libsvm")
    indexing_mode = field(
        int, default=0, enum=[-1, 0, 1],
        help=">0: 1-based indices; 0: 0-based; <0: sklearn-style auto-detect.",
    )


class CSVParserParam(Parameter):
    """csv_parser.h:23-40."""
    format = field(str, default="csv")
    label_column = field(int, default=-1, help="0-based column index of the label.")
    delimiter = field(str, default=",", help="Single-character field delimiter.")
    weight_column = field(int, default=-1, help="0-based column of instance weights.")
    dtype = field(str, default="float32", enum=["float32", "int32", "int64"],
                  help="Value dtype (data.cc instantiates real_t/int32/int64).")
    hash_bins = field(int, default=0,
                      help="> 0: every cell but the label's and the weight's "
                           "becomes the id hash(column, the cell's bytes) mod "
                           "hash_bins (docs/data.md, 'Hashed cells'); an "
                           "integer dtype only.")


class LibFMParserParam(Parameter):
    """libfm_parser.h:24-39."""
    format = field(str, default="libfm")
    indexing_mode = field(int, default=0, enum=[-1, 0, 1])


# ---------------- chunk parsers ----------------

class TextParserBase(Parser):
    """Pulls chunks from an InputSplit and parses each into a RowBlock
    (analog of TextParserBase::FillData, text_parser.h:110-146).

    Each chunk goes through the C++ native core when available (threaded
    scanner, dmlc_tpu/native) and falls back to the vectorized numpy engine
    otherwise; both produce identical blocks.
    """

    # class-level defaults so partially-constructed instances (tests drive
    # parse_chunk_* directly via __new__) behave
    _emit_dense: Optional[int] = None
    _native = None
    # per-chunk native scanner threads: 0 = the native default
    # (cores/2-ish). The data-parallel fan-out pins this to 1 — chunk-level
    # parallelism across pool workers replaces intra-chunk threading, whose
    # per-chunk thread spawn measured slower than a single lane anyway.
    _parse_nthread: int = 0
    # fast-path probing state: a corpus whose first chunks ALL reject the
    # _token_table signature (label:weight everywhere, all-binary
    # features) stops paying the qualification scan; one qualifying chunk
    # pins probing on for good. Both fields are advisory and updated
    # RACILY by pool workers — _fast_saw_hit is a monotonic plain store
    # and lost _fast_rejects increments merely delay the give-up, so races
    # cost at most a few extra qualification scans, never wrong output.
    _fast_rejects: int = 0
    _fast_saw_hit: bool = False

    def __init__(self, source: InputSplit, index_dtype=np.uint64):
        self.source = source
        self.index_dtype = index_dtype
        self._bytes = 0
        self._chunks_in = 0  # chunks consumed, for count-based resume
        self._native = None  # tri-state: None=unprobed, False=off, True=on
        self._emit_dense: Optional[int] = None  # num_col when dense mode is on
        # cumulative per-stage seconds: chunk fetch (IO) vs chunk->block
        # parse — the split read/parse attribution DeviceIter.stats() names
        # (two monotonic reads per ~MB chunk: noise)
        self._read_seconds = 0.0
        self._parse_seconds = 0.0

    def set_emit_dense(self, num_col: int, batch_rows: int = 0,
                       dtype: str = "float32",
                       pack_aux: bool = False) -> bool:
        """Opt in to emitting DenseBlock batches straight from the scanner
        (the TPU-first layout fast path). Returns False when this parser has
        no dense scanner; callers then get RowBlocks as usual. batch_rows
        and pack_aux are honored only by the fully-native stream parser;
        dtype by it and by the CSV parser (an integer plane)."""
        return False

    def use_native(self) -> bool:
        if self._native is None:
            from dmlc_tpu import native

            self._native = native.available() and self._native_supported()
        return bool(self._native)

    def _native_supported(self) -> bool:
        return True

    def parse_chunk_native(self, chunk: bytes) -> Optional[RowBlock]:
        return None

    def parse_chunk(self, chunk) -> RowBlock:
        """chunk: bytes or memoryview. The native engines consume a view's
        buffer zero-copy (length-bounded C scanners); the numpy engine
        materializes bytes once, here."""
        if self.use_native():
            block = self.parse_chunk_native(chunk)
            if block is not None:
                return block
        try:
            # overflow-range decimals (1e200) cast float64->float32 as inf
            # — the same saturation strtonum.h applies, so the numpy cast
            # warning is expected noise, not a data problem
            with np.errstate(over="ignore"):
                return self.parse_chunk_py(_chunk_bytes(chunk))
        except (ValueError, TypeError) as exc:
            # numpy conversion failures (e.g. astype on a malformed token)
            # surface as the same error type the native engine raises
            raise DMLCError(f"{type(self).__name__}: malformed input: {exc}") from exc

    def parse_chunk_py(self, chunk: bytes) -> RowBlock:
        raise NotImplementedError

    def stage_seconds(self) -> Dict[str, float]:
        """Cumulative {read, parse} seconds — the per-stage attribution
        feed for DeviceIter.stats(). ``read`` is chunk-fetch time at the
        split (for a threaded split: residual wait on its producer),
        ``parse`` is chunk->RowBlock conversion."""
        return {"read": self._read_seconds, "parse": self._parse_seconds}

    def _pull_chunk(self):
        """One serial chunk pull with the bookkeeping every consumer needs:
        read-seconds accrual, byte/chunk counters, and the byte-exact
        resume annotation positioned just AFTER the chunk (SURVEY.md §5.4)
        — shared by :meth:`next_block` and the parallel fan-out's serial
        source stage so the checkpoint schema cannot diverge. Returns
        ``(chunk, annot_or_None)``; ``(None, None)`` at end of stream."""
        # the span hands back the duration it recorded: the read-seconds
        # accrual gets the same start and the same duration (the trace
        # timeline and stage_seconds() can never disagree)
        with _telemetry.span("read") as sp:
            chunk = self.source.next_chunk()
        self._read_seconds += sp.dt
        if chunk is None:
            return None, None
        self._bytes += len(chunk)
        self._chunks_in += 1
        annot = None
        split_state = getattr(self.source, "chunk_resume_state", None)
        if split_state is not None:
            annot = {"kind": "split", "split": split_state,
                     "chunks": self._chunks_in}
        return chunk, annot

    def next_block(self) -> Optional[RowBlock]:
        while True:
            chunk, annot = self._pull_chunk()
            if chunk is None:
                return None
            with _telemetry.span("parse") as sp:
                block = self.parse_chunk(chunk)
            self._parse_seconds += sp.dt
            if len(block) > 0:
                # the annotation marks the position just AFTER this block,
                # so downstream prefetch pipelines (ThreadedParser,
                # DeviceIter) can checkpoint byte-exactly even though their
                # own view runs behind this producer
                if annot is not None:
                    block.resume_state = annot
                return block

    def before_first(self) -> None:
        self.source.before_first()
        self._chunks_in = 0

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        """Re-point this parser at another partition of the same corpus
        (InputSplit::ResetPartition, io.h:190-242) — the file listing and
        offset table are reused, so looping all parts in one process pays
        the setup cost once."""
        self.source.reset_partition(part_index, num_parts)
        self._chunks_in = 0

    # -------- checkpoint / resume (SURVEY.md §5.4 addition) --------

    def state_dict(self) -> dict:
        """Resume point at a block boundary. Byte-exact whenever the source
        exposes a chunk-synchronized state (undecorated splits AND the
        prefetching ThreadedInputSplit, whose chunks carry the position they
        were produced at); otherwise a chunk count replayed on restore."""
        split_state = getattr(self.source, "chunk_resume_state", None)
        if split_state is not None:
            return {"kind": "split", "split": split_state,
                    "chunks": self._chunks_in}
        if self._chunks_in == 0 and hasattr(self.source, "state_dict"):
            # epoch start: no chunk pulled yet, the live state is exact
            return {"kind": "split", "split": self.source.state_dict(),
                    "chunks": 0}
        return {"kind": "chunks", "chunks": self._chunks_in}

    def load_state(self, state: dict) -> None:
        if state.get("kind") == "split" and hasattr(self.source, "load_state"):
            self.source.load_state(state["split"])
            self._chunks_in = int(state["chunks"])
            return
        self.before_first()
        for _ in range(int(state["chunks"])):
            if self.source.next_chunk() is None:  # skip without parsing
                break
        self._chunks_in = int(state["chunks"])

    @property
    def bytes_read(self) -> int:
        return self._bytes

    def close(self) -> None:
        self.source.close()


def _chunk_bytes(chunk) -> bytes:
    """Chunk -> bytes without copying when it is a full-span view of bytes."""
    if isinstance(chunk, bytes):
        return chunk
    if (
        isinstance(chunk, memoryview)
        and isinstance(chunk.obj, bytes)
        and chunk.c_contiguous
        and len(chunk) == len(chunk.obj)
    ):
        return chunk.obj
    return bytes(chunk)


def _strip_comments(chunk: bytes) -> bytes:
    """Remove ``#``-to-EOL spans (IgnoreCommentAndBlank, libsvm_parser.h:67-84)."""
    if b"#" not in chunk:
        return chunk
    out = []
    for line in chunk.split(b"\n"):
        pos = line.find(b"#")
        out.append(line if pos < 0 else line[:pos])
    return b"\n".join(out)


def _tokenize_lines(chunk: bytes):
    """Split a text chunk into per-line token lists, skipping blanks.

    UTF-8 BOM at chunk start is skipped (text_parser.h:81-95).
    """
    if chunk.startswith(b"\xef\xbb\xbf"):
        chunk = chunk[3:]
    chunk = _strip_comments(chunk.replace(b"\r", b"\n"))
    lines = []
    for line in chunk.split(b"\n"):
        toks = line.split()
        if toks:
            lines.append(toks)
    return lines


def _apply_indexing_mode(index: np.ndarray, mode: int) -> np.ndarray:
    """1-based -> 0-based conversion per libsvm_parser.h:159-168."""
    if len(index) == 0:
        return index
    if mode > 0 or (mode < 0 and int(index.min()) > 0):
        return index - 1
    return index


# bytes.split() whitespace, as a byte-indexed lookup table
_WS_LUT = np.zeros(256, bool)
_WS_LUT[[9, 10, 11, 12, 13, 32]] = True

# _token_table rejections (with no success yet) before a parser stops
# trying the fast path for good — the corpus structure never qualifies
_FAST_PATH_GIVEUP = 4


def _token_table(chunk: bytes, stride: int):
    """Vectorized structure scan for simple ``label f f f...`` text chunks.

    Splits the whole chunk ONCE on whitespace+colon into a single token
    array reused for label / index / value extraction, and derives the
    per-line structure (feature counts, label positions) from numpy mask
    scans instead of a per-line Python loop. ``stride`` is sub-tokens per
    feature (2 = libsvm ``idx:val``, 3 = libfm ``field:idx:val``).

    Returns ``(tokens, nnz, first_idx)`` or None when the chunk needs the
    general path (comments, qid, label:weight, binary/mixed features — any
    line whose token/colon counts break the uniform stride). The general
    path materializes the chunk ~3x via join + replace blobs; this one
    costs a single colon->space replace + split.
    """
    if b"#" in chunk or b"qid:" in chunk:
        return None
    if chunk.startswith(b"\xef\xbb\xbf"):
        chunk = chunk[3:]
    if b"\r" in chunk:
        chunk = chunk.replace(b"\r", b"\n")
    if not chunk:
        return None
    # structure checks run on zero-copy mask scans FIRST; the Python-level
    # replace/split/array-build — the expensive part — happens only after
    # the chunk has qualified, so a rejecting chunk costs numpy scans only
    a = np.frombuffer(chunk, np.uint8)
    iscolon = a == 0x3A
    issep = _WS_LUT[a] | iscolon  # colons become separators in the split
    cpos = np.nonzero(iscolon)[0]
    if len(cpos):
        # every colon must be GLUED to non-separator bytes on both sides:
        # '2: 3' / '2 :3' / '2::3' / a chunk-edge colon all split into
        # tokens whose counts alias a clean 'idx:val' signature while the
        # general path reads them as missing-value/binary/malformed
        if cpos[0] == 0 or cpos[-1] == len(a) - 1:
            return None
        if issep[cpos - 1].any() or issep[cpos + 1].any():
            return None
    prev = np.empty_like(issep)
    prev[0] = True
    prev[1:] = issep[:-1]
    tstart = ~issep & prev
    if not tstart.any():
        return None
    lid = np.cumsum(a == 0x0A)  # line id = newlines before each byte
    nlines = int(lid[-1]) + 1
    counts = np.bincount(lid[tstart], minlength=nlines)
    ccounts = np.bincount(lid[iscolon], minlength=nlines)
    live = counts > 0
    if np.any(ccounts[~live] > 0):
        # colons on a token-less line (e.g. ':::') — the general path
        # rejects these loudly; never swallow them here
        return None
    lc, cc = counts[live], ccounts[live]
    # every live line must be exactly label + nnz uniform features
    nnz, rem = np.divmod(lc - 1, stride)
    if rem.any() or not np.array_equal(cc, (stride - 1) * nnz):
        return None
    first_idx = np.zeros(len(lc), np.int64)
    np.cumsum(lc[:-1], out=first_idx[1:])
    # every colon must belong to a FEATURE token: a colon attached to a
    # line's first token is a label colon (label:weight — or malformed),
    # whose sub-tokens would otherwise alias a uniform feature signature
    # (e.g. libsvm '1:2 3' = weighted label + binary feature parses with
    # the same token/colon counts as 'label idx:val'). tok_before[i] is
    # the index of the token the byte at i follows.
    line_first = np.full(nlines, -1, np.int64)
    line_first[np.nonzero(live)[0]] = first_idx
    tok_before = np.cumsum(tstart) - 1
    if np.any(tok_before[iscolon] == line_first[lid[iscolon]]):
        return None
    tokens = np.array(chunk.replace(b":", b" ").split())
    return tokens, nnz, first_idx


def _split_label_feats(tokens: np.ndarray, first_idx: np.ndarray):
    """(labels f32, feature sub-token array) from a :func:`_token_table`
    result — the one extraction both fast-path engines share."""
    label_mask = np.zeros(len(tokens), bool)
    label_mask[first_idx] = True
    return tokens[first_idx].astype(np.float32), tokens[~label_mask]


class LibSVMParser(TextParserBase):
    """libsvm text -> RowBlock (libsvm_parser.h:85-169)."""

    def __init__(self, source: InputSplit, args: Dict[str, str] | None = None,
                 index_dtype=np.uint64):
        super().__init__(source, index_dtype)
        self.param = LibSVMParserParam()
        self.param.init(dict(args or {}), allow_unknown=True)
        check(self.param.format == "libsvm", "LibSVMParser: format must be libsvm")

    def set_emit_dense(self, num_col: int, batch_rows: int = 0,
                       dtype: str = "float32",
                       pack_aux: bool = False) -> bool:
        if self.use_native():
            self._emit_dense = int(num_col)
            return True
        return False

    def parse_chunk_native(self, chunk: bytes) -> Optional[RowBlock]:
        from dmlc_tpu import native

        # snapshot once: a concurrent worker's NeedsCsrError fallback may
        # null _emit_dense between the check and the call (pool fan-out)
        num_col = self._emit_dense
        if num_col is not None:
            try:
                out = native.parse_libsvm_dense(
                    chunk, num_col, nthread=self._parse_nthread,
                    indexing_mode=self.param.indexing_mode)
            except native.NeedsCsrError:
                # data the dense scanner can't express (qid rows):
                # permanently fall back to the CSR path
                self._emit_dense = None
                out = None
            if out is not None:
                x, label, weight, owner, _packed = out
                return DenseBlock(x, label, weight, hold=owner)
        d = native.parse_libsvm(chunk, nthread=self._parse_nthread,
                                indexing_mode=self.param.indexing_mode)
        if d is None:
            return None
        return RowBlock(
            offset=d["offset"], label=d["label"], index=d["index"],
            value=d["value"], weight=d["weight"], qid=d["qid"],
            hold=d["_owner"],
        )

    def parse_chunk_py(self, chunk: bytes) -> RowBlock:
        fast = (_token_table(chunk, stride=2)
                if self._fast_saw_hit
                or self._fast_rejects < _FAST_PATH_GIVEUP else None)
        if fast is not None:
            self._fast_saw_hit = True
            # one splitted-token array serves label, index AND value
            tokens, nnz, first_idx = fast
            labels, feats = _split_label_feats(tokens, first_idx)
            if len(feats) == 0:
                return RowBlock(
                    offset=np.concatenate([[0], np.cumsum(nnz)]),
                    label=labels, index=np.empty(0, self.index_dtype))
            index = _apply_indexing_mode(
                feats[0::2].astype(np.int64), self.param.indexing_mode)
            return RowBlock(
                offset=np.concatenate([[0], np.cumsum(nnz)]),
                label=labels,
                index=index.astype(self.index_dtype, copy=False),
                value=feats[1::2].astype(np.float32),
            )
        self._fast_rejects += 1
        lines = _tokenize_lines(chunk)
        n = len(lines)
        label_toks = []
        qid_vals: list = []
        has_qid = False
        nnz = np.empty(n, dtype=np.int64)
        feat_toks: list = []
        for i, toks in enumerate(lines):
            label_toks.append(toks[0])
            f = toks[1:]
            if f and f[0].startswith(b"qid:"):
                qid_vals.append(int(f[0][4:]))
                f = f[1:]
                has_qid = True
            elif has_qid:
                raise DMLCError("libsvm: qid must appear on every row or none")
            nnz[i] = len(f)
            feat_toks.extend(f)
        if has_qid and len(qid_vals) != n:
            # qid first appeared on a LATER row: rows before it had none —
            # the per-row check above only trips once has_qid is set
            raise DMLCError("libsvm: qid must appear on every row or none")
        if n == 0:
            return RowBlock(np.zeros(1, np.int64), np.empty(0, np.float32),
                            np.empty(0, self.index_dtype))
        # labels (with optional :weight)
        label_arr = np.array(label_toks)
        if any(b":" in t for t in label_toks):
            pairs = np.char.partition(label_arr, b":")
            labels = pairs[:, 0].astype(np.float32)
            wcol = pairs[:, 2]
            if np.any(wcol == b""):
                raise DMLCError("libsvm: label:weight must be set on every row or none")
            weights = wcol.astype(np.float32)
        else:
            labels = label_arr.astype(np.float32)
            weights = None
        # features idx[:val]
        if feat_toks:
            blob = b" ".join(feat_toks)
            ncolon = blob.count(b":")
            if ncolon == len(feat_toks):
                # every feature has a value: one splitted-token array,
                # index/value extracted as strided views of it
                nums = np.array(blob.replace(b":", b" ").split())
                index = nums[0::2].astype(np.int64)
                value = nums[1::2].astype(np.float32)
            elif ncolon == 0:
                # all-binary features
                index = np.array(feat_toks).astype(np.int64)
                value = None
            else:
                # mixed: treat missing values as 1.0
                parts = np.char.partition(np.array(feat_toks), b":")
                index = parts[:, 0].astype(np.int64)
                vals = parts[:, 2]
                value = np.where(vals == b"", b"1", vals).astype(np.float32)
        else:
            index = np.empty(0, np.int64)
            value = None
        index = _apply_indexing_mode(index, self.param.indexing_mode)
        offset = np.concatenate([[0], np.cumsum(nnz)])
        return RowBlock(
            offset=offset,
            label=labels,
            index=index.astype(self.index_dtype, copy=False),
            value=value,
            weight=weights,
            qid=np.array(qid_vals, np.int64) if has_qid else None,
        )


class CSVParser(TextParserBase):
    """Dense csv -> RowBlock with synthetic indices (csv_parser.h:85-146)."""

    def __init__(self, source: InputSplit, args: Dict[str, str] | None = None,
                 index_dtype=np.uint64):
        super().__init__(source, index_dtype)
        self.param = CSVParserParam()
        self.param.init(dict(args or {}), allow_unknown=True)
        check(self.param.format == "csv", "CSVParser: format must be csv")
        check(len(self.param.delimiter) == 1, "CSVParser: delimiter must be one char")
        check(
            self.param.label_column != self.param.weight_column
            or self.param.label_column < 0,
            "CSVParser: label_column must differ from weight_column",
        )
        self._dtype = np.dtype(self.param.dtype)
        check_hash_bins(self.param)

    def set_emit_dense(self, num_col: int, batch_rows: int = 0,
                       dtype: str = "float32",
                       pack_aux: bool = False) -> bool:
        """Dense blocks straight from the native scanner's cell matrix.
        ``dtype`` is the consumer's plane dtype (``DeviceIter``'s
        ``x_dtype``): integer cells go to a plane of their own dtype and
        float cells to a float one, or the call raises: no id crosses a
        float32 and no real is truncated on the way."""
        check_dense_plane_dtype(self._dtype, dtype)
        if self.use_native():
            self._emit_dense = int(num_col)
            return True
        return False

    def _count_cells(self, cells: np.ndarray) -> np.ndarray:
        _telemetry.REGISTRY.counter(
            _telemetry.CSV_CELLS_METRIC, dtype=self.param.dtype).inc(
                cells.size)
        return cells

    def _count_hashed(self, cells: np.ndarray, empty: int) -> np.ndarray:
        """The books of a chunk scanned with ``hash_bins``: the hashed
        cells, the label's and weight's by the dtype asked for, and the
        hashed cells that had no bytes."""
        plain = len(cells) * sum(
            c >= 0 for c in (self.param.label_column,
                             self.param.weight_column))
        counter = _telemetry.REGISTRY.counter
        counter(_telemetry.CSV_CELLS_METRIC, dtype="hashed").inc(
            cells.size - plain)
        if plain:
            counter(_telemetry.CSV_CELLS_METRIC,
                    dtype=self.param.dtype).inc(plain)
        counter(_telemetry.CSV_EMPTY_CELLS_METRIC).inc(empty)
        return cells

    def parse_chunk_native(self, chunk: bytes) -> Optional[RowBlock]:
        from dmlc_tpu import native

        if self.param.hash_bins:
            out = native.parse_csv_hashed(
                chunk, self.param.hash_bins, delimiter=self.param.delimiter,
                nthread=self._parse_nthread, dtype=self._dtype,
                label_column=self.param.label_column,
                weight_column=self.param.weight_column)
        else:
            out = native.parse_csv(chunk, delimiter=self.param.delimiter,
                                   nthread=self._parse_nthread,
                                   dtype=self._dtype)
        if out is None:
            return None
        cells, owner = out[:2]
        n, ncol = cells.shape
        if n == 0:
            return RowBlock(np.zeros(1, np.int64), np.empty(0, np.float32),
                            np.empty(0, self.index_dtype))
        if self.param.hash_bins:
            self._count_hashed(cells, out[2])
        else:
            self._count_cells(cells)
        if self._emit_dense is not None:
            return self._cells_to_dense(cells, n, ncol, owner)
        return self._cells_to_block(cells, n, ncol)

    def _cells_to_dense(self, cells: np.ndarray, n: int, ncol: int,
                        owner) -> DenseBlock:
        return csv_cells_to_dense(
            cells, n, ncol, int(self._emit_dense),
            self.param.label_column, self.param.weight_column, owner)

    def parse_chunk_py(self, chunk: bytes) -> RowBlock:
        if chunk.startswith(b"\xef\xbb\xbf"):
            chunk = chunk[3:]
        delim = self.param.delimiter.encode()
        norm = chunk.replace(b"\r", b"\n")
        rows = [r for r in norm.split(b"\n") if r]
        n = len(rows)
        if n == 0:
            return RowBlock(np.zeros(1, np.int64), np.empty(0, np.float32),
                            np.empty(0, self.index_dtype))
        if self.param.hash_bins:
            cells = self._count_hashed(*csv_hash_cells(
                rows, delim, self.param.label_column,
                self.param.weight_column, self.param.hash_bins, self._dtype))
            return self._cells_to_block(cells, n, cells.shape[1])
        ncol = rows[0].count(delim) + 1
        # single vectorized conversion of the whole chunk
        tokens = np.array(norm.replace(delim, b" ").split())
        if len(tokens) != n * ncol:
            raise DMLCError(
                f"csv: ragged chunk - expected {n}x{ncol} cells, got {len(tokens)}"
            )
        cells = self._count_cells(
            _integer_cells(tokens, self._dtype) if self._dtype.kind == "i"
            else tokens.astype(self._dtype)).reshape(n, ncol)
        return self._cells_to_block(cells, n, ncol)

    def _cells_to_block(self, cells: np.ndarray, n: int, ncol: int) -> RowBlock:
        return csv_cells_to_block(
            cells, n, ncol, self.param.label_column,
            self.param.weight_column, self.index_dtype)


FNV64_BASIS = np.uint64(0xcbf29ce484222325)
FNV64_PRIME = np.uint64(0x100000001b3)
MAX_HASHED_COLUMNS = 256      # a cell's position is one byte of its hash
MAX_HASH_BINS = 2 ** 31 - 1   # ids are int32 whatever integer holds them


def check_hash_bins(param: CSVParserParam) -> None:
    """Raise unless ``hash_bins`` is 0 (off) or a count of bins an int32 id
    can name, asked for with an integer ``dtype``."""
    bins = param.hash_bins
    if bins == 0:
        return
    if not 0 < bins <= MAX_HASH_BINS:
        raise DMLCError(f"csv: hash_bins={bins} must be in [1, 2**31 - 1]: "
                        "the ids are int32")
    if param.dtype not in ("int32", "int64"):
        raise DMLCError(
            f"csv: hash_bins gives integer ids, so it takes dtype=int32 "
            f"(or int64), not dtype={param.dtype}: no id passes through a "
            "float")


def csv_hash_cells(rows, delim: bytes, label_column: int, weight_column: int,
                   hash_bins: int, dtype):
    """The numpy engine's scan with hashed cells (docs/data.md, "Hashed
    cells"; the native one is ``parse_csv_hashed_range``): ``(cells [n,
    ncol] of dtype, the hashed cells that had no bytes)`` from the chunk's
    non-blank lines ``rows``. A cell that is neither the label's nor the
    weight's becomes FNV-1a-64 over one byte, its 0-based position among
    such cells, then its bytes as they stand, modulo ``hash_bins``; the
    label and weight cells are whole numbers."""
    n, ncol = len(rows), rows[0].count(delim) + 1
    for r, row in enumerate(rows):
        if row.count(delim) + 1 != ncol:
            raise DMLCError(
                f"csv: ragged rows in chunk: {row.count(delim) + 1} cells, "
                f"the rows before have {ncol} (row {r} of the chunk, counted "
                "from 0)")
    plain = [c for c in (label_column, weight_column) if c >= 0]
    check(all(c < ncol for c in plain),
          f"csv: label/weight column {plain} >= num columns {ncol}")
    hashed = [c for c in range(ncol) if c not in plain]
    if len(hashed) > MAX_HASHED_COLUMNS:
        raise DMLCError("csv: hash_bins takes at most 256 hashed columns")
    text = np.frombuffer(delim.join(rows) + delim, np.uint8)
    ends = np.flatnonzero(text == delim[0]).reshape(n, ncol)
    starts = np.empty_like(ends)
    starts.flat[0] = 0
    starts.flat[1:] = ends.flat[:-1] + 1
    cells = np.empty((n, ncol), dtype)
    for c in plain:
        if (ends[:, c] == starts[:, c]).any():
            r = int(np.flatnonzero(ends[:, c] == starts[:, c])[0])
            raise DMLCError("csv: empty label or weight cell in row "
                            f"(row {r}, cell {c} of the chunk, counted from 0)")
        cells[:, c] = _integer_cells(np.array(
            [text[a:b].tobytes() for a, b in zip(starts[:, c], ends[:, c])]),
            np.dtype(dtype))
    at, lens = starts[:, hashed], (ends - starts)[:, hashed]
    with np.errstate(over="ignore"):      # the hash wraps at 64 bits
        h = np.broadcast_to(
            (FNV64_BASIS ^ np.arange(len(hashed), dtype=np.uint64))
            * FNV64_PRIME, at.shape).copy()
        for j in range(int(lens.max()) if lens.size else 0):
            more = lens > j
            h[more] = (h[more] ^ text[at[more] + j]) * FNV64_PRIME
    cells[:, hashed] = (h % np.uint64(hash_bins)).astype(dtype)
    return cells, int((lens == 0).sum())


def check_dense_plane_dtype(cell_dtype, plane_dtype) -> None:
    """Raise unless cells of ``cell_dtype`` may fill a dense plane of
    ``plane_dtype`` (``DeviceIter``'s ``x_dtype``): integer cells only a
    plane of their own dtype (ids above 2**24 do not survive a float32),
    float cells only a float plane."""
    cells, plane = np.dtype(cell_dtype).name, str(plane_dtype)
    integer = ("int32", "int64")
    if cells == plane or (cells not in integer and plane not in integer):
        return
    if plane in integer:
        raise DMLCError(
            f"dense plane: {cells} cells cannot fill a {plane} plane "
            "without passing through another number type; parse with "
            f"dtype={plane} and feed DeviceIter(x_dtype=...) the same "
            "(integer planes: 'int32')")
    raise DMLCError(
        f"dense plane: {cells} cells would cross a float ({plane}) plane, "
        "which rounds ids above 2**24; use DeviceIter(layout='dense', "
        f"x_dtype='{cells}')")


def _integer_cells(tokens: np.ndarray, dtype: np.dtype) -> np.ndarray:
    """The numpy engine's integer cells: whole decimal numbers inside
    ``dtype``'s range, or an error (the native scanner's rule; numpy's own
    cast wraps silently from int64 to int32)."""
    try:
        wide = tokens.astype(np.int64)
    except OverflowError as exc:
        raise DMLCError("csv: integer cell out of range for int64") from exc
    except ValueError as exc:
        raise DMLCError(f"csv: non-integer cell in row ({exc})") from exc
    if dtype == np.int64:
        return wide
    info = np.iinfo(dtype)
    if len(wide) and (wide.min() < info.min or wide.max() > info.max):
        raise DMLCError(f"csv: integer cell out of range for {dtype}")
    return wide.astype(dtype)


def csv_cells_to_dense(cells: np.ndarray, n: int, ncol: int, num_col: int,
                       label_column: int, weight_column: int, owner) -> DenseBlock:
    """Dense cell matrix -> DenseBlock; zero-copy when there are no
    label/weight columns and the width already matches. ``x`` keeps the
    cells' dtype (float32, or the integers of ``dtype=int32|int64``)."""
    lc, wc = label_column, weight_column
    check(lc < ncol, f"csv: label_column {lc} >= num columns {ncol}")
    check(wc < ncol, f"csv: weight_column {wc} >= num columns {ncol}")
    label = cells[:, lc].astype(np.float32) if lc >= 0 else np.zeros(n, np.float32)
    weight = cells[:, wc].astype(np.float32) if wc >= 0 else None
    if lc < 0 and wc < 0 and ncol == num_col:
        return DenseBlock(cells, label, weight, hold=owner)
    feat_cols = [c for c in range(ncol) if c != lc and c != wc]
    k = min(len(feat_cols), num_col)
    x = np.zeros((n, num_col), cells.dtype)
    x[:, :k] = cells[:, feat_cols[:k]]
    return DenseBlock(x, label, weight, hold=owner)


# synthetic CSR skeletons for CSV blocks: every row has the same k column
# indices and k-strided offsets, and block geometry repeats (chunk-sized
# blocks), so one (n, k) build serves the whole stream — rebuilding them
# per block was ~2 array builds per MB of corpus on the hot path.
# Lock-guarded: chunks parse on multiple ParallelTextParser workers, and
# an unguarded clear()+insert raced (one worker could evict the entry
# another was inserting, or two could size-check a half-updated dict).
_CSV_SKELETON_CACHE: dict = {}
_CSV_SKELETON_LOCK = threading.Lock()


def _csv_skeleton(n: int, k: int, index_dtype):
    key = (n, k, np.dtype(index_dtype).str)
    with _CSV_SKELETON_LOCK:
        hit = _CSV_SKELETON_CACHE.get(key)
        if hit is not None:
            return hit
    # build OUTSIDE the lock (array builds are the expensive part);
    # concurrent builders of the same key converge on whichever insert wins
    index = np.tile(np.arange(k, dtype=index_dtype), n)
    # k == 0 (every column is label/weight) is a legal degenerate: all
    # offsets are 0 — np.arange with step 0 would raise instead
    offset = (np.arange(0, (n + 1) * k, k, dtype=np.int64)
              if k else np.zeros(n + 1, np.int64))
    # shared across every block of the stream — freeze so an
    # accidental in-place edit cannot corrupt sibling blocks
    index.flags.writeable = False
    offset.flags.writeable = False
    with _CSV_SKELETON_LOCK:
        hit = _CSV_SKELETON_CACHE.get(key)
        if hit is None:
            if len(_CSV_SKELETON_CACHE) > 64:  # block geometries are few
                _CSV_SKELETON_CACHE.clear()
            hit = (index, offset)
            _CSV_SKELETON_CACHE[key] = hit
    return hit


def csv_cells_to_block(cells: np.ndarray, n: int, ncol: int,
                       label_column: int, weight_column: int,
                       index_dtype) -> RowBlock:
    """Dense cell matrix -> RowBlock with synthetic indices 0..k
    (csv_parser.h:120-121); shared by the native and numpy paths. The
    values keep the cells' dtype (``RowBlock.value``)."""
    lc, wc = label_column, weight_column
    check(lc < ncol, f"csv: label_column {lc} >= num columns {ncol}")
    check(wc < ncol, f"csv: weight_column {wc} >= num columns {ncol}")
    feat_cols = [c for c in range(ncol) if c != lc and c != wc]
    k = len(feat_cols)
    # the feature columns are CONTIGUOUS whenever label/weight sit at the
    # edges (or are absent) — the Criteo-like common case. A basic slice +
    # ascontiguousarray is ONE copy; the general fancy-index + astype path
    # is two full copies of the feature matrix per block.
    lo = min(feat_cols) if k else 0
    contiguous = k and feat_cols == list(range(lo, lo + k))
    if contiguous:
        values = np.ascontiguousarray(cells[:, lo:lo + k])
    else:
        values = np.ascontiguousarray(cells[:, feat_cols])
    label = cells[:, lc].astype(np.float32) if lc >= 0 else np.zeros(n, np.float32)
    weight = cells[:, wc].astype(np.float32) if wc >= 0 else None
    index, offset = _csv_skeleton(n, k, index_dtype)
    return RowBlock(
        offset=offset, label=label, index=index,
        value=values.reshape(-1), weight=weight,
    )


class LibFMParser(TextParserBase):
    """libfm ``label field:idx:val`` -> RowBlock (libfm_parser.h:85-143)."""

    def __init__(self, source: InputSplit, args: Dict[str, str] | None = None,
                 index_dtype=np.uint64):
        super().__init__(source, index_dtype)
        self.param = LibFMParserParam()
        self.param.init(dict(args or {}), allow_unknown=True)
        check(self.param.format == "libfm", "LibFMParser: format must be libfm")

    def parse_chunk_native(self, chunk: bytes) -> Optional[RowBlock]:
        from dmlc_tpu import native

        d = native.parse_libfm(chunk, nthread=self._parse_nthread,
                               indexing_mode=self.param.indexing_mode)
        if d is None:
            return None
        return RowBlock(
            offset=d["offset"], label=d["label"], index=d["index"],
            value=d["value"], field=d["field"], hold=d["_owner"],
        )

    def parse_chunk_py(self, chunk: bytes) -> RowBlock:
        fast = (_token_table(chunk, stride=3)
                if self._fast_saw_hit
                or self._fast_rejects < _FAST_PATH_GIVEUP else None)
        if fast is not None:
            self._fast_saw_hit = True
            tokens, nnz, first_idx = fast
            labels, feats = _split_label_feats(tokens, first_idx)
            if len(feats):
                fields = feats[0::3].astype(np.int64)
                index = feats[1::3].astype(np.int64)
                value = feats[2::3].astype(np.float32)
            else:
                fields = np.empty(0, np.int64)
                index = np.empty(0, np.int64)
                value = None
        else:
            self._fast_rejects += 1
            lines = _tokenize_lines(chunk)
            n = len(lines)
            if n == 0:
                return RowBlock(np.zeros(1, np.int64), np.empty(0, np.float32),
                                np.empty(0, self.index_dtype))
            label_toks = []
            nnz = np.empty(n, dtype=np.int64)
            feat_toks: list = []
            for i, toks in enumerate(lines):
                label_toks.append(toks[0])
                nnz[i] = len(toks) - 1
                feat_toks.extend(toks[1:])
            labels = np.array(label_toks).astype(np.float32)
            if feat_toks:
                blob = b" ".join(feat_toks)
                check(blob.count(b":") == 2 * len(feat_toks),
                      "libfm: features must be field:index:value triples")
                nums = np.array(blob.replace(b":", b" ").split())
                fields = nums[0::3].astype(np.int64)
                index = nums[1::3].astype(np.int64)
                value = nums[2::3].astype(np.float32)
            else:
                fields = np.empty(0, np.int64)
                index = np.empty(0, np.int64)
                value = None
        mode = self.param.indexing_mode
        # heuristic applies to BOTH field and index (libfm_parser.h:130-143)
        if len(index):
            if mode > 0 or (mode < 0 and int(index.min()) > 0 and int(fields.min()) > 0):
                index = index - 1
                fields = fields - 1
        offset = np.concatenate([[0], np.cumsum(nnz)])
        return RowBlock(
            offset=offset, label=labels,
            index=index.astype(self.index_dtype, copy=False),
            value=value,
            field=fields.astype(self.index_dtype, copy=False),
        )


def annot_key(state: Optional[dict]) -> str:
    """Canonical comparison key of a resume annotation — ONE
    normalization (strip the per-wrapper ``blocks`` delivery counter,
    JSON round-trip so tuples/dict-order/non-JSON scalars collapse to
    their wire form, sorted dump) shared by :class:`BlockCacheIter`'s
    stored-annotation match and the data service's remote ``find``
    (:mod:`dmlc_tpu.service.frame` re-exports it). Two implementations
    here would let a checkpoint restore locally but not over the
    service, or vice versa."""
    norm = {k: v for k, v in (state or {}).items() if k != "blocks"}
    return json.dumps(json.loads(json.dumps(norm, default=str)),
                      sort_keys=True)


class _WrappedParserMixin:
    """The delegation + checkpoint contract shared by the parse-ahead
    wrappers (:class:`ThreadedParser`, :class:`ParallelTextParser`): both
    decorate a :class:`TextParserBase`, deliver its blocks with resume
    annotations riding along, and restore via byte-exact seek
    (``kind='split'``) or deterministic block replay (``kind='blocks'``).
    Subclasses provide ``_started()`` (background production running?) and
    ``_quiesce()`` (stop it; the next pull re-arms lazily)."""

    base: TextParserBase
    _delivered: int
    _last_annot: Optional[dict]

    def _started(self) -> bool:
        raise NotImplementedError

    def _quiesce(self) -> None:
        raise NotImplementedError

    def set_emit_dense(self, num_col: int, batch_rows: int = 0,
                       dtype: str = "float32",
                       pack_aux: bool = False) -> bool:
        if self._started():
            # production already running: flipping block kinds mid-stream
            # would mix racily, so decline — callers handle RowBlocks too
            return False
        try:
            return self.base.set_emit_dense(num_col, batch_rows, dtype,
                                            pack_aux)
        except TypeError:  # legacy one-arg bases keep working when wrapped
            return self.base.set_emit_dense(num_col)

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        # quiesce production before re-pointing the base
        self._quiesce()
        self.base.reset_partition(part_index, num_parts)
        self._delivered = 0
        self._last_annot = None

    def state_dict(self) -> dict:
        if self._last_annot is not None:
            return dict(self._last_annot, blocks=self._delivered)
        # no annotation (epoch start, or a base without them): count
        # delivered blocks and replay on restore
        return {"kind": "blocks", "blocks": self._delivered}

    def load_state(self, state: dict) -> None:
        self._quiesce()
        if state.get("kind") == "split":
            # seek, don't replay: the base parser restores the split's
            # byte-exact position and production continues from there
            self.base.load_state(state)
            self._delivered = int(state.get("blocks", 0))
            self._last_annot = {k: v for k, v in state.items()
                                if k != "blocks"}
            return
        n = int(state["blocks"])
        self.base.before_first()
        for _ in range(n):
            if self.base.next_block() is None:
                break
        # re-quiesce: the serial replay accrued base parse seconds, which
        # must not contaminate a subclass's post-restore efficiency span
        self._quiesce()
        self._delivered = n
        self._last_annot = None

    @property
    def bytes_read(self) -> int:
        return self.base.bytes_read

    def close(self) -> None:
        self._quiesce()
        self.base.close()


class ThreadedParser(_WrappedParserMixin, Parser):
    """Parse-ahead decorator — analog of ThreadedParser (parser.h:70-126,
    ThreadedIter capacity 8)."""

    def __init__(self, base: TextParserBase, capacity: int = 8):
        self.base = base
        self._capacity = capacity
        self._delivered = 0
        self._last_annot = None  # resume_state of the last delivered block
        # the producer thread starts on first pull, not construction, so
        # callers can still configure the base (e.g. set_emit_dense) without
        # racing blocks already in flight
        self._iter: Optional[ThreadedIter] = None

    def _started(self) -> bool:
        return self._iter is not None

    def _quiesce(self) -> None:
        if self._iter is not None:
            self._iter.destroy()
            self._iter = None

    def _ensure_iter(self) -> ThreadedIter:
        if self._iter is None:
            self._iter = ThreadedIter(
                self._produce, self.base.before_first,
                max_capacity=self._capacity)
        return self._iter

    def _produce(self, cell):
        block = self.base.next_block()
        if block is None:
            return False, None
        return True, block

    def next_block(self) -> Optional[RowBlock]:
        block = self._ensure_iter().next()
        if block is not None:
            self._delivered += 1
            # byte-exact checkpoints ride the blocks (TextParserBase
            # annotates each with the state just after it) — the base
            # parser's live position runs ahead of delivery
            self._last_annot = getattr(block, "resume_state", None)
        return block

    def before_first(self) -> None:
        self._ensure_iter().before_first()
        self._delivered = 0
        self._last_annot = None

    @property
    def stall_seconds(self) -> float:
        return self._iter.stall_seconds if self._iter is not None else 0.0

    def stage_seconds(self) -> Dict[str, float]:
        # the base parser's counters accrue on the producer thread; for a
        # consumer blocked on this wrapper they name what the producer was
        # doing during the wait (read IO vs parse CPU)
        return self.base.stage_seconds()


class ParallelTextParser(_WrappedParserMixin, Parser):
    """Data-parallel chunk-parse fan-out — the N-worker successor of
    :class:`ThreadedParser`'s single producer thread (the reference fans
    every chunk across OS threads, text_parser.h:110-146; tf.data names
    parallel input parsing the canonical fix for host-bound pipelines,
    arXiv:2101.12127).

    Chunks are pulled SERIALLY from the base parser's ``InputSplit`` (split
    reads stay ordered and checkpointable — the pull is the
    :class:`OrderedWorkerPool`'s serialized source stage, and each chunk's
    ``chunk_resume_state`` is captured at pull time, before fan-out), then
    ``parse_chunk`` runs concurrently across ``num_workers`` threads with
    the per-chunk native scanner pinned to one lane (chunk-level
    parallelism replaces intra-chunk threading). Blocks deliver strictly
    in pull order, so the three contracts layered on parsing hold
    unchanged:

    - byte-exact ``resume_state`` annotations ride each block exactly as
      :class:`TextParserBase` attaches them (state captured at pull time +
      in-order delivery == the serial annotation stream);
    - ``stage_seconds()`` stays the {read, parse} attribution feed, now
      aggregated thread-safely across workers, with a
      :meth:`parallel_stats` sideband (``parse_workers`` /
      ``parse_parallelism_efficiency``) so the scaling is measurable;
    - fault tolerance: stream-level retries happen below (ResilientStream
      in the filesystems), errors escaping them rethrow in delivery order
      for DeviceIter's bounded pipeline restart, and an opt-in
      ``restart_policy`` additionally heals retryable chunk-pull errors
      in-pool via the shared fast-forward machinery (restarts bump the
      ``parse_restarts`` / ``parse_giveups`` resilience counters).
    """

    def __init__(self, base: TextParserBase, num_workers: int = 2,
                 max_ahead: Optional[int] = None,
                 restart_policy: Optional["_resilience.RetryPolicy"] = None):
        self.base = base
        self.num_workers = max(1, int(num_workers))
        # a couple of chunks in flight per worker: enough to ride out
        # parse-time variance without ballooning peak memory
        self._ahead = (int(max_ahead) if max_ahead is not None
                       else max(4, 2 * self.num_workers))
        self._restart_policy = restart_policy
        # chunk-level fan-out replaces intra-chunk scanner threads
        base._parse_nthread = 1 if self.num_workers > 1 else 0
        self._pool: Optional[OrderedWorkerPool] = None
        self._delivered = 0
        self._last_annot = None  # resume_state of the last delivered block
        # thread-safe stage aggregation: the serial pull accrues 'read' on
        # whichever worker holds the pull lock, 'parse' accrues on every
        # worker concurrently — all under one lock, into the base's
        # counters so count-replay paths (which parse on the base) share
        # the same books
        self._stage_lock = threading.Lock()
        self._parse_t_first: Optional[float] = None
        self._parse_t_last: Optional[float] = None
        # busy seconds at the current span's start: efficiency is scoped
        # to the span since the last quiesce (epoch reset / repartition /
        # restore), not diluted by inter-epoch idle wall
        self._parse_busy0 = base._parse_seconds

    # ---------------- pool plumbing ----------------

    def _chunk_stream(self):
        """The pool's SERIAL source: the base parser's own pull-and-
        annotate step (one shared implementation — the checkpoint schema
        cannot diverge between engines). Runs under the pool's pull lock,
        so the split sees a single-threaded consumer and the base's
        read/byte counters have one writer."""
        while True:
            chunk, annot = self.base._pull_chunk()
            if chunk is None:
                return
            yield (chunk, annot)

    def _parse_work(self, item):
        """The pool's PARALLEL stage: chunk -> RowBlock (+ annotation)."""
        chunk, annot = item
        sp = _telemetry.span("parse")
        try:
            with sp:
                block = self.base.parse_chunk(chunk)
        finally:
            t0, t1 = sp.t0, sp.t0 + sp.dt
            with self._stage_lock:
                self.base._parse_seconds += t1 - t0
                if self._parse_t_first is None or t0 < self._parse_t_first:
                    self._parse_t_first = t0
                if self._parse_t_last is None or t1 > self._parse_t_last:
                    self._parse_t_last = t1
        if annot is not None and len(block) > 0:
            block.resume_state = annot
        return block

    def _ensure_pool(self) -> OrderedWorkerPool:
        if self._pool is None:
            src = self.base.source
            # the position this pool's stream starts at, for deterministic
            # restart replay: a live state_dict when the source has one,
            # else the chunk-synchronized state a seek-restore left behind
            # (ThreadedInputSplit exposes no state_dict but its
            # chunk_resume_state IS the restored position after
            # load_state). With neither — and the stream not at its
            # start — a before_first() rewind would replay from the WRONG
            # origin, so pool-level restart is disabled and errors
            # propagate to the outer healers (DeviceIter re-arms through
            # the same checkpoint machinery, which stays byte-exact).
            origin = None
            if hasattr(src, "state_dict"):
                try:
                    origin = src.state_dict()
                except (DMLCError, AttributeError):
                    origin = None
            if origin is None:
                origin = getattr(src, "chunk_resume_state", None)
            at_start = self.base._chunks_in == 0 and self._delivered == 0
            policy = (self._restart_policy
                      if (origin is not None and hasattr(src, "load_state"))
                      or at_start else None)
            counters0 = (self.base._bytes, self.base._chunks_in)
            first = [True]

            def factory():
                if not first[0]:
                    # bounded source restart: reposition at this pool's
                    # origin (NOT the epoch start — the pool may have been
                    # armed mid-stream by a seek-restore); the pool then
                    # fast-forwards the already-pulled chunks, which the
                    # counter rewind below makes re-countable
                    self.base._bytes, self.base._chunks_in = counters0
                    if origin is not None and hasattr(src, "load_state"):
                        src.load_state(origin)
                    else:
                        src.before_first()
                first[0] = False
                return self._chunk_stream()

            self._pool = OrderedWorkerPool(
                factory, self._parse_work,
                num_workers=self.num_workers, max_ahead=self._ahead,
                restart_policy=policy, counter_label="parse")
        return self._pool

    def _started(self) -> bool:
        return self._pool is not None

    def _quiesce(self) -> None:
        if self._pool is not None:
            self._pool.destroy()
            self._pool = None
        with self._stage_lock:
            # start a fresh efficiency span: the gap until the next epoch
            # parses is consumer idle, not worker inefficiency
            self._parse_t_first = None
            self._parse_t_last = None
            self._parse_busy0 = self.base._parse_seconds

    # ---------------- Parser contract ----------------
    # (set_emit_dense / reset_partition / state_dict / load_state / close
    # come from _WrappedParserMixin — identical contract to ThreadedParser)

    def next_block(self) -> Optional[RowBlock]:
        pool = self._ensure_pool()
        while True:
            block = pool.next()
            if block is None:
                return None
            if len(block) == 0:
                continue  # empty chunks produce no block (base parity)
            self._delivered += 1
            self._last_annot = getattr(block, "resume_state", None)
            return block

    def resize_parse_workers(self, num_workers: int) -> bool:
        """Live parse-tier resize (the autotuner's ``parse_workers``
        knob): the pool grows/shrinks in place — chunks keep pulling
        serially and delivering in pull order, so the block stream (and
        every checkpoint annotation riding it) is byte-identical to a
        static-width run. Always returns True."""
        n = max(1, int(num_workers))
        self.num_workers = n
        # chunk-level fan-out replaces intra-chunk scanner threads; at
        # width 1 the base may use its own scanner threading again
        self.base._parse_nthread = 1 if n > 1 else 0
        self._ahead = max(4, 2 * n)
        if self._pool is not None:
            self._pool.resize(n)
            self._pool.set_max_ahead(self._ahead)
        return True

    def before_first(self) -> None:
        self._quiesce()
        self.base.before_first()
        self._delivered = 0
        self._last_annot = None

    # ---------------- metrics ----------------

    def stage_seconds(self) -> Dict[str, float]:
        with self._stage_lock:
            return dict(self.base.stage_seconds())

    def parallel_stats(self) -> dict:
        """The scaling sideband: worker count plus measured parallel
        efficiency — parse busy-seconds over the CURRENT span (since the
        last epoch reset / repartition / restore) / (span * workers);
        1.0 = every worker parsing the whole span, None before any parse.
        ``parse_busy_seconds`` stays cumulative, matching
        ``stage_seconds()['parse']``."""
        with self._stage_lock:
            busy = self.base._parse_seconds
            span_busy = busy - self._parse_busy0
            span = ((self._parse_t_last - self._parse_t_first)
                    if self._parse_t_first is not None
                    and self._parse_t_last is not None else 0.0)
        eff = (min(1.0, span_busy / (span * self.num_workers))
               if span > 0 else None)
        return {
            "parse_workers": self.num_workers,
            "parse_busy_seconds": busy,
            "parse_span_seconds": span,
            "parse_parallelism_efficiency": eff,
        }

    @property
    def stall_seconds(self) -> float:
        return self._pool.stall_seconds if self._pool is not None else 0.0


class BlockCacheIter(Parser):
    """Parse-once decorator: cold epochs tee parsed RowBlocks into the
    columnar on-disk block cache (:mod:`dmlc_tpu.io.block_cache`); warm
    epochs serve the blocks back as zero-copy mmap-backed numpy views,
    bypassing the parser — and the source filesystem — entirely.

    One layer above :class:`~dmlc_tpu.io.cached_split.CachedInputSplit`:
    that cache stores raw chunks *before* the parser (warm passes still
    re-pay the full text-parse cost); this one stores the parsed arrays,
    the tf.data ``cache()`` position (arXiv:2101.12127).

    ``base`` is a :class:`Parser` or a zero-arg factory for one — the
    factory is only invoked on a cold pass (or a healing rebuild), so warm
    epochs never construct the parser chain. Selected by the
    ``block_cache=`` knob of :func:`create_parser` /
    :func:`~dmlc_tpu.data.iterators.create_row_block_iter`, the
    ``DMLC_TPU_BLOCK_CACHE`` env directory, or a ``#blockcache=<path>``
    URI suffix (docs/data.md).

    Contracts preserved across cold and warm epochs:

    - **byte-exact checkpoints**: each cold block's ``resume_state``
      annotation is stored in the cache footer and re-attached to the
      warm-served block, so a ``DeviceIter`` checkpoint taken warm equals
      one taken cold at the same row; :meth:`load_state` accepts both the
      warm ``block_cache`` kind and the parser chain's ``split`` kind
      (mapped to a block index by annotation match).
    - **stage attribution**: warm supply cost reports as the
      ``cache_read`` stage (``stage_seconds()``), which
      ``DeviceIter.stats()`` carries next to read/parse; ``cache_state``
      reports ``cold``/``warm``.
    - **fault tolerance**: a failed per-block CRC is a classified cache
      fault (:class:`~dmlc_tpu.utils.check.CacheCorruptionError`): the bad
      cache is dropped, the source re-parsed (skipping already-delivered
      blocks), a fresh cache rewritten, and ``cache_corruptions`` /
      ``cache_rebuilds`` counted in the resilience counters — consumers
      see an unbroken, byte-identical block stream.

    **Shuffle-native warm epochs** (the deterministic epoch planner,
    :mod:`dmlc_tpu.data.epoch`): with ``shuffle_seed`` set, every warm
    epoch serves the cached blocks through an
    :class:`~dmlc_tpu.data.epoch.EpochPlan` — a seeded block permutation
    plus a windowed intra-block row shuffle, both pure functions of
    ``(seed, epoch)``, with ``num_hosts > 1`` restricting this host to its
    disjoint round-robin shard of the one global order. A cold pass stays
    sequential while shadow-writing (the blocks do not exist to permute
    yet — the documented cold-epoch-0 caveat); the plan applies from the
    first warm epoch, and the epoch counter advances on every
    ``before_first``. Plan-mode blocks carry ``kind='epoch_plan'``
    resume annotations — ``(seed, epoch, plan position)`` — so a
    mid-epoch ``state_dict``/``load_state`` restore replays the stream
    byte-identically, including into a fresh pipeline (docs/data.md).
    """

    def __init__(self, base, cache_file: str, signature: Optional[dict] = None,
                 verify: bool = True, shuffle_seed: Optional[int] = None,
                 shuffle_window: int = 0, host_id: int = 0,
                 num_hosts: int = 1):
        from dmlc_tpu.data import epoch as _epoch
        from dmlc_tpu.io import block_cache as _block_cache

        self._bc = _block_cache
        self._ep = _epoch
        self._base_factory = base if callable(base) else (lambda: base)
        self._base: Optional[Parser] = base if not callable(base) else None
        self.cache_file = cache_file
        self._signature = signature
        self._verify = verify
        self._reader = None
        self._writer = None
        self._mode = "cold"
        self._pos = 0        # warm: next plan position / block index
        self._skip = 0       # cold: blocks to shadow-write but not deliver
        self._shadow = True  # shadow-writing allowed for the current pass
        self._delivered = 0
        self._last_annot: Optional[dict] = None
        self._bytes = 0      # warm bytes served from the cache
        self._cache_read_seconds = 0.0
        # ---- epoch-plan state (docstring: shuffle-native warm epochs) ----
        check(num_hosts >= 1 and 0 <= host_id < num_hosts,
              f"BlockCacheIter: host_id {host_id} not in [0, {num_hosts})")
        self._seed = None if shuffle_seed is None else int(shuffle_seed)
        self._window = int(shuffle_window)
        self._host_id = int(host_id)
        self._num_hosts = int(num_hosts)
        self._epoch = 0           # advances on every before_first
        self._plan = None         # per-epoch EpochPlan, built lazily warm
        self._seq_restore = False  # serve this epoch's rest sequentially
        #                           (a legacy/cold-order state was restored)
        self._cold_seen = 0       # cold: blocks seen this pass (pre-filter)
        # plan-ordered reads fan out over a small OrderedWorkerPool: a
        # permuted serve materializes every block (crc + gather/copy —
        # ~2x the sequential path's supply work), so loading block N+1
        # must overlap delivering block N or the shuffle tax lands
        # straight on the pipeline wall. Sequential warm serving stays
        # single-threaded zero-copy.
        self._plan_pool: Optional[OrderedWorkerPool] = None
        # validated by the knob table; live-resizable via
        # resize_plan_read_workers (the autotuner's plan_read knob)
        self.plan_read_workers = _knobs.resolve("plan_read_workers")
        self._cr_lock = threading.Lock()  # _cache_read_seconds writers
        # the plan's own books (plan_stats): blocks served in plan order,
        # rows gathered by permute_block_rows and the seconds that took
        # (on the pool's workers, inside their cache_read spans), and the
        # seconds the serving thread waited on the pool
        self._plan_blocks = 0
        self._plan_rows_permuted = 0
        self._plan_permute_seconds = 0.0
        self._plan_wait_seconds = 0.0
        # per-block uniform-column-pattern verdicts (epoch-invariant —
        # GIL-atomic dict ops, shared across plan-read workers)
        self._uniform_cols: Dict[int, bool] = {}
        self._open_reader()

    @property
    def _plan_armed(self) -> bool:
        """A plan governs warm serving (seeded shuffle and/or sharding)."""
        return self._seed is not None or self._num_hosts > 1

    # ---------------- mode plumbing ----------------

    @property
    def cache_state(self) -> str:
        """``warm`` when blocks come from the cache, else ``cold`` —
        surfaced by ``DeviceIter.stats()['cache_state']``."""
        return "warm" if self._mode == "warm" else "cold"

    @property
    def plan_state(self) -> Optional[dict]:
        """The epoch planner's live identity — ``None`` when no plan is
        armed, else seed/epoch/position/sharding plus ``order``:
        ``'plan'`` when the current pass serves in plan order,
        ``'sequential'`` for cold passes and sequential restores.
        Surfaced by ``DeviceIter.stats()['shuffle_seed'/'epoch']``
        (docs/observability.md)."""
        if not self._plan_armed:
            return None
        sequential = (self._mode != "warm" or self._seq_restore
                      or self._seed is None)
        return {"shuffle_seed": self._seed, "epoch": self._epoch,
                "pos": self._pos, "window": self._window,
                "host_id": self._host_id, "num_hosts": self._num_hosts,
                "order": "sequential" if sequential else "plan"}

    def plan_stats(self) -> Optional[dict]:
        """``DeviceIter.stats()['plan']``: what serving in plan order has
        cost so far, ``None`` when no plan is armed. Cumulative, so a
        window's cost is the difference of two readings: ``blocks`` served
        in plan order and ``rows_permuted`` (rows delivered through
        ``permute_block_rows``); ``permute_seconds`` (the ``plan_permute``
        spans: the row gathers, on the plan pool's workers and inside their
        ``cache_read`` spans) and ``wait_seconds`` (the ``plan_wait`` spans:
        the serving thread blocked on the pool's next block);
        ``uniform_blocks`` (cached blocks whose id arrays pass through
        un-gathered); and the live ``epoch`` and ``order`` of
        :attr:`plan_state`."""
        state = self.plan_state
        if state is None:
            return None
        with self._cr_lock:
            permute_seconds = self._plan_permute_seconds
        return {"blocks": self._plan_blocks,
                "rows_permuted": self._plan_rows_permuted,
                "permute_seconds": permute_seconds,
                "wait_seconds": self._plan_wait_seconds,
                "uniform_blocks": sum(list(self._uniform_cols.values())),
                "epoch": state["epoch"], "order": state["order"]}

    @property
    def base(self) -> Parser:
        if self._base is None:
            self._base = self._base_factory()
        return self._base

    def _open_reader(self) -> bool:
        reader = self._bc.open_block_cache(
            self.cache_file, self._signature, verify=self._verify)
        if reader is None:
            self._mode = "cold"
            return False
        self._reader = reader
        self._mode = "warm"
        self._pos = 0
        self._uniform_cols.clear()  # verdicts are per published cache
        return True

    def _drop_reader(self) -> None:
        reader, self._reader = self._reader, None
        if reader is not None:
            reader.close()

    def _abort_writer(self) -> None:
        writer, self._writer = self._writer, None
        if writer is not None:
            writer.abort()

    def _ensure_writer(self):
        if self._writer is None and self._shadow:
            self._writer = self._bc.BlockCacheWriter(
                self.cache_file, signature=self._signature)
        return self._writer

    # ---------------- block delivery ----------------

    def next_block(self) -> Optional[RowBlock]:
        if self._mode == "warm":
            if self._plan_armed and not self._seq_restore:
                return self._next_warm_plan()
            return self._next_warm()
        return self._next_cold()

    def _next_warm(self) -> Optional[RowBlock]:
        reader = self._reader
        while self._pos < reader.num_blocks:
            i = self._pos
            if self._seq_restore and self._num_hosts > 1 \
                    and i % self._num_hosts != self._host_id:
                # sequential serving of a restored sharded cold stream:
                # the round-robin delivery filter of the cold pass applies
                # by sequential block index (== cold _cold_seen)
                self._pos += 1
                continue
            with _telemetry.span("cache_read", book=self._book_cache_read):
                try:
                    segments = reader.load_segments(i)
                except CacheCorruptionError:
                    segments = None
                if segments is not None:
                    block = RowBlock.from_segments(segments,
                                                   hold=reader.hold)
                    # span export: the block's contiguous cache span rides
                    # along so the service wire encoder reuses the mmap
                    # bytes with zero re-encode — the reader stays open for
                    # the block's lifetime via hold, which pins the same mmap
                    block.encoded = reader.block_encoded(i)
                    annot = reader.resume(i)
                    if annot is not None:
                        block.resume_state = annot
                    self._bytes += reader.block_nbytes(i)
            if segments is None:
                self._heal_corruption()
                return self._next_cold()
            self._pos += 1
            self._delivered += 1
            self._last_annot = annot
            return block
        return None

    def _book_cache_read(self, dt: float) -> None:
        with self._cr_lock:
            self._cache_read_seconds += dt

    def _book_plan_permute(self, dt: float) -> None:
        with self._cr_lock:
            self._plan_permute_seconds += dt

    def _book_plan_wait(self, dt: float) -> None:
        self._plan_wait_seconds += dt   # the serving thread alone

    def _ensure_plan(self):
        if self._plan is None:
            self._plan = self._ep.EpochPlan(
                self._seed, self._epoch, self._reader.num_blocks,
                num_hosts=self._num_hosts, host_id=self._host_id,
                window=self._window)
        return self._plan

    def _plan_read_work(self, pos: int):
        """One plan-ordered block load — the pool's PARALLEL stage. All
        materialization happens HERE, inside the timed ``cache_read``
        span: either the row gather copies or ``copy=`` does, so the
        permuted pattern's page faults land under cache_read and never
        leak into convert (docs/data.md)."""
        plan = self._plan
        reader = self._reader
        bidx = plan.block_at(pos)
        with _telemetry.span("cache_read", book=self._book_cache_read):
            rows = reader.block_rows(bidx)
            rowperm = plan.row_order(bidx, rows)
            segments = reader.load_segments(
                bidx, copy=rowperm is None and plan.permuted)
            # a row-gathered block may pass permutation-invariant id
            # arrays through as views — keep the mmap pinned then
            hold = (None if rowperm is None and plan.permuted
                    else reader.hold)
            block = RowBlock.from_segments(segments, hold=hold)
            if rowperm is not None:
                uniform = self._uniform_cols.get(bidx)
                if uniform is None:
                    # one read-only pass, memoized: blocks recur every
                    # epoch, so only the first epoch pays the scan
                    uniform = self._ep.uniform_column_pattern(block)
                    self._uniform_cols[bidx] = uniform
                with _telemetry.span("plan_permute",
                                     book=self._book_plan_permute,
                                     epoch=plan.epoch, block=bidx,
                                     rows=rows):
                    block = self._ep.permute_block_rows(
                        block, rowperm, uniform_columns=uniform)
        return block, reader.block_nbytes(bidx), 0 if rowperm is None else rows

    def _quiesce_plan_pool(self) -> None:
        pool, self._plan_pool = self._plan_pool, None
        if pool is not None:
            pool.destroy()

    def _ensure_plan_pool(self) -> OrderedWorkerPool:
        if self._plan_pool is None:
            plan = self._ensure_plan()
            start = self._pos
            self._plan_pool = OrderedWorkerPool(
                lambda: iter(range(start, len(plan))),
                self._plan_read_work,
                num_workers=self.plan_read_workers,
                max_ahead=2 * self.plan_read_workers,
                counter_label="cache_read")
        return self._plan_pool

    def _next_warm_plan(self) -> Optional[RowBlock]:
        plan = self._ensure_plan()
        healed = 0
        while self._pos < len(plan):
            pool = self._ensure_plan_pool()
            try:
                # the serving thread's wait for the pool's next block in
                # plan order: what of the plan's work the pool's read-ahead
                # did not hide
                with _telemetry.span("plan_wait", book=self._book_plan_wait,
                                     epoch=plan.epoch, pos=self._pos):
                    item = pool.next()
            except CacheCorruptionError:
                check(healed == 0,
                      f"block cache {self.cache_file}: still corrupt "
                      "after a full rebuild")
                healed += 1
                self._quiesce_plan_pool()
                self._rebuild_cache(corruption=True)
                # the rebuild is deterministic: same blocks, same plan —
                # re-arm the pool at the failed position and retry
                continue
            if item is None:
                return None
            block, nbytes, permuted = item
            annot = plan.state(self._pos + 1)
            block.resume_state = annot
            self._bytes += nbytes
            self._plan_blocks += 1
            self._plan_rows_permuted += permuted
            self._pos += 1
            self._delivered += 1
            self._last_annot = annot
            return block
        return None

    def _rebuild_cache(self, corruption: bool = False) -> None:
        """Plan-mode cache (re)build: drain the source into a fresh cache
        in one silent pass, publish, reopen. Parsing is deterministic, so
        the rebuilt blocks are byte-identical to the lost ones and the
        plan stream continues unbroken at the same position."""
        if corruption:
            _resilience.record_event("cache_corruptions")
            _resilience.record_event("cache_rebuilds")
        self._drop_reader()  # releases the reader's eviction pin first
        self._bc._artifact_store(self.cache_file).discard(self.cache_file)
        self._abort_writer()
        base = self.base
        base.before_first()
        writer = self._bc.BlockCacheWriter(self.cache_file,
                                           signature=self._signature)
        try:
            while True:
                block = base.next_block()
                if block is None:
                    break
                check(hasattr(block, "to_segments"),
                      "epoch plan requires columnar RowBlocks: the base "
                      "parser emits an uncacheable block kind")
                writer.add_block(
                    block.to_segments(), rows=len(block),
                    num_col=block.num_col,
                    resume=getattr(block, "resume_state", None))
            writer.finish()
        except BaseException:
            writer.abort()
            raise
        pos = self._pos  # _open_reader rewinds; the plan position survives
        check(self._open_reader(),
              f"block cache {self.cache_file}: rebuild did not publish a "
              "readable cache")
        self._pos = pos

    def _heal_corruption(self) -> None:
        """Warm block ``self._pos`` failed its integrity check: drop the
        bad cache, re-parse the source (skipping the blocks already
        delivered this epoch — chunk grouping is deterministic, so block k
        cold is block k warm), rewrite the full cache, and resume delivery
        exactly at the broken block."""
        _resilience.record_event("cache_corruptions")
        _resilience.record_event("cache_rebuilds")
        self._drop_reader()  # releases the reader's eviction pin first
        self._bc._artifact_store(self.cache_file).discard(self.cache_file)
        self._abort_writer()
        self._mode = "cold"
        self._shadow = True
        self._skip = self._pos
        self._pos = 0
        self._cold_seen = 0  # re-counts through the skipped prefix
        self.base.before_first()

    def _next_cold(self) -> Optional[RowBlock]:
        while True:
            block = self.base.next_block()
            if block is None:
                writer, self._writer = self._writer, None
                if writer is not None:
                    writer.finish()  # fsync + atomic publish
                return None
            if not hasattr(block, "to_segments"):
                # non-RowBlock emits (a base with dense/COO mode already
                # armed): pass through uncached — the cache stores the
                # columnar CSR layout only. An epoch plan cannot order
                # blocks that never reach the cache, so the combination
                # is rejected rather than silently serving unshuffled.
                check(not self._plan_armed,
                      "epoch plan requires columnar RowBlocks: the base "
                      "parser emits an uncacheable block kind")
                self._abort_writer()
                self._shadow = False
            annot = getattr(block, "resume_state", None)
            writer = self._ensure_writer()
            if writer is not None:
                writer.add_block(block.to_segments(), rows=len(block),
                                 num_col=block.num_col, resume=annot)
            seen = self._cold_seen
            self._cold_seen += 1
            if self._skip > 0:
                self._skip -= 1
                continue
            if self._num_hosts > 1 and seen % self._num_hosts != self._host_id:
                # pod-sharded cold pass: every block is shadow-written,
                # but delivery is round-robin by sequential block index —
                # the hosts' cold streams stay disjoint and union to the
                # corpus even before the first planned warm epoch
                continue
            if self._num_hosts > 1 and annot is not None:
                # the checkpoint must carry the shard cursor: a plain
                # split state restored later could not reconstruct how
                # many blocks the filter had consumed (same shape as
                # state_dict's cold wrapping — one builder, no drift)
                annot = dict(self._plan_annot(0), cold=annot,
                             seen=seen + 1)
                block.resume_state = annot
            self._delivered += 1
            self._last_annot = annot
            return block

    def before_first(self) -> None:
        # an interrupted cold pass cannot publish: drop the partial tmp
        self._abort_writer()
        self._quiesce_plan_pool()
        if self._delivered or self._pos or self._cold_seen:
            # a pass actually ran: the rewind starts the NEXT epoch (the
            # plan's permutation is keyed by this counter, so each warm
            # epoch draws a fresh order; idempotent for back-to-back
            # rewinds with nothing delivered in between)
            self._epoch += 1
        self._plan = None
        self._seq_restore = False
        self._cold_seen = 0
        self._skip = 0
        self._delivered = 0
        self._last_annot = None
        if self._mode == "warm":
            self._pos = 0
            return
        if self._open_reader():
            return  # the completed cold pass published: serve warm now
        self._shadow = True
        self.base.before_first()

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        raise DMLCError(
            "BlockCacheIter does not support reset_partition; the cache is "
            "bound to one partition (use the partition-qualified "
            ".splitN.partK cache per part)")

    # -------- checkpoint / resume --------

    def _plan_annot(self, pos: int) -> dict:
        """``(seed, epoch, plan position)`` — the epoch-plan resume
        annotation (docs/data.md): everything a fresh pipeline needs to
        replay the stream byte-identically from ``pos``. Delegates to the
        ONE shape builder (:func:`dmlc_tpu.data.epoch.plan_state_dict`)."""
        return self._ep.plan_state_dict(self._seed, self._window,
                                        self._epoch, pos, self._host_id,
                                        self._num_hosts)

    def state_dict(self) -> dict:
        if self._mode == "warm":
            if self._plan_armed and not self._seq_restore:
                return self._plan_annot(self._pos)
            return {"kind": "block_cache", "block": self._pos}
        if hasattr(self.base, "state_dict"):
            base_state = self.base.state_dict()
        else:
            base_state = {"kind": "blocks", "blocks": self._delivered}
        if self._num_hosts > 1:
            # the sharded cold pass filters delivery by sequential block
            # index: the checkpoint must carry that cursor too
            return dict(self._plan_annot(0), cold=base_state,
                        seen=self._cold_seen)
        return base_state

    _annot_key = staticmethod(annot_key)

    def _find_block(self, state: dict) -> Optional[int]:
        """Block index to resume at for a parser-chain annotation: the
        stored annotations mark the position just AFTER each block, so a
        match at block i resumes at i + 1."""
        if not state.get("chunks") and not state.get("blocks"):
            return 0  # epoch-start state
        key = self._annot_key(state)
        reader = self._reader
        for i in range(reader.num_blocks):
            annot = reader.resume(i)
            if annot is not None and self._annot_key(annot) == key:
                return i + 1
        return None

    def load_state(self, state: dict) -> None:
        kind = state.get("kind")
        if kind == "epoch_plan":
            self._load_plan_state(state)
            return
        if self._plan_armed:
            self._load_legacy_into_plan(state)
            return
        if kind == "block_cache":
            n = int(state["block"])
            self._abort_writer()
            if self._mode == "warm" or self._open_reader():
                self._pos = n
                self._delivered = n
                self._last_annot = self._reader.resume(n - 1) if n else None
                return
            # cache gone: rebuild from source, shadow-writing the skipped
            # prefix so the rebuilt cache is still complete
            self._shadow = True
            self._skip = n
            self._delivered = n
            self._last_annot = None
            self.base.before_first()
            return
        if self._mode == "warm":
            if kind == "blocks":
                # a delivered-block count maps 1:1 onto cache block indices
                # (warm serves the exact cold block sequence)
                n = int(state["blocks"])
                self._pos = n
                self._delivered = n
                self._last_annot = (self._reader.resume(n - 1)
                                    if n else None)
                return
            idx = self._find_block(state)
            if idx is not None:
                self._pos = idx
                self._delivered = idx
                self._last_annot = (self._reader.resume(idx - 1)
                                    if idx else None)
                return
            # annotation unknown to this cache (foreign/stale state):
            # fall back to the parser chain
            self._drop_reader()
            self._mode = "cold"
        # cold mid-stream seek: this pass can no longer produce a complete
        # cache — disable shadow-writing until the next epoch start
        self._abort_writer()
        self._shadow = False
        self._skip = 0
        self.base.load_state(state)
        self._delivered = int(state.get("blocks", state.get("chunks", 0))
                              or 0)
        self._last_annot = None

    def _load_plan_state(self, state: dict) -> None:
        """Restore a ``kind='epoch_plan'`` state. The state's plan
        identity (seed/window/epoch/sharding) is adopted WHOLESALE — the
        state IS the stream position, and replay must be byte-identical
        even into a pipeline constructed with different knobs."""
        check(state.get("unit") in (None, "block"),
              "epoch_plan state over snapshot BATCHES (unit='batch') "
              "cannot restore into the block cache's block stream — "
              "restore it into a snapshot-armed DeviceIter "
              "(docs/data.md snapshot section)")
        self._abort_writer()
        self._quiesce_plan_pool()
        seed = state.get("seed")
        self._seed = None if seed is None else int(seed)
        self._window = int(state.get("window", 0))
        self._host_id = int(state.get("host_id", 0))
        self._num_hosts = int(state.get("num_hosts", 1))
        self._epoch = int(state.get("epoch", 0))
        self._plan = None
        self._skip = 0
        if "cold" in state:
            # a checkpoint from a sharded cold pass: the base annotation
            # rides under 'cold', the shard cursor under 'seen'
            cold = state["cold"]
            seen = int(state.get("seen", 0))
            if self._mode == "warm" or self._open_reader():
                idx = self._find_block(cold) if cold is not None else None
                if idx is not None:
                    # the cache (now published) holds the cold stream:
                    # serve its remainder sequentially with the shard
                    # filter — exactly what the cold pass would deliver
                    self._seq_restore = True
                    self._pos = idx
                    self._cold_seen = idx
                    self._delivered = max(
                        0, -(-(idx - self._host_id) // self._num_hosts))
                    self._last_annot = dict(state)
                    return
                self._drop_reader()
                self._mode = "cold"
            # resume the sharded cold pass itself (mid-stream seek: this
            # pass can no longer publish a complete cache)
            self._seq_restore = False
            self._shadow = False
            self._mode = "cold"
            if cold is not None and hasattr(self.base, "load_state"):
                self.base.load_state(cold)
            self._cold_seen = seen
            self._delivered = max(
                0, -(-(seen - self._host_id) // self._num_hosts))
            self._last_annot = dict(state)
            return
        # plan-position state: (seed, epoch, pos) into the warm cache
        target = int(state["pos"])
        self._seq_restore = False
        if self._mode != "warm" and not self._open_reader():
            # cache gone: one silent full rebuild pass, then serve from
            # the plan position (parsing is deterministic — the rebuilt
            # blocks are the ones the state was taken over)
            self._rebuild_cache()
        self._pos = target
        self._delivered = target
        self._cold_seen = 0
        self._last_annot = dict(state) if target else None

    def _load_legacy_into_plan(self, state: dict) -> None:
        """A sequential-order state (legacy warm ``block_cache`` position,
        delivered-``blocks`` count, or a parser-chain ``split``/``chunks``
        annotation from a cold pass) restored into a plan-armed pipeline:
        the recorded position only exists in the SEQUENTIAL stream, so the
        remainder of this epoch serves sequentially — byte-identical to
        the stream the state came from — and the plan resumes at the next
        ``before_first`` (docs/data.md)."""
        kind = state.get("kind")
        self._abort_writer()
        self._quiesce_plan_pool()
        self._skip = 0
        if self._mode != "warm" and not self._open_reader():
            if kind in ("block_cache", "blocks"):
                # cache-relative positions only exist in the cache
                self._rebuild_cache()
            else:
                self._legacy_cold_seek(state)
                return
        if kind == "block_cache":
            idx: Optional[int] = int(state["block"])
        elif kind == "blocks":
            # delivered == sequential index in the unsharded legacy runs
            # these states come from
            idx = int(state["blocks"])
        else:
            idx = self._find_block(state)
        if idx is None:
            # annotation unknown to this cache (foreign/stale state):
            # fall back to the parser chain, mid-stream
            self._drop_reader()
            self._mode = "cold"
            self._legacy_cold_seek(state)
            return
        self._seq_restore = True
        self._pos = idx
        self._cold_seen = idx
        self._delivered = idx
        self._last_annot = (self._reader.resume(idx - 1) if idx else None)

    def _legacy_cold_seek(self, state: dict) -> None:
        """Mid-stream seek of the parser chain itself (the chunk count
        approximates the shard cursor — exact for the non-empty-chunk
        corpora the parsers emit 1:1)."""
        self._seq_restore = False
        self._shadow = False
        self.base.load_state(state)
        n = int(state.get("blocks", state.get("chunks", 0)) or 0)
        self._cold_seen = n
        self._delivered = n
        self._last_annot = None

    # ---------------- metrics ----------------

    def stage_seconds(self) -> Dict[str, float]:
        out = {"read": 0.0, "parse": 0.0}
        if self._base is not None:
            fn = getattr(self._base, "stage_seconds", None)
            if callable(fn):
                out.update(fn())
        out["cache_read"] = self._cache_read_seconds
        return out

    def parallel_stats(self) -> Optional[dict]:
        if self._mode != "warm" and self._base is not None:
            fn = getattr(self._base, "parallel_stats", None)
            if callable(fn):
                return fn()
        return None

    def resize_parse_workers(self, num_workers: int) -> bool:
        """Autotune passthrough: the parse tier only exists on cold
        passes — warm epochs bypass the parser entirely, so the knob
        reports unavailable (False) until a cold pass arms the base."""
        if self._base is None:
            return False
        fn = getattr(self._base, "resize_parse_workers", None)
        return bool(fn(num_workers)) if callable(fn) else False

    def resize_plan_read_workers(self, num_workers: int) -> bool:
        """Live plan-read-pool resize (the autotuner's
        ``plan_read_workers`` knob): applies to the running pool when a
        plan-ordered warm epoch is being served, and to every pool built
        after. Delivery stays in plan order either way."""
        n = max(1, int(num_workers))
        self.plan_read_workers = n
        if self._plan_pool is not None:
            self._plan_pool.resize(n)
            self._plan_pool.set_max_ahead(2 * n)
        return True

    @property
    def bytes_read(self) -> int:
        cold = self._base.bytes_read if self._base is not None else 0
        return cold + self._bytes

    def close(self) -> None:
        self._abort_writer()
        self._quiesce_plan_pool()
        self._drop_reader()
        if self._base is not None:
            self._base.close()


# ---------------- factory & registry (src/data.cc) ----------------

def _resolve_parse_workers(parse_workers: Optional[int]) -> int:
    """None -> DMLC_TPU_PARSE_WORKERS env (validated loudly by the knob
    table, :mod:`dmlc_tpu.utils.knobs`), else min(4, cpu count); 1 keeps
    today's single-producer ThreadedParser path."""
    return _knobs.resolve("parse_workers", parse_workers)


def _parallel_chunk_source(uri: str, part_index: int, num_parts: int,
                           **split_kw) -> InputSplit:
    """Chunk source for the parse fan-out. Plain SINGLE-FILE local text
    corpora get the zero-copy mmap reader (the serial pull must stay far
    above the pool's aggregate parse rate, and the stream engine's copying
    pull costs a core per ~500 MB/s; single-file windows make its chunk
    grouping byte-identical to the stream engine's, so per-chunk-sensitive
    semantics — indexing_mode=-1 auto-detection, per-chunk validation —
    cannot diverge between parse_workers settings). Everything else —
    multi-file corpora, remote URIs, chunk caches, shuffle decorators —
    keeps the standard split stack, whose chunks ARE the workers=1
    engine's."""
    plain = ("#" not in uri
             and not any(split_kw.get(k) for k in
                         ("shuffle", "num_shuffle_parts", "index_uri",
                          "recurse_directories")))
    if plain and uri.split("?", 1)[0] not in ("stdin",):
        try:
            split = create_mmap_text_split(
                uri, part_index, num_parts,
                chunk_bytes=split_kw.get("chunk_bytes", DEFAULT_CHUNK_BYTES))
            if len(split.files) == 1:
                return split
            split.close()  # multi-file: joins change chunk grouping
        except (DMLCError, OSError, ValueError):
            pass  # not local / not mappable: the stream stack handles it
    return create_input_split(
        uri, part_index, num_parts, "text", threaded=True, **split_kw)


def _make_text_parser(cls, threaded_default: bool):
    def factory(uri, args, part_index, num_parts, index_dtype, threaded,
                parse_workers=None, **split_kw):
        workers = _resolve_parse_workers(parse_workers)
        if threaded and threaded_default and workers > 1:
            source = _parallel_chunk_source(
                uri, part_index, num_parts, **split_kw)
            base = cls(source, args, index_dtype=index_dtype)
            return ParallelTextParser(base, num_workers=workers)
        source = create_input_split(
            uri, part_index, num_parts, "text",
            threaded=threaded, **split_kw,
        )
        base = cls(source, args, index_dtype=index_dtype)
        if threaded and threaded_default:
            return ThreadedParser(base)
        return base
    return factory


# CSV is registered unthreaded in the reference (data.cc:51-60 wraps libsvm
# and libfm only); we thread it anyway — the vectorized chunk parse benefits
# identically, and tests cover both paths.
PARSER_REGISTRY.register("libsvm", "libsvm text format")(
    _make_text_parser(LibSVMParser, True))
PARSER_REGISTRY.register("libfm", "libfm field:index:value format")(
    _make_text_parser(LibFMParser, True))
PARSER_REGISTRY.register("csv", "dense csv format")(
    _make_text_parser(CSVParser, True))


def _resolve_block_cache(spec: URISpec, part_index: int, num_parts: int,
                         explicit: Optional[str]) -> Optional[str]:
    """Block-cache path resolution: explicit ``block_cache=`` knob, then
    the ``#blockcache=<path>`` URI suffix, then the ``DMLC_TPU_BLOCK_CACHE``
    env **directory** (cache file auto-named from a hash of the URI+args).
    Multi-part loads get the same ``.splitN.partK`` qualification as
    ``#cachefile`` so parts never collide."""
    path = explicit if explicit is not None else spec.block_cache
    if path is None:
        env_dir = os.environ.get("DMLC_TPU_BLOCK_CACHE", "").strip()
        if env_dir:
            key_src = spec.uri + "?" + "&".join(
                f"{k}={v}" for k, v in sorted(spec.args.items()))
            key = hashlib.sha1(key_src.encode()).hexdigest()[:16]
            path = os.path.join(env_dir, f"{key}.blockcache")
    if path is None:
        return None
    if num_parts != 1:
        path = f"{path}.split{num_parts}.part{part_index}"
    return path


# intra-block row-shuffle window the legacy ``shuffle=True`` decorator arg
# maps onto (it asked for record-level shuffling; the plan's windowed row
# shuffle is its successor — docs/data.md deprecation note)
LEGACY_SHUFFLE_WINDOW = 4096


def _signature_args(spec: URISpec) -> dict:
    """URI args as they enter a cache/snapshot signature. The ``engine``
    selector is stripped: every engine emits byte-identical blocks AND
    identical chunk grouping (the A/B parity suites of
    ``tests/test_native_reader.py`` and ``tests/test_parallel_parse.py``),
    so a cache written under one engine serves them all — baking the
    knob into the key would force a full cold re-parse on every engine
    switch."""
    args = dict(spec.args)
    args.pop("engine", None)
    return args


def create_parser(
    uri: str,
    part_index: int = 0,
    num_parts: int = 1,
    type_: str = "auto",
    index_dtype=np.uint64,
    threaded: bool = True,
    parse_workers: Optional[int] = None,
    block_cache: Optional[str] = None,
    snapshot: Optional[str] = None,
    service: Optional[str] = None,
    service_job: Optional[str] = None,
    shuffle_seed: Optional[int] = None,
    shuffle_window: int = 0,
    pod_sharding=False,
    engine: Optional[str] = None,
    **split_kw,
) -> Parser:
    """Parser factory — analog of dmlc::Parser::Create (src/data.cc:62-85).

    ``type_='auto'`` resolves from the URI's ``format=`` arg, defaulting to
    libsvm (data.cc:70-76). URI args (``?k=v``) flow into the parser params.

    ``engine`` pins the text-parse engine (explicit knob > ``?engine=``
    URI arg > ``DMLC_TPU_PARSE_ENGINE`` env > ``auto``): ``native`` the
    streaming C++ reader, ``python`` the vectorized numpy engine,
    ``auto`` the routing of docs/data.md's engine-selection table.
    Every engine emits byte-identical blocks, so the knob stays OUTSIDE
    the block-cache signature — one cache serves them all.

    ``parse_workers`` sizes the Python engine's data-parallel chunk-parse
    fan-out (:class:`ParallelTextParser`): 1 keeps the single-producer
    :class:`ThreadedParser`, None auto-sizes to ``DMLC_TPU_PARSE_WORKERS``
    or ``min(4, cpu count)``. The fully-native reader keeps its own C++
    threading and ignores the knob (docs/data.md).

    ``block_cache`` names a parse-once columnar block cache
    (:class:`BlockCacheIter`): the first epoch shadow-writes parsed
    blocks, warm epochs serve them back as zero-copy mmap views without
    parsing. Also selectable via a ``#blockcache=<path>`` URI suffix or
    the ``DMLC_TPU_BLOCK_CACHE`` env directory; the cache self-invalidates
    when the source files, partition, or parser config drift
    (docs/data.md block cache section).

    ``snapshot`` (or a ``#snapshot=<path>`` URI suffix) names a
    device-native snapshot store (:mod:`dmlc_tpu.io.snapshot`): the path
    and its staleness signature are stamped onto the returned parser as
    ``snapshot_path`` / ``snapshot_signature``, and a ``DeviceIter``
    built over it arms the store automatically — cold epochs shadow-write
    the post-convert device-layout batches, warm epochs stream them into
    HBM with zero parse AND zero convert work (docs/data.md snapshot
    section: block cache = parser output, snapshot = device layout).
    Composable with ``block_cache`` (the cold snapshot pass then reads
    the warm cache); NOT with ``shuffle_seed`` — the snapshot freezes one
    epoch's order, so shuffled snapshot epochs come from ``DeviceIter``'s
    own ``snapshot_shuffle_seed`` (a permutation over stored batches).

    ``service`` (or a ``#service=<host:port>`` URI suffix) names a
    RowBlock data-service dispatcher: parsing then happens on a remote
    parse-worker fleet and the returned parser is the drop-in
    :class:`~dmlc_tpu.service.client.ServiceParser` streaming parsed
    blocks over TCP — the dataset spec (URI, partitioning, parser
    config) is the DISPATCHER's; every other argument here is ignored
    (docs/service.md).

    ``shuffle_seed`` arms the deterministic epoch planner
    (:mod:`dmlc_tpu.data.epoch`) on the block cache: warm epochs serve
    the cached blocks through a seeded per-epoch block permutation plus
    a windowed intra-block row shuffle (``shuffle_window`` rows per
    window; 0 = block-level shuffle only), with ``(seed, epoch, plan
    position)`` recorded in the resume annotations for byte-identical
    mid-epoch restores. ``pod_sharding`` additionally restricts this
    host to its disjoint shard of the one global order — ``True``
    resolves ``(host_id, num_hosts)`` from the tracker env contract /
    ``jax.distributed`` (:func:`dmlc_tpu.parallel.distributed.
    pod_identity`), or pass an explicit ``(host_id, num_hosts)`` tuple.
    Both require ``block_cache``; the legacy split-layer ``shuffle`` /
    ``num_shuffle_parts`` decorator args combined with ``block_cache``
    are DEPRECATED and map onto these knobs for one release
    (docs/data.md shuffle-native cache section).
    """
    spec = URISpec(uri, part_index, num_parts)
    if service is None:
        service = spec.service
    if service is not None:
        # the DISPATCHER owns partitioning: silently handing every rank
        # the full dataset would duplicate training data — reject loudly
        check(part_index == 0 and num_parts == 1,
              "create_parser(service=...): client-side part_index/"
              "num_parts are not supported — the dispatcher owns the "
              "dataset's partitioning (docs/service.md)")
        # same for the epoch plan: silently dropping the knobs would hand
        # the user unshuffled epochs they asked to shuffle
        check(shuffle_seed is None and shuffle_window == 0
              and not pod_sharding,
              "create_parser(service=...): client-side shuffle_seed/"
              "shuffle_window/pod_sharding are not supported — the "
              "dispatcher owns the dataset's plan (Dispatcher(plan=...), "
              "docs/service.md plan distribution)")
        check(snapshot is None,
              "create_parser(service=...): client-side snapshot= is not "
              "supported — the dispatcher decides whether the fleet "
              "ships device-layout snapshot frames "
              "(Dispatcher(snapshot=...), docs/service.md)")
        from dmlc_tpu.service.client import ServiceParser
        from dmlc_tpu.service.dispatcher import DEFAULT_JOB

        # the registered job this client binds to (multi-tenant service,
        # docs/service.md): explicit knob > `?job=` URI arg > default
        job = (service_job if service_job is not None
               else spec.args.get("job", DEFAULT_JOB))
        return ServiceParser(service, job=job)
    if type_ == "auto":
        type_ = spec.args.get("format", "libsvm")
    check(type_ == "csv" or "hash_bins" not in spec.args,
          f"hash_bins is an argument of format=csv (docs/data.md, 'Hashed "
          f"cells'); format={type_} has no cells to hash")
    bc_path = _resolve_block_cache(spec, part_index, num_parts, block_cache)
    snap_path = snapshot if snapshot is not None else spec.snapshot
    if snap_path is not None and num_parts != 1:
        snap_path = f"{snap_path}.split{num_parts}.part{part_index}"
    # the snapshot stores one epoch's batch order: a source-side shuffle
    # would change the order under it every epoch. Reject here — shuffled
    # snapshot epochs come from DeviceIter's snapshot_shuffle_seed, a
    # permutation over the STORED batches (docs/data.md).
    check(snap_path is None or shuffle_seed is None,
          "snapshot= cannot combine with shuffle_seed= (the snapshot "
          "freezes one epoch's batch order) — use DeviceIter's "
          "snapshot_shuffle_seed for shuffled snapshot epochs "
          "(docs/data.md)")
    if spec.block_cache is not None or spec.snapshot is not None:
        # the fragment is cache/snapshot routing sugar, not a chunk
        # cachefile: strip it so downstream engines see a plain URI
        uri = uri.split("#", 1)[0]
    def _stamp_snapshot(parser: Parser) -> Parser:
        """Arm the device-native snapshot store on the built parser:
        DeviceIter reads these attributes at construction (docs/data.md
        snapshot section). The signature is the block cache's source/
        config key — any source or parser-config drift invalidates the
        stored snapshot the same way it invalidates the cache."""
        if snap_path is not None:
            from dmlc_tpu.io import block_cache as _bc

            parser.snapshot_path = snap_path
            parser.snapshot_signature = _bc.source_signature(
                spec.uri, part_index, num_parts,
                format=type_, args=_signature_args(spec),
                index_dtype=np.dtype(index_dtype).str,
                chunk_bytes=int(split_kw.get("chunk_bytes",
                                             DEFAULT_CHUNK_BYTES)),
                split={k: v for k, v in sorted(split_kw.items())
                       if k != "chunk_bytes"})
        return parser

    if bc_path is None:
        check(shuffle_seed is None and shuffle_window == 0
              and not pod_sharding,
              "shuffle_seed/shuffle_window/pod_sharding require a "
              "block_cache: the epoch plan orders cached blocks "
              "(docs/data.md)")
        return _stamp_snapshot(_create_parser_uncached(
            uri, spec, part_index, num_parts, type_, index_dtype, threaded,
            parse_workers, engine=engine, **split_kw))
    if split_kw.get("shuffle") or split_kw.get("num_shuffle_parts"):
        # the old hard rejection ("the cache would freeze the first
        # epoch's order into every warm epoch") is gone: the epoch plan
        # IS shuffled warm serving. Legacy decorator args map onto the
        # plan knobs for one release, then the combination errors
        # (docs/data.md deprecation note).
        warnings.warn(
            "block_cache + shuffle decorator args (shuffle/"
            "num_shuffle_parts) now map onto the shuffle-native epoch "
            "plan; pass shuffle_seed/shuffle_window directly — this "
            "mapping will be removed in the next release (docs/data.md)",
            DeprecationWarning, stacklevel=2)
        if shuffle_seed is None:
            shuffle_seed = int(split_kw.get("seed", 0) or 0)
        if split_kw.pop("shuffle", None) and shuffle_window == 0:
            shuffle_window = LEGACY_SHUFFLE_WINDOW
        split_kw.pop("num_shuffle_parts", None)
        # the seed now lives in the plan: leaving it in split_kw would
        # bake it into the cache signature and force a full cold
        # re-parse on every seed change (plan knobs are signature-free)
        split_kw.pop("seed", None)
        get_logger().warning(
            "create_parser: mapping legacy shuffle decorator args onto "
            "the epoch plan (effective shuffle_seed=%s, shuffle_window=%s)",
            shuffle_seed, shuffle_window)
    check(shuffle_window == 0 or shuffle_seed is not None,
          "shuffle_window requires shuffle_seed: the row-shuffle rng is "
          "keyed by the seed, so a window alone would silently serve "
          "sequential epochs (docs/data.md)")
    host_id, num_hosts = 0, 1
    if pod_sharding:
        if isinstance(pod_sharding, (tuple, list)):
            host_id, num_hosts = int(pod_sharding[0]), int(pod_sharding[1])
        else:
            from dmlc_tpu.parallel.distributed import pod_identity

            host_id, num_hosts = pod_identity()
        check(num_parts == 1,
              "pod_sharding shards the one logical epoch at the cache "
              "block level; combining it with num_parts partitioning "
              "would double-shard — use one or the other (docs/data.md)")
    from dmlc_tpu.io import block_cache as _block_cache

    # engine/worker knobs (threaded, parse_workers, engine=) are
    # deliberately OUTSIDE the signature: every engine emits byte-identical
    # blocks AND identical chunk grouping (tests/test_native_reader.py,
    # tests/test_parallel_parse.py: the A/B parity suites), so a
    # cache written by one serves them all. Split-layer config that CHANGES
    # the grouping or content — chunk_bytes above all: the heal and
    # count-based resume paths skip re-parsed blocks by index, which is
    # only sound when re-parse grouping matches the cached grouping — is
    # INSIDE it, so a drifted config invalidates instead of mis-serving.
    signature = _block_cache.source_signature(
        spec.uri, part_index, num_parts,
        format=type_, args=_signature_args(spec),
        index_dtype=np.dtype(index_dtype).str,
        chunk_bytes=int(split_kw.get("chunk_bytes", DEFAULT_CHUNK_BYTES)),
        split={k: v for k, v in sorted(split_kw.items())
               if k != "chunk_bytes"})

    def build() -> Parser:
        return _create_parser_uncached(
            uri, spec, part_index, num_parts, type_, index_dtype, threaded,
            parse_workers, engine=engine, **split_kw)

    # plan knobs stay OUTSIDE the signature: the plan orders blocks at
    # read time, so one cache serves every (seed, window, sharding)
    cached = BlockCacheIter(
        build, bc_path, signature=signature,
        shuffle_seed=shuffle_seed,
        shuffle_window=shuffle_window,
        host_id=host_id, num_hosts=num_hosts)
    # the parse width the lazily-built base WILL use: the autotuner seeds
    # its parse_workers knob from this before any cold pass builds the
    # parser (seeding from the table default would let a later "grow"
    # silently shrink an explicitly wider pool)
    cached.parse_workers_hint = _resolve_parse_workers(parse_workers)
    return _stamp_snapshot(cached)


def _create_parser_uncached(
    uri: str,
    spec: URISpec,
    part_index: int,
    num_parts: int,
    type_: str,
    index_dtype,
    threaded: bool,
    parse_workers: Optional[int],
    engine: Optional[str] = None,
    **split_kw,
) -> Parser:
    # engine selection (docs/data.md engine-selection table): explicit
    # create_parser(engine=) knob > ?engine= URI arg > the validated
    # DMLC_TPU_PARSE_ENGINE env accessor > auto
    engine = _knobs.parse_engine(
        engine if engine is not None else spec.args.get("engine"))
    split_uri = spec.uri
    if "#" in uri:
        # a `#cachefile` suffix activates the chunk cache at the split
        # layer (create_input_split re-derives the partition-qualified
        # name); every engine sources through the same split stack
        split_uri = f"{spec.uri}#{uri.split('#', 1)[1]}"
    # hot path: fully-native streaming pipeline (read+chunk+parse in C++)
    # for plain local text corpora; decorated/remote/unsupported URIs take
    # the Python engine below (identical chunk semantics, tested A/B)
    if (engine in ("auto", "native")
            and os.environ.get("DMLC_TPU_NO_NATIVE_READER", "0") in ("", "0")):
        from dmlc_tpu.data import native_parser as _np_mod

        if _np_mod.native_reader_eligible(uri, type_, threaded, split_kw):
            try:
                return _np_mod.NativeStreamParser(
                    spec.uri, spec.args, part_index, num_parts, type_,
                    index_dtype=index_dtype,
                    chunk_bytes=split_kw.get("chunk_bytes", DEFAULT_CHUNK_BYTES),
                )
            except DMLCError:
                pass  # fall back to the Python engine
        elif _np_mod.native_feed_eligible(uri, type_, threaded, split_kw):
            # remote corpora: Python range-reads feed the C++ chunk-parser
            try:
                return _np_mod.NativeFeedParser(
                    spec.uri, spec.args, part_index, num_parts, type_,
                    index_dtype=index_dtype,
                    chunk_bytes=split_kw.get("chunk_bytes", DEFAULT_CHUNK_BYTES),
                )
            except DMLCError:
                pass  # fall back to the Python engine
    if engine == "native":
        # reaching here means the fused reader could not serve this
        # config (decorated/remote/unsupported URI, threaded=False,
        # DMLC_TPU_NO_NATIVE_READER, or a load failure): fall back
        # LOUDLY — silently running a different path would make the
        # knob lie
        get_logger().warning(
            "engine=native unavailable for uri=%r format=%r "
            "(URI/threading outside the fused reader's eligibility, "
            "DMLC_TPU_NO_NATIVE_READER, or toolchain); using the Python "
            "engine", uri, type_)
    entry = PARSER_REGISTRY.find(type_)
    if entry is None:
        raise DMLCError(
            f"unknown parser format {type_!r}; known: {list(PARSER_REGISTRY.list_names())}"
        )
    parser = entry.body(
        split_uri, spec.args, part_index, num_parts, index_dtype, threaded,
        parse_workers=parse_workers, **split_kw
    )
    if engine == "python":
        _pin_python_scanner(parser)
    return parser


def _pin_python_scanner(parser: Parser) -> None:
    """engine='python' means the pure-numpy chunk scanner, not just the
    registry stack: the registry parsers opportunistically route
    ``parse_chunk`` through the native C scanners (``use_native``), which
    would make the explicit knob lie — an operator isolating a suspected
    native-scanner bug, or a parity referee, must get numpy all the way
    down. Walk the decorator chain and pin the base's native probe off
    (the outputs are byte-identical either way — the A/B parity suites
    of ``tests/test_native_reader.py``)."""
    base = parser
    while not isinstance(base, TextParserBase):
        nxt = getattr(base, "base", None)
        if nxt is None:
            return  # non-text stack (e.g. recordio): nothing to pin
        base = nxt
    base._native = False
