"""Fully-native streaming parser: the whole read->chunk->parse pipeline runs
in C++ (native/src/reader.cc) with one GIL-releasing pull per parsed block.

This is the TPU-first hot path for local text corpora: where the reference
stacks ThreadedInputSplit (prefetch thread) + ThreadedParser (parse-ahead
thread) + per-chunk parse threads in C++ (src/io/threaded_input_split.h,
src/data/parser.h:70-126), this class delegates the identical pipeline to
the native core, so on a TPU-VM host parsing overlaps JAX dispatch and
host->HBM DMA without touching the GIL.

``create_parser`` (dmlc_tpu.data.parsers) routes eligible URIs here: local
filesystem, text formats (libsvm / csv / libfm), no cache or shuffle
decorators. Everything else takes the Python engine, which shares chunk
semantics with this path (both mirror input_split_base.cc).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from dmlc_tpu.data.parsers import (
    CSVParserParam,
    LibFMParserParam,
    LibSVMParserParam,
    Parser,
    _csv_skeleton,
    check_dense_plane_dtype,
    csv_cells_to_block,
    csv_cells_to_dense,
)
from dmlc_tpu.data.row_block import CooBlock, DenseBlock, RowBlock
from dmlc_tpu.io.filesystem import LocalFileSystem, get_filesystem
from dmlc_tpu.io.input_split import DEFAULT_CHUNK_BYTES, LineSplitter
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import DMLCError, check
from dmlc_tpu.utils.timer import get_time


def list_partition_files(uri: str) -> Tuple[List[str], List[int]]:
    """Expand a local URI (';' lists, dirs, regex basenames) to (paths, sizes)
    using the same matching rules as the input-split engine."""
    fs = get_filesystem(uri)
    check(isinstance(fs, LocalFileSystem), "native reader requires local files")
    lister = LineSplitter(fs, uri)
    paths = [info.path.name for info in lister.files]
    sizes = [info.size for info in lister.files]
    return paths, sizes


class NativeStreamParser(Parser):
    """Parser facade over :class:`dmlc_tpu.native.Reader`.

    The native reader owns partitioning (byte-range + record-boundary
    adjustment), chunking, and multi-threaded parsing; this class wraps the
    returned buffers zero-copy into RowBlock / DenseBlock.
    """

    def __init__(
        self,
        uri: str,
        args: Optional[Dict[str, str]],
        part_index: int,
        num_parts: int,
        fmt_name: str,
        index_dtype=np.uint64,
        chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    ):
        check(fmt_name in ("libsvm", "csv", "libfm"),
              f"native reader does not support format {fmt_name!r}")
        # same partition validation as the Python engine (create_input_split):
        # num_parts=0 would SIGFPE in the native byte-range divide, and an
        # out-of-range part would silently yield an empty stream
        check(num_parts >= 1, f"num_parts must be >= 1, got {num_parts}")
        check(0 <= part_index < num_parts,
              f"part_index {part_index} out of range for {num_parts} parts")
        self.fmt_name = fmt_name
        self.index_dtype = index_dtype
        self.chunk_bytes = chunk_bytes
        self.part_index = part_index
        self.num_parts = num_parts
        args = dict(args or {})
        if fmt_name == "libsvm":
            self.param = LibSVMParserParam()
        elif fmt_name == "csv":
            self.param = CSVParserParam()
        else:
            self.param = LibFMParserParam()
        self.param.init(args, allow_unknown=True)
        if fmt_name == "csv":
            # the fused reader's csv scanner emits float32 cells only; a
            # DMLCError here routes the caller to the Python engine's
            # stack, whose per-chunk scanner is native for int32/int64 too
            # (native.parse_csv(dtype=)) and raises proper config errors
            check(self.param.dtype == "float32",
                  "native reader: csv dtype must be float32")
            # hashed cells are integer ids (docs/data.md, "Hashed cells"):
            # the per-chunk scanner's too
            check(self.param.hash_bins == 0,
                  "native reader: csv hash_bins needs the per-chunk "
                  "scanner (integer cells)")
            # mirror CSVParser.__init__'s config validation (parsers.py) so
            # bad configs fail loudly instead of silently mis-parsing
            check(len(self.param.delimiter) == 1,
                  "CSVParser: delimiter must be one char")
            check(
                self.param.label_column != self.param.weight_column
                or self.param.label_column < 0,
                "CSVParser: label_column must differ from weight_column",
            )
        self._init_source(uri)
        self._reader = None
        self._emit_dense: Optional[int] = None
        self._emit_bf16 = False
        self._pack_aux = False
        self._emit_coo: Optional[int] = None
        self._coo_row_bucket = 0
        self._coo_nnz_bucket = 0
        self._coo_elide = False
        self._coo_csr_wire = False
        self._stall = 0.0
        self._blocks_out = 0  # delivered blocks, for count-based resume
        self._batch_rows = 0

    def _init_source(self, uri: str) -> None:
        """Resolve the byte source. Base class: local files, listed with the
        engine's matching rules (the native reader reads them itself)."""
        self.paths, self.sizes = list_partition_files(uri)

    # ---------------- configuration ----------------

    def set_emit_dense(self, num_col: int, batch_rows: int = 0,
                       dtype: str = "float32",
                       pack_aux: bool = False) -> bool:
        """Emit DenseBlock batches straight from the native dense scanner.
        With ``batch_rows``, the native reader additionally repacks rows
        into exact [batch_rows, num_col] blocks off-GIL (the consumer can
        then slice views instead of concatenating); ``dtype='bfloat16'``
        makes that repack pass emit bf16 x — half the host->HBM bytes in
        the MXU's preferred operand width. Must be called before the first
        pull (the reader pipeline starts lazily). libfm has no dense
        analog. ``pack_aux`` (batch mode only) packs label/weight into two
        trailing x columns — one [B, D+2] array per batch, ONE device_put
        instead of three (api.h DenseResult packed_aux docs); in bf16 mode
        the aux columns are bf16 too, so callers opt in only when their
        labels/weights are bf16-exact."""
        # this reader's cells are float32: an integer plane is refused
        # here, at construction, and never filled by a cast
        check_dense_plane_dtype("float32", dtype)
        if self._reader is not None or self.fmt_name == "libfm":
            return False
        self._emit_dense = int(num_col)
        self._batch_rows = int(batch_rows)
        self._emit_bf16 = dtype == "bfloat16"
        self._pack_aux = bool(pack_aux) and batch_rows > 0
        return True

    def set_emit_coo(self, num_col: int, row_bucket: int = 0,
                     nnz_bucket: int = 0, elide_unit: bool = False,
                     csr_wire: bool = False) -> bool:
        """Emit CooBlock batches straight from the native parse: int32
        (row, col) coordinate pairs with OOB bucket padding, optional
        all-ones value elision — the whole convert stage of the BCOO
        pipeline moves off-GIL into the C++ parse threads. One CooBlock per
        chunk (natural-block mode). ``csr_wire`` ships cols + row_ptr
        instead of (row, col) pairs — half the coordinate bytes over the
        host->device link; the DeviceIter consumer rebuilds row ids on
        device (native/src/api.h CooResult docs). Must be called before the
        first pull. csv has no sparse analog; int32 coords require
        num_col + 1 < 2^31."""
        if (self._reader is not None or self.fmt_name == "csv"
                or int(num_col) + 1 >= (1 << 31)):
            return False
        self._emit_coo = int(num_col)
        self._coo_row_bucket = int(row_bucket)
        self._coo_nnz_bucket = int(nnz_bucket)
        self._coo_elide = bool(elide_unit)
        self._coo_csr_wire = bool(csr_wire)
        return True

    # ---------------- pipeline ----------------

    def _stream_config(self):
        """(fmt, kwargs) shared by the pull-mode Reader and the push-mode
        Feeder — one place for format selection and repack policy."""
        from dmlc_tpu import native

        if self._emit_coo is not None and self.fmt_name in ("libsvm", "libfm"):
            fmt = (native.FMT_LIBFM_COO if self.fmt_name == "libfm"
                   else native.FMT_LIBSVM_COO)
        elif self.fmt_name == "libsvm":
            fmt = (native.FMT_LIBSVM_DENSE if self._emit_dense is not None
                   else native.FMT_LIBSVM)
        elif self.fmt_name == "csv":
            # label/weight columns configured and no dense repack: the
            # native merge pass splits them out (FMT_CSV_SPLIT), so the
            # RowBlock wrap below is zero-copy — the reference re-walks
            # the cell matrix in its consumer instead (csv_parser.h:120)
            lc = getattr(self.param, "label_column", -1)
            wc = getattr(self.param, "weight_column", -1)
            fmt = (native.FMT_CSV_SPLIT
                   if self._emit_dense is None and (lc >= 0 or wc >= 0)
                   else native.FMT_CSV)
        else:
            fmt = native.FMT_LIBFM
        repack = (fmt == native.FMT_LIBSVM_DENSE
                  or (fmt == native.FMT_CSV and self._emit_dense is not None))
        coo = fmt in (native.FMT_LIBSVM_COO, native.FMT_LIBFM_COO)
        kwargs = dict(
            num_col=(self._emit_coo if coo else self._emit_dense) or 0,
            indexing_mode=getattr(self.param, "indexing_mode", 0),
            delimiter=getattr(self.param, "delimiter", ","),
            chunk_bytes=self.chunk_bytes,
            batch_rows=self._batch_rows if repack else 0,
            label_col=getattr(self.param, "label_column", -1),
            weight_col=getattr(self.param, "weight_column", -1),
            out_bf16=bool(repack and self._batch_rows and self._emit_bf16),
            row_bucket=self._coo_row_bucket if coo else 0,
            nnz_bucket=self._coo_nnz_bucket if coo else 0,
            elide_unit=self._coo_elide if coo else False,
            csr_wire=self._coo_csr_wire if coo else False,
            pack_aux=bool(repack and self._pack_aux),
        )
        return fmt, kwargs

    def _ensure_reader(self):
        if self._reader is None:
            from dmlc_tpu import native

            fmt, kwargs = self._stream_config()
            self._reader = native.Reader(
                self.paths, self.sizes, self.part_index, self.num_parts,
                fmt, **kwargs)
        return self._reader

    def next_block(self):
        from dmlc_tpu import native

        reader = self._ensure_reader()
        t0 = get_time()
        out = reader.next()
        self._stall += get_time() - t0
        if out is None:
            return None
        self._blocks_out += 1
        fmt, data = out
        if fmt == native.FMT_LIBSVM_DENSE:
            x, label, weight, owner, packed = data
            return DenseBlock(x, label, weight, hold=owner, packed=packed)
        if fmt in (native.FMT_LIBSVM_COO, native.FMT_LIBFM_COO):
            return CooBlock(
                data["coords"], data["values"], data["label"],
                data["weight"], data["n_rows"], data["nnz"],
                int(self._emit_coo), hold=data["_owner"],
                row_ptr=data.get("row_ptr"))
        if fmt in (native.FMT_LIBSVM, native.FMT_LIBFM):
            return RowBlock(
                offset=data["offset"], label=data["label"],
                index=data["index"], value=data["value"],
                weight=data["weight"], qid=data["qid"],
                field=data["field"], hold=data["_owner"],
            )
        if fmt == native.FMT_CSV_SPLIT:
            values, label, weight, n, owner = data
            k = values.shape[1]
            index, offset = _csv_skeleton(n, k, self.index_dtype)
            if label is None:
                label = np.zeros(n, np.float32)
            return RowBlock(
                offset=offset, label=label, index=index,
                value=values.reshape(-1), weight=weight, hold=owner)
        cells, owner = data
        n, ncol = cells.shape
        if self._emit_dense is not None:
            return csv_cells_to_dense(
                cells, n, ncol, int(self._emit_dense),
                self.param.label_column, self.param.weight_column, owner)
        return csv_cells_to_block(
            cells, n, ncol, self.param.label_column,
            self.param.weight_column, self.index_dtype)

    def before_first(self) -> None:
        if self._reader is not None:
            self._reader.before_first()
        self._blocks_out = 0

    def reset_partition(self, part_index: int, num_parts: int) -> None:
        """Re-point at another partition; the file listing (paths/sizes) is
        reused — only the native reader is rebuilt, lazily."""
        check(num_parts >= 1, f"num_parts must be >= 1, got {num_parts}")
        check(0 <= part_index < num_parts,
              f"part_index {part_index} out of range for {num_parts} parts")
        # keep bytes_read cumulative across partitions, matching the Python
        # engine's accumulating counter
        self._bytes_base = self.bytes_read
        self.close()
        self.part_index = part_index
        self.num_parts = num_parts
        self._blocks_out = 0

    # -------- checkpoint / resume (SURVEY.md §5.4 addition) --------

    def state_dict(self) -> dict:
        """Resume point at a block boundary. Chunking in the native reader is
        deterministic, so a block count replays exactly. Partition identity
        rides along so restore onto a differently-pointed parser re-applies
        the recorded shard first."""
        return {"kind": "blocks", "blocks": self._blocks_out,
                "part_index": self.part_index, "num_parts": self.num_parts}

    def load_state(self, state: dict) -> None:
        check(state.get("kind") == "blocks",
              f"native parser: incompatible resume state {state.get('kind')!r}")
        part, nparts = state.get("part_index"), state.get("num_parts")
        if (nparts is not None and part is not None
                and (part, nparts) != (self.part_index, self.num_parts)):
            self.reset_partition(int(part), int(nparts))
        n = int(state["blocks"])
        self.before_first()
        reader = self._ensure_reader()
        for _ in range(n):
            if reader.next() is None:
                break
        self._blocks_out = n

    @property
    def bytes_read(self) -> int:
        live = self._reader.bytes_read if self._reader is not None else 0
        return getattr(self, "_bytes_base", 0) + live

    @property
    def stall_seconds(self) -> float:
        """Consumer-side wait on the native pipeline."""
        return self._stall

    @property
    def parse_workers(self) -> int:
        """The native reader's own C++ parse-thread count — it keeps its
        own threading and ignores the Python engine's ``parse_workers``
        knob (docs/data.md)."""
        from dmlc_tpu import native

        return native.default_nthread()

    def parallel_stats(self) -> dict:
        """Scaling sideband in the same shape ParallelTextParser reports
        (DeviceIter.stats() consumes either): the C++ core does not expose
        per-thread busy seconds, so efficiency is unmeasured here."""
        return {
            "parse_workers": self.parse_workers,
            "parse_busy_seconds": None,
            "parse_span_seconds": None,
            "parse_parallelism_efficiency": None,
            "engine": "native",
        }

    def close(self) -> None:
        if self._reader is not None:
            self._reader.close()
            self._reader = None


def _native_eligible(uri: str, type_: str, threaded: bool, split_kw: Dict,
                     want_local: bool) -> bool:
    """Shared native-routing predicate; want_local picks pull-mode (local
    files, the Reader) vs push-mode (remote streams, the Feeder)."""
    from dmlc_tpu import native

    if not threaded or type_ not in ("libsvm", "csv", "libfm"):
        return False
    if "#" in uri or "engine=python" in uri:
        return False  # cachefile decorator / explicit engine opt-out
    for key in ("shuffle", "num_shuffle_parts", "index_uri"):
        if split_kw.get(key):
            return False
    if split_kw.get("recurse_directories"):
        return False
    base = uri.split("?", 1)[0]
    if base in ("stdin",):
        return False
    try:
        fs = get_filesystem(base)
    except DMLCError:
        return False
    if isinstance(fs, LocalFileSystem) != want_local:
        return False
    return native.available()


def native_reader_eligible(uri: str, type_: str, threaded: bool,
                           split_kw: Dict) -> bool:
    """True when create_parser can route to the native stream parser."""
    return _native_eligible(uri, type_, threaded, split_kw, want_local=True)


class NativeFeedParser(NativeStreamParser):
    """Remote corpora through the native pipeline (BASELINE config #2-style
    cloud streams): a Python feed thread range-reads this partition through
    the FileSystem layer (S3 / GCS / HTTP / anything registered) and pushes
    raw bytes into the C++ chunk feeder (reader.cc push mode), which owns
    record-aligned chunking, threaded parsing, and batch repack — so remote
    corpora get the same off-GIL parse path as local files instead of the
    single-threaded Python engine.

    Partitioning (byte ranges, record-boundary adjustment, newline
    injection at text file joins) stays with the Python input-split engine,
    which already speaks every filesystem; the feed thread streams exactly
    this partition's bytes (InputSplitBase._read).
    """

    FEED_CHUNK = 1 << 20

    def _init_source(self, uri: str) -> None:
        self.uri = uri
        self.paths = self.sizes = None
        self._feed_thread = None
        self._feed_exc = None  # original feed-thread exception (cause chain)

    def _make_split(self):
        from dmlc_tpu.io.input_split import LineSplitter

        split = LineSplitter(get_filesystem(self.uri), self.uri)
        split.reset_partition(self.part_index, self.num_parts)
        return split

    def _start_feed(self) -> None:
        import threading

        feeder = self._reader
        split = self._make_split()

        def run() -> None:
            try:
                while True:
                    data = split._read(self.FEED_CHUNK)
                    if not data or not feeder.push(data):
                        break
                feeder.finish()
            except Exception as exc:  # noqa: BLE001
                # a mid-stream remote failure must NOT look like EOF: record
                # it so the consumer's next() raises after the queue drains.
                # The C ABI carries only the message string; keep the
                # exception OBJECT here so next_block can restore the cause
                # chain (the resilience classifier walks __cause__ — a
                # retryable stream fault must stay retryable-class for the
                # DeviceIter pipeline-restart path).
                self._feed_exc = exc
                feeder.fail(f"feed failed: {exc}")
            finally:
                try:
                    split.close()
                except Exception:  # noqa: BLE001
                    pass

        # the feed thread inherits the creator's pipeline scope so its
        # retries/resumes land under the owning pipeline's label
        self._feed_thread = threading.Thread(
            target=_telemetry.scoped_target(run), name="dmlc-feed",
            daemon=True)
        self._feed_thread.start()

    def _stop_feed(self) -> None:
        if self._feed_thread is not None:
            if self._reader is not None:
                self._reader.abort()
            self._feed_thread.join()
            self._feed_thread = None

    def _ensure_reader(self):
        if self._reader is None:
            from dmlc_tpu import native

            fmt, kwargs = self._stream_config()
            self._reader = native.Feeder(fmt, **kwargs)
            self._start_feed()
        return self._reader

    def next_block(self):
        try:
            return super().next_block()
        except DMLCError as exc:
            cause = self._feed_exc
            if cause is not None and exc.__cause__ is None:
                # restore the original exception behind the ABI's string:
                # classification (retryable vs fatal) needs the real class
                self._feed_exc = None
                raise exc from cause
            raise

    def before_first(self) -> None:
        self._feed_exc = None  # cleared BEFORE the new feed thread starts
        if self._reader is not None:
            self._stop_feed()
            if self._reader.error() is not None:
                # errors are STICKY in the native pipeline (before_first
                # stays stopped) — a failed feeder cannot restart. Rebuild
                # it so an epoch reset after a fault (e.g. DeviceIter's
                # bounded pipeline restart) gets a clean stream instead of
                # replaying the stale error.
                self._reader.close()
                self._reader = None
                self._ensure_reader()  # fresh feeder + feed thread
            else:
                self._reader.before_first()
                self._start_feed()
        self._blocks_out = 0

    def close(self) -> None:
        self._stop_feed()
        super().close()


def native_feed_eligible(uri: str, type_: str, threaded: bool,
                         split_kw: Dict) -> bool:
    """True when create_parser can route a REMOTE uri to the chunk feeder."""
    return _native_eligible(uri, type_, threaded, split_kw, want_local=False)
