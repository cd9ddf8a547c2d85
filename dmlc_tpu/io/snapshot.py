"""Device-native snapshot store: post-convert device-layout batches on disk.

The parse-once block cache (:mod:`dmlc_tpu.io.block_cache`) stores PARSER
output — warm epochs still pay the full host-side convert/pack cost per
batch, which caps them near the text-parse ceiling even though the
measured ``device_put`` floor sits ~70x higher (ROADMAP item 3). This
module stores the pipeline one stage later, at the highest-leverage point
left: the exact *post-convert, device-layout* batches ``DeviceIter``
ships — packed dense ``[B, num_col + 2]`` slabs (features | label |
weight) in float32 or bfloat16, padded-ELL sparse batches, or
int8-quantized slabs with per-column scale — at one fixed batch geometry
recorded in the footer. Warm snapshot epochs then mmap each batch's
segments straight into the transfer path and issue the one ``device_put``
with **zero host convert work**: the warm rate is bounded by transfer,
not host packing (the ads-scale training-infra recipe, arXiv:2501.10546
§4; tf.data's materialize-the-expensive-prefix argument,
arXiv:2101.12127 §5).

Format v1 ("DMLCSN01", pinned by ``tests/data/snapshot_v1.golden``) is a
sibling of block-cache v1 built from the SAME machinery
(:func:`~dmlc_tpu.io.block_cache.write_segments` /
:func:`~dmlc_tpu.io.block_cache.read_segments` /
:func:`~dmlc_tpu.io.block_cache.finish_container` /
:func:`~dmlc_tpu.io.block_cache.open_container`)::

    [header]   magic "DMLCSN01" (8B) + version u32 LE + 4 zero pad bytes
    [segments] per batch, its positional arrays (a0, a1, ...): the v1
               segment encoding — 64-byte-aligned starts, raw
               little-endian C-order bytes, one crc32 per batch
    [footer]   utf-8 JSON (sort_keys): {"version", "signature",
               "geometry", "rows", "batches": [{"kind", "pos", "end",
               "rows", "crc", "resume", "arrays": {name: [dtype_str,
               abs_offset, nbytes]}, "shapes": {name: [dims...]}}, ...]}
    [tail]     u64 footer_offset + u64 footer_len + u32 footer_crc LE
               + magic "DMLCSN01"

A batch is ``(kind, arr0, arr1, ...)`` — exactly a ``DeviceIter`` host
batch minus the leading kind string: ``("dense_packed", xp)``,
``("dense", x, y, w)``, ``("ell", indices, values, label, weight)``,
``("dense_packed_q8", q8, scale)``. Arrays may be 2-D (the footer stores
shapes; :func:`~dmlc_tpu.io.block_cache.read_segments` views are reshaped
on load), so one decode path serves every fixed-geometry layout.

Staleness is TWO-keyed: the ``signature`` (source files + parser config,
same discipline as the block cache) catches source drift, and the
``geometry`` — ``{batch_size, num_col, layout, x_dtype, pack_aux, quant,
drop_remainder, max_nnz}`` — catches pipeline-shape drift: a snapshot
written at a different batch size or dtype must self-invalidate at open
(:func:`open_snapshot` drops it and counts ``snapshot_invalidations``),
never serve wrong-shaped batches.

This module owns the FORMAT plus the order-following feed
(:class:`SnapshotIter`); the pipeline integration — the shadow write over
the convert stage, the ``snapshot_read`` stage attribution, checkpoints —
lives in :mod:`dmlc_tpu.data.device` (the io layer stays free of
data-layer imports, like the block cache).
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Callable, List, Optional, Tuple

import numpy as np

from dmlc_tpu.io import faults
from dmlc_tpu.io import resilience as _resilience
from dmlc_tpu.utils import knobs as _knobs
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import CacheCorruptionError, DMLCError, check

SNAPSHOT_MAGIC = b"DMLCSN01"
SNAPSHOT_VERSION = 1

# positional segment names: batch arrays are stored in tuple order under
# a0..aN (a snapshot batch is (kind, *arrays), not the named CSR columns
# of the block cache) — bounded so the canonical write order is total
MAX_BATCH_ARRAYS = 8
SNAPSHOT_SEGMENT_NAMES = tuple(f"a{i}" for i in range(MAX_BATCH_ARRAYS))


def quantize_int8(arr) -> Tuple[np.ndarray, np.ndarray]:
    """Per-column symmetric int8 quantization of a 2-D float batch:
    returns ``(q8, scale)`` with ``scale`` float32 per column
    (``absmax / 127``; zero columns get scale 1.0 so dequant is exact
    zeros). The device dequantizes with one fused multiply
    (``dequant_q8``) — the opt-in that quarters snapshot bytes for value
    ranges that tolerate 8-bit precision.

    Thin wrapper: the implementation lives in
    :mod:`dmlc_tpu.ops.device_decode` (the single sanctioned device-side
    dtype path, quantize and dequant audited as one pair). Imported
    lazily — this module must stay importable without jax (the service
    frame codec's no-jax contract)."""
    from dmlc_tpu.ops.device_decode import quantize_int8 as _impl

    return _impl(arr)


class SnapshotWriter:
    """Streams checksummed device-layout batches to a store-allocated
    staging file; :meth:`finish` writes the footer (geometry + per-batch
    resume annotations) and publishes through the artifact store — the
    shadow half of a cold epoch (the convert stage's output tees in
    here)."""

    def __init__(self, path: str, signature: Optional[dict] = None,
                 geometry: Optional[dict] = None):
        from dmlc_tpu.io import block_cache as _bc

        self._bc = _bc
        self.path = path
        self._sig = signature or {}
        self._geom = _bc._normalize(geometry or {})
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        # process-unique staging name from the store (docs/store.md):
        # concurrent writers can never clobber each other's bytes
        self.tmp_path = _bc._artifact_store(path).stage_path(path)
        self._f = open(self.tmp_path, "wb")
        self._f.write(_bc.container_header(SNAPSHOT_MAGIC, SNAPSHOT_VERSION))
        self._entries: List[dict] = []
        self._rows = 0
        self._finished = False

    def add_batch(self, kind: str, arrays, rows: int,
                  resume: Optional[dict] = None) -> None:
        """Append one device-layout batch: ``arrays`` is the positional
        tuple of numpy arrays behind ``kind`` (2-D allowed — shapes are
        recorded); ``resume`` is the pipeline's resume annotation for the
        position just after this batch, stored so warm epochs re-attach
        byte-exact checkpoint states."""
        check(self._f is not None and not self._finished,
              "SnapshotWriter: writer already finished/aborted")
        check(len(arrays) <= MAX_BATCH_ARRAYS,
              f"SnapshotWriter: batch carries {len(arrays)} arrays "
              f"(max {MAX_BATCH_ARRAYS})")
        # the shadow write's own cost, visible on the trace timeline next
        # to the convert spans it rides behind (cold-epoch overhead is a
        # real stage even though stats() folds it into consumer wall)
        with _telemetry.span("snapshot_write", rows=int(rows)):
            f = self._f
            arrs = [np.ascontiguousarray(a) for a in arrays]
            segments = {SNAPSHOT_SEGMENT_NAMES[i]: a.reshape(-1)
                        for i, a in enumerate(arrs)}
            pos = self._bc._pad_to(f, self._bc._ALIGN)
            end, crc, arr_meta = self._bc.write_segments(
                f, segments, names=SNAPSHOT_SEGMENT_NAMES)
            resume_json = (json.loads(json.dumps(resume))
                           if resume is not None else None)
            self._entries.append({
                "kind": str(kind), "pos": pos, "end": end,
                "rows": int(rows), "crc": crc, "resume": resume_json,
                "arrays": arr_meta,
                "shapes": {SNAPSHOT_SEGMENT_NAMES[i]: list(a.shape)
                           for i, a in enumerate(arrs)},
            })
            self._rows += int(rows)

    def finish(self) -> None:
        """Write footer + tail, fsync, atomically publish at ``path``."""
        check(self._f is not None and not self._finished,
              "SnapshotWriter: writer already finished/aborted")
        footer = {
            "version": SNAPSHOT_VERSION,
            "signature": self._sig,
            "geometry": self._geom,
            "rows": self._rows,
            "batches": self._entries,
        }
        f, self._f = self._f, None
        self._bc.finish_container(f, self.tmp_path, self.path, footer,
                                  SNAPSHOT_MAGIC)
        self._finished = True

    def abort(self) -> None:
        """Drop the partial tmp file (interrupted cold pass)."""
        if self._f is not None:
            self._f.close()
            self._f = None
        try:
            os.remove(self.tmp_path)
        except OSError:
            pass

    def close(self) -> None:
        if not self._finished:
            self.abort()


class SnapshotReader:
    """mmap-backed snapshot reader: batches decode to zero-copy read-only
    numpy views in the stored shapes.

    Views alias the mmap — numpy pins the buffer owner via the view's
    ``base`` chain, and :meth:`close` tolerates still-exported views
    (GC reclaims the mmap once the last one dies), the same lifetime
    contract as the block-cache reader.
    """

    def __init__(self, path: str, signature: Optional[dict] = None,
                 geometry: Optional[dict] = None, verify: bool = True):
        from dmlc_tpu.io import block_cache as _bc

        self._bc = _bc
        self.path = path
        self.verify = verify
        self._store_pinned = False
        self._file, self._mm, footer = _bc.open_container(
            path, SNAPSHOT_MAGIC, SNAPSHOT_VERSION, f"snapshot {path}")
        try:
            self.signature = footer.get("signature") or {}
            self.geometry = footer.get("geometry") or {}
            self.rows = int(footer.get("rows", 0))
            self._batches = footer["batches"]
            if signature is not None and self.signature != _bc._normalize(
                    signature):
                raise DMLCError(
                    f"snapshot {path}: source signature mismatch "
                    f"(stale snapshot)")
            if geometry is not None and self.geometry != _bc._normalize(
                    geometry):
                # the load-bearing staleness check this format adds: a
                # snapshot written at a different batch_size / x_dtype /
                # padding config must never serve wrong-shaped batches
                raise DMLCError(
                    f"snapshot {path}: batch geometry mismatch "
                    f"(stored {self.geometry})")
            # pin/refcount (docs/store.md): a warm epoch streaming this
            # snapshot can never lose it to a byte-budget eviction
            _bc._artifact_store(path).pin(path)
            self._store_pinned = True
        except Exception:
            self.close()
            raise

    # ---------------- accessors ----------------

    @property
    def num_batches(self) -> int:
        return len(self._batches)

    @property
    def hold(self):
        """The buffer owner views must pin (the mmap)."""
        return self._mm

    def kind(self, i: int) -> str:
        return self._batches[i]["kind"]

    def resume(self, i: int) -> Optional[dict]:
        """The stored resume annotation of batch ``i`` (the pipeline
        position just after it), or None when the producer had none."""
        return self._batches[i]["resume"]

    def batch_rows(self, i: int) -> int:
        return int(self._batches[i]["rows"])

    def batch_nbytes(self, i: int) -> int:
        e = self._batches[i]
        return int(e["end"]) - int(e["pos"])

    def load_batch(self, i: int, copy: bool = False) -> tuple:
        """Decode batch ``i`` to ``(kind, arr0, arr1, ...)`` — zero-copy
        read-only views over the mmap, reshaped to the stored shapes.

        ``copy=True`` materializes into process memory (plan-ordered warm
        epochs serve a permuted pattern OS readahead cannot predict; the
        copy forces those page faults to land inside the caller's timed
        ``snapshot_read`` region — same attribution discipline as the
        block cache's permuted serves).

        Raises :class:`CacheCorruptionError` on a crc mismatch (or an
        injected ``snapshot_read`` fault) — the consumer heals by
        dropping the snapshot and re-converting from the source.
        """
        faults.maybe_fail("snapshot_read", self.path)
        entry = self._batches[i]
        if self.verify:
            with memoryview(self._mm)[
                    int(entry["pos"]): int(entry["end"])] as span:
                ok = zlib.crc32(span) & 0xFFFFFFFF == int(entry["crc"])
            if not ok:
                raise CacheCorruptionError(
                    f"snapshot {self.path}: crc mismatch on batch {i}")
        segments = self._bc.read_segments(self._mm, entry["arrays"])
        shapes = entry.get("shapes") or {}
        out = []
        for name in SNAPSHOT_SEGMENT_NAMES:
            if name not in segments:
                break
            arr = segments[name]
            shape = shapes.get(name)
            if shape is not None and len(shape) != 1:
                arr = arr.reshape(shape)
            if copy:
                arr = np.array(arr)
            out.append(arr)
        return (entry["kind"], *out)

    def batch_span(self, i: int, copy: bool = False) -> tuple:
        """Batch ``i`` as its raw container bytes: ``(kind, span,
        layout)`` with ``span`` the verbatim ``[pos, end)`` u8 view over
        the mmap and ``layout`` the hashable segment map
        (:func:`~dmlc_tpu.io.block_cache.span_layout`, offsets rebased
        to the span) — the device-decode tier's input: the consumer
        ``device_put``s the span untouched (one contiguous transfer)
        and :func:`dmlc_tpu.ops.device_decode.decode_span` slices and
        bitcasts it in HBM. No per-segment host views are built.

        crc + fault semantics match :meth:`load_batch`; ``copy=True``
        materializes the span (plan-ordered warm epochs — same
        attribution discipline as ``load_batch``)."""
        faults.maybe_fail("snapshot_read", self.path)
        entry = self._batches[i]
        pos, end = int(entry["pos"]), int(entry["end"])
        if self.verify:
            with memoryview(self._mm)[pos:end] as mv:
                ok = zlib.crc32(mv) & 0xFFFFFFFF == int(entry["crc"])
            if not ok:
                raise CacheCorruptionError(
                    f"snapshot {self.path}: crc mismatch on batch {i}")
        span = np.asarray(memoryview(self._mm)[pos:end])
        if copy:
            span = np.array(span)
        layout = self._bc.span_layout(entry["arrays"],
                                      entry.get("shapes"), base=pos)
        return entry["kind"], span, layout

    def close(self) -> None:
        # the eviction pin drops first, unconditionally (see the
        # block-cache reader: an unlinked-but-mapped file keeps serving)
        if getattr(self, "_store_pinned", False):
            self._store_pinned = False
            try:
                self._bc._artifact_store(self.path).drop(self.path)
            except OSError:
                pass
        # best-effort: the mmap cannot close while exported views are
        # alive (BufferError) — GC reclaims it once the last view dies
        mm = getattr(self, "_mm", None)
        if mm is not None:
            try:
                mm.close()
                self._mm = None
            except BufferError:
                pass
        f = getattr(self, "_file", None)
        if f is not None:
            self._file = None
            f.close()


def open_snapshot(path: str, signature: Optional[dict] = None,
                  geometry: Optional[dict] = None,
                  verify: bool = True) -> Optional[SnapshotReader]:
    """Open a published snapshot, or None when it is missing or must be
    rebuilt (unreadable / wrong version / signature mismatch / **batch
    geometry mismatch** — the stale file is dropped via the store and a
    ``snapshot_invalidations`` resilience event counted, so callers
    simply fall back to a cold convert pass). A miss on a path the store
    manifest marks as EVICTED counts ``store_rebuilds_after_eviction``
    (docs/store.md)."""
    from dmlc_tpu.io import block_cache as _bc

    if not os.path.exists(path):
        # light probe: only consults the store when the directory already
        # carries a manifest (never creates state for an unmanaged dir)
        _bc._store_manager().note_missing(path)
        return None
    try:
        return SnapshotReader(path, signature=signature, geometry=geometry,
                              verify=verify)
    except DMLCError:
        _resilience.record_event("snapshot_invalidations")
        _bc._artifact_store(path).discard(path)
        return None


class SnapshotIter:
    """The warm feed: serves a snapshot's batches in a given order with
    reads prefetched on a small
    :class:`~dmlc_tpu.io.threaded_iter.OrderedWorkerPool`, so loading
    (mmap fault + crc) of batch N+1 overlaps the transfer of batch N —
    the host half of the HBM double-buffer.

    ``order`` is an index array (an epoch plan's permutation over batch
    indices) or None for sequential; ``start`` resumes mid-epoch at a
    plan position. ``next()`` returns ``(host_batch, resume, nbytes)``
    with ``host_batch = (kind, *arrays)``, or None at end of epoch. Each
    read is timed into a ``snapshot_read`` span and reported through the
    ``on_read`` callback (the consumer's stage-busy meter).

    ``raw=True`` is the device-decode feed: ``host_batch`` becomes
    ``("device_span", span, layout, kind)`` — the batch's verbatim
    container bytes (:meth:`SnapshotReader.batch_span`) instead of
    decoded host views, for consumers that transfer the span untouched
    and decode in HBM. Resume annotations, ordering, and timing are
    identical, so checkpoint states restore across the two modes.
    """

    def __init__(self, reader: SnapshotReader,
                 order: Optional[np.ndarray] = None, start: int = 0,
                 read_workers: Optional[int] = None,
                 on_read: Optional[Callable[[float], None]] = None,
                 raw: bool = False):
        from dmlc_tpu.io.threaded_iter import OrderedWorkerPool

        self.reader = reader
        self._order = order
        self._on_read = on_read
        self._raw = raw
        n = reader.num_batches if order is None else len(order)
        workers = _knobs.resolve("snapshot_read_workers", read_workers)
        self._pool = OrderedWorkerPool(
            lambda: iter(range(int(start), int(n))),
            self._read,
            num_workers=workers,
            max_ahead=2 * workers,
            counter_label="snapshot_read")

    def resize(self, read_workers: int) -> bool:
        """Live read-pool resize (the autotuner's
        ``snapshot_read_workers`` knob): batches keep delivering in
        serving order across the width change. Always returns True."""
        n = max(1, int(read_workers))
        self._pool.resize(n)
        self._pool.set_max_ahead(2 * n)
        return True

    def _read(self, pos: int):
        reader = self.reader
        i = int(pos) if self._order is None else int(self._order[pos])
        with _telemetry.span("snapshot_read", book=self._on_read):
            # permuted serves materialize HERE, inside the timed
            # region, so out-of-order page faults are attributed to
            # snapshot_read and never leak into dispatch/transfer
            copy = self._order is not None
            if self._raw:
                kind, span, layout = reader.batch_span(i, copy=copy)
                batch = ("device_span", span, layout, kind)
            else:
                batch = reader.load_batch(i, copy=copy)
        return batch, reader.resume(i), reader.batch_nbytes(i)

    @property
    def stall_seconds(self) -> float:
        return self._pool.stall_seconds

    @stall_seconds.setter
    def stall_seconds(self, value: float) -> None:
        self._pool.stall_seconds = value

    def next(self):
        return self._pool.next()

    def destroy(self) -> None:
        self._pool.destroy()
