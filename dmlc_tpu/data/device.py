"""Async host->HBM batch pipeline.

This is the TPU-native replacement for the reference's ThreadedIter-based
prefetch chain (SURVEY.md north star): parsed RowBlocks are rebatched to a
fixed shape on the host (so XLA compiles one step), converted to the chosen
device layout, and ``jax.device_put`` is issued ahead of consumption —
double-buffered by default — so the accelerator never waits on input.
``jax.device_put`` on TPU is asynchronous: it returns immediately while the
DMA proceeds, which is what lets a pure-Python loop overlap transfer with
compute. Stall time (consumer waiting on host data) is tracked, because the
BASELINE target is ">=90% host->HBM line-rate with zero input-bound stalls".

Layouts: 'dense' (padded [B, D], MXU-friendly), 'ell' (static-shape sparse),
'bcoo' (jax.experimental.sparse interop). See dmlc_tpu.ops.sparse.

Stage attribution (tf.data's per-stage cost naming, arXiv:2101.12127): every
second of consumer wall is attributed to a named pipeline stage — read,
cache_read, parse, convert, dispatch, transfer — in ``stats()['stages']``, so "the
pipeline is at X% of bound" always decomposes into which stage owns the gap
(a 50% gap with stalls reading 0.000s is an artifact of the measurement,
not a property of the pipeline). The convert stage runs on
a small :class:`~dmlc_tpu.io.threaded_iter.OrderedWorkerPool` packing into a
ring of reusable preallocated host staging buffers, so layout conversion for
batch N+1 overlaps the dispatch (and DMA) of batch N.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import weakref
from collections import deque
from typing import Iterator, Optional, Tuple

import jax
import numpy as np

from dmlc_tpu.data import autotune as _autotune
from dmlc_tpu.data.parsers import Parser
from dmlc_tpu.data.row_block import (
    CooBlock, DenseBlock, RowBlock, RowBlockContainer,
)
from dmlc_tpu.io import block_cache as _block_cache
from dmlc_tpu.io import resilience as _resilience
from dmlc_tpu.io import snapshot as _snapshot
from dmlc_tpu.io.threaded_iter import OrderedWorkerPool, ThreadedIter
from dmlc_tpu.ops import device_decode as _device_decode
from dmlc_tpu.ops.sparse import (
    EllBatch, block_to_bcoo_host, block_to_dense, block_to_ell,
    ell_truncated_slots, parts_to_csr_host,
)
from dmlc_tpu.utils import knobs as _knobs
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import CacheCorruptionError, DMLCError, check
from dmlc_tpu.utils.timer import StageMeter, get_time


def _store_counters() -> dict:
    """The tiered store's counter triple for ``stats()['store']``
    (lazy import: the store manager sits above this module's io deps)."""
    from dmlc_tpu.store import store_counters

    return store_counters()


# resume marker: yielded by the natural-block producer for skipped blocks
# (identity-compared — value comparison would touch device arrays)
_SKIPPED = object()


def rebatch_blocks(
    blocks: Iterator[RowBlock], batch_size: int, drop_remainder: bool = False,
    work=contextlib.nullcontext,
) -> Iterator[RowBlock]:
    """Re-slice a stream of variable-size RowBlocks into fixed-size batches.

    The final partial batch is emitted as-is (callers pad via
    ``pad_rows_to``) unless ``drop_remainder``. ``work()`` gives a context
    manager that is entered around this function's own work on each
    incoming block (the push, and the merge and slices when a batch fills)
    and never around the pull from ``blocks`` or a ``yield``: the serial
    stage's ``merge`` span.
    """
    pending = RowBlockContainer()
    pending_rows = 0
    for block in blocks:
        out = []
        with work():
            pending.push_block(block)
            pending_rows += len(block)
            if pending_rows >= batch_size:
                merged = pending.to_block()
                pos = 0
                while pos + batch_size <= len(merged):
                    out.append(merged.slice(pos, pos + batch_size))
                    pos += batch_size
                pending = RowBlockContainer()
                pending_rows = len(merged) - pos
                if pending_rows:
                    pending.push_block(merged.slice(pos, len(merged)))
        yield from out
    if pending_rows and not drop_remainder:
        with work():
            tail = pending.to_block()
        yield tail


def rebatch_parts(
    blocks: Iterator[RowBlock], batch_size: int, drop_remainder: bool = False,
    work=contextlib.nullcontext,
) -> Iterator[list]:
    """:func:`rebatch_blocks` without its copies: every fixed-size batch as
    the list of row-range views (``RowBlock.slice``) that make it up, in
    order, for a consumer that packs them in one pass into a buffer of its
    own (:func:`~dmlc_tpu.ops.sparse.parts_to_csr_host`). No block is
    merged, so nothing here allocates by the batch. ``work()`` is entered
    around the grouping of each incoming block, as in
    :func:`rebatch_blocks`."""
    parts: list = []    # views, total rows pending < batch_size
    pending = 0
    for block in blocks:
        out = []
        with work():
            if len(block):
                parts.append(block)
                pending += len(block)
            while pending >= batch_size:
                take, need = [], batch_size
                while need > 0:
                    p = parts[0]
                    if len(p) <= need:
                        take.append(parts.pop(0))
                        need -= len(p)
                    else:
                        take.append(p.slice(0, need))
                        parts[0] = p.slice(need, len(p))
                        need = 0
                pending -= batch_size
                out.append(take)
        yield from out
    if pending and not drop_remainder:
        yield parts


def _require_bf16_exact(packed_col, src, what: str) -> None:
    """``packed_col`` is a just-assigned bfloat16 aux column, ``src`` the
    float32 source values: raise when the cast lost precision. Shared by
    the local convert-pool pack and the service worker's snapshot-frame
    pack, so no bf16 path can silently corrupt labels/weights."""
    if not np.array_equal(np.asarray(packed_col, dtype=np.float32),
                          np.asarray(src, dtype=np.float32)):
        raise DMLCError(
            f"bfloat16 aux packing: this batch's {what}s are not "
            "bf16-exact — packing would silently corrupt them. Keep the "
            f"{what}s float32-packable (pack_aux=False locally, or an "
            "f32 snapshot geometry on the service) or use "
            "x_dtype='float32' (docs/data.md pack_aux)")


def pack_dense_batches(blocks, batch_size: int, num_col: int,
                       dtype=None, drop_remainder: bool = False):
    """Pack a RowBlock stream into fixed-geometry ``[B, num_col + 2]``
    slabs (features | label | weight) — the exact layout
    :class:`PackedDenseBatch` ships and the snapshot store persists.
    Yields ``(packed, resume_annotation)`` per batch; the epoch tail is
    row-padded to ``B`` (pad rows carry weight 0 -> masked downstream)
    unless ``drop_remainder``. Used by the data service's snapshot frames
    (worker-side packing, docs/service.md) so a fleet can ship
    device-layout bf16 batches at half the CSR wire bytes. A bfloat16
    target validates label/weight losslessness per batch, like the local
    pack path."""
    B, nc = int(batch_size), int(num_col)
    dt = np.dtype(np.float32) if dtype is None else np.dtype(dtype)
    aux_check = dt.kind == "V" or dt.itemsize < 4  # narrower than f32
    for block in rebatch_blocks(iter(blocks), B,
                                drop_remainder=drop_remainder):
        x, y, w = block_to_dense(block, nc,
                                 pad_rows_to=(B if len(block) != B
                                              else None))
        if x.dtype.kind == "i":
            raise DMLCError(
                f"packed dense batches (the service's snapshot frames): "
                f"the source serves {x.dtype} cells, and the [B, num_col + "
                "2] slab is one float array that would round ids above "
                "2**24; serve an integer CSV as block frames "
                "(Dispatcher(snapshot=None)) into DeviceIter(layout="
                "'dense', x_dtype='int32') (docs/service.md)")
        packed = np.empty((B, nc + 2), dt)
        packed[:, :nc] = x
        packed[:, nc] = y
        packed[:, nc + 1] = w
        if aux_check:
            _require_bf16_exact(packed[:, nc], y, "label")
            _require_bf16_exact(packed[:, nc + 1], w, "weight")
        yield packed, getattr(block, "resume_state", None)


_RING_FREE = object()  # sentinel: slot never attached / explicitly released
# the convert pool's name in pool_seconds / pool_events and in its stall
# diagnostic
_POOL_LABEL = "convert"


class _StagingRing:
    """Ring of reusable preallocated host staging buffers.

    Convert workers pack batches into these instead of allocating fresh
    arrays per batch. A slot cycles free -> packing (acquired) -> in-flight
    (attached to the device arrays built from it) -> free again once BOTH
    hold for every one of those arrays:

    * its transfer has completed (``is_ready()``). On a TPU ``device_put``
      returns before the DMA has read host memory — a 64 MiB put returned
      in 0.4 ms, was ready 8 ms later, and the device held bytes written
      to the source AFTER the call (TPU v5e, jax 0.9.0) — and a consumer
      that drops each batch at dispatch can let the array die first. The
      ring keeps the array itself alive until then;
    * it has been garbage-collected (a weakref, taken once the transfer
      is done): the CPU backend may alias the host buffer for the
      array's whole life, so a live array pins its slot.

    Never elapsed time. When every slot is busy a fresh unpooled
    allocation is handed out (counted as a miss): the ring is an
    allocator fast path, never a blocking resource.
    """

    def __init__(self, make_bufs, depth: int, key=None):
        self._make = make_bufs
        self.key = key      # what the buffers were sized for, if it varies
        self._depth = max(1, int(depth))
        self._lock = threading.Lock()
        # [bufs_dict, _RING_FREE | None (acquired) | [array-or-weakref]]
        self._slots: list = []
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _released(handles: list) -> bool:
        """Has every array built from the slot landed AND died? Arrays
        whose transfer is done are downgraded to weakrefs on the way, so
        the ring stops holding them (and their HBM) alive."""
        for i in range(len(handles)):   # no enumerate(): the tuple it
            h = handles[i]              # yields would keep the array alive
            if not isinstance(h, weakref.ref):
                if not h.is_ready():
                    return False
                handles[i] = h = weakref.ref(h)
            if h() is not None:
                return False
        return True

    def acquire(self) -> dict:
        with self._lock:
            for slot in self._slots:
                refs = slot[1]
                if refs is None:  # acquired, not yet attached: busy
                    continue
                if refs is _RING_FREE or self._released(refs):
                    slot[1] = None
                    self.hits += 1
                    return slot[0]
            if len(self._slots) < self._depth:
                bufs = self._make()
                self._slots.append([bufs, None])
                return bufs
            self.misses += 1
            return self._make()

    def attach(self, bufs: dict, handles) -> None:
        """Tie the slot to EVERY device array built from it (a batch can
        fan one slot's buffers into several arrays — x/y/w — and any one
        of them in flight or alive must pin the whole slot);
        ``handles=None`` or empty releases the slot immediately (batch
        dropped before any transfer, e.g. a resume replay)."""
        with self._lock:
            for slot in self._slots:
                if slot[0] is bufs:
                    slot[1] = list(handles) if handles else _RING_FREE
                    return

    def reclaim(self) -> None:
        """Free every slot that was acquired and never attached: the
        workers that held them are gone (the producer was torn down), so a
        ring that outlives its producer calls this in place of dying."""
        with self._lock:
            for slot in self._slots:
                if slot[1] is None:
                    slot[1] = _RING_FREE

    def set_depth(self, depth: int) -> None:
        """Live depth resize (the autotuner's staging-ring follow-on to
        prefetch/convert_ahead changes): growing allows more pooled
        slots to be allocated on demand; shrinking only stops NEW slots —
        already-allocated ones keep recycling (their memory is already
        paid for, and in-flight weakrefs must stay valid)."""
        with self._lock:
            self._depth = max(1, int(depth))

    def stats(self) -> dict:
        with self._lock:
            return {"depth": len(self._slots), "hits": self.hits,
                    "misses": self.misses}


# dense rebatch part descriptors: ("packed", x2d) carries a [n, D+2] slab
# (features|label|weight columns), ("arr", x, y, w_or_None) split views,
# ("blk", RowBlock) defers the CSR->dense scatter to the convert worker

def _plen(part) -> int:
    if part[0] == "arr":
        return len(part[2])
    return len(part[1])


def _pslice(part, a: int, b: int):
    kind = part[0]
    if kind == "packed":
        return ("packed", part[1][a:b])
    if kind == "arr":
        return ("arr", part[1][a:b], part[2][a:b],
                part[3][a:b] if part[3] is not None else None)
    return ("blk", part[1].slice(a, b))


def _adopt_pipeline_scope(source, label: str, max_depth: int = 8) -> None:
    """Stamp a pipeline label onto the thread primitives a parser chain
    built BEFORE its DeviceIter existed (a threaded input split starts
    prefetching at parser construction). Walks the chain's wrapper
    attributes and calls ``adopt_scope`` on every ThreadedIter /
    OrderedWorkerPool found — monotonic None -> label, so primitives that
    already have a scope are untouched."""
    seen = set()
    stack = [(source, 0)]
    while stack:
        obj, depth = stack.pop()
        if obj is None or id(obj) in seen or depth > max_depth:
            continue
        seen.add(id(obj))
        adopt = getattr(obj, "adopt_scope", None)
        if callable(adopt):
            adopt(label)
        for name in ("source", "base", "_base", "_iter", "_pool"):
            stack.append((getattr(obj, name, None), depth + 1))


def _csr_coords_impl(cols, row_ptr):
    """Rebuild BCOO (row, col) coordinate pairs from the CSR wire format.

    ``row_ptr`` is [rows_padded + 1] with pad rows pointing at the real
    nnz, so row id of entry j = #{i >= 1 : row_ptr[i] <= j} — computed as
    a scatter-add of 1 at each row start followed by an inclusive prefix
    sum. Entries past the real nnz count every row and land on the OOB
    row rows_padded, which every BCOO op masks (same padding contract as
    the native (row, col) emit, native/src/api.h CooResult). O(nnz) VPU
    work per batch in exchange for HALF the coordinate bytes over the
    host->device link.
    """
    import jax.numpy as jnp

    nnz = cols.shape[0]
    incr = jnp.zeros((nnz + 1,), jnp.int32).at[row_ptr[1:]].add(
        1, mode="drop")
    rows = jnp.cumsum(incr)[:nnz]
    return jnp.stack([rows, cols], axis=1)


_csr_coords = jax.jit(_csr_coords_impl)


@jax.tree_util.register_pytree_node_class
class PackedDenseBatch:
    """One [B, num_col + 2] device array: features in columns [:num_col],
    label in column num_col, weight in column num_col + 1.

    Shipping the batch as ONE array instead of [x, y, w] removes the
    per-array device_put overhead (measured ~2x on the 3-array put,
    benchmarks/bench_transfer_floor.py aux leg). Registered as a pytree so
    it passes straight into jit: ``x, y, w = batch`` works both eagerly
    and under trace (the slices then fuse into the consumer's graph for
    free — the TPU-first contract: one contiguous HBM buffer, views carved
    where XLA can fuse them). y/w are cast to float32 so consumers see the
    same dtypes as the unpacked path even for bf16-packed batches.
    """

    __slots__ = ("packed", "num_col")

    def __init__(self, packed, num_col: int):
        self.packed = packed
        self.num_col = int(num_col)

    @property
    def x(self):
        return self.packed[:, : self.num_col]

    @property
    def y(self):
        return _device_decode.widen_f32(self.packed[:, self.num_col])

    @property
    def w(self):
        return _device_decode.widen_f32(self.packed[:, self.num_col + 1])

    def __iter__(self):
        return iter((self.x, self.y, self.w))

    def __getitem__(self, i):
        # tuple-compatibility: batch[0]/batch[1]/batch[2] == x/y/w, so
        # consumers written against the split-array contract keep working.
        # Dispatch lazily — building all three would launch discarded
        # slice/cast ops on every single-element access.
        if i == 0 or i == -3:
            return self.x
        if i == 1 or i == -2:
            return self.y
        if i == 2 or i == -1:
            return self.w
        if isinstance(i, slice):
            return (self.x, self.y, self.w)[i]
        raise IndexError(i)

    def __len__(self) -> int:
        # 3, like the (x, y, w) tuple this stands in for — row count is
        # batch.packed.shape[0] / batch.x.shape[0]
        return 3

    def tree_flatten(self):
        return (self.packed,), self.num_col

    @classmethod
    def tree_unflatten(cls, num_col, children):
        return cls(children[0], num_col)


def _plan_position_before(annot: Optional[dict]) -> Optional[dict]:
    """The epoch-plan position just before the block that carries
    ``annot`` (its ``resume_state``, the position just after it), or
    ``None`` where the block was not served by a plan position (no
    annotation, a parser chain's, a sharded cold pass's)."""
    if (not isinstance(annot, dict) or annot.get("kind") != "epoch_plan"
            or "cold" in annot or int(annot.get("pos", 0)) < 1):
        return None
    return dict(annot, pos=int(annot["pos"]) - 1)


class _SnapshotFeed:
    """The warm-snapshot producer in the ``_host_iter`` slot: wraps a
    :class:`~dmlc_tpu.io.snapshot.SnapshotIter` and emits the pool item
    shape ``(host_batch, None, annot, batch_id)`` the consumer fill loop
    expects — no staging bufs (the batch views alias the snapshot mmap;
    numpy pins it via the view base chain until the transfer's arrays die),
    the resume annotation resolved per serving order: the stored pipeline
    annotation for sequential epochs, a ``(seed, epoch, position)``
    plan annotation for plan-ordered ones, and the batch's id
    ``(epoch, position served)``."""

    def __init__(self, feed, start: int = 0, plan_annot=None,
                 epoch: int = 0):
        self._feed = feed
        self._epoch = int(epoch)
        self._pos = int(start)  # plan/sequential position of the next batch
        self._plan_annot = plan_annot  # pos-after -> annot dict (plan order)
        self.served_bytes = 0

    @property
    def stall_seconds(self) -> float:
        return self._feed.stall_seconds

    @stall_seconds.setter
    def stall_seconds(self, value: float) -> None:
        self._feed.stall_seconds = value

    def next(self):
        item = self._feed.next()
        if item is None:
            return None
        host_batch, resume, nbytes = item
        self.served_bytes += nbytes
        bid = (self._epoch, self._pos)
        self._pos += 1
        if self._plan_annot is not None:
            annot = self._plan_annot(self._pos)
        else:
            annot = resume
        return host_batch, None, annot, bid

    def resize_read_workers(self, num_workers: int) -> bool:
        """Autotune passthrough to the snapshot read pool."""
        return self._feed.resize(num_workers)

    def destroy(self) -> None:
        self._feed.destroy()


class DeviceIter:
    """Double-buffered host->device batch iterator with stage attribution.

    Pipeline stages, each ahead of the next:
      1. parser/iterator thread (already prefetched upstream),
      2. serial rebatch stage + a ``convert_workers``-wide
         :class:`OrderedWorkerPool` packing batches into reusable host
         staging buffers (layout conversion for batch N+1 overlaps the
         dispatch of batch N),
      3. this object: ``device_put`` issued ``prefetch`` batches ahead.

    ``stats()['stages']`` decomposes consumer wall time into named costs
    (read / parse / convert / dispatch / device_decode / transfer) — see
    the module docstring; ``stats()['stage_busy']`` carries the raw
    per-stage busy counters the attribution is derived from.
    """

    def __init__(
        self,
        source,
        num_col: int,
        batch_size: int,
        layout: str = "dense",
        *,
        mesh=None,
        data_axis: str = "data",
        shardings=None,
        max_nnz: Optional[int] = None,
        fields: bool = False,
        prefetch: Optional[int] = None,
        convert_ahead: Optional[int] = None,
        convert_workers: Optional[int] = None,
        transfer_sample: Optional[int] = None,
        drop_remainder: bool = False,
        device=None,
        elide_unit_values: bool = False,
        x_dtype: str = "float32",
        nnz_bucket: Optional[int] = None,
        row_bucket: int = 1024,
        csr_wire: bool = True,
        pack_aux: Optional[bool] = None,
        pipeline_label: Optional[str] = None,
        snapshot: Optional[str] = None,
        snapshot_signature: Optional[dict] = None,
        snapshot_quant: Optional[str] = None,
        snapshot_shuffle_seed: Optional[int] = None,
        snapshot_read_workers: Optional[int] = None,
        device_decode: Optional[bool] = None,
        autotune: Optional[bool] = None,
        autotune_interval: Optional[int] = None,
    ):
        check(layout in ("dense", "ell", "bcoo"), f"unknown layout {layout!r}")
        check(batch_size is not None or layout == "bcoo",
              "batch_size=None (natural blocks) requires layout='bcoo'")
        check(layout != "bcoo" or (mesh is None and shardings is None),
              "layout='bcoo' takes no mesh= / shardings=: a ragged batch is "
              "one flat list of slots whose count differs from batch to "
              "batch, with no batch axis of equal shares to place over "
              "chips; shard 'dense' or 'ell' batches")
        # the libfm field plane (docs/data.md): every ELL batch carries
        # RowBlock.field slot for slot beside its indices, through the
        # convert pool, the put and the snapshot tier
        check(not fields or layout == "ell",
              "fields=True carries the libfm field plane in the 'ell' "
              f"batch kind only (layout={layout!r})")
        check(not fields
              or not callable(getattr(source, "resize_pipeline_depth", None)),
              "fields=True: the service wire serves no field plane")
        check(not fields or shardings is None
              or (len(tuple(shardings)) >= 5
                  and tuple(shardings)[4] is not None),
              "fields=True with shardings= needs a fifth sharding, the "
              "field plane's: it would not be placed")
        self.fields = bool(fields)
        self.field_plane_bytes = 0      # of bytes_to_device: the plane's
        # non-zeros block_to_ell cut from rows longer than max_nnz: a
        # wrong max_nnz is seen here, not as silently shorter rows
        self._ell_truncated = 0
        # the books of stats()['ell']: real non-zeros and slots shipped
        self._ell_nnz = 0
        self._ell_slots = 0
        self._ell_truncated_lock = threading.Lock()
        self.source = source
        self.num_col = num_col
        self.batch_size = batch_size
        self.layout = layout
        self.mesh = mesh
        self.data_axis = data_axis
        self.shardings = tuple(shardings) if shardings is not None else None
        self.max_nnz = max_nnz
        # queue-depth knobs resolve through the knob table (explicit arg
        # > DMLC_TPU_PREFETCH / DMLC_TPU_CONVERT_AHEAD env > default), so
        # a config the autotuner emitted is reusable by exporting it
        self.prefetch = _knobs.resolve("prefetch", prefetch)
        self.drop_remainder = drop_remainder
        self.device = device
        # opt-in: skip transferring all-ones value arrays (binary-feature
        # corpora) and synthesize them on device — saves 4 B/nnz of
        # host->HBM traffic. Off by default: each synthesis is one extra
        # device op per batch; whether that pays on a directly attached
        # chip is not measured.
        self.elide_unit_values = bool(elide_unit_values)
        # 'bfloat16' ships dense x at half the bytes in the MXU's preferred
        # operand width; the native repack converts in its single copy pass,
        # the python fallback converts per block (round-to-nearest-even)
        # 'int32' is the plane of id columns (a CSV parsed with
        # dtype=int32: docs/data.md "Integer cells"): the cells reach the
        # device as the integers the text held, through the convert pool
        # and the staging ring, and every block is held to the plane's
        # dtype (_require_plane_dtype): no tier converts between integer
        # and float cells
        check(x_dtype in ("float32", "bfloat16", "int32"),
              f"unknown x_dtype {x_dtype!r}")
        check(x_dtype == "float32" or layout == "dense",
              f"x_dtype={x_dtype!r} applies to the dense layout only")
        self.x_dtype = x_dtype
        self.dense_plane_bytes = 0      # of bytes_to_device: the x plane's
        if x_dtype == "int32":
            check(snapshot_quant is None,
                  "x_dtype='int32': snapshot_quant='int8' quantizes a float "
                  "plane; ids are not quantized")
        # bcoo shape quantization: round nnz (and, in natural-block mode,
        # rows) UP to bucket multiples so batch shapes repeat instead of
        # being unique per batch. A novel-shape transfer costs a fresh
        # transfer plan and a recompile in any downstream jit. The nnz
        # padding uses OUT-OF-BOUNDS coords, which every BCOO op masks —
        # load-bearing for elide_unit_values, where the device synthesizes
        # ones for pad slots too (see block_to_bcoo_host). NOTE: batches
        # then carry mat.nse > true nnz — padding is part of the shape;
        # stats()['bcoo'] counts both.
        # Default (None) derives the bucket: batch_size * max_nnz when both
        # are known (one exact repeating shape), capped at 512k nnz; for
        # fixed batches with no max_nnz a slot a row (batch_size, at least
        # 4096); 16384 for chunk-sized natural blocks. Set 0 to disable
        # (exact shapes, e.g. for interop tests). The cap: batch_size *
        # max_nnz is a ceiling, not a density estimate — for corpora whose
        # rows run far below max_nnz the uncapped product multiplies
        # host->HBM bytes without bound. A slot a row: the pad stays under
        # one slot a row past the fullest batch, and the high-water mark
        # below climbs through few shapes (each one a compile downstream:
        # 19 s for the FM's ragged step) where 4096-slot steps at 65,536
        # rows of 29.4 +- 17 non-zeros would open half a dozen.
        # A FIXED batch_size pads to the stream's high-water mark: every
        # batch takes the largest bucket multiple any batch before it
        # needed (_plan_bcoo_pad_nnz), so the shapes only ever grow, and
        # once an epoch has passed every later epoch of the same rows is
        # ONE shape: a downstream jit compiles in the first epoch and never
        # again. The pad is then (the fullest batch - the mean batch) +
        # under one bucket (PERF.md §5).
        if nnz_bucket is None:
            if batch_size is not None and max_nnz:
                nnz_bucket = min(int(batch_size) * int(max_nnz), 512 * 1024)
            elif batch_size is not None:
                nnz_bucket = max(4096, int(batch_size))
            else:
                nnz_bucket = 16384
        self.nnz_bucket = int(nnz_bucket)
        # nse values already emitted at a fixed batch_size (bucket
        # multiples, ascending: the high-water marks), and the books of
        # stats()['bcoo']: real non-zeros and slots shipped
        self._emitted_nse: set = set()
        self._bcoo_nnz = 0
        self._bcoo_slots = 0
        self.row_bucket = int(row_bucket)
        # fixed-batch bcoo ships cols + row_ptr (8 B a slot less than the
        # (row, col) pairs) and rebuilds the row ids on the device; needs
        # bucketed shapes, as the natural-block emit below does
        self.csr_wire = (bool(csr_wire) and layout == "bcoo"
                         and self.nnz_bucket > 0)
        self._skip_blocks = 0  # producer-put resume: blocks to drop unput
        self._ones_cache: dict = {}  # elided-values ones, keyed by length
        self.stall_seconds = 0.0        # consumer wait for a ready batch
        self.host_stall_seconds = 0.0   # of which: waiting on host convert
        self.batches_fed = 0
        self.bytes_to_device = 0
        # the telemetry scope every span/metric this pipeline causes is
        # labeled with — down to filesystem retries on producer threads.
        # Two concurrent DeviceIters therefore keep fully disjoint books
        # (docs/observability.md).
        self.pipeline_label = (pipeline_label
                               or _telemetry.new_pipeline_label())
        # thread primitives the parser chain already constructed (a
        # threaded input split starts prefetching at parser build, before
        # this pipeline exists) capture the scope NOW, at iterator
        # construction — without this their pre-first-pull work landed in
        # the process-wide books only (the old adoption-window caveat)
        _adopt_pipeline_scope(source, self.pipeline_label)
        # DMLC_TPU_TRACE=chrome:<path> (docs/data.md) dumps the span rings
        # as a Chrome trace on close; profiler annotations need no switch
        # (every telemetry.span carries one)
        trace_mode, trace_path = _telemetry.trace_mode()
        self._trace_export = trace_path if trace_mode == "chrome" else None
        if (layout == "bcoo" and batch_size is None
                and hasattr(source, "set_emit_coo")):
            # ask the parser for device-ready COO batches: coordinate
            # assembly, bucket padding, and unit-value elision move off-GIL
            # into the C++ parse threads; the convert thread then only
            # issues the (async) device_put. Safe to ignore the answer —
            # _convert handles CooBlock and RowBlock alike. csr_wire
            # (default) ships cols + row_ptr instead of (row, col) pairs —
            # half the coordinate bytes over the link; _put_inner rebuilds
            # the row ids on device (a VPU prefix-sum; whether the saved
            # link bytes pay for it here is ROADMAP S7's). Requires shape
            # bucketing: _csr_coords is jit-cached by shape, so exact-shape
            # mode (bucket 0) would retrace per batch — pair wire there.
            csr_wire = csr_wire and self.nnz_bucket > 0 and self.row_bucket > 0
            try:
                source.set_emit_coo(num_col, row_bucket=self.row_bucket,
                                    nnz_bucket=self.nnz_bucket,
                                    elide_unit=self.elide_unit_values,
                                    csr_wire=bool(csr_wire))
            except TypeError:  # sources without the extended signature
                source.set_emit_coo(num_col, row_bucket=self.row_bucket,
                                    nnz_bucket=self.nnz_bucket,
                                    elide_unit=self.elide_unit_values)
        # aux packing (label/weight as two trailing x columns -> ONE
        # device_put per dense batch; PackedDenseBatch). Auto: on for f32
        # single-device dense (lossless always); bf16 packs the aux in
        # bf16 too, so it needs the caller's explicit promise that labels/
        # weights are bf16-exact; mesh batches keep split arrays (their
        # shardings are per-array).
        if pack_aux is None:
            pack_aux = (layout == "dense" and mesh is None
                        and x_dtype == "float32")
        # an integer plane keeps split arrays as a mesh does: the label
        # and weight are float32 and no column of an int32 x holds them
        self.pack_aux = (bool(pack_aux) and layout == "dense"
                         and mesh is None and x_dtype != "int32")
        # bf16 aux packing casts labels/weights to bfloat16 too — sound
        # ONLY when they are bf16-exact. That used to be an undocumented
        # caller promise; it is now VALIDATED at pack time (a round-trip
        # compare per batch) so a lossy corpus raises instead of silently
        # training on corrupted labels (docs/data.md pack_aux).
        self._aux_exact_check = (self.pack_aux
                                 and self.x_dtype == "bfloat16")
        # ---- device-native snapshot store (docs/data.md snapshot) ----
        # cold epochs shadow-write the post-convert batches; warm epochs
        # mmap them straight into the transfer path with zero convert
        # work (a new 'snapshot_read' stage), bounded by transfer instead
        # of host packing (ROADMAP item 3, arXiv:2501.10546).
        if snapshot is None:
            snapshot = getattr(source, "snapshot_path", None)
            if snapshot is not None and snapshot_signature is None:
                snapshot_signature = getattr(source, "snapshot_signature",
                                             None)
        self.snapshot_path = snapshot
        self._snap_sig = snapshot_signature
        self._snap_quant = snapshot_quant
        self._snap_seed = (None if snapshot_shuffle_seed is None
                           else int(snapshot_shuffle_seed))
        self._snap_read_workers = (
            None if snapshot is None
            else _knobs.resolve("snapshot_read_workers",
                                snapshot_read_workers))
        # ---- device-decode tier (docs/data.md three-tier decode) ----
        # armed, warm snapshot epochs (and service snapshot spans)
        # device_put each batch's raw container span VERBATIM and decode
        # in HBM (ops/device_decode) — zero per-batch host numpy decode;
        # host convert busy reads 0 and a 'device_decode' stage appears
        self.device_decode = _knobs.device_decode(device_decode)
        self.device_decode_bytes = 0  # verbatim span bytes transferred
        # span batches by the lowering that decoded them (span_route)
        self._decode_routes = {"pallas": 0, "xla": 0}
        self._snap_epoch = 0    # advances per reset() while snapshot armed
        self._snap_pos0 = 0     # warm start position (mid-epoch restore)
        self._snap_reader = None
        self._snap_writer = None
        self._snap_serving = False   # current producer is the warm feed
        self._snap_seq_restore = False  # serve this epoch sequentially
        self._snap_shadow = True  # a fresh pass may publish the snapshot
        # a restore the snapshot cannot reproduce (e.g. a BLOCK-plan
        # state replayed by the source) suspends warm serving for the
        # rest of the epoch — the seeked source owns the stream
        self._snap_suspend = False
        if snapshot is not None:
            check(batch_size is not None,
                  "snapshot= requires a fixed batch_size: the store "
                  "persists one batch geometry (docs/data.md)")
            check(layout != "bcoo",
                  "snapshot= cannot store layout='bcoo': the store persists "
                  "one batch geometry, and a ragged batch's slot count "
                  "differs from batch to batch; the block cache is the "
                  "kind's warm tier (docs/data.md)")
            check(layout == "dense" or (layout == "ell" and max_nnz),
                  "snapshot v1 stores fixed-geometry batches: layout "
                  "'dense', or 'ell' with max_nnz pinned (docs/io.md)")
            check(mesh is None and shardings is None,
                  "snapshot= serves single-put batches; mesh/shardings "
                  "pipelines are not snapshot-servable")
            check(snapshot_quant in (None, "int8"),
                  f"unknown snapshot_quant {snapshot_quant!r}")
            check(snapshot_quant is None or (layout == "dense"
                                             and self.pack_aux),
                  "snapshot_quant='int8' applies to packed dense "
                  "batches (layout='dense' with pack_aux)")
            src_plan = getattr(source, "plan_state", None) or {}
            check(src_plan.get("shuffle_seed") is None,
                  "snapshot= cannot combine with a source-side epoch "
                  "plan (shuffle_seed on the block cache): the snapshot "
                  "freezes one epoch's batch order — shuffle snapshot "
                  "epochs with snapshot_shuffle_seed= instead "
                  "(docs/data.md)")
        if layout == "dense" and hasattr(source, "set_emit_dense"):
            # ask the parser for HBM-ready dense batches (skips CSR), repacked
            # to this batch size (and target dtype) off-GIL when the native
            # reader is in play; safe to ignore the answer —
            # _host_batches_dense handles all kinds
            try:
                source.set_emit_dense(num_col, batch_rows=batch_size,
                                      dtype=x_dtype,
                                      pack_aux=self.pack_aux)
            except TypeError:  # sources without the extended signature
                source.set_emit_dense(num_col)
        # the host pipeline starts LAZILY on first pull: load_state must be
        # able to arm the skip-counter before the producer thread begins
        # converting/transferring (otherwise resume re-transfers whatever
        # the eager pipeline already prefetched)
        self._convert_ahead = _knobs.resolve("convert_ahead", convert_ahead)
        # conversion-worker pool width (fixed-batch layouts): >= 1. The
        # packing work is numpy slice-assignment (GIL released), so two
        # workers overlap convert-for-N+1 with the consumer's dispatch of
        # N even before true multi-core parallelism.
        self.convert_workers = _knobs.resolve("convert_workers",
                                              convert_workers)
        # transfer-completion sideband: every Nth delivered batch is
        # block_until_ready'd and the wait recorded as the 'transfer'
        # stage — the async-dispatch blind spot sampled instead of
        # invisible. 0 disables.
        if transfer_sample is None:
            transfer_sample = int(
                os.environ.get("DMLC_TPU_TRANSFER_SAMPLE", "32") or 32)
        self.transfer_sample = max(0, int(transfer_sample))
        self._last_wait = 0.0       # the last handed-out batch's wait
        self._host_iter_obj = None  # OrderedWorkerPool | ThreadedIter
        # (device batch, batch id) pairs put and not yet handed out
        self._inflight: deque = deque()
        # a batch's id is (epoch, seq): given where the batch first exists
        # (the serial stage's merge, the snapshot feed's stored position,
        # the natural block) and carried as the labels epoch= / batch= by
        # every span of its life: merge, convert, dispatch /
        # device_decode, next. The epoch counts reset()s; a mid-epoch
        # seek-restore continues the count at the restored batch.
        self._epoch = 0
        self._first_seq = 0
        self._last_bid: Tuple[int, int] = (0, 0)
        # the epoch boundary (_prestart_next_epoch): did this epoch run to
        # its end; has a reset() followed such an end before (the consumer
        # runs epochs back to back); is the producer that is running the
        # NEXT epoch's, ahead of the reset() that will adopt it
        self._ended = False
        self._looped = False
        self._prestarted = False
        self._adopted = False
        self._ended_state: Optional[dict] = None
        self._epochs_prestarted = 0
        # ---- stage attribution state (module docstring) ----
        # raw busy/blocked counters, written by pipeline threads
        # (cache_read: warm block-cache supply, docs/data.md block cache).
        # Both meters are registry-backed under this pipeline's label, so
        # stats(), the pod snapshot, and the trace all read one set of
        # books (docs/observability.md).
        self._busy = StageMeter("read", "cache_read", "snapshot_read",
                                "parse", "convert", "dispatch",
                                "device_decode",
                                metric=_telemetry.STAGE_BUSY_METRIC,
                                scope=self.pipeline_label)
        # consumer-wall attribution (the partition stats() reports)
        self._attr = StageMeter("read", "cache_read", "snapshot_read",
                                "parse", "convert", "dispatch",
                                "device_decode", "transfer",
                                metric=_telemetry.STAGE_WALL_METRIC,
                                scope=self.pipeline_label)
        self._transfer_samples = 0
        self._t_first: Optional[float] = None  # first consumer pull
        self._t_last: Optional[float] = None   # latest consumer activity
        self._ring: Optional[_StagingRing] = None
        self._ring_folded = {"hits": 0, "misses": 0}  # of the live ring
        self._ring_init_lock = threading.Lock()
        # byte-exact resume (SURVEY.md §5.4): blocks annotated by the parser
        # chain carry the source state just after them; the convert thread
        # maps each produced batch to (latest block boundary, rows past it)
        # and the consumer keeps the annotation of the last delivered batch
        self._annot_fifo: deque = deque()
        self._boundaries: deque = deque()
        self._cur_boundary = None          # (rows_at_end, source_state)
        self._last_resume: Optional[dict] = None
        self._drop_rows = 0                # rows to drop after a seek-restore
        self._suppress_before_first = False
        # last trace context seen on a source block (service clients stamp
        # block.trace_ctx from the grant's wire context) — links the
        # dispatch span into the (job, part) trace even though rebatching
        # and the convert pool detach the device_put from the block object
        self._last_trace_ctx: Optional[tuple] = None
        # ---- fault tolerance (docs/resilience.md) ----
        # stream-level retries/resumes happen below, in the filesystems; a
        # retryable error that ESCAPES them (budget exhausted, producer
        # died) re-arms the whole host pipeline at the last delivered batch
        # via the checkpoint machinery, bounded by this policy's attempts.
        self._retry_policy = _resilience.RetryPolicy.from_env()
        # resilience deltas are scoped to THIS pipeline's label: events
        # from a concurrent pipeline (or ambient filesystem use) can no
        # longer contaminate stats()['resilience']
        self._res_base = _resilience.counters_snapshot(self.pipeline_label)
        self.pipeline_restarts = 0
        self.pipeline_giveups = 0
        # lifetime restart/giveup tally: pipeline_restarts is a PER-EPOCH
        # budget counter (reset() zeroes it), so the autotuner's
        # resilience sensor must read this monotonic twin or restarts
        # early in a new epoch hide behind the previous epoch's count
        self._faults_lifetime = 0
        # ---- consumer-side input-wait counter ----
        # every second the consumer MEASURABLY waited for input: the wait
        # for a batch handle (stall_seconds' feed) PLUS the sampled
        # transfer landings — registry-backed under this pipeline's
        # label, so the autotuner (and the pod table) can trust one
        # counter where stall_seconds alone reads 0.000 on a
        # transfer-bound epoch whose waits hide in the async blind spot
        self._input_wait = _telemetry.REGISTRY.counter(
            _telemetry.INPUT_WAIT_METRIC, pipeline=self.pipeline_label)
        # the convert pool's books beside its own (stats()['pool']): the
        # serial stage's seconds inside its `merge` spans, and the staging
        # rings' hits and misses summed over the epochs' rings
        pool = {"pool": _POOL_LABEL, "pipeline": self.pipeline_label}
        self._merge_seconds = _telemetry.REGISTRY.counter(
            _telemetry.POOL_SECONDS_METRIC, state="merge", **pool)
        self._ring_events = {
            kind: _telemetry.REGISTRY.counter(
                _telemetry.POOL_EVENTS_METRIC, kind="ring_" + kind, **pool)
            for kind in ("hits", "misses")}
        self._batches_total = 0  # monotonic across epochs (reset() zeroes
        #                          batches_fed; the tuner needs a cursor)
        # ---- online autotuner (docs/data.md autotune; ROADMAP item 4) --
        # a feedback controller that re-sizes the pipeline's pool widths
        # and queue depths between epochs (and every autotune_interval
        # batches) toward gap_stage == transfer, reading only the
        # registry counters above. Armed by autotune=True or
        # DMLC_TPU_AUTOTUNE=1.
        self.autotuner: Optional[_autotune.AutoTuner] = None
        self._autotune_interval = 0
        self._tune_mark: Optional[dict] = None
        if _knobs.autotune_enabled(autotune):
            self._autotune_interval = _knobs.autotune_interval(
                autotune_interval)
            self.autotuner = _autotune.AutoTuner(
                self._autotune_knobs(), scope=self.pipeline_label)

    @property
    def _host_iter(self):
        if self._host_iter_obj is None:
            # the producer is built lazily by the epoch's first pull: pool
            # start and (warm) the snapshot's opening show as their own span
            with _telemetry.span("producer_start", epoch=self._epoch):
                self._host_iter_obj = self._start_producer()
        return self._host_iter_obj

    def _start_producer(self):
        if (self.snapshot_path is not None and not self._snap_suspend
                and self._open_snapshot()):
            # warm snapshot epoch: the source chain (parse AND convert) is
            # bypassed entirely — batches stream off the snapshot mmap
            # into device_put
            feed = self._snapshot_feed()
            self._snap_serving = True
            return feed
        if self.batch_size is None:
            # natural-block mode: convert + (async) device_put on ONE
            # producer thread — puts must not interleave across workers
            # because the skip-credit resume counts whole blocks
            return ThreadedIter.from_factory(
                self._host_batches, max_capacity=self._convert_ahead)
        if self.snapshot_path is not None and self._snap_shadow:
            # cold snapshot epoch: the convert stage's output tees into
            # the shadow writer (published at epoch end, served warm from
            # the next epoch on)
            self._arm_snapshot_writer()
        return OrderedWorkerPool(
            self._serial_batches, self._convert_work,
            num_workers=self.convert_workers,
            max_ahead=self._convert_ahead,
            counter_label=_POOL_LABEL,
        )

    # ---------------- snapshot store (docs/data.md snapshot) ----------------

    def _snapshot_geometry(self) -> dict:
        """The batch-shape identity a snapshot is bound to: any drift
        (batch size, width, dtype, layout, padding policy, quantization)
        self-invalidates the stored file at open instead of serving
        wrong-shaped batches."""
        geometry = {
            "v": _snapshot.SNAPSHOT_VERSION,
            "batch_size": int(self.batch_size),
            "num_col": int(self.num_col),
            "layout": self.layout,
            "x_dtype": self.x_dtype,
            "pack_aux": bool(self.pack_aux),
            "quant": self._snap_quant,
            "drop_remainder": bool(self.drop_remainder),
            "max_nnz": (int(self.max_nnz)
                        if self.layout == "ell" and self.max_nnz else None),
        }
        if self.fields:
            # a key of its own only when armed: a snapshot without the
            # plane keeps the geometry (and the bytes) it always had
            geometry["fields"] = True
        return geometry

    def _open_snapshot(self) -> bool:
        if self._snap_reader is None:
            self._snap_reader = _snapshot.open_snapshot(
                self.snapshot_path, signature=self._snap_sig,
                geometry=self._snapshot_geometry())
        return self._snap_reader is not None

    def _drop_snap_reader(self) -> None:
        reader, self._snap_reader = self._snap_reader, None
        if reader is not None:
            reader.close()

    def _arm_snapshot_writer(self) -> None:
        if self._snap_writer is None:
            self._snap_writer = _snapshot.SnapshotWriter(
                self.snapshot_path, signature=self._snap_sig,
                geometry=self._snapshot_geometry())

    def _abort_snapshot_writer(self) -> None:
        writer, self._snap_writer = self._snap_writer, None
        if writer is not None:
            writer.abort()

    def _finish_snapshot_writer(self) -> None:
        """End of a complete cold pass: fsync + atomically publish the
        shadow-written snapshot (idempotent; a partial pass never gets
        here — mid-epoch restores abort the writer instead)."""
        writer, self._snap_writer = self._snap_writer, None
        if writer is not None:
            writer.finish()

    def _write_snapshot_batch(self, host_batch, annot) -> None:
        """Tee one converted batch into the shadow writer (consumer
        thread — production order IS delivery order here). ``dense_packed``
        batches optionally quantize to int8 + per-column scale."""
        kind = host_batch[0]
        arrays = host_batch[1:]
        if self._snap_quant == "int8" and kind == "dense_packed":
            q, scale = _snapshot.quantize_int8(
                np.asarray(arrays[0], dtype=np.float32))
            kind, arrays = "dense_packed_q8", (q, scale)
        self._snap_writer.add_batch(kind, arrays, rows=self.batch_size,
                                    resume=annot)

    def _snapshot_feed(self) -> _SnapshotFeed:
        """Build the warm feed for this epoch: sequential, or — with a
        ``snapshot_shuffle_seed`` armed — the epoch plan's permutation
        over snapshot BATCH indices (PR 8's planner, one tier up:
        :func:`dmlc_tpu.data.epoch.block_permutation` keyed by
        ``(seed, epoch)``), with ``(seed, epoch, position)`` resume
        annotations so mid-epoch restores replay byte-identically."""
        from dmlc_tpu.data import epoch as _epoch

        reader = self._snap_reader
        order = None
        plan_annot = None
        if self._snap_seed is not None and not self._snap_seq_restore:
            order = _epoch.block_permutation(
                self._snap_seed, self._snap_epoch, reader.num_batches)
            seed, ep = self._snap_seed, self._snap_epoch

            def plan_annot(pos):
                return {"source": _epoch.plan_state_dict(
                    seed, 0, ep, pos, 0, 1, unit="batch"),
                    "skip_rows": 0}
        start = self._snap_pos0
        self._snap_pos0 = 0
        feed = _snapshot.SnapshotIter(
            reader, order=order, start=start,
            read_workers=self._snap_read_workers,
            on_read=lambda dt: self._add_busy("snapshot_read", dt),
            raw=self.device_decode)
        return _SnapshotFeed(feed, start=start, plan_annot=plan_annot,
                             epoch=self._epoch)

    def _invalidate_snapshot(self) -> None:
        """A warm batch failed its integrity check: classified snapshot
        corruption — drop the file so the restart path re-arms COLD from
        the source (sequential states) or rebuilds deterministically
        (plan states); the stream stays byte-identical either way."""
        _resilience.record_event("snapshot_corruptions")
        self._drop_snap_reader()  # releases the reader's eviction pin
        _block_cache._artifact_store(self.snapshot_path).discard(
            self.snapshot_path)

    def _rebuild_snapshot(self) -> None:
        """Deterministic full rebuild (vanished/corrupt snapshot under a
        plan-position restore): drive the cold convert pipeline end to
        end, writing every batch and delivering none — parsing and
        packing are deterministic, so the rebuilt batches are
        byte-identical to the lost ones and the plan stream continues
        unbroken at the same position."""
        _resilience.record_event("snapshot_rebuilds")
        self._drop_snap_reader()  # releases the reader's eviction pin
        _block_cache._artifact_store(self.snapshot_path).discard(
            self.snapshot_path)
        self._teardown_producer()
        self._snap_serving = False
        self._abort_snapshot_writer()
        self._arm_snapshot_writer()
        pool = OrderedWorkerPool(
            self._serial_batches, self._convert_work,
            num_workers=self.convert_workers,
            max_ahead=self._convert_ahead, counter_label=_POOL_LABEL)
        try:
            while True:
                item = pool.next()
                if item is None:
                    break
                host_batch, bufs, annot, _bid = item
                self._write_snapshot_batch(host_batch, annot)
                if bufs is not None and self._ring is not None:
                    self._ring.attach(bufs, None)  # nothing transferred
            self._finish_snapshot_writer()
        except BaseException:
            self._abort_snapshot_writer()
            raise
        finally:
            pool.destroy()
        self._teardown_producer()  # clear the silent pass's bookkeeping
        check(self._open_snapshot(),
              f"snapshot {self.snapshot_path}: rebuild did not publish a "
              "readable snapshot")

    # ------------- online autotuner (docs/data.md autotune) -------------

    def _autotune_knobs(self) -> list:
        """The live-resizable knob set for this pipeline's shape: queue
        depths always; the parse tier when the source chain can resize
        (ParallelTextParser, possibly behind a BlockCacheIter); the plan
        and snapshot read pools when those tiers exist. convert_workers
        stays static (one knob per stage — convert pressure grows
        convert_ahead; docs/data.md)."""
        knobs = [
            _autotune.Knob("prefetch", lambda: self.prefetch,
                           self._apply_prefetch),
            _autotune.Knob("convert_ahead", lambda: self._convert_ahead,
                           self._apply_convert_ahead),
        ]
        if callable(getattr(self.source, "resize_parse_workers", None)):
            pstats = None
            fn = getattr(self.source, "parallel_stats", None)
            if callable(fn):
                try:
                    pstats = fn()
                except Exception:  # noqa: BLE001 - sensor, never fatal
                    pstats = None
            # seed order: the live pool's real width > the width the
            # source chain will build with (BlockCacheIter stamps the
            # resolved hint before its lazy base exists) > table default
            self._knob_parse_workers = int(
                (pstats or {}).get("parse_workers")
                or getattr(self.source, "parse_workers_hint", 0)
                or _knobs.resolve("parse_workers"))
            knobs.append(_autotune.Knob(
                "parse_workers", lambda: self._knob_parse_workers,
                self._apply_parse_workers))
        if callable(getattr(self.source, "resize_plan_read_workers",
                            None)):
            knobs.append(_autotune.Knob(
                "plan_read_workers",
                lambda: int(getattr(self.source, "plan_read_workers",
                                    0) or _knobs.resolve(
                                        "plan_read_workers")),
                self._apply_plan_read_workers))
        if self.snapshot_path is not None:
            knobs.append(_autotune.Knob(
                "snapshot_read_workers",
                lambda: int(self._snap_read_workers
                            or _knobs.resolve("snapshot_read_workers")),
                self._apply_snapshot_read_workers))
        if callable(getattr(self.source, "resize_pipeline_depth", None)):
            # service-fed pipeline: the read stage's relief knob is the
            # client's pipelined fetch window (STAGE_KNOB_FALLBACK —
            # there is no local parse fan-out to widen)
            knobs.append(_autotune.Knob(
                "service_pipeline_depth",
                lambda: int(getattr(self.source, "pipeline_depth", 0)
                            or _knobs.resolve("service_pipeline_depth")),
                self.source.resize_pipeline_depth))
        return knobs

    def _apply_prefetch(self, n: int) -> bool:
        self.prefetch = max(1, int(n))
        self._refresh_ring_depth()
        return True  # takes effect on the consumer's next _fill

    def _apply_convert_ahead(self, n: int) -> bool:
        self._convert_ahead = max(1, int(n))
        obj = self._host_iter_obj
        if isinstance(obj, OrderedWorkerPool):
            obj.set_max_ahead(self._convert_ahead)
        elif isinstance(obj, ThreadedIter):
            obj.set_capacity(self._convert_ahead)
        self._refresh_ring_depth()
        return True

    def _apply_parse_workers(self, n: int) -> bool:
        fn = getattr(self.source, "resize_parse_workers", None)
        if not callable(fn) or not fn(int(n)):
            return False  # tier bypassed (warm cache) or not resizable
        self._knob_parse_workers = max(1, int(n))
        return True

    def _apply_plan_read_workers(self, n: int) -> bool:
        fn = getattr(self.source, "resize_plan_read_workers", None)
        return callable(fn) and bool(fn(int(n)))

    def _apply_snapshot_read_workers(self, n: int) -> bool:
        self._snap_read_workers = max(1, int(n))
        obj = self._host_iter_obj
        if isinstance(obj, _SnapshotFeed):
            obj.resize_read_workers(self._snap_read_workers)
        return True

    def _autotune_mark_now(self) -> dict:
        """One sensor reading — the tuner's windows are deltas between
        consecutive marks, all read off the registry-backed books."""
        res = _resilience.counters_snapshot(self.pipeline_label)
        return {
            "t": get_time(),
            "batches": self._batches_total,
            "busy": self._busy.seconds(),
            "transfer_wall": self._attr.seconds().get("transfer", 0.0),
            "input_wait": self._input_wait.value,
            # monotonic: registry counters never rewind, and the restart
            # tally is the lifetime twin, not the per-epoch budget —
            # otherwise a new epoch's early restarts would clamp away
            # under the previous epoch's count and skip the cooldown
            "res": sum(res.values()) + self._faults_lifetime,
        }

    def _autotune_step(self) -> None:
        """Run one controller step over the window since the last mark
        (called at every reset() epoch boundary, and every
        ``autotune_interval`` delivered batches)."""
        if self.autotuner is None:
            return
        mark, now = self._tune_mark, self._autotune_mark_now()
        self._tune_mark = now
        if mark is None:
            return  # first mark: no window yet
        busy = {k: max(0.0, now["busy"].get(k, 0.0) - mark["busy"].get(k, 0.0))
                for k in now["busy"]}
        self.autotuner.step({
            "wall": now["t"] - mark["t"],
            "batches": now["batches"] - mark["batches"],
            "input_wait": max(0.0, now["input_wait"] - mark["input_wait"]),
            "busy": busy,
            # the sampled transfer sideband scaled to the whole window:
            # every transfer_sample-th batch blocks until its bytes land
            "transfer_est": max(0.0, now["transfer_wall"]
                                - mark["transfer_wall"])
            * max(1, self.transfer_sample),
            "resilience_events": max(0, now["res"] - mark["res"]),
        })

    def _ring_depth(self) -> int:
        # every buffer that can be referenced concurrently: pool-ahead
        # converted batches + put-issued prefetch + one per worker
        # mid-pack + slack
        return (self._convert_ahead + self.prefetch
                + self.convert_workers + 2)

    def _refresh_ring_depth(self) -> None:
        if self._ring is not None:
            self._ring.set_depth(self._ring_depth())

    # ---------------- host side ----------------

    def _add_busy(self, stage: str, seconds: float) -> None:
        self._busy.add(stage, seconds)

    def _stage_span(self, stage: str, **labels) -> _telemetry.span:
        """One pipeline stage's span: the duration it records is the
        duration its ``stage_busy`` counter gets."""
        return _telemetry.span(
            stage, book=functools.partial(self._busy.add, stage), **labels)

    def _blocks(self) -> Iterator[RowBlock]:
        if self._suppress_before_first:
            # seek-restored: the source already sits at the resume position
            self._suppress_before_first = False
        else:
            self.source.before_first()
        stage_fn = getattr(self.source, "stage_seconds", None)
        while True:
            # supply-wait attribution: time blocked on the source, split
            # read vs parse via the source's own stage counters when it
            # has them (the Python parser chain); the fused native reader
            # reports none, so its whole supply cost lands under 'parse'
            # (read+parse in one C++ pipeline — documented in docs/data.md)
            s0 = stage_fn() if stage_fn is not None else None
            with _telemetry.span("parse") as sp:
                blk = self.source.next_block()
                s1 = stage_fn() if stage_fn is not None else None
                if s1 is not None and any(
                        s1.get(k, 0.0) > s0.get(k, 0.0)
                        for k in ("read", "cache_read", "parse")):
                    # the parser-side span sites fired in this window and
                    # are on the ring already. Otherwise (the fused native
                    # supply, read+parse in one C++ pipeline, with or
                    # without a BlockCacheIter in front) the supply wait IS
                    # the 'parse' span — exactly what the busy attribution
                    # charges it to below; the profiler's timeline has the
                    # wait under that name either way
                    sp.skip_ring()
            dt = sp.dt
            read = cache_read = 0.0
            if s1 is not None:
                read = min(max(0.0, s1["read"] - s0["read"]), dt)
                # warm block-cache supply (mmap read + crc) reports under
                # its own stage — a warm epoch's "parse" is then honestly
                # ~zero, which is the whole claim of the cache
                cache_read = min(
                    max(0.0, s1.get("cache_read", 0.0)
                        - s0.get("cache_read", 0.0)),
                    dt - read)
            self._add_busy("read", read)
            self._add_busy("cache_read", cache_read)
            self._add_busy("parse", dt - read - cache_read)
            if blk is None:
                return
            ctx = getattr(blk, "trace_ctx", None)
            if ctx is not None:
                self._last_trace_ctx = ctx
            yield blk

    def _tracked_blocks(self) -> Iterator[RowBlock]:
        """Source blocks with (a) a resume-prefix drop after a seek-restore
        and (b) block-boundary bookkeeping for byte-exact checkpoints."""
        self._boundaries.clear()
        self._cur_boundary = None
        rows = 0
        drop = self._drop_rows
        self._drop_rows = 0
        first = True
        for block in self._blocks():
            # read the annotation BEFORE any drop-slice: it marks the
            # position AFTER the block, which the tail slice still ends at
            annot = getattr(block, "resume_state", None)
            if first:
                first = False
                before = _plan_position_before(annot)
                if before is not None:
                    # a plan-served stream: the position BEFORE its first
                    # block is the plan's too, so a checkpoint taken inside
                    # that block names (seed, epoch, pos) as every later one
                    # does. A batch count would be replayed from the epoch
                    # start of whatever pipeline restores it — another
                    # epoch's order in a fresh one
                    self._boundaries.append((-drop, before))
            if drop > 0:
                if drop >= len(block):
                    drop -= len(block)
                    continue
                block = block.slice(drop, len(block))
                drop = 0
            rows += len(block)
            if annot is not None:
                self._boundaries.append((rows, annot))
            self._require_plane_dtype(block)
            yield block

    def _require_plane_dtype(self, block) -> None:
        """Hold a source block's cells to this pipeline's plane: integer
        cells (a CSV parsed with ``dtype=int32|int64``) feed a dense plane
        of their own dtype and nothing else, and an integer plane takes
        nothing else. Raised here, where the block arrives, for the
        sources that cannot say at construction what they will serve (a
        block cache, a service): never converted."""
        cells = block.x if isinstance(block, DenseBlock) else getattr(
            block, "value", None)
        kind = "f" if cells is None else cells.dtype.kind
        if kind != "i" and self.x_dtype != "int32":
            return
        if (self.layout == "dense" and cells is not None
                and str(cells.dtype) == self.x_dtype):
            return
        raise DMLCError(
            f"DeviceIter(layout={self.layout!r}, x_dtype={self.x_dtype!r}): "
            f"the source serves "
            f"{'no' if cells is None else str(cells.dtype)} cells; integer "
            "cells reach the device only as a dense plane of their own "
            "dtype (layout='dense', x_dtype='int32' over a CSV parsed with "
            "dtype=int32), and an integer plane takes no float cells: "
            "nothing converts between them (docs/data.md, Integer cells)")

    def _push_annot(self, rows_emitted: int) -> Optional[dict]:
        """Record the resume annotation for the batch ending at
        ``rows_emitted`` (rows of real data since stream/resume start).
        Returns the annotation so the serial stage can also ride it on
        the work item (the snapshot shadow writer stores it per batch)."""
        while self._boundaries and self._boundaries[0][0] <= rows_emitted:
            self._cur_boundary = self._boundaries.popleft()
        if self._cur_boundary is None:
            self._annot_fifo.append(None)
            return None
        r, state = self._cur_boundary
        annot = {"source": state, "skip_rows": rows_emitted - r}
        self._annot_fifo.append(annot)
        return annot

    def _host_batches(self):
        # natural-block mode only (BCOO interop: nnz varies per batch
        # anyway, so fixed-shape rebatching buys no compile reuse — skip
        # the merge/slice copies and convert parser blocks as they come).
        # device_put is issued HERE on the convert thread (it is async:
        # returns a handle while the DMA proceeds), so the consumer thread
        # only pops ready handles — one pipeline thread instead of a GIL
        # ping-pong between convert and put
        epoch = self._epoch
        for seq, block in enumerate(self._blocks()):
            if self._skip_blocks > 0:
                # resume fast-path: skip without converting/transferring
                self._skip_blocks -= 1
                yield _SKIPPED
                continue
            bid = (epoch, seq)      # a natural block is its own batch
            with self._stage_span("convert", epoch=epoch, batch=seq):
                hb = self._convert(block)
            yield self._put(hb, None, bid), bid

    def _serial_batches(self):
        """The pool's SERIAL stage: pull blocks, rebatch to fixed size,
        emit per-batch work descriptors (no per-batch copies here — the
        packing/conversion runs in the pool's parallel stage). Whatever
        time this stage spends beyond waiting on the source (merge/slice
        bookkeeping) is charged to 'convert'; where that work runs it is
        a ``merge`` span (:meth:`_merge_span`), labeled with the batch it
        works towards. Every descriptor ends with its batch's id."""
        inner = (self._serial_batches_dense() if self.layout == "dense"
                 else self._serial_batches_sparse())
        while True:
            b0 = self._busy.seconds()
            t0 = get_time()
            try:
                item = next(inner)
            except StopIteration:
                return
            dt = get_time() - t0
            b1 = self._busy.seconds()
            # supply = everything the SOURCE spent inside this pull —
            # including warm cache reads, which previously leaked into
            # 'convert' and inflated it by the cache_read amount
            supply = ((b1["read"] - b0["read"])
                      + (b1["parse"] - b0["parse"])
                      + (b1["cache_read"] - b0["cache_read"]))
            self._add_busy("convert", max(0.0, dt - supply))
            yield item

    def _merge_span(self, epoch: int, seq: int) -> _telemetry.span:
        """The serial stage's own work towards batch ``(epoch, seq)``, on
        the ring and the profiler's timeline where it runs. Its seconds
        are inside what :meth:`_serial_batches` charges to 'convert' and
        have a counter of their own (``stats()['pool']['merge_seconds']``)."""
        return _telemetry.span("merge", book=self._merge_seconds.inc,
                               epoch=epoch, batch=seq)

    def _take_first_seq(self) -> int:
        seq, self._first_seq = self._first_seq, 0
        return seq

    def _serial_batches_sparse(self):
        emitted = 0
        epoch, seq = self._epoch, self._take_first_seq()
        if self.layout == "bcoo" and self.csr_wire:
            # the CSR wire is packed from the source's own blocks: the
            # serial stage only groups row-range views, and the one copy a
            # slot takes is the worker's, into a recycled buffer
            # (_pack_csr_parts). The pad is planned HERE, as below
            for parts in rebatch_parts(
                self._tracked_blocks(), self.batch_size, self.drop_remainder,
                work=lambda: self._merge_span(epoch, seq),
            ):
                emitted += sum(len(p) for p in parts)
                annot = self._push_annot(emitted)
                pad = self._plan_bcoo_pad_nnz(
                    sum(len(p.index) for p in parts))
                yield ("csr_parts", parts, pad, annot, (epoch, seq))
                seq += 1
            return
        for block in rebatch_blocks(
            self._tracked_blocks(), self.batch_size, self.drop_remainder,
            work=lambda: self._merge_span(epoch, seq),  # seq: as it stands
        ):
            emitted += len(block)
            annot = self._push_annot(emitted)
            # bcoo nnz-bucket planning stays HERE, in stream order: the
            # tail batch pads its nse into the set of already-emitted
            # shapes, which must be complete by then — pool workers
            # convert out of order, so they cannot own this bookkeeping
            pad = (self._plan_bcoo_pad_nnz(len(block.index))
                   if self.layout == "bcoo" else None)
            yield ("convert_block", block, pad, annot, (epoch, seq))
            seq += 1

    def _serial_batches_dense(self):
        """Dense serial stage: group incoming blocks into exact-B part
        lists using views only (DenseBlock/RowBlock slices); the per-batch
        copy — one packing pass into a staging-ring buffer — is deferred
        to the convert workers (:meth:`_pack_dense_parts`)."""
        B = self.batch_size
        parts: list = []  # part descriptors, total rows pending < B
        pending = 0
        emitted = 0
        epoch, seq = self._epoch, self._take_first_seq()

        def batch(kind, payload, rows):
            # one descriptor: its rows counted, its annotation pushed, its
            # id taken (called inside the block's merge span)
            nonlocal emitted, seq
            emitted += rows
            item = (kind, payload, self._push_annot(emitted), (epoch, seq))
            seq += 1
            return item

        for block in self._tracked_blocks():
            out = []
            with self._merge_span(epoch, seq):
                packed = isinstance(block, DenseBlock) and block.packed
                if packed and not parts and len(block) == B:
                    # native packed batch at exactly B rows: zero further
                    # host work — the whole (x|label|weight) batch is ONE
                    # array
                    span = getattr(block, "device_span", None)
                    if (span is not None and self.device_decode
                            and self.snapshot_path is None):
                        # a service snapshot frame: the service
                        # client kept the frame's verbatim payload bytes +
                        # layout — ship the raw span and decode in HBM
                        # instead of device_put'ing the host-decoded view.
                        # (With a local snapshot tee armed the host arrays
                        # are still needed by the shadow writer, so keep
                        # the decoded route.)
                        out.append(batch("span_ready", span, B))
                    else:
                        out.append(batch("dense_ready", block.x, B))
                elif packed and not parts and len(block) < B:
                    # partial packed block — for the native reader this
                    # only occurs at the stream tail (flush) or right
                    # before an error surfaces, so treat it as the epoch
                    # remainder: dropped under drop_remainder, else padded
                    # into a full packed batch so the epoch's pytree kind
                    # and shape stay uniform (pad rows carry weight 0 ->
                    # masked)
                    if not self.drop_remainder:
                        out.append(batch("dense_parts",
                                         [("packed", block.x)], len(block)))
                else:
                    if packed:
                        # parts pending from non-packed blocks (mixed
                        # engines) or an oversize block: keep the packed
                        # slab as a part — the pack stage reads its
                        # feature/label/weight columns
                        parts.append(("packed", block.x))
                    elif isinstance(block, DenseBlock):
                        parts.append(("arr", block.x, block.label,
                                      block.weight))
                    else:
                        parts.append(("blk", block))
                    pending += len(block)
                    while pending >= B:
                        take, need = [], B
                        while need > 0:
                            p = parts[0]
                            n = _plen(p)
                            if n <= need:
                                take.append(parts.pop(0))
                                need -= n
                            else:
                                take.append(_pslice(p, 0, need))
                                parts[0] = _pslice(p, need, n)
                                need = 0
                        pending -= B
                        out.append(batch("dense_parts", take, B))
            yield from out
        if pending and not self.drop_remainder:
            yield batch("dense_parts", parts, pending)

    def _convert_work(self, item):
        """The pool's PARALLEL stage: per-batch layout conversion/packing.
        Returns ``(host_batch, staging_bufs_or_None, resume_annot,
        batch_id)`` — the bufs ride to :meth:`_put` so the ring slot can
        be tied to the device array; the annotation rides to the snapshot
        shadow writer; the id (the descriptor's last element) labels this
        span and rides on to the put and the hand-out."""
        bid = item[-1]
        with self._stage_span("convert", epoch=bid[0], batch=bid[1]):
            kind = item[0]
            if kind == "dense_ready":
                return ("dense_packed", item[1]), None, item[2], bid
            if kind == "span_ready":
                # (raw u8 payload, layout, stored kind) from the
                # service client — already device-decodable, no host
                # conversion at all
                raw, layout, skind = item[1]
                return (("device_span", raw, layout, skind), None, item[2],
                        bid)
            if kind == "dense_parts":
                hb, bufs = self._pack_dense_parts(item[1])
                return hb, bufs, item[2], bid
            if kind == "csr_parts":
                hb, bufs = self._pack_csr_parts(item[1], item[2])
                return hb, bufs, item[3], bid
            # ("convert_block", block, bcoo pad plan, annot, id)
            return (self._convert(item[1], pad_plan=(item[2],)), None,
                    item[3], bid)

    def _staging_ring(self) -> _StagingRing:
        # called concurrently by pool workers: double-checked under the
        # ring-init lock, or two rings would race into existence and the
        # loser's buffers could never recycle (attach() would scan the
        # survivor and no-op)
        if self._ring is None:
            with self._ring_init_lock:
                if self._ring is None:
                    B, nc = self.batch_size, self.num_col
                    xdt = self._x_np_dtype()
                    if self.pack_aux:
                        def make():
                            return {"packed": np.empty((B, nc + 2), xdt)}
                    else:
                        def make():
                            return {"x": np.empty((B, nc), xdt),
                                    "y": np.empty(B, np.float32),
                                    "w": np.empty(B, np.float32)}
                    self._ring_folded = {"hits": 0, "misses": 0}
                    self._ring = _StagingRing(make, self._ring_depth())
        return self._ring

    def _csr_bufs(self, nnz_out: int) -> Optional[dict]:
        """A staging-ring slot for a CSR-wire batch of ``nnz_out`` slots,
        or None (the caller allocates) where the ring holds another count.
        The count only grows (_plan_bcoo_pad_nnz), so a larger one replaces
        the ring and a worker still on a smaller one goes without. This
        ring OUTLIVES its producer (:meth:`_teardown_producer`): a new
        epoch's first batches are packed into memory the last epoch already
        touched, where fresh arrays of this size are page faults by the
        thousand (PERF.md §6 PR 37)."""
        ring = self._ring
        if ring is None or ring.key < nnz_out:
            with self._ring_init_lock:
                ring = self._ring
                if ring is None or ring.key < nnz_out:
                    B = self.batch_size

                    def make():
                        return {"cols": np.empty(nnz_out, np.int32),
                                "row_ptr": np.empty(B + 1, np.int32),
                                "vals": np.empty(nnz_out, np.float32),
                                "label": np.empty(B, np.float32),
                                "weight": np.empty(B, np.float32)}
                    self._fold_ring_events()
                    self._ring_folded = {"hits": 0, "misses": 0}
                    ring = self._ring = _StagingRing(
                        make, self._ring_depth(), key=nnz_out)
        return ring.acquire() if ring.key == nnz_out else None

    def _pack_csr_parts(self, parts, pad_nnz: int):
        """One packing pass: the batch's row-range views into a staging
        slot as the CSR wire, rows padded to ``batch_size`` and slots to
        the planned count. Returns the host batch + its ring bufs."""
        bufs = self._csr_bufs(pad_nnz)
        return ("bcoo_csr",) + parts_to_csr_host(
            parts, self.num_col, pad_rows_to=self.batch_size,
            unit_values_as_none=self.elide_unit_values,
            pad_nnz_to=pad_nnz, out=bufs), bufs

    def _part_xyw(self, part):
        if part[0] == "arr":
            return part[1], part[2], part[3]
        # ("blk", RowBlock): the CSR->dense scatter, on the worker
        return block_to_dense(part[1], self.num_col, copy=False)

    def _pack_dense_parts(self, parts):
        """One packing pass: copy part views into a staging-ring buffer
        (slice assignment casts to the target dtype in the same pass) and
        zero-fill rows past the parts' total (the epoch-tail pad). Returns
        the host batch + its ring bufs."""
        B, nc = self.batch_size, self.num_col
        bufs = self._staging_ring().acquire()
        pos = 0
        if self.pack_aux:
            xp = bufs["packed"]
            for p in parts:
                n = _plen(p)
                if p[0] == "packed":
                    xp[pos:pos + n] = p[1]
                else:
                    x, y, w = self._part_xyw(p)
                    xp[pos:pos + n, :nc] = x[:, :nc] if x.shape[1] > nc else x
                    xp[pos:pos + n, nc] = y
                    if w is None:
                        xp[pos:pos + n, nc + 1] = 1.0
                    else:
                        xp[pos:pos + n, nc + 1] = w
                    if self._aux_exact_check:
                        # the slice assignment above just cast label/
                        # weight to bfloat16 — verify the round trip is
                        # lossless NOW, instead of silently training on
                        # corrupted aux values (the old undocumented
                        # caller promise, made checkable)
                        self._require_bf16_exact(
                            xp[pos:pos + n, nc], y, "label")
                        if w is not None:
                            self._require_bf16_exact(
                                xp[pos:pos + n, nc + 1], w, "weight")
                pos += n
            if pos < B:
                xp[pos:] = 0  # pad rows: weight 0 -> masked downstream
            return ("dense_packed", xp), bufs
        xb, yb, wb = bufs["x"], bufs["y"], bufs["w"]
        for p in parts:
            n = _plen(p)
            if p[0] == "packed":
                xb[pos:pos + n] = p[1][:, :nc]
                yb[pos:pos + n] = p[1][:, nc]
                wb[pos:pos + n] = p[1][:, nc + 1]
            else:
                x, y, w = self._part_xyw(p)
                xb[pos:pos + n] = x[:, :nc] if x.shape[1] > nc else x
                yb[pos:pos + n] = y
                if w is None:
                    wb[pos:pos + n] = 1.0
                else:
                    wb[pos:pos + n] = w
            pos += n
        if pos < B:
            xb[pos:] = 0
            yb[pos:] = 0
            wb[pos:] = 0
        return ("dense", xb, yb, wb), bufs

    # one guard for every bf16 aux-packing site (module docstring)
    _require_bf16_exact = staticmethod(_require_bf16_exact)

    def _x_np_dtype(self):
        if self.x_dtype == "bfloat16":
            from dmlc_tpu.native import bf16_dtype

            return bf16_dtype()
        return np.dtype(self.x_dtype)

    def _plan_bcoo_pad_nnz(self, nnz: int) -> Optional[int]:
        """nnz-bucket pad target for a bcoo batch of ``nnz`` non-zeros, and
        the books of ``stats()['bcoo']``. At a fixed ``batch_size`` the
        target is the stream's high-water mark: the batch's own bucket
        multiple or the largest emitted so far, whichever is more. A batch
        fuller than
        every one before it opens one new shape (a fresh transfer plan and
        a downstream jit recompile); every other batch, the short tail of
        an epoch included, repeats the last, and from the second epoch of
        the same rows on there is one shape. MUST run in stream order (the
        serial stage): pool workers convert out of order."""
        self._bcoo_nnz += nnz
        if not self.nnz_bucket:
            self._bcoo_slots += nnz
            return None
        pad_nnz = -(-max(nnz, 1) // self.nnz_bucket) * self.nnz_bucket
        if self.batch_size is not None:
            pad_nnz = max(pad_nnz, max(self._emitted_nse, default=0))
            self._emitted_nse.add(pad_nnz)
        self._bcoo_slots += pad_nnz
        return pad_nnz

    def _convert(self, block: RowBlock, pad_plan: Optional[tuple] = None):
        if isinstance(block, CooBlock):
            # native COO emit: already device-layout (coords/values/label/
            # weight assembled + bucket-padded off-GIL) — nothing to do here
            self._bcoo_nnz += block.nnz
            self._bcoo_slots += len(block.coords)
            if block.row_ptr is not None:
                return ("bcoo_csr", block.coords, block.row_ptr,
                        block.values, block.label, block.weight, block.shape)
            return ("bcoo", block.coords, block.values, block.label,
                    block.weight, block.shape)
        pad = (self.batch_size
               if self.batch_size is not None and len(block) != self.batch_size
               else None)
        if self.layout == "dense":
            x, y, w = block_to_dense(block, self.num_col, pad_rows_to=pad)
            return ("dense", x, y, w)
        if self.layout == "ell":
            cut = ell_truncated_slots(block, self.max_nnz)
            ell = block_to_ell(block, self.num_col, max_nnz=self.max_nnz,
                               pad_rows_to=pad, fields=self.fields)
            with self._ell_truncated_lock:
                self._ell_truncated += cut
                self._ell_nnz += len(block.index) - cut
                self._ell_slots += ell.indices.size
            return ("ell",) + tuple(a for a in ell if a is not None)
        # bcoo: all host-side work (coords/values/label assembly) happens
        # here on the convert thread; the device transfer is async
        if pad is None and self.batch_size is None and self.row_bucket:
            # natural-block mode: quantize the row dimension too
            pad = -(-len(block) // self.row_bucket) * self.row_bucket
        # nse planning: precomputed in stream order by the serial stage
        # (pool mode); computed here for the single-thread natural mode
        pad_nnz = (pad_plan[0] if pad_plan is not None
                   else self._plan_bcoo_pad_nnz(len(block.index)))
        return ("bcoo",) + block_to_bcoo_host(
            block, self.num_col, pad_rows_to=pad,
            unit_values_as_none=self.elide_unit_values,
            pad_nnz_to=pad_nnz)

    # ---------------- device side ----------------

    def _ones_for(self, n: int):
        """Device ones for an elided-value batch (binary-feature corpora):
        created on the SAME device the puts target (BCOO must not mix
        committed arrays across devices) and CACHED per length — every
        batch in an nnz bucket shares the identical ones array, so one
        device allocation serves the whole epoch instead of one dispatch
        per batch. With nnz_bucket=0 (exact shapes) every batch could pin
        a new length forever — don't cache there."""
        dv = self._ones_cache.get(n)
        if dv is None:
            if self.device is not None:
                with jax.default_device(self.device):
                    dv = jax.numpy.ones(n, jax.numpy.float32)
            else:
                dv = jax.numpy.ones(n, jax.numpy.float32)
            if self.nnz_bucket:
                self._ones_cache[n] = dv
        return dv

    def _put(self, host_batch, ring_bufs, bid):
        # the transfer is attributable in a jax.profiler / Perfetto trace
        # (SURVEY.md §5.1): the span is also a profiler annotation
        dd0 = self._busy.seconds()["device_decode"]
        # device_put joins the (job, part) trace the source block carried
        # — the timeline shows grant -> parse -> recv -> decode ->
        # dispatch as one causal chain; the batch's id is beside it
        ctx = self._last_trace_ctx or (None, None)
        with self._stage_span("dispatch", trace_id=ctx[0], parent_id=ctx[1],
                              epoch=bid[0], batch=bid[1]) as sp:
            try:
                out = self._put_inner(host_batch, bid)
            finally:
                # the device_span branch meters its decode dispatch as its
                # own 'device_decode' stage NESTED in this window — take it
                # out so the busy meters stay disjoint (attribution
                # partitions wall)
                sp.exclude(self._busy.seconds()["device_decode"] - dd0)
        if ring_bufs is not None and self._ring is not None:
            # tie the staging slot to ALL device arrays of the batch: the
            # slot frees only when every transfer has landed and the
            # consumer has dropped every array, never before — a retained
            # label/weight array must pin the slot as surely as the
            # feature matrix
            self._ring.attach(ring_bufs, jax.tree_util.tree_leaves(out))
        return out

    def _put_inner(self, host_batch, bid):
        kind = host_batch[0]
        if kind == "device_span":
            return self._put_device_span(host_batch, bid)
        if kind == "dense_packed":
            xp = host_batch[1]
            self.bytes_to_device += xp.nbytes
            self.dense_plane_bytes += xp.nbytes
            d = (jax.device_put(xp, self.device)
                 if self.device is not None else jax.device_put(xp))
            return PackedDenseBatch(d, self.num_col)
        if kind == "dense_packed_q8":
            # int8-quantized snapshot batch: ship q + per-column scale
            # (1/4 the f32 bytes over the link) and dequantize with one
            # fused device multiply — still zero HOST convert work
            q, scale = host_batch[1], host_batch[2]
            self.bytes_to_device += q.nbytes + scale.nbytes
            out = (jax.device_put([q, scale], self.device)
                   if self.device is not None
                   else jax.device_put([q, scale]))
            return PackedDenseBatch(_device_decode.dequant_q8(*out),
                                    self.num_col)
        if kind == "bcoo_csr":
            from jax.experimental import sparse as jsparse

            cols, row_ptr, vals, label, weight, shape = host_batch[1:]
            arrs = [cols, row_ptr, label, weight] if vals is None else [
                vals, cols, row_ptr, label, weight]
            self.bytes_to_device += sum(a.nbytes for a in arrs)
            out = (jax.device_put(arrs, self.device)
                   if self.device is not None else jax.device_put(arrs))
            if vals is None:
                dc, dp, dl, dw = out
                dv = self._ones_for(len(cols))
            else:
                dv, dc, dp, dl, dw = out
            coords = _csr_coords(dc, dp)
            return jsparse.BCOO((dv, coords), shape=shape), dl, dw
        if kind == "bcoo":
            from jax.experimental import sparse as jsparse

            coords, vals, label, weight, shape = host_batch[1:]
            arrs = [coords, label, weight] if vals is None else [
                vals, coords, label, weight]
            self.bytes_to_device += sum(a.nbytes for a in arrs)
            out = (jax.device_put(arrs, self.device)
                   if self.device is not None else jax.device_put(arrs))
            if vals is None:
                dc, dl, dw = out
                dv = self._ones_for(len(coords))
            else:
                dv, dc, dl, dw = out
            return jsparse.BCOO((dv, dc), shape=shape), dl, dw
        arrays = host_batch[1:]
        self.bytes_to_device += sum(a.nbytes for a in arrays)
        if kind == "ell" and self.fields:
            self.field_plane_bytes += arrays[4].nbytes
        elif kind == "dense":
            self.dense_plane_bytes += arrays[0].nbytes
        if self.mesh is not None:
            from dmlc_tpu.parallel.mesh import local_batch_to_global

            if self.shardings is not None:
                # exact placement the consumer's jit expects (e.g. a learner's
                # batch_shardings()) — committed arrays must match in JAX
                out = tuple(
                    jax.make_array_from_process_local_data(sh, np.asarray(a))
                    for sh, a in zip(self.shardings, arrays)
                )
            else:
                out = local_batch_to_global(self.mesh, arrays, axis=self.data_axis)
        elif self.device is not None:
            out = tuple(jax.device_put(arrays, self.device))
        else:
            out = tuple(jax.device_put(arrays))
        if kind == "ell":
            return EllBatch(*out)
        return out  # (x, y, w)

    def _put_device_span(self, host_batch, bid):
        """The third warm tier (``device_decode=True``): the snapshot
        batch's verbatim container bytes crossed the pipeline as ONE
        contiguous u8 span — ship it as-is and decode in HBM
        (``ops/device_decode``). Zero per-batch host numpy work; the
        decode dispatch is metered as its own 'device_decode' stage
        (disjoint from 'dispatch' — see :meth:`_put`)."""
        _, span, layout, snap_kind = host_batch
        self.bytes_to_device += span.nbytes
        self.device_decode_bytes += span.nbytes
        self._decode_routes[_device_decode.span_route(layout)] += 1
        d = (jax.device_put(span, self.device)
             if self.device is not None else jax.device_put(span))
        with self._stage_span("device_decode", epoch=bid[0], batch=bid[1]):
            segs = _device_decode.decode_span(d, layout)
            out = [segs[name] for name, *_ in layout]
            if snap_kind == "dense_packed":
                return PackedDenseBatch(out[0], self.num_col)
            if snap_kind == "dense_packed_q8":
                return PackedDenseBatch(
                    _device_decode.dequant_q8(out[0], out[1]), self.num_col)
            if snap_kind == "ell":
                if self.fields:     # the fifth segment's nbytes
                    self.field_plane_bytes += layout[4][3]
                return EllBatch(*out)
            return tuple(out)  # "dense": (x, y, w)

    def _maybe_restart_pipeline(self, exc: BaseException) -> bool:
        """Bounded consumer-side recovery from a retryable pipeline error.

        The host pipeline (pool/ThreadedIter) is poisoned once an error
        reaches the consumer; instead of failing the epoch, tear it down
        and re-arm at the batch after the last one DELIVERED, through the
        same state_dict/load_state machinery checkpoint resume uses —
        byte-exact seek when the source chain annotates blocks, a
        deterministic replay otherwise. Returns True when re-armed (caller
        keeps pulling); False when ``exc`` must propagate (fatal class, or
        restart budget exhausted).
        """
        verdict = _resilience.restart_verdict(
            self._retry_policy, self.pipeline_restarts, exc)
        if verdict == "giveup":
            self.pipeline_giveups += 1
            self._faults_lifetime += 1
            return False
        if verdict != "restart":
            return False
        used = self.pipeline_restarts
        self.pipeline_restarts += 1
        self._faults_lifetime += 1
        _resilience.restart_backoff(self._retry_policy, used, exc)
        try:
            self.load_state(self.state_dict())
        except BaseException as nxt:  # noqa: BLE001 - replay hit the fault
            # the replay consumed more budget-worthy failures: recurse
            # (bounded by the same attempts counter) until re-armed or out
            return self._maybe_restart_pipeline(nxt)
        return True

    def _fill(self) -> None:
        producer_put = self.batch_size is None  # natural-block mode put already
        while len(self._inflight) < self.prefetch:
            try:
                item = self._host_iter.next()
            except BaseException as exc:  # noqa: BLE001 - classified below
                if self._snap_serving and isinstance(exc,
                                                     CacheCorruptionError):
                    # corrupt warm snapshot batch: drop the file FIRST so
                    # the restart below re-arms from the source (or a
                    # deterministic rebuild) instead of re-reading the
                    # same bad bytes forever
                    self._invalidate_snapshot()
                if self._maybe_restart_pipeline(exc):
                    continue
                raise
            if item is None:
                # a COMPLETE cold pass publishes its shadow snapshot here
                # (mid-epoch restores abort the writer before this point)
                self._finish_snapshot_writer()
                return
            if item is _SKIPPED:
                # resume marker that load_state's drain missed (stream
                # shorter than the recorded position) — never hand it out
                continue
            if producer_put:
                self._inflight.append(item)     # (device batch, id)
            else:
                host_batch, bufs, annot, bid = item
                if self._snap_writer is not None:
                    self._write_snapshot_batch(host_batch, annot)
                if self._snap_serving:
                    # warm feed: the source-side fifo is idle (nothing is
                    # parsed) — pair the stored annotation with delivery
                    # through the same fifo the cold path uses
                    self._annot_fifo.append(annot)
                self._inflight.append((self._put(host_batch, bufs, bid),
                                       bid))

    def __iter__(self):
        return self

    def _account_window(self, t0: float, busy0: dict, t1: float) -> None:
        """Attribute the consumer-wall window [t0, t1] to named stages.

        The window is partitioned: dispatch measured on this thread is
        charged directly; the remainder (time blocked on the pipeline) is
        split over the read/parse/convert busy DELTAS the pipeline threads
        accrued during the window, scaled down when they overlap (pool
        workers running concurrently can accrue more busy-seconds than the
        window holds). Whatever the deltas don't explain stays
        unattributed — it shows up as the 'other' residue against
        wall_seconds instead of being smeared over stages.
        """
        busy1 = self._busy.seconds()
        d_disp = busy1["dispatch"] - busy0["dispatch"]
        d_decode = busy1["device_decode"] - busy0["device_decode"]
        consumer_put = self.batch_size is not None
        window = (t1 - t0) - ((d_disp + d_decode) if consumer_put else 0.0)
        weights = {k: busy1[k] - busy0[k]
                   for k in ("read", "cache_read", "snapshot_read",
                             "parse", "convert")}
        if not consumer_put:
            # natural-block mode dispatches on the producer thread: its put
            # time is part of what the consumer waited on
            weights["dispatch"] = d_disp
            weights["device_decode"] = d_decode
        wsum = sum(weights.values())
        if wsum > 0 and window > 0:
            scale = min(1.0, window / wsum)
            for k, v in weights.items():
                if v > 0:
                    self._attr.add(k, v * scale)
        if consumer_put:
            # measured directly on this thread (not pipeline-blocked time):
            # charged unscaled, like dispatch — the device_decode share is
            # the jit dispatch of the on-device span decode
            self._attr.add("dispatch", d_disp)
            if d_decode > 0:
                self._attr.add("device_decode", d_decode)

    def __next__(self):
        # every consumer-side step runs under this pipeline's telemetry
        # scope, so the pools/threads it lazily creates inherit the label
        with _telemetry.scope(self.pipeline_label):
            if self._prestarted:
                # the producer that is running is the next epoch's: this
                # one stays ended until reset()
                raise StopIteration
            if self._host_iter_obj is not None and not self._adopted:
                return self._next_spanned()
            # the epoch's first pull builds the producer (or finds the one
            # the last epoch's end started) and waits for its first batch:
            # the turnaround the chip sits idle through
            self._adopted = False
            with _telemetry.span("first_batch", epoch=self._epoch):
                return self._next_spanned()

    def _next_spanned(self):
        # the ring gets one 'next' per batch handed out, labeled with the
        # wait it cost and the batch's id; the pull that ends the epoch
        # (StopIteration) shows on the profiler's timeline only
        with _telemetry.span("next") as sp:
            try:
                out = self._next_scoped()
            except StopIteration:
                sp.skip_ring()
                raise
            sp.labels["waited_s"] = round(self._last_wait, 6)
            sp.labels["epoch"], sp.labels["batch"] = self._last_bid
        return out

    def _next_scoped(self):
        # stall = wall time the consumer spends in here before a batch is
        # available (covers host-parse waits AND device-side transfer setup
        # — everything between "consumer wants a batch" and "batch handed
        # out"); with the prefetch pipeline keeping up this is ~0.
        # NOTE: device_put is async, so this times the wait for a batch
        # HANDLE — a transfer still in flight at first on-device use is
        # invisible here; the sampled transfer sideband below makes that
        # blind spot measurable
        t0 = get_time()
        if self._t_first is None:
            self._t_first = t0
        busy0 = self._busy.seconds()
        self._fill()
        if not self._inflight:
            t_end = get_time()
            self._account_window(t0, busy0, t_end)
            self._t_last = t_end
            self._ended = True
            self._prestart_next_epoch()
            raise StopIteration
        out, self._last_bid = self._inflight.popleft()
        waited = self._last_wait = get_time() - t0
        self.stall_seconds += waited
        # the trustworthy input-bound counter (module docstring): handle
        # waits land here AND in stall_seconds; sampled transfer
        # landings below land here only
        self._input_wait.inc(waited)
        self.host_stall_seconds += self._host_iter.stall_seconds
        self._host_iter.stall_seconds = 0.0
        self.batches_fed += 1
        self._batches_total += 1
        if self._annot_fifo:
            # production order == delivery order, so the head annotation
            # belongs to the batch just handed out
            self._last_resume = self._annot_fifo.popleft()
        # issue the replacement transfer before handing the batch out —
        # pipeline work, not consumer stall, so outside the stall metric
        # (still inside the attribution window: it is consumer wall)
        self._fill()
        self._account_window(t0, busy0, get_time())
        if (self.transfer_sample
                and self.batches_fed % self.transfer_sample == 0):
            # transfer-completion sideband: block until THIS batch's bytes
            # actually land — the per-batch residue async dispatch hides
            with _telemetry.span("transfer") as sp:
                jax.block_until_ready(out)
            self._attr.add("transfer", sp.dt)
            # a sampled landing IS consumer-side input waiting: without
            # this, a transfer-bound epoch reads stall 0.000 while half
            # the wall hides in the async blind spot
            self._input_wait.inc(sp.dt)
            self._transfer_samples += 1
        if (self._autotune_interval
                and self._batches_total % self._autotune_interval == 0):
            self._autotune_step()
        self._t_last = get_time()
        return out

    def reset(self) -> None:
        """New epoch: restart the host pipeline. The producer thread is
        JOINED (not just signalled) before annotation state is cleared —
        an in-flight produce step could otherwise append a stale old-epoch
        annotation after the clear and desync the fifo for the whole next
        epoch. With a snapshot armed this is also the epoch boundary the
        store keys on: the next pass serves warm once a snapshot is
        published, and the plan epoch advances so each warm epoch draws a
        fresh batch permutation. Where the last epoch's end has started
        this one's producer already (:meth:`_prestart_next_epoch`), the
        restart is done and this only adopts it."""
        with _telemetry.scope(self.pipeline_label), \
                _telemetry.span("epoch_reset", epoch=self._epoch
                                + (0 if self._prestarted else 1)):
            self._reset()

    def _reset(self) -> None:
        if self._prestarted:
            self._prestarted, self._adopted = False, True
            self._epochs_prestarted += 1
        else:
            self._looped = self._looped or self._ended
            self._adopted = False
            self._begin_epoch()
        self._ended = False
        self._ended_state = None
        self._last_resume = None
        self.batches_fed = 0
        self.pipeline_restarts = 0  # fresh fault budget per epoch
        self.pipeline_giveups = 0

    def _begin_epoch(self) -> None:
        """What a new epoch's producer must find done before it starts:
        the last one torn down, the epoch counted, the resume and snapshot
        bookkeeping at its start. The consumer's own counters
        (``batches_fed``, the checkpoint, the fault budget) are
        :meth:`_reset`'s."""
        advanced = self.batches_fed > 0
        if advanced:
            # epoch-boundary tuning step over the finished epoch's window
            # (no-op unless autotune is armed); knob changes apply to the
            # pools the NEXT epoch builds
            self._autotune_step()
        self._teardown_producer()
        self._epoch += 1
        self._first_seq = 0
        self._skip_blocks = 0
        self._drop_rows = 0
        self._suppress_before_first = False
        if self.snapshot_path is not None:
            self._abort_snapshot_writer()  # mid-epoch reset: partial pass
            self._snap_shadow = True
            self._snap_seq_restore = False
            self._snap_suspend = False
            self._snap_pos0 = 0
            if advanced:
                self._snap_epoch += 1

    def _prestart_next_epoch(self) -> None:
        """At an epoch's end, with every batch handed out: start the next
        epoch's producer now, so that its first batches are read and
        converted while the device works through the steps still queued,
        and the ``reset()`` that follows finds them waiting (its first
        ``next()`` only puts them). Only for a consumer seen to run epochs back
        to back (a ``reset()`` has followed an epoch's end before), on the
        convert pool, with no snapshot tier (its warm feed opens in a few
        ms: nothing to hide) and a source that rewinds locally (a service
        client's ``before_first`` asks other processes for an epoch).
        Until ``reset()`` the iterator stays ended: ``next()`` raises,
        ``state_dict()`` is the ended epoch's, ``load_state()`` drops the
        head start."""
        if not (self._looped and self.batch_size is not None
                and self.snapshot_path is None
                and getattr(self.source, "service_stats", None) is None):
            return
        self._ended_state = self.state_dict()
        self._begin_epoch()
        self._prestarted = True
        self._host_iter     # the pool's threads start pulling at once

    def _drop_prestart(self) -> None:
        """A restore in place of the ``reset()`` the head start was for."""
        self._teardown_producer()
        self._epoch -= 1
        self._prestarted = False

    # -------- checkpoint / resume (SURVEY.md §5.4 addition) --------

    def state_dict(self) -> dict:
        """Mid-epoch resume point. When the source chain annotates blocks
        (the Python parser stack), the state composes the split layer's
        byte-exact position — restore SEEKS there, O(1) in epoch position.
        Otherwise: batch count, replayed deterministically on restore.
        Transfers in flight (not yet handed out) are dropped either way."""
        if self._prestarted:
            return dict(self._ended_state)
        if self._last_resume is not None:
            return {"kind": "source", "batches": self.batches_fed,
                    **self._last_resume}
        return {"kind": "batches", "batches": self.batches_fed}

    def _teardown_producer(self) -> None:
        self._inflight.clear()
        if self._host_iter_obj is not None:
            self._host_iter_obj.destroy()
            self._host_iter_obj = None
        self._snap_serving = False
        self._annot_fifo.clear()
        # drop the staging ring with the producer: slots acquired by
        # now-dead workers would otherwise stay busy forever. The CSR
        # wire's ring (_csr_bufs) takes those slots back and stays
        self._fold_ring_events()
        if self._ring is not None and self._ring.key is not None:
            self._ring.reclaim()
        else:
            self._ring = None

    def _fold_ring_events(self) -> dict:
        """Move the live staging ring's hits and misses not yet counted
        into ``pool_events`` (a ring lives one producer), and give the
        totals."""
        if self._ring is not None:
            now = self._ring.stats()
            for kind, counter in self._ring_events.items():
                counter.inc(now[kind] - self._ring_folded[kind])
            self._ring_folded = now
        return {kind: int(c.value) for kind, c in self._ring_events.items()}

    def load_state(self, state: dict) -> None:
        with _telemetry.scope(self.pipeline_label):
            self._load_state_scoped(state)

    def _load_snapshot_state(self, state: dict) -> bool:
        """Restore into warm snapshot serving when possible. Returns True
        when the state was fully handled; False hands it to the normal
        source-seek/replay machinery (cold restore).

        Snapshot batches are 1:1 with pipeline batches at one geometry,
        so the delivered-batch count IS the warm resume position — a
        checkpoint taken against a block-cache (or plain) pipeline
        restores into a warm snapshot pipeline byte-identically, and vice
        versa (the stored per-batch annotations are the cold pipeline's
        own states). Plan-position states (``kind='epoch_plan'`` with
        ``unit='batch'`` under ``source``) adopt the state's plan
        identity wholesale; a vanished snapshot under a plan state
        triggers a deterministic full rebuild."""
        kind = state.get("kind")
        n = int(state.get("batches", 0))
        src = state.get("source") if kind == "source" else None
        plan = (src if isinstance(src, dict)
                and src.get("kind") == "epoch_plan"
                and src.get("unit") == "batch" else None)
        if plan is not None:
            self._teardown_producer()
            self._abort_snapshot_writer()
            self._snap_shadow = False
            self._snap_suspend = False
            self._snap_seq_restore = False
            seed = plan.get("seed")
            self._snap_seed = None if seed is None else int(seed)
            self._snap_epoch = int(plan.get("epoch", 0))
            pos = int(plan.get("pos", n))
            if not self._open_snapshot():
                self._rebuild_snapshot()
            self._snap_pos0 = pos
            self.batches_fed = n
            self._last_resume = ({"source": dict(plan), "skip_rows": 0}
                                 if pos else None)
            return True
        if isinstance(src, dict) and src.get("kind") == "epoch_plan":
            # a BLOCK-plan state (shuffled/sharded block cache): its
            # position lives in the cache's permuted block stream, which
            # this snapshot (always sequential-order — snapshot + source
            # plan is rejected at construction) cannot reproduce. Hand it
            # to the source, which replays the plan byte-identically.
            return False
        if kind not in ("source", "batches") or not self._open_snapshot():
            return False
        if n > self._snap_reader.num_batches:
            # stale count (shrunk source rebuilt elsewhere): the cold
            # machinery owns foreign states
            return False
        self._teardown_producer()
        self._abort_snapshot_writer()
        self._snap_shadow = False
        self._snap_suspend = False
        # a sequential position restored into a plan-armed pipeline: the
        # position only exists in the SEQUENTIAL stream, so the rest of
        # this epoch serves sequentially — byte-identical to the stream
        # the state came from — and the plan resumes next epoch (the
        # same contract as the block cache's legacy restores)
        self._snap_seq_restore = self._snap_seed is not None
        self._snap_pos0 = n
        self.batches_fed = n
        if kind == "source":
            self._last_resume = {k: state[k]
                                 for k in ("source", "skip_rows")}
        else:
            self._last_resume = (self._snap_reader.resume(n - 1)
                                 if n else None)
        return True

    def _load_state_scoped(self, state: dict) -> None:
        if self._prestarted:
            self._drop_prestart()
        self._ended = False
        if self.snapshot_path is not None:
            if self._load_snapshot_state(state):
                return
            # cold restore below: a mid-epoch seek can no longer shadow-
            # write a complete snapshot, and the seeked SOURCE owns the
            # stream for the rest of this epoch (a warm snapshot cannot
            # reproduce e.g. a block-plan order) — both resume at the
            # next reset()
            self._abort_snapshot_writer()
            self._snap_shadow = False
            self._snap_suspend = True
        if state.get("kind") == "source":
            # byte-exact restore: seek the source (parser -> split) to the
            # block boundary, drop the few rows into it, rebatch from there
            # — no prefix bytes are re-read or re-parsed
            self._teardown_producer()
            self._skip_blocks = 0
            self.source.load_state(state["source"])
            self._drop_rows = int(state["skip_rows"])
            self._suppress_before_first = True
            self._last_resume = {k: state[k] for k in ("source", "skip_rows")}
            self.batches_fed = self._first_seq = int(state["batches"])
            return
        n = int(state["batches"])
        # natural-block mode puts on the producer thread, so skipping must
        # happen THERE (before conversion/transfer): tear down any running
        # producer first, THEN arm the skip counter — the replacement
        # producer (lazily started by the drain below) sees the credits
        # from its first iteration, with no thread racing the hand-off
        self._teardown_producer()
        self._skip_blocks = n if self.batch_size is None else 0
        self._drop_rows = 0
        self._suppress_before_first = False
        self._last_resume = None
        for _ in range(n):
            item = self._host_iter.next()
            if item is None:  # replay: nothing transferred
                break
            if (self.batch_size is not None and item is not _SKIPPED
                    and item[1] is not None and self._ring is not None):
                # replayed batch never reaches _put: free its staging slot
                self._ring.attach(item[1], None)
            if self._annot_fifo:
                # keep the 1-push/1-pop pairing: each replayed batch pushed
                # an annotation; consume it like a delivery would (it also
                # upgrades later checkpoints to byte-exact)
                self._last_resume = self._annot_fifo.popleft()
        self.batches_fed = n

    def dump_trace(self, path: str) -> int:
        """Export the span rings as a Chrome-trace/Perfetto JSON at
        ``path`` (docs/observability.md trace-export workflow). Returns
        the number of span events written. The trace covers the whole
        process — load it in Perfetto / ``chrome://tracing`` and filter by
        the ``pipeline`` arg to isolate this iterator's spans."""
        return _telemetry.export_chrome_trace(path)

    def close(self) -> None:
        if self._host_iter_obj is not None:
            self._host_iter_obj.destroy()
        self._abort_snapshot_writer()
        self._drop_snap_reader()
        if hasattr(self.source, "close"):
            self.source.close()
        if self._trace_export:
            # DMLC_TPU_TRACE=chrome:<path> — dump on close, when every
            # stage has finished writing spans
            try:
                self.dump_trace(self._trace_export)
            except OSError as exc:
                from dmlc_tpu.utils.check import get_logger

                get_logger().warning("trace export to %s failed: %s",
                                     self._trace_export, exc)

    def _pool_stats(self) -> dict:
        """``stats()['pool']``: what the convert pools of this pipeline's
        epochs waited for so far (``pool_seconds`` / ``pool_events`` under
        this pipeline's label; all 0 while no pool has run, as in warm
        snapshot epochs and natural-block mode)."""
        seconds = _telemetry.REGISTRY.sum_by(
            _telemetry.POOL_SECONDS_METRIC, "state", pool=_POOL_LABEL,
            pipeline=self.pipeline_label)
        events = _telemetry.REGISTRY.sum_by(
            _telemetry.POOL_EVENTS_METRIC, "kind", pool=_POOL_LABEL,
            pipeline=self.pipeline_label)
        ring = self._fold_ring_events()
        out = {state + "_seconds": seconds.get(state, 0.0)
               for state in ("window_wait", "pull_wait", "pull", "work",
                             "ready_wait", "merge")}
        out.update(items=int(events.get("items", 0)),
                   stall_seconds=self.host_stall_seconds,
                   ring_hits=ring["hits"], ring_misses=ring["misses"])
        return out

    def stats(self) -> dict:
        """Throughput counters + per-stage wall attribution.

        ``stages`` partitions consumer wall (``wall_seconds``, first pull
        to latest delivery) into read / cache_read / snapshot_read / parse
        / convert / dispatch / device_decode / transfer; by construction
        their sum never exceeds wall, and the
        difference is unattributed consumer time ('other': the caller's
        own compute between pulls, e.g. a training step). ``stage_busy``
        carries the raw per-thread busy counters the attribution is
        scaled from (these may legitimately exceed wall when pool workers
        overlap). ``transfer`` is a SAMPLED sideband (every
        ``transfer_sample`` batches) — multiply by the sample period for
        a rough whole-stream estimate.

        ``resilience`` sits next to the stage attribution: retry / resume /
        giveup counters accrued by the I/O stack since this iterator was
        built (process-wide deltas — see docs/resilience.md), plus this
        iterator's own bounded pipeline-restart counts.

        ``parse_workers`` / ``parse_parallelism_efficiency`` (with the full
        ``parse_parallel`` sideband) report the source chain's data-parallel
        parse fan-out — how many chunk-parse lanes fed this pipeline and
        how fully they ran in parallel (docs/data.md ``parse_workers``).

        With a :class:`~dmlc_tpu.service.client.ServiceParser` as the
        source, no text is read or parsed in this process: ``read`` is
        then the wait for a frame (locate, connect, socket read, CRC) and
        ``parse`` the frame's decode to a ``RowBlock``, and a ``service``
        entry carries the client's books of the wire
        (``ServiceParser.service_stats()``: ``wire_bytes``, ``frames``,
        ``wire_version``, ``fastpath_blocks``, ``parts_by_worker``,
        ``retries``, ``failovers``, ``giveups``, ``recv_seconds``,
        ``decode_seconds``). A local source has no such entry.

        ``pool`` is what the convert pool's threads spent their time on,
        summed over the pools of the epochs so far (seconds):
        ``window_wait_seconds`` (workers waiting for the ``convert_ahead``
        window: the consumer is behind, the feed has headroom),
        ``pull_wait_seconds`` (waiting for the serial stage),
        ``pull_seconds`` (in the serial stage: source and merge),
        ``work_seconds`` (converting) — the four partition the workers'
        wall time — ``merge_seconds`` (the serial stage's own ``merge``
        spans, inside ``pull_seconds`` and inside ``stage_busy``'s
        ``convert``), ``ready_wait_seconds`` over ``items`` (how long
        converted batches lay finished before the consumer took them),
        ``stall_seconds`` (the consumer waiting on the pool:
        ``host_stall_seconds``), and the staging rings' ``ring_hits`` /
        ``ring_misses`` over every epoch's ring. ``now`` is this
        reading's ``get_time()``, the clock of the span rings.

        ``plan`` is what serving in the epoch plan's order has cost so far
        (``BlockCacheIter.plan_stats()``: blocks and rows, the
        ``plan_permute`` and ``plan_wait`` spans' seconds, the live epoch
        and order), ``None`` with no plan armed.
        """
        wall = 0.0
        if self._t_first is not None and self._t_last is not None:
            wall = max(0.0, self._t_last - self._t_first)
        # scoped to this pipeline's label: a concurrent DeviceIter's
        # retries/restarts no longer bleed into this one's delta
        resilience = _resilience.counters_delta(self._res_base,
                                                self.pipeline_label)
        resilience["pipeline_restarts"] = self.pipeline_restarts
        resilience["pipeline_giveups"] = self.pipeline_giveups
        # parse-parallelism sideband: the source chain reports its fan-out
        # width + measured efficiency (ParallelTextParser / the native
        # reader); single-lane sources report 1 worker, no efficiency
        pstats = None
        fn = getattr(self.source, "parallel_stats", None)
        if callable(fn):
            try:
                pstats = fn()
            except Exception:  # noqa: BLE001 - stats must never break stats
                pstats = None
        plan_state = getattr(self.source, "plan_state", None) or {}
        plan_stats = getattr(self.source, "plan_stats", None)
        out = {
            "batches": self.batches_fed,
            # epochs whose producer the epoch before them started at its
            # end, ahead of their reset() (_prestart_next_epoch)
            "epochs_prestarted": self._epochs_prestarted,
            "bytes_to_device": self.bytes_to_device,
            # of which the libfm field plane (0 unless fields=True)
            "field_plane_bytes": self.field_plane_bytes,
            # of which the dense kind's x plane as put (a packed batch's
            # whole slab; 0 for another layout, and for batches that cross
            # as a snapshot's raw span), and the plane's dtype
            "dense_plane_bytes": self.dense_plane_bytes,
            "x_dtype": self.x_dtype,
            # cells the CSV parser has scanned in this process, by the
            # cell dtype it was asked for (telemetry.csv_cells())
            "csv_cells": _telemetry.csv_cells(),
            # of the cells hashed to an id (csv_cells["hashed"], a parser
            # with ?hash_bins=), those that had no bytes
            "csv_empty_cells": _telemetry.csv_empty_cells(),
            # non-zeros the ELL convert cut from rows longer than max_nnz
            # (counted where convert runs: a warm snapshot epoch adds none)
            "ell_truncated_slots": self._ell_truncated,
            # the ell kind's books, counted where convert lays a batch out
            # (as ell_truncated_slots): the non-zeros it kept and the slots
            # shipped, B x max_nnz a batch; 1 - nnz / slots is the share
            # of slots that are padding, which a learner's step is told
            # (``real``) and need not sort, read or permute like real ones
            # (docs/ops.md, "The sorted walk"); zeros for another layout
            "ell": {"nnz": self._ell_nnz, "slots": self._ell_slots},
            # the bcoo kind's ragged books, counted where a batch's shape
            # is planned: the real non-zeros, the slots shipped (the pad
            # share is 1 - nnz / slots), and the slot counts emitted at a
            # fixed batch_size, ascending (a second one inside a timed
            # window is a recompile downstream); zeros for another layout
            "bcoo": {"nnz": self._bcoo_nnz, "slots": self._bcoo_slots,
                     "shapes": sorted(self._emitted_nse)},
            # the telemetry scope label every span/metric of this
            # pipeline carries (docs/observability.md)
            "pipeline": self.pipeline_label,
            # block-cache mode of the source chain: 'cold' (parsing +
            # shadow-writing), 'warm' (serving mmap'd parsed blocks), or
            # None when no block cache is armed (docs/data.md)
            "cache_state": getattr(self.source, "cache_state", None),
            # device-native snapshot store: None when not armed, 'warm'
            # while this epoch streams stored device-layout batches
            # (convert busy stays ~0), 'cold' while converting +
            # shadow-writing (docs/data.md snapshot section)
            "snapshot_state": (None if self.snapshot_path is None
                               else ("warm" if self._snap_serving
                                     else "cold")),
            # the snapshot plan identity (permutation over BATCH indices,
            # pure function of (seed, epoch)) — None seed = sequential
            "snapshot_seed": (self._snap_seed
                              if self.snapshot_path is not None else None),
            "snapshot_epoch": (self._snap_epoch
                               if self.snapshot_path is not None
                               else None),
            # third warm tier (docs/data.md three-tier decode table): is
            # device-side span decode armed, and how many verbatim
            # container bytes crossed as raw u8 spans (decoded in HBM —
            # each such batch does ZERO per-batch host numpy decode)
            "device_decode": self.device_decode,
            "device_decode_bytes": self.device_decode_bytes,
            # how many span batches each decode lowering served: the
            # Pallas byte-plane kernel or plain XLA (ops/device_decode)
            "device_decode_routes": dict(self._decode_routes),
            # the epoch planner's identity when the source serves a
            # shuffle-native / pod-sharded cache: the seed and epoch every
            # delivered byte is a function of, None with no plan armed
            # (docs/data.md shuffle-native cache; docs/observability.md)
            "shuffle_seed": plan_state.get("shuffle_seed"),
            "epoch": plan_state.get("epoch"),
            # what serving in plan order has cost (cumulative; None with
            # no plan armed): BlockCacheIter.plan_stats()
            "plan": plan_stats() if callable(plan_stats) else None,
            "stall_seconds": self.stall_seconds,
            "host_stall_seconds": self.host_stall_seconds,
            # consumer-side input-bound waiting the tuner can trust:
            # handle waits + sampled transfer landings (a transfer-bound
            # epoch shows it even when stall_seconds reads ~0)
            "input_wait_seconds": self._input_wait.value,
            # the online controller's full decision record: None when
            # autotune is off (docs/observability.md schema)
            "autotune": (self.autotuner.snapshot()
                         if self.autotuner is not None else None),
            "stages": self._attr.seconds(),
            "stage_busy": self._busy.seconds(),
            "wall_seconds": wall,
            "transfer_samples": self._transfer_samples,
            "convert_workers": self.convert_workers,
            "parse_workers": (pstats or {}).get("parse_workers", 1),
            "parse_parallelism_efficiency": (pstats or {}).get(
                "parse_parallelism_efficiency"),
            "parse_parallel": pstats,
            "staging_ring": (self._ring.stats() if self._ring is not None
                             else None),
            "pool": self._pool_stats(),
            # this reading's time on the span rings' clock: a reader of
            # spans_snapshot() cuts the ring at a stats() it kept
            "now": get_time(),
            "resilience": resilience,
            # tiered artifact store (docs/store.md): live on-disk bytes
            # under management across every store this process touched,
            # plus the process-wide eviction / eviction-triggered-rebuild
            # tallies — process-wide because budget pressure from ANY
            # pipeline is what evicts this one's artifacts
            "store": _store_counters(),
        }
        service = getattr(self.source, "service_stats", None)
        if callable(service):
            out["service"] = service()
        return out
