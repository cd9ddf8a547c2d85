"""Parse-once columnar RowBlock cache: the on-disk format, writer, reader.

The chunk cache (:mod:`dmlc_tpu.io.cached_split`) caches raw bytes BEFORE
the parser, so warm passes still re-pay the full text-parse cost every
epoch. This module caches AFTER the parser — the highest-leverage point in
the pipeline per tf.data's ``cache()`` study (arXiv:2101.12127 §5) and the
preprocessing/training decoupling argument of the tf.data-service paper
(arXiv:2210.14826): the first (cold) epoch shadow-writes each parsed
block's columnar arrays; warm epochs serve the arrays back as zero-copy
mmap-backed numpy views, bypassing the parser entirely.

This module owns the FORMAT only — it moves named 1-D numpy segments, not
RowBlocks (the RowBlock <-> segments conversion lives in
:meth:`dmlc_tpu.data.row_block.RowBlock.to_segments`, keeping the io layer
free of data-layer imports). The pipeline integration —
``BlockCacheIter`` — lives in :mod:`dmlc_tpu.data.parsers`.

Format v1 (pinned by ``tests/data/blockcache_v1.golden``)::

    [header]   magic "DMLCBC01" (8B) + version u32 LE + 4 zero pad bytes
    [segments] per block, per present array: raw little-endian bytes,
               each array start padded to 64-byte alignment (mmap-friendly
               for numpy views)
    [footer]   utf-8 JSON (sort_keys): {"version", "signature", "num_col",
               "rows", "blocks": [{"pos", "end", "rows", "crc", "resume",
               "arrays": {name: [dtype_str, abs_offset, nbytes]}}, ...]}
    [tail]     u64 footer_offset + u64 footer_len + u32 footer_crc LE
               + magic "DMLCBC01"

Integrity: each block carries a crc32 over its whole ``[pos, end)`` span
(checked on every warm read — zlib crc runs at GB/s, noise next to the
text parse it replaces), the footer carries its own crc, and both file
ends carry the magic so truncation is detected structurally. The writer
streams to a store-allocated staging file and publishes through the
tiered artifact store (:mod:`dmlc_tpu.store`: fsync + atomic rename +
manifest record + byte-budget enforcement) — a crash can never leave a
torn-but-valid-looking cache, and readers pin the cache they serve so
eviction can never take a tier away mid-epoch (docs/store.md).

Staleness: a cache is keyed by a **source signature** (file sizes+mtimes,
partition ``splitN.partK``, parser/format/engine config —
:func:`source_signature`). :func:`open_block_cache` returns ``None`` for a
missing, unreadable, or signature-mismatched cache (dropping the stale
file and counting a ``cache_invalidations`` resilience event), so callers
simply rebuild.
"""

from __future__ import annotations

import json
import mmap
import os
import struct
import zlib
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from dmlc_tpu.io import faults
from dmlc_tpu.io import resilience as _resilience
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import CacheCorruptionError, DMLCError, check

BLOCK_CACHE_MAGIC = b"DMLCBC01"
BLOCK_CACHE_VERSION = 1


def _store_manager():
    """Lazy import of the tiered-store manager (it sits above the
    resilience/telemetry layers, so the io formats bind to it at call
    time, never at package init)."""
    from dmlc_tpu.store import manager

    return manager


def _artifact_store(path: str):
    """The :class:`~dmlc_tpu.store.manager.ArtifactStore` owning
    ``path``'s directory."""
    return _store_manager().store_for(path)
_TAIL_FMT = "<QQI"  # footer offset, footer length, footer crc32
_TAIL_LEN = struct.calcsize(_TAIL_FMT) + len(BLOCK_CACHE_MAGIC)
_ALIGN = 64

# canonical segment order (fixed so the golden layout is deterministic);
# optional arrays are simply absent from a block's footer entry
SEGMENT_NAMES = ("offset", "label", "weight", "qid", "field", "index", "value")


def container_header(magic: bytes, version: int) -> bytes:
    """The shared v1 container header: 8-byte magic + u32 LE version +
    4 zero pad bytes — one builder for every DMLC segment container
    (block cache, device-native snapshot)."""
    check(len(magic) == 8, "container magic must be 8 bytes")
    return magic + struct.pack("<I", version) + b"\0" * 4


_HEADER = container_header(BLOCK_CACHE_MAGIC, BLOCK_CACHE_VERSION)


def _pad_to(f, align: int) -> int:
    pos = f.tell()
    rem = pos % align
    if rem:
        f.write(b"\0" * (align - rem))
        pos += align - rem
    return pos


def write_segments(f, segments: Dict[str, Optional[np.ndarray]],
                   crc: int = 0, names=SEGMENT_NAMES) -> tuple:
    """Serialize the present ``names`` arrays (default
    :data:`SEGMENT_NAMES`) at ``f``'s current (already-aligned) position —
    the v1 segment encoding shared by the on-disk cache block, the
    data-service wire frame (:mod:`dmlc_tpu.service.frame`), and the
    device-native snapshot store (:mod:`dmlc_tpu.io.snapshot`, which
    passes its own positional name order): canonical order, each array
    start padded to 64-byte alignment, raw little-endian C-order bytes,
    one crc32 rolling over padding + payload. Returns ``(end, crc,
    arrays)`` with ``arrays`` mapping name ->
    ``[dtype_str, abs_offset, nbytes]`` (the footer/meta schema every
    container stores)."""
    arrays: Dict[str, list] = {}
    for name in names:
        arr = segments.get(name)
        if arr is None:
            continue
        arr = np.ascontiguousarray(arr)
        start = f.tell()
        rem = start % _ALIGN
        if rem:
            padding = b"\0" * (_ALIGN - rem)
            f.write(padding)
            crc = zlib.crc32(padding, crc)
            start += len(padding)
        raw = arr.tobytes()  # canonical C-order little-endian payload
        f.write(raw)
        crc = zlib.crc32(raw, crc)
        # extension dtypes (ml_dtypes bfloat16 in snapshot segments) read
        # as void through .str ('<V2') — their registered NAME round-trips
        # through np.dtype(); standard dtypes keep .str (golden-pinned)
        dtype_str = (arr.dtype.str if arr.dtype.kind != "V"
                     else arr.dtype.name)
        arrays[name] = [dtype_str, start, len(raw)]
    return f.tell(), crc & 0xFFFFFFFF, arrays


def _segment_dtype(dtype_str: str) -> np.dtype:
    """Resolve a stored segment dtype. Extension names ('bfloat16') only
    resolve once ml_dtypes has registered them — a client process that
    never imported jax (e.g. a host-block service consumer decoding bf16
    snapshot frames) must not crash on the lookup."""
    try:
        return np.dtype(dtype_str)
    except TypeError:
        import ml_dtypes  # noqa: F401 - import registers the dtypes

        return np.dtype(dtype_str)


def read_segments(buf, arrays: Dict[str, list]) -> Dict[str, np.ndarray]:
    """Decode a :func:`write_segments` ``arrays`` mapping over ``buf``
    (an mmap or bytes) into {name: zero-copy numpy view} — shared by the
    warm cache reader, the service frame decoder, and the snapshot
    reader."""
    out: Dict[str, np.ndarray] = {}
    for name, (dtype_str, off, nbytes) in arrays.items():
        dt = _segment_dtype(dtype_str)
        out[name] = np.frombuffer(buf, dtype=dt,
                                  count=nbytes // dt.itemsize,
                                  offset=int(off))
    return out


def span_layout(arrays: Dict[str, list], shapes=None, base: int = 0):
    """A batch's footer/frame ``arrays`` (+ optional ``shapes``) mapping
    as a hashable span layout: ``((name, dtype_str, rel_offset, nbytes,
    shape), ...)`` with offsets rebased to ``base`` (the batch's ``pos``
    for an on-disk container span, 0 for a wire-frame payload). The
    compile-time constant :func:`dmlc_tpu.ops.device_decode.decode_span`
    slices and bitcasts a verbatim-transferred u8 span by — built here
    (jax-free, beside the footer schema it reads) so snapshot readers
    and service frame decoders share one definition."""
    entries = []
    for name, (dtype_str, off, nbytes) in arrays.items():
        shape = (shapes or {}).get(name)
        dt = _segment_dtype(dtype_str)
        shape = (tuple(int(d) for d in shape) if shape is not None
                 else (int(nbytes) // dt.itemsize,))
        entries.append((str(name), str(dtype_str), int(off) - int(base),
                        int(nbytes), shape))
    return tuple(entries)


def finish_container(f, tmp_path: str, path: str, footer: dict,
                     magic: bytes) -> None:
    """The shared publish tail: write the crc'd JSON ``footer`` + tail
    record + closing ``magic``, then publish through the artifact store
    (:mod:`dmlc_tpu.store` — fsync + atomic rename + manifest record +
    byte-budget enforcement). One implementation so a crash can never
    leave a torn-but-valid-looking container of either format."""
    payload = json.dumps(footer, sort_keys=True,
                         separators=(",", ":")).encode()
    off = _pad_to(f, _ALIGN)
    f.write(payload)
    f.write(struct.pack(_TAIL_FMT, off, len(payload),
                        zlib.crc32(payload) & 0xFFFFFFFF))
    f.write(magic)
    _artifact_store(path).publish_file(
        tmp_path, path, tier=_store_manager().tier_for_magic(magic),
        signature=footer.get("signature"), fobj=f)


def open_container(path: str, magic: bytes, version: int, what: str):
    """mmap a published container and verify its structure (header magic +
    version, tail magic, footer crc): the shared open half of
    :func:`finish_container`. Returns ``(file, mmap, footer_dict)``;
    raises :class:`DMLCError` — with the file/mmap already closed — on
    any structural problem."""
    header = container_header(magic, version)
    f = mm = None
    try:
        size = os.path.getsize(path)
        check(size >= len(header) + _TAIL_LEN, f"{what}: too short")
        f = open(path, "rb")
        mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    except (OSError, DMLCError) as exc:
        if mm is not None:
            mm.close()
        if f is not None:
            f.close()  # the fd must not leak when the mmap fails
        raise DMLCError(f"{what}: unreadable: {exc}") from exc
    try:
        head = mm[: len(header)]
        check(head[:8] == magic, f"{what}: bad magic")
        (ver,) = struct.unpack("<I", head[8:12])
        check(ver == version, f"{what}: version {ver} != {version}")
        tail = mm[size - _TAIL_LEN:]
        check(tail[-8:] == magic, f"{what}: truncated (no tail magic)")
        off, length, crc = struct.unpack(
            _TAIL_FMT, tail[: struct.calcsize(_TAIL_FMT)])
        check(off + length <= size - _TAIL_LEN,
              f"{what}: footer out of range")
        with memoryview(mm)[off: off + length] as mv:
            payload_crc = zlib.crc32(mv) & 0xFFFFFFFF
            payload = bytes(mv)  # json needs bytes; footer is small
        check(payload_crc == crc, f"{what}: footer crc mismatch")
        return f, mm, json.loads(payload)
    except Exception:
        try:
            mm.close()
        except BufferError:  # pragma: no cover - no views exported yet
            pass
        f.close()
        raise


class BlockCacheWriter:
    """Streams checksummed columnar block segments to a store-allocated
    staging file; :meth:`finish` writes the footer and publishes through
    the artifact store (fsync + atomic rename + manifest + budget)."""

    def __init__(self, path: str, signature: Optional[dict] = None):
        self.path = path
        self._sig = signature or {}
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        # process-unique staging name from the store: two writers racing
        # the same path (concurrent service workers) can never clobber
        # each other's half-written bytes (docs/store.md)
        self.tmp_path = _artifact_store(path).stage_path(path)
        self._f = open(self.tmp_path, "wb")
        self._f.write(_HEADER)
        self._entries: List[dict] = []
        self._num_col = 0
        self._rows = 0
        self._finished = False

    def add_block(self, segments: Dict[str, Optional[np.ndarray]],
                  rows: int, num_col: int = 0,
                  resume: Optional[dict] = None) -> None:
        """Append one block. ``segments`` maps :data:`SEGMENT_NAMES` to 1-D
        arrays (``None`` = absent); ``resume`` is the block's JSON-friendly
        resume annotation (position just after the block), stored so warm
        epochs can re-attach byte-exact checkpoint states."""
        check(self._f is not None and not self._finished,
              "BlockCacheWriter: writer already finished/aborted")
        # the shadow-write's own cost, visible on the trace timeline next
        # to the parse spans it rides behind (cold-epoch overhead is a
        # real stage even though stats() folds it into supply wall)
        with _telemetry.span("cache_write", rows=int(rows)):
            f = self._f
            pos = _pad_to(f, _ALIGN)
            end, crc, arrays = write_segments(f, segments)
            # resume annotations round-trip through JSON (tuples -> lists,
            # dict order normalized) so cold- and warm-served states
            # compare equal byte for byte
            resume_json = (json.loads(json.dumps(resume))
                           if resume is not None else None)
            self._entries.append({
                "pos": pos, "end": end, "rows": int(rows),
                "crc": crc, "resume": resume_json,
                "arrays": arrays,
            })
            self._rows += int(rows)
            self._num_col = max(self._num_col, int(num_col))

    def finish(self) -> None:
        """Write footer + tail, fsync, atomically publish at ``path``."""
        check(self._f is not None and not self._finished,
              "BlockCacheWriter: writer already finished/aborted")
        f = self._f
        footer = {
            "version": BLOCK_CACHE_VERSION,
            "signature": self._sig,
            "num_col": self._num_col,
            "rows": self._rows,
            "blocks": self._entries,
        }
        finish_container(f, self.tmp_path, self.path, footer,
                         BLOCK_CACHE_MAGIC)
        self._f = None
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


class EncodedSegments(NamedTuple):
    """One block's ``DMLCBC01`` segment span, exactly as the cache file
    stores it.

    ``data`` is a zero-copy view of the span (keep ``hold`` referenced
    while it is alive), ``arrays`` maps segment name ->
    ``[dtype_str, span_offset, nbytes]`` (the footer/meta schema with
    offsets relative to the span start), ``crc`` is the crc32 of
    ``data`` — the per-block integrity word the cache footer stores.
    """

    data: memoryview
    arrays: Dict[str, tuple]
    crc: int
    rows: int
    num_col: int
    hold: object


class BlockCacheReader:
    """mmap-backed reader: blocks decode to zero-copy numpy views.

    Views returned by :meth:`load_segments` alias the mmap — callers keep
    the reader's ``buffer`` (exposed as ``hold``) alive for as long as the
    views are; the mmap itself is closed only by GC once every view died.
    """

    def __init__(self, path: str, signature: Optional[dict] = None,
                 verify: bool = True):
        self.path = path
        self.verify = verify
        self._store_pinned = False
        self._file, self._mm, footer = open_container(
            path, BLOCK_CACHE_MAGIC, BLOCK_CACHE_VERSION,
            f"block cache {path}")
        try:
            self.signature = footer.get("signature") or {}
            self.num_col = int(footer.get("num_col", 0))
            self.rows = int(footer.get("rows", 0))
            self._blocks = footer["blocks"]
            if signature is not None and self.signature != _normalize(
                    signature):
                raise DMLCError(
                    f"block cache {path}: source signature mismatch "
                    f"(stale cache)")
            # pin/refcount (docs/store.md): while this reader serves the
            # cache, a byte-budget squeeze may never evict it — a warm
            # epoch cannot lose its tier mid-epoch. Dropped at close().
            _artifact_store(path).pin(path)
            self._store_pinned = True
        except Exception:
            self.close()
            raise

    # ---------------- accessors ----------------

    @property
    def num_blocks(self) -> int:
        return len(self._blocks)

    @property
    def hold(self):
        """The buffer owner views must pin (the mmap)."""
        return self._mm

    def resume(self, i: int) -> Optional[dict]:
        """The stored resume annotation of block ``i`` (position just
        after it), or None when the producing parser had none."""
        return self._blocks[i]["resume"]

    def block_rows(self, i: int) -> int:
        return int(self._blocks[i]["rows"])

    def block_nbytes(self, i: int) -> int:
        e = self._blocks[i]
        return int(e["end"]) - int(e["pos"])

    def load_segments(self, i: int,
                      copy: bool = False) -> Dict[str, np.ndarray]:
        """Decode block ``i`` to {name: zero-copy read-only numpy view}.

        ``copy=True`` materializes the arrays into process memory instead
        (no ``hold`` needed): plan-ordered warm epochs serve blocks in a
        permuted pattern the OS readahead cannot predict, and the copy
        forces the page faults to land HERE — inside the caller's timed
        ``cache_read`` region — instead of leaking into whichever
        downstream stage first touches the lazy views (the same
        attribution class of bug PR 6 fixed for the serial path).

        Raises :class:`CacheCorruptionError` on a crc mismatch (or when a
        ``cache_read`` fault is injected) — callers heal by dropping the
        cache and re-parsing the source.
        """
        faults.maybe_fail("cache_read", self.path)
        entry = self._blocks[i]
        if self.verify:
            # checksum straight off the page cache: slicing the mmap would
            # memcpy the whole block span; a memoryview slice does not
            with memoryview(self._mm)[
                    int(entry["pos"]): int(entry["end"])] as span:
                ok = zlib.crc32(span) & 0xFFFFFFFF == int(entry["crc"])
            if not ok:
                raise CacheCorruptionError(
                    f"block cache {self.path}: crc mismatch on block {i}")
        segments = read_segments(self._mm, entry["arrays"])
        if copy:
            segments = {k: np.array(v) for k, v in segments.items()}
        return segments

    def block_encoded(self, i: int):
        """Block ``i``'s contiguous segment span as an
        :class:`EncodedSegments` view over the mmap — ZERO-COPY span
        export. A parse worker serving a warm cache hands this straight
        to the wire encoder (the frame payload IS the cache span, no
        per-array ``tobytes`` re-buffering) and a vectored send ships the
        mmap pages themselves. The view aliases the mmap via ``hold``;
        keep the reader open while it lives."""
        entry = self._blocks[i]
        pos, end = int(entry["pos"]), int(entry["end"])
        span = memoryview(self._mm)[pos:end]
        arrays = {name: (dt, int(off) - pos, int(nb))
                  for name, (dt, off, nb) in entry["arrays"].items()}
        return EncodedSegments(
            data=span, arrays=arrays, crc=int(entry["crc"]),
            rows=int(entry["rows"]),
            num_col=self.num_col, hold=self._mm)

    def close(self) -> None:
        # the eviction pin drops first, unconditionally — even when
        # exported views keep the mmap alive (an unlinked-but-mapped file
        # keeps serving on POSIX, so releasing the pin is always safe)
        if getattr(self, "_store_pinned", False):
            self._store_pinned = False
            try:
                _artifact_store(self.path).drop(self.path)
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


# ---------------- cache-key signature + open helper ----------------

def _normalize(obj):
    """JSON round-trip: the stored signature is what JSON preserves."""
    return json.loads(json.dumps(obj, sort_keys=True))


def source_signature(uri: str, part_index: int, num_parts: int,
                     **config) -> dict:
    """The staleness key a block cache is bound to.

    Captures the source file set with sizes and mtimes (local paths; remote
    URIs record sizes via the filesystem layer, mtime ``None``), the
    partition identity, and whatever parser/format/engine ``config`` the
    caller passes — any drift invalidates the cache on open.
    """
    base = uri.split("#", 1)[0].split("?", 1)[0]
    files: List[list] = []
    for part in base.split(";"):
        if not part:
            continue
        local = part[7:] if part.startswith("file://") else (
            part if "://" not in part else None)
        if local is not None:
            if os.path.isdir(local):
                for name in sorted(os.listdir(local)):
                    fp = os.path.join(local, name)
                    if os.path.isfile(fp):
                        st = os.stat(fp)
                        files.append([fp, st.st_size, st.st_mtime_ns])
            elif os.path.exists(local):
                st = os.stat(local)
                files.append([local, st.st_size, st.st_mtime_ns])
            else:
                files.append([part, None, None])
            continue
        try:  # remote: sizes from the filesystem layer, no mtimes
            from dmlc_tpu.io.filesystem import get_filesystem
            from dmlc_tpu.io.uri import URI

            fs = get_filesystem(part)
            info = fs.get_path_info(URI(part))
            if info.type == "directory":
                for f in fs.list_directory(info.path):
                    if f.type == "file":
                        files.append([str(f.path), f.size, None])
            else:
                files.append([str(info.path), info.size, None])
        except Exception:  # noqa: BLE001 - unreachable source: path-only key
            files.append([part, None, None])
    return _normalize({
        "cache_version": BLOCK_CACHE_VERSION,
        "files": files,
        "partition": [int(part_index), int(num_parts)],
        "config": config,
    })


def open_block_cache(path: str, signature: Optional[dict] = None,
                     verify: bool = True) -> Optional[BlockCacheReader]:
    """Open a published cache, or None when it is missing or must be
    rebuilt (unreadable / wrong version / signature mismatch — the stale
    file is dropped via the store and a ``cache_invalidations``
    resilience event counted). A miss on a path the store manifest marks
    as EVICTED counts a ``store_rebuilds_after_eviction`` event — the
    rebuild the caller now runs is the budget's doing (docs/store.md)."""
    if not os.path.exists(path):
        # light probe: only consults the store when the directory already
        # carries a manifest (never creates state for an unmanaged dir)
        _store_manager().note_missing(path)
        return None
    try:
        return BlockCacheReader(path, signature=signature, verify=verify)
    except DMLCError:
        _resilience.record_event("cache_invalidations")
        _artifact_store(path).discard(path)
        return None
