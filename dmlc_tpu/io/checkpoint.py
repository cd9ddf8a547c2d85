"""The ``DMLCCK01`` model-state container: one file of a checkpoint.

What a learner knows is tables of rows and a few small arrays; a file
holds some of a checkpoint's tables' rows, in their **logical** columns
(a ``[rows, 44]`` float32 table is 176 bytes a row here, whatever padding
the device's layout adds), in chunks of whole rows, each chunk under its
own CRC-32. Rows are addressed by **global row id**: a table's entry says
which ids this file holds (``first_id + i * id_stride`` for the file's
``i``-th row), so a table written by four shards under a cyclic
:class:`~dmlc_tpu.parallel.mesh.RowDeal` is read back under any other
deal, each reader taking only the rows it owns
(:mod:`dmlc_tpu.models._checkpoint`; docs/checkpoint.md).

Layout (little-endian), the block cache's container head and tail
(:func:`dmlc_tpu.io.block_cache.container_header` /
:func:`~dmlc_tpu.io.block_cache.open_container`)::

    0    magic "DMLCCK01", u32 version (1), 4 zero bytes
    16   u64 header length, then the header: JSON (the learner's static
         metadata, ``step``, the iterator's ``state_dict()``, the deal and
         which shard of it this file is, every table's name / dtype /
         shape / ids)
    ...  chunks, each at a multiple of 64: ``rows * row_bytes`` raw bytes,
         C order
    ...  the index, at a multiple of 64: JSON ``{"header_crc32", "chunks":
         [{"table", "row0", "rows", "offset", "nbytes", "crc32"}, ...]}``
    end  u64 index offset, u64 index length, u32 CRC-32 of the index,
         magic "DMLCCK01"

The CRC is zlib's (the standard library computes it; the benchmark's
plain reader, ``cellbench/reference/ckpt_plain_read.py``, shares no code
with this module). The writer goes through :func:`dmlc_tpu.io.stream.\
open_stream` to a path the artifact store staged, and publishes through
the store's ``checkpoint`` tier: fsync, atomic rename, manifest record. A
writer that dies first leaves a ``.tmp`` orphan and no published file.
"""

from __future__ import annotations

import json
import os
import re
import struct
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from dmlc_tpu.io import block_cache as _bc
from dmlc_tpu.io import faults
from dmlc_tpu.io.stream import open_stream
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import DMLCError, check

CHECKPOINT_MAGIC = b"DMLCCK01"
CHECKPOINT_VERSION = 1
CHECKPOINT_SUFFIX = ".dmlcck"
_HEADER = _bc.container_header(CHECKPOINT_MAGIC, CHECKPOINT_VERSION)
_LEN = struct.Struct("<Q")


def checkpoint_name(step: int, shard: int = 0, shards: int = 1) -> str:
    """The file name of shard ``shard`` of ``shards`` of the checkpoint
    after ``step`` steps."""
    return f"ckpt-{int(step):012d}-{shard:05d}-of-{shards:05d}" \
        + CHECKPOINT_SUFFIX


_NAME = re.compile(r"^ckpt-(\d{12})-(\d{5})-of-(\d{5})"
                   + re.escape(CHECKPOINT_SUFFIX) + "$")


def parse_checkpoint_name(name: str) -> Optional[Tuple[int, int, int]]:
    """``(step, shard, shards)`` of a name :func:`checkpoint_name` made,
    else ``None``."""
    m = _NAME.match(os.path.basename(name))
    return tuple(int(x) for x in m.groups()) if m else None


class CheckpointWriter:
    """Streams one file of a checkpoint to a store-staged path:
    :meth:`add_chunk` for every run of rows, :meth:`finish` to write the
    index and publish. ``header`` is JSON-able and must carry ``tables``:
    ``{name: {"dtype", "shape", "first_id", "id_stride", ...}}`` with
    ``shape`` the shape of this file's part of the table."""

    def __init__(self, path: str, header: dict):
        self.path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self.tmp_path = _bc._artifact_store(path).stage_path(path)
        # through the stream layer: wherever a block cache can be written
        self._f = open_stream(self.tmp_path, "w")
        payload = json.dumps(header, sort_keys=True,
                             separators=(",", ":")).encode()
        self._f.write(_HEADER + _LEN.pack(len(payload)) + payload)
        self._header_crc = zlib.crc32(payload) & 0xFFFFFFFF
        self.tables = header["tables"]
        self._chunks: List[dict] = []
        self.nbytes = 0

    def add_chunk(self, table: str, row0: int, rows: np.ndarray) -> int:
        """Append rows ``[row0, row0 + len(rows))`` of this file's part of
        ``table`` (a scalar table: its one value, ``row0`` 0). Returns the
        chunk's CRC."""
        check(self._f is not None, "CheckpointWriter: already finished")
        want = self.tables[table]
        data = np.ascontiguousarray(rows, np.dtype(want["dtype"]).newbyteorder("<"))
        check(list(data.shape[1:]) == list(want["shape"][1:]),
              f"CheckpointWriter: a chunk of {table} is shaped "
              f"{data.shape}, the table {want['shape']}")
        faults.maybe_fail("ckpt_write", self.path)
        offset = _bc._pad_to(self._f, _bc._ALIGN)
        view = memoryview(data).cast("B") if data.ndim else data.tobytes()
        crc = zlib.crc32(view) & 0xFFFFFFFF
        self._f.write(view)
        self._chunks.append({
            "table": table, "row0": int(row0),
            "rows": int(data.shape[0]) if data.ndim else 1,
            "offset": offset, "nbytes": data.nbytes, "crc32": crc})
        self.nbytes += data.nbytes
        return crc

    def finish(self) -> None:
        """Write the index and the tail, then publish through the store's
        ``checkpoint`` tier: fsync before the rename, the manifest record
        synced after it. Returns once the file is published and durable."""
        f = self._f
        faults.maybe_fail("ckpt_publish", self.path)
        payload = json.dumps(
            {"header_crc32": self._header_crc, "chunks": self._chunks},
            sort_keys=True, separators=(",", ":")).encode()
        off = _bc._pad_to(f, _bc._ALIGN)
        f.write(payload)
        f.write(struct.pack(_bc._TAIL_FMT, off, len(payload),
                            zlib.crc32(payload) & 0xFFFFFFFF))
        f.write(CHECKPOINT_MAGIC)
        with _telemetry.span("ckpt_sync"):
            faults.maybe_fail("ckpt_sync", self.path)
            f.flush()
            os.fsync(f.fileno())
        with _telemetry.span("ckpt_publish"):
            _bc._artifact_store(self.path).publish_file(
                self.tmp_path, self.path, tier="checkpoint", fobj=f)
        self._f = None

    def abort(self) -> None:
        """Drop the staged bytes (an exception on the way): nothing was
        published."""
        f, self._f = self._f, None
        if f is not None:
            f.close()
            try:
                os.remove(self.tmp_path)
            except OSError:
                pass


class CheckpointReader:
    """One published file, structure verified at open (both magics, the
    index's CRC, the header's CRC, every chunk inside the file: a
    truncated file is refused here); a chunk's bytes are verified when it
    is read, and a failure names the chunk."""

    def __init__(self, path: str):
        self.path = path
        what = f"checkpoint {path}"
        self._f, self._mm, index = _bc.open_container(
            path, CHECKPOINT_MAGIC, CHECKPOINT_VERSION, what)
        try:
            (n,) = _LEN.unpack(self._mm[len(_HEADER):len(_HEADER) + 8])
            start = len(_HEADER) + 8
            payload = self._mm[start:start + n]
            check(len(payload) == n
                  and zlib.crc32(payload) & 0xFFFFFFFF
                  == index["header_crc32"],
                  f"{what}: header crc mismatch")
            self.header = json.loads(payload)
            self.chunks: List[dict] = index["chunks"]
            size = len(self._mm)
            for k, c in enumerate(self.chunks):
                check(c["offset"] + c["nbytes"] <= size,
                      f"{what}: chunk {k} ({c['table']} rows from "
                      f"{c['row0']}) lies past the end of the file")
        except Exception:
            self.close()
            raise
        self.tables: Dict[str, dict] = self.header["tables"]
        self._by_table: Dict[str, List[int]] = {}
        for k, c in enumerate(self.chunks):
            self._by_table.setdefault(c["table"], []).append(k)

    def chunks_of(self, table: str) -> List[int]:
        """Indices of ``table``'s chunks, ascending by ``row0``."""
        return self._by_table.get(table, [])

    def chunk_bytes(self, k: int) -> memoryview:
        """Chunk ``k``'s bytes as they lie in the mapped file, unverified
        and not copied: release what is made of them before
        :meth:`close`."""
        c = self.chunks[k]
        faults.maybe_fail("ckpt_read", self.path)
        return memoryview(self._mm)[c["offset"]:c["offset"] + c["nbytes"]]

    def verify_chunk(self, k: int, data) -> None:
        c = self.chunks[k]
        if zlib.crc32(data) & 0xFFFFFFFF != c["crc32"]:
            raise DMLCError(
                f"checkpoint {self.path}: crc mismatch in chunk {k} "
                f"(table {c['table']}, rows {c['row0']}.."
                f"{c['row0'] + c['rows']})")

    def decode(self, k: int, data) -> np.ndarray:
        """Chunk ``k``'s bytes as an array of its table's dtype,
        ``[rows, ...]`` (a scalar table: ``[]``)."""
        c = self.chunks[k]
        want = self.tables[c["table"]]
        shape = want["shape"]
        out = np.frombuffer(data, np.dtype(want["dtype"]).newbyteorder("<"))
        return out.reshape([c["rows"]] + list(shape[1:]) if shape else [])

    def read_chunk(self, k: int) -> np.ndarray:
        """Chunk ``k``, CRC-checked and decoded."""
        data = self.chunk_bytes(k)
        self.verify_chunk(k, data)
        return self.decode(k, data)

    def close(self) -> None:
        mm, f = getattr(self, "_mm", None), getattr(self, "_f", None)
        self._mm = self._f = None
        if mm is not None:
            try:
                mm.close()
            except BufferError:  # a chunk view still out: the gc closes it
                pass
        if f is not None:
            f.close()

    def __enter__(self) -> "CheckpointReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
