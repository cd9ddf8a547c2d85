"""A plain reader of the ``DMLCCK01`` model-state container: numpy and the
standard library, importing nothing of the program.

It reads a checkpoint's files the way the format's description
(``docs/checkpoint.md``) says they lie, and shares no line with the
program's writer or reader: the magic at both ends, the header's length
and JSON, the tail's offset, length and CRC-32 of the index, the index's
CRC of the header, every chunk's CRC-32 (zlib's, which the standard
library computes), every chunk inside the file and no row of a table
missing or doubled. What it returns is what the comparison that decides
``correct`` needs: the header, rows of a table by **global row id**, the
wrapping uint32 sum of every table's bits, and which files the store's
journal holds as published (:func:`published`).

A file it cannot vouch for raises ``ValueError`` naming the file and, for
a chunk, the chunk.
"""

from __future__ import annotations

import json
import mmap
import os
import struct
import zlib

import numpy as np

MAGIC = b"DMLCCK01"
TAIL = struct.Struct("<QQI")   # index offset, index length, index crc32


def published(directory: str) -> set:
    """Names of the files the store's journal of ``directory`` holds as
    published checkpoints (``.dmlc_store/manifest.jsonl``: one JSON event
    a line, replayed in order; a ``publish`` of tier ``checkpoint`` adds
    its ``path``, an ``evict`` or a ``remove`` takes it away; a torn last
    line is skipped, as the store skips it)."""
    out: set = set()
    try:
        with open(os.path.join(directory, ".dmlc_store",
                               "manifest.jsonl")) as fh:
            lines = fh.readlines()
    except OSError:
        return out
    for raw in lines:
        try:
            event = json.loads(raw)
        except ValueError:
            continue
        if event.get("op") == "publish" and event.get("tier") == "checkpoint":
            out.add(event.get("path"))
        elif event.get("op") in ("evict", "remove"):
            out.discard(event.get("path"))
    return out


class PlainCheckpoint:
    """The files of one checkpoint, every byte verified at open."""

    def __init__(self, paths):
        self.files = [_read_file(p) for p in paths]
        self.header = self.files[0]["header"]
        steps = {f["header"]["step"] for f in self.files}
        if len(steps) != 1:
            raise ValueError(f"{paths}: files of different steps {steps}")

    def rows(self, table: str, ids) -> np.ndarray:
        """Rows of ``table`` at global row ids ``ids``."""
        ids = np.asarray(ids, np.int64)
        out = found = None
        for f in self.files:
            t = f["tables"].get(table)
            if t is None:
                continue
            data = t["data"]
            if out is None:
                out = np.zeros((len(ids),) + data.shape[1:], data.dtype)
                found = np.zeros(len(ids), bool)
            rel = ids - t["first_id"]
            local = rel // t["id_stride"]
            here = (rel % t["id_stride"] == 0) & (local >= 0) & (
                local < len(data))
            out[here] = data[local[here]]
            found |= here
        if out is None or not found.all():
            raise ValueError(f"table {table}: ids not in any file: "
                             f"{ids[~found][:4] if out is not None else ids[:4]}")
        return out

    def bit_sums(self) -> dict:
        """``{table: wrapping uint32 sum of its bits}`` over the rows that
        stand for an id (a 4-byte dtype's elements as uint32)."""
        out = {}
        for f in self.files:
            for name, t in f["tables"].items():
                bits = np.ascontiguousarray(t["data"]).view(np.uint32)
                out[name] = (out.get(name, 0) + int(
                    bits.sum(dtype=np.uint64))) % (1 << 32)
        return out


def _read_file(path: str) -> dict:
    with open(path, "rb") as fh:
        raw = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
    try:
        return _read_mapped(path, raw)
    finally:
        raw.close()


def _read_mapped(path: str, raw) -> dict:
    size = len(raw)
    if size < 24 + TAIL.size + 8 or raw[:8] != MAGIC:
        raise ValueError(f"{path}: not a DMLCCK01 file")
    if raw[-8:] != MAGIC:
        raise ValueError(f"{path}: truncated: no closing magic")
    (version,) = struct.unpack("<I", raw[8:12])
    if version != 1:
        raise ValueError(f"{path}: version {version}")
    (hlen,) = struct.unpack("<Q", raw[16:24])
    header_raw = raw[24:24 + hlen]
    off, length, crc = TAIL.unpack(raw[size - 8 - TAIL.size:size - 8])
    if off + length > size - 8 - TAIL.size:
        raise ValueError(f"{path}: the index lies past the end")
    index_raw = raw[off:off + length]
    if zlib.crc32(index_raw) != crc:
        raise ValueError(f"{path}: the index's crc does not match")
    index = json.loads(index_raw)
    if zlib.crc32(header_raw) != index["header_crc32"]:
        raise ValueError(f"{path}: the header's crc does not match")
    header = json.loads(header_raw)
    tables = {}
    for name, t in header["tables"].items():
        dtype = np.dtype(t["dtype"]).newbyteorder("<")
        shape = t["shape"]
        tables[name] = dict(t, data=np.zeros(shape, dtype),
                            seen=np.zeros(shape[0] if shape else 1, np.int32))
    for k, c in enumerate(index["chunks"]):
        what = (f"{path}: chunk {k} (table {c['table']}, rows {c['row0']}.."
                f"{c['row0'] + c['rows']})")
        if c["offset"] + c["nbytes"] > off:
            raise ValueError(what + " lies past the chunks' end")
        data = raw[c["offset"]:c["offset"] + c["nbytes"]]
        if zlib.crc32(data) != c["crc32"]:
            raise ValueError(what + ": crc mismatch")
        t = tables[c["table"]]
        values = np.frombuffer(data, t["data"].dtype)
        if t["data"].ndim:
            t["data"][c["row0"]:c["row0"] + c["rows"]] = values.reshape(
                (c["rows"],) + t["data"].shape[1:])
        else:
            t["data"][...] = values.reshape(())
        t["seen"][c["row0"]:c["row0"] + c["rows"]] += 1
    for name, t in tables.items():
        if not (t["seen"] == 1).all():
            raise ValueError(f"{path}: table {name}: rows missing or "
                             "written twice")
    return {"path": path, "header": header, "tables": tables}
