"""Plain reference: the order in which the epoch plan serves a text file.

Written from the contract in ``docs/data.md`` ("``shuffle_seed=``: the
shuffle-native warm cache"), with numpy and the standard library,
importing nothing of the program. The contract:

* **Blocks.** The parser cuts the text into chunks of at most 1 MiB that
  end at a line's end (the last newline inside the budget), and a block is
  one chunk's rows; the block cache keeps them in file order.
* **Block order.** Epoch ``e`` visits the blocks in
  ``Generator(Philox(key=[seed mod 2**64, (2**32 - 1) << 32 | e]))
  .permutation(num_blocks)``.
* **Row order.** The rows of block ``b`` come in
  ``Generator(Philox(key=[seed mod 2**64, e << 32 | b])).permutation(rows)``
  where the window is at least the block's rows; a shorter window ``w``
  shuffles each run of ``w`` rows in place, in turn, from that one
  generator; a window of 0 or 1 leaves them as they are.
* Nothing is carried from block to block or epoch to epoch: the order is a
  function of ``(seed, epoch)`` and the blocks' row counts.

The blocks' row counts come from the text itself (``cut_text``; every
function here that reads text takes the file's bytes), which
needs no cache to exist, or from the published cache's own index
(``cache_block_rows``: the container's footer, read here by its layout).
``epoch_rows`` turns them into the file-row number served at every position
of an epoch; ``read_rows`` takes rows of the text by number, ``parse_libfm``
reads their labels, ids and fields, and ``row_hashes`` / ``order_sum`` fold
them into the order-sensitive sum the harness folds on the device.
"""

from __future__ import annotations

import json
import struct
import zlib

import numpy as np

CHUNK_BYTES = 1 << 20
_M64, _M32 = (1 << 64) - 1, (1 << 32) - 1
_MAGIC = b"DMLCBC01"
_TAIL = struct.Struct("<QQI")


def _generator(seed: int, hi: int, lo: int) -> np.random.Generator:
    key = np.array([seed & _M64, ((hi & _M32) << 32) | (lo & _M32)],
                   np.uint64)
    return np.random.Generator(np.random.Philox(key=key))


def block_order(seed: int, epoch: int, num_blocks: int) -> np.ndarray:
    if num_blocks <= 1:
        return np.arange(max(0, num_blocks), dtype=np.int64)
    return _generator(seed, _M32, epoch).permutation(num_blocks).astype(
        np.int64)


def row_order(seed: int, epoch: int, block: int, rows: int,
              window: int) -> np.ndarray:
    out = np.arange(rows, dtype=np.int64)
    if window <= 1 or rows <= 1:
        return out
    gen = _generator(seed, epoch, block)
    if window >= rows:
        return gen.permutation(rows).astype(np.int64)
    for start in range(0, rows, window):
        gen.shuffle(out[start:start + window])
    return out


def cut_text(data: bytes, chunk_bytes: int = CHUNK_BYTES):
    """``(rows of every block, byte offset of every row's start with the
    text's length last)`` of a file's text cut as the parser cuts it."""
    ends = np.flatnonzero(np.frombuffer(data, np.uint8) == 0x0A) + 1
    if not len(ends) or ends[-1] != len(data):
        ends = np.append(ends, len(data))       # a last line with no newline
    starts = np.concatenate([[0], ends]).astype(np.int64)
    block_rows, row, pos = [], 0, 0
    while row < len(ends):
        # the last line end inside [pos, pos + chunk_bytes]; a single line
        # longer than the budget is a block of its own
        last = int(np.searchsorted(ends, pos + chunk_bytes, side="right"))
        last = max(last, row + 1)
        block_rows.append(last - row)
        row, pos = last, int(ends[last - 1])
    return np.asarray(block_rows, np.int64), starts


def cache_block_rows(path: str) -> np.ndarray:
    """The rows of every block of a published block cache, from the
    container's index: the last 28 bytes are ``u64 footer offset, u64
    footer length, u32 footer crc32, magic``; the footer is JSON with one
    entry a block."""
    with open(path, "rb") as f:
        f.seek(-(_TAIL.size + len(_MAGIC)), 2)
        tail = f.read()
        offset, length, crc = _TAIL.unpack(tail[:_TAIL.size])
        if tail[_TAIL.size:] != _MAGIC:
            raise ValueError(f"{path}: not a block cache (no magic at the end)")
        f.seek(offset)
        footer = f.read(length)
    if zlib.crc32(footer) != crc:
        raise ValueError(f"{path}: the footer's crc does not match")
    return np.asarray([b["rows"] for b in json.loads(footer)["blocks"]],
                      np.int64)


def epoch_rows(seed: int, epoch: int, block_rows, window: int,
               limit: int | None = None) -> np.ndarray:
    """The file-row number served at each position of epoch ``epoch`` (its
    first ``limit`` positions where given)."""
    block_rows = np.asarray(block_rows, np.int64)
    first = np.concatenate([[0], np.cumsum(block_rows)])
    out, have = [], 0
    for b in block_order(seed, epoch, len(block_rows)):
        out.append(first[b] + row_order(seed, epoch, int(b),
                                        int(block_rows[b]), window))
        have += int(block_rows[b])
        if limit is not None and have >= limit:
            break
    rows = np.concatenate(out) if out else np.zeros(0, np.int64)
    return rows if limit is None else rows[:limit]


def read_rows(data: bytes, starts: np.ndarray, rows) -> bytes:
    """The text of the given rows of ``data`` (by number, in the order
    given), joined."""
    return b"".join(data[starts[r]:starts[r + 1]] for r in np.asarray(rows))


_COLONS = bytes.maketrans(b":", b" ")
_NOT_MARKS = bytes(set(range(256)) - set(b":\n"))


def parse_libfm(data: bytes, max_nnz: int):
    """``(ids [n, max_nnz] int64, fields, labels [n] int64)`` of libfm text
    (``label field:id:value ...`` a line, every line ended by a newline); a
    short row's tail is id -1 and field -1, a longer one is cut."""
    # a line's slots from its colons: the text with all but ':' and the
    # newlines taken out is short enough to search
    marks = np.frombuffer(data.translate(None, _NOT_MARKS), np.uint8)
    ends = np.flatnonzero(marks == 0x0A)
    nnz = np.diff(np.concatenate([[0], ends - np.arange(len(ends))])) // 2
    # whole numbers alone (a click log's text: every value 1) read three
    # times as fast as floats; a '.', a sign or an exponent takes the other
    whole = not data.translate(None, b"0123456789 :\n")
    flat = np.fromstring(data.translate(_COLONS), sep=" ",
                         dtype=np.int64 if whole else np.float64)
    at = np.concatenate([[0], np.cumsum(1 + 3 * nnz)])
    if at[-1] != len(flat):
        raise ValueError("libfm text: a line is not 'label f:i:v f:i:v ...'")
    at = at[:-1]
    ids = np.full((len(at), max_nnz), -1, np.int64)
    fields = np.full((len(at), max_nnz), -1, np.int64)
    if len(nnz) and nnz.min() == nnz.max():     # one reshape, no gathers
        k = min(int(nnz[0]), max_nnz)
        table = flat.reshape(len(at), -1)
        fields[:, :k], ids[:, :k] = table[:, 1:3 * k:3], table[:, 2:1 + 3 * k:3]
        return ids, fields, table[:, 0].astype(np.int64)
    for k in range(max_nnz):
        has = nnz > k
        fields[has, k] = flat[at[has] + 1 + 3 * k]
        ids[has, k] = flat[at[has] + 2 + 3 * k]
    return ids, fields, flat[at].astype(np.int64)


def row_hashes(ids, fields, labels) -> np.ndarray:
    """One uint32 a row from its label, ids and fields, slot by slot in
    order: ``h = label + 1``, then for each real slot ``h = h * 1000003 +
    (id + 1) * (2 * field + 3)``, all modulo 2**32."""
    h = (np.asarray(labels, np.uint64) + 1) & _M32
    for k in range(ids.shape[1]):
        real = ids[:, k] >= 0
        term = ((ids[:, k] + 1).astype(np.uint64)
                * (2 * fields[:, k] + 3).astype(np.uint64)) & _M32
        h = np.where(real, (h * 1000003 + term) & _M32, h)
    return h.astype(np.uint64)


def order_sum(hashes_in_served_order) -> int:
    """The order-sensitive sum of an epoch: ``sum((position + 1) *
    hash)`` modulo 2**32."""
    h = np.asarray(hashes_in_served_order, np.uint64)
    weights = (np.arange(len(h), dtype=np.uint64) + 1) & _M32
    return int(np.sum((weights * h) & _M32, dtype=np.uint64) & _M32)
