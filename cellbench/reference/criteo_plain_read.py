"""Plain reference: a Criteo-format click log (``label\\tI1..I13\\tC1..C26``,
any cell possibly empty) read as the slots of a field-aware factorization
machine over hashed ids (PR 55, configuration ``criteo_ffm``).

numpy and the standard library, importing nothing of the program. The text
is split on the delimiter by plain Python, and every cell but the label's
is hashed by this file's own FNV-1a, the contract of docs/data.md ("Hashed
cells") written out once more:

    h = 0xcbf29ce484222325                          (FNV-1a 64, offset basis)
    for byte in [position] + the cell's bytes:      (position: the cell's
        h = ((h xor byte) * 0x100000001b3) mod 2**64      0-based place among
    id = h mod hash_bins                                  the non-label cells)

The cell's bytes are what stands between two delimiters, nothing trimmed or
folded; an empty cell is the position byte alone, a value of its column like
any other. Column ``c`` is field ``c``, every slot's value is 1, and all
columns share the one id space: what ``ffm_adagrad.py`` then trains on.

Two broken readings are the comparison's controls: ``position_byte=False``
leaves the column out of the hash (equal texts of two columns collide), and
``drop_empty=True`` gives an empty cell no slot (index -1, value 0: the row
trains on fewer than 39 slots and its normalisation ``r`` changes).
"""

from __future__ import annotations

import numpy as np

_BASIS = 0xcbf29ce484222325
_PRIME = 0x100000001b3
_MASK = (1 << 64) - 1


def cell_id(position: int, cell: bytes, hash_bins: int,
            position_byte: bool = True) -> int:
    h = _BASIS
    if position_byte:
        h = ((h ^ position) * _PRIME) & _MASK
    for byte in cell:
        h = ((h ^ byte) * _PRIME) & _MASK
    return h % hash_bins


def split_rows(path: str, rows: int, columns: int, delimiter: str = "\t"):
    """``(labels [rows] float32, cells)``: the first ``rows`` rows' label
    and their ``columns`` other cells, bytes, in file order."""
    delim = delimiter.encode()
    labels = np.zeros(rows, np.float32)
    cells = []
    with open(path, "rb") as f:
        for r in range(rows):
            line = f.readline()
            if not line:
                raise ValueError(f"{path}: only {r} rows, wanted {rows}")
            toks = line.rstrip(b"\r\n").split(delim)
            if len(toks) != columns + 1:
                raise ValueError(f"{path}: row {r} has {len(toks)} cells, "
                                 f"wanted {columns + 1}")
            labels[r] = float(int(toks[0]))
            cells.append(toks[1:])
    return labels, cells


def hashed_rows(labels, cells, hash_bins: int, position_byte: bool = True,
                drop_empty: bool = False):
    """``(indices [rows, C] int64, fields int64, values float32, labels)``
    as ``ffm_adagrad.parse_libfm_rows`` shapes them."""
    rows, cols = len(cells), len(cells[0])
    idx = np.empty((rows, cols), np.int64)
    val = np.ones((rows, cols), np.float32)
    seen: dict = {}
    for r, row in enumerate(cells):
        for c, cell in enumerate(row):
            if drop_empty and not cell:
                idx[r, c], val[r, c] = -1, 0.0
                continue
            key = (c, cell)
            if key not in seen:
                seen[key] = cell_id(c, cell, hash_bins, position_byte)
            idx[r, c] = seen[key]
    fld = np.broadcast_to(np.arange(cols, dtype=np.int64), idx.shape).copy()
    return idx, fld, val, labels


def parse_hashed_rows(path: str, rows: int, columns: int, hash_bins: int,
                      delimiter: str = "\t", **reading):
    return hashed_rows(*split_rows(path, rows, columns, delimiter),
                       hash_bins, **reading)
