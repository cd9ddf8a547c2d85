"""Plain reference: a delimited table of id columns read as the slots of a
field-aware factorization machine (PR 48, configuration ``kdd12_ffm_csv``).

The text is ``label<d>id0<d>...<d>id(C-1)``, whole numbers, one line a
row; every column is an id space of its own. Column ``c`` is field ``c``,
and its id ``x`` is table row ``offsets[c] + x`` with value 1: what an
offline pass to ``field:id:1`` text would have written. The arithmetic that
follows is ``ffm_adagrad.py``'s. Plain Python over the lines, importing
nothing of the program.

``through="float32"`` is the control's reading: the cells and the offsets
held in float32 on their way to a table row, as a parser that hands out
float cells would hold them. A float32 has 24 bits of whole number, so a
row above 16,777,216 comes out as a neighbour's.
"""

from __future__ import annotations

import numpy as np


def parse_column_rows(path: str, rows: int, offsets, delimiter: str = "\t",
                      through: str = "int64"):
    """The first ``rows`` rows of the table: ``(indices [rows, C] int64,
    fields int64, values float32, labels float32)``, shaped as
    ``ffm_adagrad.parse_libfm_rows`` gives them (no slot is padding)."""
    offsets = [int(o) for o in offsets]
    cols = len(offsets)
    idx = np.empty((rows, cols), np.int64)
    lab = np.zeros(rows, np.float32)
    delim = delimiter.encode()
    with open(path, "rb") as f:
        for r in range(rows):
            toks = f.readline().strip().split(delim)
            if len(toks) != cols + 1:
                raise ValueError(f"{path}: row {r} has {len(toks)} cells, "
                                 f"wanted {cols + 1}")
            lab[r] = float(int(toks[0]))
            for c in range(cols):
                cell = int(toks[1 + c])
                if through == "float32":
                    idx[r, c] = int(np.float32(offsets[c])
                                    + np.float32(cell))
                else:
                    idx[r, c] = offsets[c] + cell
    fld = np.broadcast_to(np.arange(cols, dtype=np.int64), idx.shape).copy()
    return idx, fld, np.ones(idx.shape, np.float32), lab
