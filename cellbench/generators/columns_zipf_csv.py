"""Seeded click log as a delimited table of id columns (PR 48):
``label\\tid0\\t...\\tid10``, whole numbers, one line a row.

The draws are ``fields_zipf_libfm``'s own (``draw_rows``, imported): the
same seed gives the same rows, so a cell fed by this file and one fed by
the libfm text train the same rows in two encodings. What differs is what
the publisher of such a table writes: every column is an id space of its
own, so column ``c`` holds the row's id **less the column's offset** (the
cumulative sizes of ``_field_vocabs``), and the field is the column's
position, nowhere in the text. The sums the harness checks are over the
table rows the columns stand for (id plus offset), which are the libfm
file's ids.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from cellbench.generators.fields_zipf_libfm import (
    _ID_DIGITS, _TABLES, CHUNK_ROWS, _field_vocabs, _merge, check_params,
    checksums, draw_rows)


def column_vocabs(params: dict) -> list:
    """The columns' vocabulary sizes: ``fields_zipf_libfm``'s."""
    return [int(v) for v in _field_vocabs(params["num_features"],
                                          params["fields"])]


def offsets_of(vocabs) -> np.ndarray:
    """Table row of every column's id 0: the cumulative vocabularies."""
    vocabs = np.asarray(vocabs, np.int64)
    return np.concatenate([[0], np.cumsum(vocabs)[:-1]])


def column_offsets(params: dict) -> np.ndarray:
    return offsets_of(column_vocabs(params))


def format_rows(local: np.ndarray, labels: np.ndarray) -> bytes:
    """The text of one chunk: ``<label>\\t<id>...\\t<id>\\n`` per row, from
    a fixed-width byte matrix whose zero bytes (the ids' leading holes) are
    then dropped, as ``fields_zipf_libfm.format_rows`` does."""
    rows, cols = local.shape
    tok = 1 + _ID_DIGITS
    mat = np.zeros((rows, 1 + cols * tok + 1), np.uint8)
    mat[:, 0] = labels + ord("0")
    mat[:, -1] = ord("\n")
    toks = mat[:, 1:1 + cols * tok].reshape(rows, cols, tok)
    toks[:, :, 0] = ord("\t")
    hi_tab, lo_pad, lo_bare = _TABLES
    u = local.astype(np.uint32)
    hi, lo = u // np.uint32(10_000), u % np.uint32(10_000)
    toks[:, :, 1:6] = hi_tab[hi]
    lo_txt = lo_pad[lo]
    small = hi == 0
    lo_txt[small] = lo_bare[lo[small]]
    toks[:, :, 6:10] = lo_txt
    flat = mat.reshape(-1)
    return flat[flat != 0].tobytes()


def generate(params: dict, seed: int, rows: int, path: str,
             threads: int = 8) -> dict:
    """Write ``rows`` rows to ``path`` and return their checksums."""
    check_params(params)
    offsets = column_offsets(params)
    n_chunks = -(-rows // CHUNK_ROWS)
    seqs = np.random.SeedSequence(int(seed)).spawn(n_chunks)

    def one(i: int):
        n = min(CHUNK_ROWS, rows - i * CHUNK_ROWS)
        ids, labels = draw_rows(params, seqs[i], n)
        return format_rows(ids - offsets, labels), checksums(ids, labels)

    total = {"rows": 0, "index_sum": 0, "index_sq_sum": 0, "label_sum": 0}
    tmp = path + ".partial"
    with open(tmp, "wb") as out, ThreadPoolExecutor(threads) as pool:
        for text, sums in pool.map(one, range(n_chunks)):
            out.write(text)
            total = _merge(total, sums)
    os.replace(tmp, path)
    total["bytes"] = os.path.getsize(path)
    return total
