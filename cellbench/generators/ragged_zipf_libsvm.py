"""Seeded ragged corpus in libsvm text: ``label id:value id:value ...``.

Rows of differing length with real values, as the LIBSVM page's
``kdd2010 (bridge to algebra)`` has them and no row of
``fields_zipf_libfm`` does:

- a row's length is ``clip(round(exp(N(len_mu, len_sigma^2))), len_min,
  len_max)``: log-normal, a long right tail;
- its ids are bounded power-law ranks (exponent ``zipf_s``) over the whole
  table, sent through ``fields_zipf_libfm``'s multiplicative bijection so
  that popular ids are spread over the table; within a row they are
  distinct (a repeat is drawn again, uniformly) and ascending, and they
  are printed **1-based**, as the LIBSVM page's files are: ids
  ``1 .. num_features``;
- every value of a row is ``1 / sqrt(length)`` printed with six
  significant digits (instances of unit length), so the parser reads
  decimals and not the one character ``1``;
- labels are planted as in ``fields_zipf_libfm``: every id votes +1 or -1
  by a hash bit, weighted by the row's value, and the label is 1 where
  the votes plus seeded noise are positive.

Everything is a pure function of ``(params, seed)``; chunks are drawn from
``SeedSequence(seed).spawn`` children, so the bytes do not depend on how
many threads wrote them. The checksums are over the ids as printed: the
id space the program trains in (libFM reads a 1-based file as it stands
and leaves attribute 0 unused).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from cellbench.generators.fields_zipf_libfm import _merge, _multiplier, _vote

CHUNK_ROWS = 131_072
_ID_DIGITS = 8   # printed ids below 10**8
_VAL_CHARS = 10  # '%.6g' of 1 / sqrt(L), L >= 1: at most 0.0xxxxxxx


def draw_rows(params: dict, seed_seq, rows: int):
    """``(lengths [rows] int64, ids [nnz] int64 0-based and ascending
    within a row, labels [rows] uint8)`` of one chunk."""
    rng = np.random.default_rng(seed_seq)
    vocab = int(params["num_features"])
    s = float(params["zipf_s"])
    lens = np.clip(np.rint(np.exp(rng.normal(
        float(params["len_mu"]), float(params["len_sigma"]), rows))),
        int(params["len_min"]), int(params["len_max"])).astype(np.int64)
    nnz = int(lens.sum())
    row_of = np.repeat(np.arange(rows, dtype=np.int64), lens)
    # inverse CDF of the continuous power law x**-s on [1, vocab + 1)
    top = float(vocab + 1) ** (1.0 - s)
    rank = np.floor((rng.random(nnz) * (top - 1.0) + 1.0) ** (1.0 / (1.0 - s)))
    rank = np.minimum(rank.astype(np.int64) - 1, vocab - 1)
    ids = (rank * _multiplier(vocab)) % vocab
    # distinct and ascending within a row: sort by (row, id) and draw every
    # repeat again, uniformly over the table, until none is left
    key = row_of * vocab + ids
    while True:
        key.sort()
        dup = np.flatnonzero(key[1:] == key[:-1]) + 1
        if not len(dup):
            break
        key[dup] = (key[dup] // vocab) * vocab + rng.integers(
            0, vocab, len(dup))
    ids = key % vocab
    x = 1.0 / np.sqrt(lens.astype(np.float64))
    votes = np.add.reduceat(_vote(ids).astype(np.float64), np.concatenate(
        [[0], np.cumsum(lens)[:-1]])) * x
    noise = rng.normal(0.0, float(params["label_noise"]), rows)
    labels = ((votes + noise) > 0).astype(np.uint8)
    return lens, ids, labels


def _value_table(len_max: int) -> np.ndarray:
    """``'%.6g' % (1 / sqrt(L))`` for L = 0 .. len_max as rows of
    ``_VAL_CHARS`` bytes, left-aligned, the rest holes (zero bytes)."""
    table = np.zeros((len_max + 1, _VAL_CHARS), np.uint8)
    for length in range(1, len_max + 1):
        text = (b"%.6g" % (1.0 / np.sqrt(length)))
        table[length, :len(text)] = np.frombuffer(text, np.uint8)
    return table


def format_rows(lens: np.ndarray, ids: np.ndarray, labels: np.ndarray,
                values: np.ndarray) -> bytes:
    """libsvm text of one chunk: ``<label> <id>:<value> ... \\n`` per row,
    ids 1-based. ``values`` is :func:`_value_table`."""
    # one token: '\n' and the label in front of a row's first token, then
    # ' ', the id's digits, ':', the value; zero bytes are holes that the
    # compress step drops
    width = 2 + 1 + _ID_DIGITS + 1 + _VAL_CHARS
    mat = np.zeros((len(ids), width), np.uint8)
    first = np.concatenate([[0], np.cumsum(lens)[:-1]])
    mat[first, 0] = ord("\n")
    mat[first, 1] = labels + ord("0")
    mat[:, 2] = ord(" ")
    printed = ids + 1
    for d in range(_ID_DIGITS):
        digit = (printed // 10 ** d) % 10
        mat[:, 2 + _ID_DIGITS - d] = np.where(printed >= 10 ** d,
                                              digit + ord("0"), 0)
    mat[:, 3 + _ID_DIGITS] = ord(":")
    mat[:, 4 + _ID_DIGITS:] = values[np.repeat(lens, lens)]
    flat = mat.reshape(-1)
    # the chunk's leading newline moves to its end
    return flat[flat != 0][1:].tobytes() + b"\n"


def checksums(lens: np.ndarray, ids: np.ndarray, labels: np.ndarray) -> dict:
    """Order-independent sums a device consumer can repeat in uint32, over
    the ids as printed."""
    u = (ids + 1).astype(np.uint64)
    return {
        "rows": int(len(lens)),
        "index_sum": int(u.sum() % (1 << 32)),
        "index_sq_sum": int(((u * u) % (1 << 32)).sum() % (1 << 32)),
        "label_sum": int(labels.sum()),
        "nnz": int(len(ids)),
    }


def generate(params: dict, seed: int, rows: int, path: str,
             threads: int = 8) -> dict:
    """Write ``rows`` rows to ``path`` and return their checksums."""
    check_params(params)
    n_chunks = -(-rows // CHUNK_ROWS)
    seqs = np.random.SeedSequence(int(seed)).spawn(n_chunks)
    values = _value_table(int(params["len_max"]))

    def one(i: int):
        n = min(CHUNK_ROWS, rows - i * CHUNK_ROWS)
        lens, ids, labels = draw_rows(params, seqs[i], n)
        return (format_rows(lens, ids, labels, values),
                checksums(lens, ids, labels))

    total = {"rows": 0, "index_sum": 0, "index_sq_sum": 0, "label_sum": 0,
             "nnz": 0}
    tmp = path + ".partial"
    with open(tmp, "wb") as out, ThreadPoolExecutor(threads) as pool:
        for text, sums in pool.map(one, range(n_chunks)):
            out.write(text)
            total = _merge(total, sums)
    os.replace(tmp, path)
    total["bytes"] = os.path.getsize(path)
    return total


def check_params(params: dict) -> None:
    for key in ("num_features", "zipf_s", "label_noise", "len_mu",
                "len_sigma", "len_min", "len_max"):
        if key not in params:
            raise ValueError(f"ragged_zipf_libsvm: missing parameter {key!r}")
    if params["num_features"] + 1 >= 10 ** _ID_DIGITS:
        raise ValueError("ragged_zipf_libsvm: ids need more than 8 digits")
    if float(params["zipf_s"]) == 1.0:
        raise ValueError("ragged_zipf_libsvm: zipf_s must differ from 1")
    if not 1 <= int(params["len_min"]) <= int(params["len_max"]):
        raise ValueError("ragged_zipf_libsvm: len_min .. len_max is empty")
