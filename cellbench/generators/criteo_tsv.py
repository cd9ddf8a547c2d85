"""Seeded click log in the form of the Criteo Display Advertising Challenge's
``train.txt`` (PR 55): ``label\\tI1..I13\\tC1..C26``, tab-separated, one line
a row, a 0/1 label, 13 integer cells written in decimal, 26 categorical
cells of 8 lower-case hex digits, and any of the 39 possibly empty.

What is drawn (the configuration's ``assumed`` says why each):

* a categorical column has a vocabulary of its own, the sizes geometric
  (``fields_zipf_libfm._field_vocabs`` over ``categorical_values``); a row's
  value is a bounded power-law rank (exponent ``zipf_s``) and the value's
  text is fixed by the seed: rank ``r`` of column ``c`` is the 32-bit word
  ``(r * a_c + b_c) mod 2**32`` (``a_c`` odd, so two ranks never share a
  word), written as 8 hex digits;
* an integer cell is ``max(-2, round(exp(N(mu, sigma^2))) - 3)``;
* an integer cell is empty with probability ``integer_empty``, a
  categorical one with ``categorical_empty``.

The consumer hashes every cell to a table row (docs/data.md, "Hashed
cells": FNV-1a 64 over the cell's position, one byte, then its bytes,
modulo ``hash_bins``), so the sums this generator returns, and the labels it
plants (``fields_zipf_libfm._vote``: every id votes by a hash bit), are over
those hashed ids, worked out here by this file's own FNV-1a. It hashes the
values that occur, once each (a categorical column's distinct ranks of a
chunk, the integer cells' whole range), not every cell.

Everything is a pure function of ``(params, seed)``; chunks are drawn from
``SeedSequence(seed).spawn`` children, the columns' words from one more.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from cellbench.generators.fields_zipf_libfm import (
    _field_vocabs, _merge, _vote, checksums)

CHUNK_ROWS = 65_536
INT_LOW, INT_HIGH = -2, 99_997     # an integer cell's range: 6 bytes of text
_INT_BYTES, _HEX_BYTES = 6, 8
_FNV_BASIS = np.uint64(0xcbf29ce484222325)
_FNV_PRIME = np.uint64(0x100000001b3)
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_PARAMS = ("hash_bins", "integer_columns", "categorical_columns",
           "categorical_values", "zipf_s", "integer_log_mean",
           "integer_log_sigma", "integer_empty", "categorical_empty",
           "label_noise")


def fnv1a(position, text: np.ndarray) -> np.ndarray:
    """FNV-1a 64 of every row of ``text`` [n, w] uint8 behind the byte
    ``position`` (a number or [n]); a zero byte is a hole, not text."""
    with np.errstate(over="ignore"):      # the hash wraps at 64 bits
        h = np.broadcast_to(
            (_FNV_BASIS ^ np.asarray(position, np.uint64)) * _FNV_PRIME,
            text.shape[:1]).copy()
        for j in range(text.shape[1]):
            there = text[:, j] != 0
            h[there] = (h[there] ^ text[there, j]) * _FNV_PRIME
    return h


def _integer_text() -> np.ndarray:
    """The decimal text of ``INT_LOW..INT_HIGH`` in 6 bytes, right-aligned,
    the bytes before a number holes."""
    values = np.arange(INT_LOW, INT_HIGH + 1)
    text = np.zeros((len(values), _INT_BYTES), np.uint8)
    for i in range(10 - INT_LOW):              # a sign, a lone 0: by hand
        s = str(int(values[i])).encode()
        text[i, _INT_BYTES - len(s):] = np.frombuffer(s, np.uint8)
    big = values >= 10
    mag = values[big]
    for d in range(5):
        digit = (mag // 10 ** d) % 10
        text[big, _INT_BYTES - 1 - d] = np.where(
            mag >= 10 ** d, digit + ord("0"), 0)
    return text


_INT_TEXT = _integer_text()


def hex_text(words: np.ndarray) -> np.ndarray:
    """``[n, 8]`` uint8: 32-bit ``words`` as 8 lower-case hex digits."""
    shifts = np.arange(28, -4, -4, dtype=np.uint64)
    return _HEX[((words.astype(np.uint64)[:, None] >> shifts)
                 & np.uint64(15)).astype(np.intp)]


def column_words(params: dict, seed: int):
    """``(a [C], b [C])`` uint64: a categorical column's rank ``r`` is the
    word ``(r * a + b) mod 2**32``."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC417E0]))
    cols = params["categorical_columns"]
    return (rng.integers(0, 1 << 31, cols, dtype=np.uint64) * np.uint64(2)
            + np.uint64(1), rng.integers(0, 1 << 32, cols, dtype=np.uint64))


@functools.lru_cache(maxsize=2)
def column_tables(hash_bins: int, n_int: int, n_cat: int):
    """``(ids of an integer column's whole range [n_int, values], id of
    every column's empty cell [n_int + n_cat])``, int64."""
    bins = np.uint64(hash_bins)
    of_value = np.stack([fnv1a(c, _INT_TEXT) % bins for c in range(n_int)])
    no_text = np.zeros((1, 0), np.uint8)
    of_empty = np.array([fnv1a(c, no_text)[0] % bins
                         for c in range(n_int + n_cat)])
    return of_value.astype(np.int64), of_empty.astype(np.int64)


def draw_rows(params: dict, seed: int, seed_seq, rows: int):
    """One chunk, as ``(ints, int_empty, words, cat_empty, ids [rows, 39]
    int64, labels [rows] uint8)``: the integer cells' values, the
    categorical cells' words, which of each are empty, every cell's hashed
    id and the planted labels."""
    rng = np.random.default_rng(seed_seq)
    n_int, n_cat = params["integer_columns"], params["categorical_columns"]
    bins = np.uint64(params["hash_bins"])
    ints = np.rint(np.exp(rng.normal(
        params["integer_log_mean"], params["integer_log_sigma"],
        (rows, n_int)))) - 3
    ints = np.clip(ints, INT_LOW, INT_HIGH).astype(np.int64)
    int_empty = rng.random((rows, n_int)) < params["integer_empty"]
    of_value, of_empty = column_tables(params["hash_bins"], n_int, n_cat)
    ids = np.empty((rows, n_int + n_cat), np.int64)
    ids[:, :n_int] = np.take_along_axis(of_value, (ints - INT_LOW).T, 1).T
    vocabs = _field_vocabs(params["categorical_values"], n_cat)
    mult, salt = column_words(params, seed)
    s = float(params["zipf_s"])
    words = np.empty((rows, n_cat), np.uint64)
    for c, vocab in enumerate(vocabs):
        u = rng.random(rows)
        # inverse CDF of the continuous power law x**-s on [1, vocab + 1)
        top = float(vocab + 1) ** (1.0 - s)
        rank = np.floor((u * (top - 1.0) + 1.0) ** (1.0 / (1.0 - s)))
        rank = np.minimum(rank.astype(np.int64) - 1, vocab - 1)
        with np.errstate(over="ignore"):
            words[:, c] = (rank.astype(np.uint64) * mult[c] + salt[c]) \
                & np.uint64(0xFFFFFFFF)
        seen, where = np.unique(words[:, c], return_inverse=True)
        ids[:, n_int + c] = (fnv1a(n_int + c, hex_text(seen)) % bins)[where]
    cat_empty = rng.random((rows, n_cat)) < params["categorical_empty"]
    empty = np.concatenate([int_empty, cat_empty], axis=1)
    ids = np.where(empty, of_empty, ids)
    votes = _vote(ids).sum(axis=1, dtype=np.int32)
    noise = rng.normal(0.0, float(params["label_noise"]), rows)
    labels = ((votes + noise) > 0).astype(np.uint8)
    return ints, int_empty, words, cat_empty, ids, labels


def format_rows(ints, int_empty, words, cat_empty, labels) -> bytes:
    """The text of one chunk, from a fixed-width byte matrix whose zero
    bytes (the holes before a number, an empty cell's text) are then
    dropped, as ``fields_zipf_libfm.format_rows`` does."""
    rows, n_int = ints.shape
    n_cat = words.shape[1]
    int_tok, cat_tok = 1 + _INT_BYTES, 1 + _HEX_BYTES
    mat = np.zeros((rows, 1 + n_int * int_tok + n_cat * cat_tok + 1), np.uint8)
    mat[:, 0] = labels + ord("0")
    mat[:, -1] = ord("\n")
    toks = mat[:, 1:1 + n_int * int_tok].reshape(rows, n_int, int_tok)
    toks[:, :, 0] = ord("\t")
    toks[:, :, 1:] = np.where(int_empty[:, :, None], 0,
                              _INT_TEXT[ints - INT_LOW])
    toks = mat[:, 1 + n_int * int_tok:-1].reshape(rows, n_cat, cat_tok)
    toks[:, :, 0] = ord("\t")
    toks[:, :, 1:] = np.where(
        cat_empty[:, :, None], 0,
        hex_text(words.reshape(-1)).reshape(rows, n_cat, _HEX_BYTES))
    flat = mat.reshape(-1)
    return flat[flat != 0].tobytes()


def generate(params: dict, seed: int, rows: int, path: str,
             threads: int = 8) -> dict:
    """Write ``rows`` rows to ``path`` and return their checksums: the sums
    of ``fields_zipf_libfm.checksums`` over the cells' hashed ids."""
    check_params(params)
    n_chunks = -(-rows // CHUNK_ROWS)
    seqs = np.random.SeedSequence(int(seed)).spawn(n_chunks)

    def one(i: int):
        n = min(CHUNK_ROWS, rows - i * CHUNK_ROWS)
        ints, int_empty, words, cat_empty, ids, labels = draw_rows(
            params, seed, seqs[i], n)
        return (format_rows(ints, int_empty, words, cat_empty, labels),
                checksums(ids, labels))

    total = {"rows": 0, "index_sum": 0, "index_sq_sum": 0, "label_sum": 0}
    tmp = path + ".partial"
    with open(tmp, "wb") as out, ThreadPoolExecutor(threads) as pool:
        for text, sums in pool.map(one, range(n_chunks)):
            out.write(text)
            total = _merge(total, sums)
    os.replace(tmp, path)
    total["bytes"] = os.path.getsize(path)
    return total


def check_params(params: dict) -> None:
    for key in _PARAMS:
        if key not in params:
            raise ValueError(f"criteo_tsv: missing parameter {key!r}")
    if not 0 < params["hash_bins"] < 2 ** 31:
        raise ValueError("criteo_tsv: hash_bins must be in [1, 2**31 - 1]")
    if params["integer_columns"] + params["categorical_columns"] > 256:
        raise ValueError("criteo_tsv: a cell's position is one byte")
    if float(params["zipf_s"]) == 1.0:
        raise ValueError("criteo_tsv: zipf_s must differ from 1")
