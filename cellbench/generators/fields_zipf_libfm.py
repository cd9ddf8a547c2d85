"""Seeded click-log corpus in libfm text: ``label field:index:1 ...``.

One categorical id per field and row. Field ``f`` owns the id range
``[offset_f, offset_f + vocab_f)``; a row's id in a field is a bounded
power-law rank (exponent ``zipf_s``) sent through a multiplicative
bijection of the field's range, so popular ids are spread over the whole
table and not packed at the front of each range. Labels are planted: every
id votes +1 or -1 by a hash bit, and the label is 1 where the votes plus
seeded noise are positive, so a model that learns per-id weights sees its
loss fall.

Everything is a pure function of ``(params, seed)``; chunks are drawn from
``SeedSequence(seed).spawn`` children, so the bytes do not depend on how
many threads wrote them. Formatting is numpy over a fixed-width byte
matrix that is then compressed, not a Python loop per row.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 262_144
_HASH_A = np.uint64(0x9E3779B97F4A7C15)
_ID_DIGITS = 9  # ids below 10**9


def _field_vocabs(num_features: int, fields: int) -> np.ndarray:
    """Vocabulary sizes that sum to ``num_features``: geometric, the
    largest field about half the table (user and ad ids dominate a click
    log), the smallest a few hundred or fewer."""
    shares = 0.5 ** np.arange(1, fields + 1, dtype=np.float64)
    shares[-1] = shares[-2]  # the halves then sum to 1
    vocabs = np.maximum(1, np.floor(shares * num_features)).astype(np.int64)
    vocabs[0] += num_features - int(vocabs.sum())
    return vocabs


def _multiplier(vocab: int) -> int:
    """An odd multiplier near vocab / golden ratio, coprime to ``vocab``."""
    a = max(1, int(vocab * 0.6180339887)) | 1
    while np.gcd(a, vocab) != 1:
        a += 2
    return a


def _vote(ids: np.ndarray) -> np.ndarray:
    """+1 / -1 per id from one bit of a 64-bit multiplicative hash."""
    h = (ids.astype(np.uint64) + np.uint64(1)) * _HASH_A
    return ((h >> np.uint64(40)) & np.uint64(1)).astype(np.int8) * 2 - 1


def draw_rows(params: dict, seed_seq, rows: int):
    """``(ids [rows, fields] int64, labels [rows] uint8)`` of one chunk."""
    rng = np.random.default_rng(seed_seq)
    vocabs = _field_vocabs(params["num_features"], params["fields"])
    offsets = np.concatenate([[0], np.cumsum(vocabs)[:-1]])
    s = float(params["zipf_s"])
    ids = np.empty((rows, len(vocabs)), np.int64)
    for f, (vocab, off) in enumerate(zip(vocabs, offsets)):
        u = rng.random(rows)
        # inverse CDF of the continuous power law x**-s on [1, vocab + 1)
        top = float(vocab + 1) ** (1.0 - s)
        rank = np.floor((u * (top - 1.0) + 1.0) ** (1.0 / (1.0 - s)))
        rank = np.minimum(rank.astype(np.int64) - 1, vocab - 1)
        ids[:, f] = off + (rank * _multiplier(int(vocab))) % vocab
    votes = _vote(ids).sum(axis=1, dtype=np.int32)
    noise = rng.normal(0.0, float(params["label_noise"]), rows)
    labels = ((votes + noise) > 0).astype(np.uint8)
    return ids, labels


def _digit_tables():
    """``(hi, lo_pad, lo_bare)``: the decimal text of 0..99999 in 5 bytes
    and of 0..9999 in 4, right-aligned. ``hi`` and ``lo_bare`` leave their
    leading zeros as holes (``hi`` all five for 0, ``lo_bare`` keeps the
    units digit); ``lo_pad`` keeps its zeros, for ids of five digits up."""
    def table(n, width, bare_units):
        v = np.arange(n, dtype=np.int64)
        t = np.zeros((n, width), np.uint8)
        for d in range(width):
            digit = (v // 10 ** d) % 10
            keep = v >= 10 ** d
            if d == 0 and bare_units:
                keep = np.ones(n, bool)
            t[:, width - 1 - d] = np.where(keep, digit + ord("0"), 0)
        return t
    lo_pad = (np.arange(10_000)[:, None] // 10 ** np.arange(3, -1, -1)) % 10
    return (table(100_000, 5, False), (lo_pad + ord("0")).astype(np.uint8),
            table(10_000, 4, True))


_TABLES = _digit_tables()


def format_rows(ids: np.ndarray, labels: np.ndarray) -> bytes:
    """libfm text of one chunk: ``<label> <f>:<id>:1 ... \\n`` per row."""
    rows, fields = ids.shape
    # one row of the matrix: label, then per field ' ' f ':' id(9) ':1',
    # then '\n'; zero bytes are holes the compress step drops
    tok = 1 + 2 + 1 + _ID_DIGITS + 2
    width = 1 + fields * tok + 1
    template = np.zeros(width, np.uint8)
    for f in range(fields):
        base = 1 + f * tok
        template[base] = ord(" ")
        ftxt = str(f).encode()
        template[base + 3 - len(ftxt):base + 3] = np.frombuffer(ftxt, np.uint8)
        template[base + 3] = ord(":")
        template[base + 4 + _ID_DIGITS] = ord(":")
        template[base + 5 + _ID_DIGITS] = ord("1")
    template[-1] = ord("\n")
    mat = np.empty((rows, width), np.uint8)
    mat[:] = template
    mat[:, 0] = labels + ord("0")
    toks = mat[:, 1:1 + fields * tok].reshape(rows, fields, tok)
    hi_tab, lo_pad, lo_bare = _TABLES
    u = ids.astype(np.uint32)
    hi, lo = u // np.uint32(10_000), u % np.uint32(10_000)
    toks[:, :, 4:9] = hi_tab[hi]
    lo_txt = lo_pad[lo]
    small = hi == 0
    lo_txt[small] = lo_bare[lo[small]]
    toks[:, :, 9:13] = lo_txt
    flat = mat.reshape(-1)
    return flat[flat != 0].tobytes()


def checksums(ids: np.ndarray, labels: np.ndarray) -> dict:
    """Order-independent sums a device consumer can repeat in uint32."""
    u = ids.astype(np.uint64)
    return {
        "rows": int(ids.shape[0]),
        "index_sum": int(u.sum() % (1 << 32)),
        "index_sq_sum": int(((u * u) % (1 << 32)).sum() % (1 << 32)),
        "label_sum": int(labels.sum()),
    }


def _merge(a: dict, b: dict) -> dict:
    out = {k: a[k] + b[k] for k in a}
    for k in ("index_sum", "index_sq_sum"):
        out[k] %= 1 << 32
    return out


def generate(params: dict, seed: int, rows: int, path: str,
             threads: int = 8) -> dict:
    """Write ``rows`` rows to ``path`` and return their checksums."""
    check_params(params)
    n_chunks = -(-rows // CHUNK_ROWS)
    seqs = np.random.SeedSequence(int(seed)).spawn(n_chunks)

    def one(i: int):
        n = min(CHUNK_ROWS, rows - i * CHUNK_ROWS)
        ids, labels = draw_rows(params, seqs[i], n)
        return format_rows(ids, labels), checksums(ids, labels)

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
    for key in ("num_features", "fields", "zipf_s", "label_noise"):
        if key not in params:
            raise ValueError(f"fields_zipf_libfm: missing parameter {key!r}")
    if params["num_features"] >= 10 ** _ID_DIGITS:
        raise ValueError("fields_zipf_libfm: ids need more than 9 digits")
    if float(params["zipf_s"]) == 1.0:
        raise ValueError("fields_zipf_libfm: zipf_s must differ from 1")
