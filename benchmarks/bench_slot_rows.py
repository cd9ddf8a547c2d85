"""Time the ragged (``bcoo``) FM step's own pieces on the chip, alone, at
the kddb_fm cell's shape: 65,536 rows of 4 to 256 non-zeros (about 1.93 M
slots) over a table of 29,890,097 rows (PR 37). ``ROW_BLOCK`` /
``ROW_CHUNK`` in ops/slot_rows.py come from here:

    chiprun -- python3 benchmarks/bench_slot_rows.py [--grid] [--step]
    chiprun -- python3 benchmarks/bench_slot_rows.py --permute

Always: XLA's ``segment_sum`` and ``take`` over the batch's rows, the two
kernels at the module's block and chunk, and the row ids rebuilt from the
row pointer (``DeviceIter``'s ``csr_wire``). ``--grid`` adds the kernels
over a grid of (rows a block, slots a chunk); ``--step`` the whole
``FMLearner(layout="bcoo")`` step on the route the chip takes, and the same
rows through ``layout="ell"`` at K = 256. ``--permute`` runs only the
leg behind ``sorted_walk.GATHER_OPERAND_BYTES``: XLA's gather of
``[w, N]`` lane-major columns by a permutation at w = 8, 9, 16 and five N
from 1,048,576 to 2,097,152, beside one two-operand sort a column.

One JSON line per timing (median ms of five warm calls); needs a TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.generators import ragged_zipf_libsvm as gen
from dmlc_tpu.ops import slot_rows as sr
from dmlc_tpu.ops import sorted_walk as sw

W1, F, B, BUCKET = 29_890_097, 8, 65_536, 4096
PARAMS = {"num_features": W1 - 2, "zipf_s": 1.1, "label_noise": 1.0,
          "len_mu": 3.2561, "len_sigma": 0.5, "len_min": 4, "len_max": 256}


def timed(name: str, fn, *args, **extra):
    t = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    extra["first_call_s"] = round(time.perf_counter() - t, 2)
    ms = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(1e3 * (time.perf_counter() - t))
    print(json.dumps({"what": name, "ms": round(statistics.median(ms), 3),
                      **extra}), flush=True)
    return out


def batch(seed: int):
    lens, ids, labels = gen.draw_rows(PARAMS, np.random.SeedSequence(seed), B)
    nnz = int(lens.sum())
    n = -(-nnz // BUCKET) * BUCKET
    rows = np.full(n, B, np.int32)
    rows[:nnz] = np.repeat(np.arange(B, dtype=np.int32), lens)
    cols = np.full(n, W1 - 1, np.int32)
    cols[:nnz] = ids + 1
    vals = np.zeros(n, np.float32)
    vals[:nnz] = np.repeat(1.0 / np.sqrt(lens), lens)
    ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    return lens, rows, cols, vals, ptr, labels.astype(np.float32), nnz


def permute_leg() -> None:
    rng = np.random.default_rng(0)
    for n in (1 << 20, 1_310_720, 1_572_864, 1_929_216, 1 << 21):
        perm = jnp.asarray(rng.permutation(n), jnp.int32)
        for w in (8, 9, 16):
            cols = jnp.asarray(rng.normal(size=(w, n)), jnp.float32)
            timed("permute_columns", jax.jit(sw.permute_columns), cols, perm,
                  n=n, w=w, operand_mb=round(4e-6 * -(-w // 8) * 8 * n, 1))
        timed("scatter_columns_by_sort", jax.jit(sw.scatter_columns_by_sort),
              cols[:9], perm, n=n, w=9)
        inverse = timed("inverse_permutation",
                        jax.jit(sw.inverse_permutation), perm, n=n)
        timed("permute_wide_columns", jax.jit(sw.permute_wide_columns),
              cols[:9], perm, inverse, n=n, w=9)
        timed("one_sort_of_every_column", jax.jit(
            lambda c, i: jnp.stack(jax.lax.sort((i,) + tuple(c),
                                                num_keys=1)[1:])),
            cols[:9], perm, n=n, w=9)


def main() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    if "--permute" in sys.argv:
        return permute_leg()
    lens, rows, cols, vals, ptr, labels, nnz = batch(37)
    n = len(rows)
    print(json.dumps({"slots": n, "nnz": nnz, "rows": B}), flush=True)
    rng = np.random.default_rng(0)
    rid = jnp.asarray(rows)
    q = jnp.asarray(rng.normal(size=n), jnp.float32) * (rid < B)
    a = jnp.asarray(rng.normal(size=(n, F)), jnp.float32) * (rid < B)[:, None]
    tq = jnp.asarray(rng.normal(size=B), jnp.float32)
    ta = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)

    want = timed("segment_sum_xla", jax.jit(lambda q, a, r: tuple(
        jax.ops.segment_sum(x, r, num_segments=B, indices_are_sorted=True)
        for x in (q, a))), q, a, rid)
    want_t = timed("take_xla", jax.jit(lambda q, a, r: tuple(
        jnp.take(x, r, axis=0, mode="fill", fill_value=0)
        for x in (q, a))), tq, ta, rid)

    def kernels(block, chunk):
        got = timed("rows_sum_kernel", jax.jit(
            lambda q, a, r: sr.rows_sum_kernel((q, a), r, B, block, chunk)),
            q, a, rid, block=block, chunk=chunk)
        got_t = timed("rows_take_kernel", jax.jit(
            lambda q, a, r: sr.rows_take_kernel((q, a), r, block, chunk)),
            tq, ta, rid, block=block, chunk=chunk)
        gap = max(float(jnp.abs(x - y).max()) for x, y in zip(
            want + want_t, got + got_t))
        print(json.dumps({"what": "kernels_against_xla", "block": block,
                          "chunk": chunk, "max_abs_gap": gap}), flush=True)

    kernels(sr.ROW_BLOCK, sr.ROW_CHUNK)
    if "--grid" in sys.argv:
        for block in (256, 512, 1024, 2048, 4096):
            for chunk in (128, 256, 512, 1024):
                if (block, chunk) != (sr.ROW_BLOCK, sr.ROW_CHUNK):
                    try:
                        kernels(block, chunk)
                    except Exception as exc:  # noqa: BLE001 - a tile Mosaic refuses
                        print(json.dumps({"what": "refused", "block": block,
                                          "chunk": chunk,
                                          "why": repr(exc)[:200]}), flush=True)

    from dmlc_tpu.data.device import _csr_coords

    timed("csr_coords", _csr_coords, jnp.asarray(cols), jnp.asarray(ptr))
    pair = np.stack([rows, cols], axis=1)
    t = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(jax.device_put([vals, pair]))
    print(json.dumps({"what": "put_pair_wire", "ms": round(
        200 * (time.perf_counter() - t), 3), "bytes": vals.nbytes
        + pair.nbytes}), flush=True)
    t = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(jax.device_put([vals, cols, ptr]))
    print(json.dumps({"what": "put_csr_wire", "ms": round(
        200 * (time.perf_counter() - t), 3), "bytes": vals.nbytes
        + cols.nbytes + ptr.nbytes}), flush=True)

    if "--step" not in sys.argv:
        return
    from jax.experimental import sparse as jsparse

    from dmlc_tpu.models import FMLearner
    from dmlc_tpu.ops.sparse import EllBatch
    from dmlc_tpu.utils import telemetry

    model = FMLearner(W1 - 1, F, layout="bcoo", seed=1)
    mat = jsparse.BCOO((jnp.asarray(vals), jnp.asarray(pair)),
                       shape=(B, W1 - 1))
    lab, wgt = jnp.asarray(labels), jnp.ones(B, jnp.float32)
    timed("fm_bcoo_step", lambda: model.step((mat, lab, wgt)))
    print(json.dumps({"routes": {k: getattr(telemetry, k)() for k in (
        "table_update_routes", "table_gather_routes", "grad_scatter_routes",
        "slot_rows_routes")}}), flush=True)
    del model
    k = int(lens.max())
    idx = np.full((B, k), W1 - 1, np.int32)
    val = np.zeros((B, k), np.float32)
    real = np.arange(k) < lens[:, None]
    idx[real], val[real] = cols[:nnz], vals[:nnz]
    model = FMLearner(W1 - 1, F, layout="ell", seed=1)
    ell = EllBatch(jnp.asarray(idx), jnp.asarray(val), lab, wgt)
    timed("fm_ell_step_at_the_longest_row", lambda: model.step(ell), k=k)


if __name__ == "__main__":
    main()
