"""A/B the sparse device layouts on the real chip (BASELINE config #4).

Times the batched sparse matvec ``out[b] = sum_k w[idx[b,k]] * val[b,k]``
— the inner op of every linear learner over libsvm/libfm data — across the
three device layouts (dense, ELL, BCOO) and both ELL execution paths
(XLA gather vs the Pallas one-hot kernel, ops/pallas_sparse.py), at:

  - HIGGS-like shapes (D=28, K=28: dense data in sparse clothing),
  - a mid-sparsity hashed-features shape (D=4096),
  - KDD2012-like shapes (D=1M, K=16: truly sparse).

Writes one JSON line per (shape, path) to stdout — each naming the device
it ran on — and the aggregate to ``SPARSE_TPU_<tag>.json``. A path that
fails to lower or run is recorded as an ``error`` row and the exit code is
then non-zero.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: E402,F401 - turns the compile cache on

from dmlc_tpu.ops.pallas_sparse import ell_matvec_pallas  # noqa: E402
from dmlc_tpu.ops.sparse import EllBatch, ell_matvec  # noqa: E402

REPS = 50
WARMUP = 3


def time_op(fn, *args) -> float:
    """Median-of-3 of REPS sequential dispatches (seconds per call)."""
    for _ in range(WARMUP):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(3):
        t0 = time.monotonic()
        out = None
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.monotonic() - t0) / REPS)
    return sorted(samples)[1]


def _device_tag() -> str:
    devs = jax.devices()
    return f"{devs[0].platform}/{devs[0].device_kind}/{len(devs)}"


def bench_shape(name: str, B: int, K: int, D: int, results: list) -> None:
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    idx_np = np.sort(
        rng.integers(0, D, size=(B, K)).astype(np.int32), axis=1)
    val_np = rng.normal(size=(B, K)).astype(np.float32)
    idx, val = jnp.asarray(idx_np), jnp.asarray(val_np)
    batch = EllBatch(idx, val, None, None)
    flops = 2.0 * B * K

    def record(path: str, sec: float) -> None:
        row = {
            "shape": name, "B": B, "K": K, "D": D, "path": path,
            "usec_per_call": round(sec * 1e6, 2),
            "gflops": round(flops / sec / 1e9, 2),
            "device": _device_tag(),
        }
        results.append(row)
        print(json.dumps(row), flush=True)

    def failed(path: str, exc: BaseException) -> None:
        row = {"shape": name, "path": path, "device": _device_tag(),
               "error": f"{type(exc).__name__}: {exc}"[:400]}
        results.append(row)
        print(json.dumps(row), flush=True)

    record("ell_xla_gather", time_op(jax.jit(ell_matvec), w, batch))
    # the grid-K one-hot kernel, only where a tile fits VMEM
    # (_pick_block_b); for high D no pallas kernel can win by construction
    # — see the ops/pallas_sparse.py module docstring
    from dmlc_tpu.ops.pallas_sparse import _pick_block_b, _valid_block_b

    auto_bb = _pick_block_b(B, D)
    if auto_bb:
        # in grid mode also sweep the lane tile explicitly: the one in-band
        # loss on record (D=1024/K=48, 3x) used the default bb=256, and
        # tile choice vs shape must be attributable before any auto-gate
        # cites this data. The tile list is built from VALIDATED tiles
        # only (_valid_block_b — the constraints the kernel enforces), and
        # the auto-pick run is ALWAYS included, so the canonical
        # 'ell_pallas_onehot' label is guaranteed.
        runs = [(0, "ell_pallas_onehot")]  # the production auto-pick path
        if os.environ.get("DMLC_SPARSE_GRID"):
            runs += [(bb, f"ell_pallas_bb{bb}") for bb in (128, 256)
                     if bb != auto_bb and _valid_block_b(B, D, bb)]
        for bb, label in runs:
            try:
                record(label, time_op(
                    functools.partial(ell_matvec_pallas, block_b=bb),
                    w, idx, val))
            except Exception as exc:  # noqa: BLE001 - recorded, fails the run
                failed(label, exc)
    else:
        results.append({"shape": name, "path": "ell_pallas_onehot",
                        "skipped": "no tile within VMEM; XLA gather is the "
                                   "right lowering (see "
                                   "ops/pallas_sparse.py)"})

    # dense matmul reference (only sensible when a [B, D] dense fits)
    if D <= 8192:
        x = np.zeros((B, D), np.float32)
        np.put_along_axis(x, idx_np, val_np, axis=1)
        xd = jnp.asarray(x)
        record("dense_matmul",
               time_op(jax.jit(lambda a, b: a @ b), xd, w))

    # BCOO (jax.experimental.sparse)
    try:
        from jax.experimental import sparse as jsparse

        rows = np.repeat(np.arange(B), K).astype(np.int32)
        coords = np.stack([rows, idx_np.reshape(-1)], axis=1)
        mat = jsparse.BCOO(
            (jnp.asarray(val_np.reshape(-1)), jnp.asarray(coords)),
            shape=(B, D))

        @jax.jit
        def bcoo_mv(m, v):
            return m @ v

        record("bcoo_matvec", time_op(bcoo_mv, mat, w))
    except Exception as exc:  # noqa: BLE001 - recorded, fails the run
        failed("bcoo_matvec", exc)


def main() -> int:
    dev = jax.devices()[0]
    print(f"# device: {dev} ({_device_tag()})", flush=True)
    results: list = []

    def write_results(prefix: str) -> int:
        tag = os.environ.get("DMLC_BENCH_TAG", "r02")
        out_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), f"{prefix}_{tag}.json")
        with open(out_path, "w") as f:
            json.dump({"device": str(dev), "results": results}, f, indent=1)
        print(f"# wrote {out_path}", flush=True)
        errors = [r for r in results if "error" in r]
        if errors:
            print(f"# FAIL {len(errors)} path(s) failed: "
                  f"{[(r['shape'], r['path']) for r in errors]}", flush=True)
        return 1 if errors else 0

    if os.environ.get("DMLC_SPARSE_GRID"):
        # disentangling grid for the routing decision: the A/B on record
        # (SPARSE_TPU_r05.json) has pallas winning at (D=512,K=32),
        # (D=2048,K=64), (D=4096,K=64) but losing 3x at (D=1024,K=48) — a
        # full D x K cross separates "D=1024" from "K=48"
        for D in (512, 1024, 2048, 4096):
            for K in (32, 48, 64):
                bench_shape(f"grid_d{D}_k{K}", B=8192, K=K, D=D,
                            results=results)
        return write_results("SPARSE_TPU_GRID")
    bench_shape("higgs_like", B=8192, K=28, D=28, results=results)
    # the auto-router's candidate band (ops/pallas_sparse.py gate): every
    # threshold decision must be backed by a CURRENT measurement of the
    # grid-K kernel at these widths
    bench_shape("hashed_512", B=8192, K=32, D=512, results=results)
    bench_shape("hashed_1k", B=8192, K=48, D=1024, results=results)
    bench_shape("hashed_2k", B=8192, K=64, D=2048, results=results)
    bench_shape("hashed_4k", B=8192, K=64, D=4096, results=results)
    bench_shape("kdd_like", B=8192, K=16, D=1 << 20, results=results)
    return write_results("SPARSE_TPU")


if __name__ == "__main__":
    sys.exit(main())
