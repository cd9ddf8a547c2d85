"""Time the transposition the line side of a kernel's slots makes in VMEM
(ops/sorted_walk.py: ``slot_layout``), alone on the chip, at kdd12_ffm's
1,048,576 slots of 44 columns: a chunk's ``[48, 128]`` rows to ``[128,
128]`` lines (the forward kernel's way out) and its lines back to ``[48,
128]`` (the update kernel's way in), each in the ways Mosaic compiles,
``--reps`` times a chunk so that the slope is the transposition and the
intercept the stream (PERF.md §6, PR 47):

    chiprun -- python3 benchmarks/bench_line_transpose.py

One JSON line per timing (median ms of five warm calls); needs a TPU.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

N, R, C, LANES = 1 << 20, 48, 128, 128
CHUNKS_A_STEP = 16


def to_lines(way: str, reps: int, x_ref, o_ref):
    for c in range(CHUNKS_A_STEP):
        at = slice(c * C, (c + 1) * C)
        out = jnp.zeros((C, LANES if way == "padded" else R), jnp.float32)
        for r in range(reps):
            x = x_ref[:, at] + float(r)
            if way == "padded":
                x = jnp.concatenate(
                    [x, jnp.zeros((LANES - R, C), jnp.float32)])
            out = out + x.T
        if way == "padded":
            o_ref[at, :] = out
        else:
            o_ref[at, :R] = out
            o_ref[at, R:] = jnp.zeros((C, LANES - R), jnp.float32)


def to_columns(way: str, reps: int, x_ref, o_ref):
    for c in range(CHUNKS_A_STEP):
        at = slice(c * C, (c + 1) * C)
        out = jnp.zeros((R, C), jnp.float32)
        for r in range(reps):
            if way == "whole":
                out = out + (x_ref[at, :] + float(r)).T[:R]
            else:
                out = out + (x_ref[at, :R] + float(r)).T
        o_ref[:, at] = out


def call(kernel, src, dst):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    step = CHUNKS_A_STEP * C
    spec = lambda shape: pl.BlockSpec(                      # noqa: E731
        (step, shape[1]) if shape[0] == N else (shape[0], step),
        (lambda i: (i, 0)) if shape[0] == N else (lambda i: (0, i)))
    return jax.jit(pl.pallas_call(
        kernel, grid=(N // step,), in_specs=[spec(src)],
        out_specs=spec(dst), out_shape=jax.ShapeDtypeStruct(dst, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))))


def timed(name, fn, x, **note):
    jax.block_until_ready(fn(x))
    ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ms.append((time.perf_counter() - t0) * 1e3)
    print(json.dumps({"piece": name, "ms": round(statistics.median(ms), 3),
                      "min_ms": round(min(ms), 3), **note}), flush=True)


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("bench_line_transpose: needs a TPU")
    print(json.dumps({"device": dev.device_kind, "jax": jax.__version__}))
    cols = jax.random.normal(jax.random.key(0), (R, N), jnp.float32)
    lines = jax.random.normal(jax.random.key(1), (N, LANES), jnp.float32)
    for reps in (1, 2, 4):
        for way in ("padded", "narrow"):
            timed("to_lines", call(functools.partial(to_lines, way, reps),
                                   (R, N), (N, LANES)), cols, way=way,
                  reps=reps, chunks=N // C)
        for way in ("whole", "narrow"):
            timed("to_columns", call(functools.partial(
                to_columns, way, reps), (N, LANES), (R, N)), lines, way=way,
                reps=reps, chunks=N // C)
    # what XLA takes for the same passes
    timed("xla_to_lines", jax.jit(lambda x: jnp.pad(
        x.T, ((0, 0), (0, LANES - R)))), cols)
    timed("xla_to_columns", jax.jit(lambda x: x.T[:R]), lines)


if __name__ == "__main__":
    main()
