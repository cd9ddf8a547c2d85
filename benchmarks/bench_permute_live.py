"""Time XLA's permute of a kernel's slot side group by group
(ops/sorted_walk.py: ``permute_live``), alone on the chip, at the cells'
shapes: 1,048,576 slots as ``[N, 128]`` float32 lines (kdd12_ffm) and as
``[9, N]`` lane-major columns (kdd12_fm), 16 groups of which 11 or all 16
are live (the live ones first), and kdd12_ffm_csv's 720,896 lines in 11
live groups. Beside the helper: the one gather it replaces (``whole``), the
ways of landing the groups in one buffer that were tried and lost, and one
gather whose dead indices all name one slot or run in storage order (no
index is cheap). PERF.md §6, PR 49:

    chiprun -- python3 benchmarks/bench_permute_live.py

One JSON line per timing (median ms of ten warm calls); needs a TPU.
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

from dmlc_tpu.ops import sorted_walk as sw


def take(payload, index, axis):
    at = index if axis == 0 else (slice(None), index)
    return payload.at[at].get(mode="promise_in_bounds", unique_indices=True)


def group_shape(payload, m, axis):
    return (m, payload.shape[1]) if axis == 0 else (payload.shape[0], m)


def at_group(g, m, axis):
    return (g * m, 0) if axis == 0 else (0, g * m)


def whole(payload, index, live, axis):
    payload = jax.lax.optimization_barrier(payload)
    return jax.lax.optimization_barrier(take(payload, index, axis))


def concat(payload, index, live, axis):
    # a cond a group, its branches one group; XLA lands the parts in the
    # result by a second pass
    payload = jax.lax.optimization_barrier(payload)
    m = index.shape[0] // live.shape[0]
    parts = [jax.lax.cond(
        live[g], lambda i: take(payload, i, axis),
        lambda i: jnp.zeros(group_shape(payload, m, axis), payload.dtype),
        index[g * m:(g + 1) * m]) for g in range(live.shape[0])]
    return jax.lax.optimization_barrier(jnp.concatenate(parts, axis=axis))


def chain(payload, index, live, axis):
    # the result through every cond, a live group written into it in place,
    # over zeros
    payload = jax.lax.optimization_barrier(payload)
    m = index.shape[0] // live.shape[0]
    n = index.shape[0]
    out = jnp.zeros(group_shape(payload, n, axis), payload.dtype)
    for g in range(live.shape[0]):
        out = jax.lax.cond(
            live[g],
            lambda o, i: jax.lax.dynamic_update_slice(
                o, take(payload, i, axis), at_group(g, m, axis)),
            lambda o, i: o, out, index[g * m:(g + 1) * m])
    return jax.lax.optimization_barrier(out)


def chain_unwritten(payload, index, live, axis):
    # as chain, over a buffer that nobody has written: a dead group is
    # written as zeros (permute_live's way for lines; for columns it shows
    # what a gather inside a conditional pays for an operand left in HBM)
    payload = jax.lax.optimization_barrier(payload)
    m = index.shape[0] // live.shape[0]
    n = index.shape[0]
    out = jax.lax.empty(group_shape(payload, n, axis), payload.dtype)
    for g in range(live.shape[0]):
        out = jax.lax.cond(
            live[g],
            lambda o, i: jax.lax.dynamic_update_slice(
                o, take(payload, i, axis), at_group(g, m, axis)),
            lambda o, i: jax.lax.dynamic_update_slice(
                o, jnp.zeros(group_shape(payload, m, axis), payload.dtype),
                at_group(g, m, axis)),
            out, index[g * m:(g + 1) * m])
    return jax.lax.optimization_barrier(out)


def loop(payload, index, live, axis):
    payload = jax.lax.optimization_barrier(payload)
    m = index.shape[0] // live.shape[0]
    n = index.shape[0]

    def body(g, out):
        i = jax.lax.dynamic_slice(index, (g * m,), (m,))
        part = jax.lax.cond(
            live[g], lambda i: take(payload, i, axis),
            lambda i: jnp.zeros(group_shape(payload, m, axis), payload.dtype),
            i)
        return jax.lax.dynamic_update_slice(
            out, part, (g * m, 0) if axis == 0 else (0, g * m))

    out = jax.lax.fori_loop(
        0, live.shape[0], body,
        jnp.zeros(group_shape(payload, n, axis), payload.dtype))
    return jax.lax.optimization_barrier(out)


def one_row(payload, index, live, axis):
    # one gather, the dead groups' indices all naming slot 0
    m = index.shape[0] // live.shape[0]
    return whole(payload, jnp.where(jnp.repeat(live, m), index, 0), live,
                 axis)


def in_order(payload, index, live, axis):
    # one gather, the dead groups' indices in storage order
    m = index.shape[0] // live.shape[0]
    iota = jax.lax.iota(jnp.int32, index.shape[0])
    return whole(payload, jnp.where(jnp.repeat(live, m), index, iota), live,
                 axis)


def helper(payload, index, live, axis):
    run = index.shape[0] // live.shape[0]
    return sw.permute_live(payload, index, run * jnp.sum(live),
                           "lines" if axis == 0 else "columns")


WAYS = dict(whole=whole, permute_live=helper, chain=chain,
            chain_unwritten=chain_unwritten, concat=concat, loop=loop,
            one_row=one_row, in_order=in_order)


def timed(fn, *args, calls=10):
    fn(*args).block_until_ready()
    fn(*args).block_until_ready()
    out = []
    for _ in range(calls):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("bench_permute_live: needs a TPU")
    rng = np.random.default_rng(0)
    shapes = [("lines", 0, (1 << 20, 128), 16, (11, 16)),
              ("columns", 1, (9, 1 << 20), 16, (11, 16)),
              ("lines_csv", 0, (11 << 16, 128), 11, (11,))]
    for name, axis, shape, groups, lives in shapes:
        n = shape[axis]
        payload = jnp.asarray(rng.random(shape, np.float32))
        index = jnp.asarray(rng.permutation(n).astype(np.int32))
        for alive in lives:
            live = jnp.arange(groups) < alive
            for way, fn in WAYS.items():
                try:
                    ms = timed(jax.jit(fn, static_argnums=3), payload, index,
                               live, axis)
                    err = None
                except Exception as e:  # noqa: BLE001 - report, go on
                    ms, err = None, repr(e)[:200]
                print(json.dumps({
                    "shape": name, "dims": list(shape), "groups": groups,
                    "live": alive, "way": way, "ms": ms, "error": err,
                    "device_kind": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
