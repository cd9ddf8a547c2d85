"""The dense gradient of a table gather, built block by block.

``jnp.take(table, ids)`` transposes into a scatter-add of the cotangent
rows into a zero table, and XLA's TPU scatter-add walks its updates one
element at a time: 10.4 ns per float32 element on a v5e, 100 ms for the
1,048,576 nine-wide rows of a KDD12 factorization-machine step (PERF.md
§5, PR 24). This module builds the same dense gradient another way:

A. **Sort once in batch space** (:func:`sorted_payload`): the N = B*K
   slots are sorted by table id carrying their payload (the F factor
   columns and the linear column), in aligned chunks of ``C`` slots whose
   first and last ids are kept apart.
B. **Write every block of the gradient once**
   (:func:`grad_scatter_pallas`): a Pallas kernel walks the blocks of
   ``T`` table ids and the chunks in step. For block ``t`` it loops over
   the chunks that hold ids below ``(t + 1) * T``, forms
   ``onehot[T, C] = (t * T + iota == ids)`` and accumulates
   ``payload[R, C] @ onehot.T -> [R, T]`` on the MXU. Slots of a shared
   chunk that belong to a neighbouring block match no lane; duplicates are
   summed by the contraction; a block no slot hits is written as zeros, so
   there is no separate zero fill. The chunks arrive by hand-written
   double-buffered DMA, the next one (the next block's first included) in
   flight while this one is contracted.

float32 accuracy comes from splitting the payload three ways into
bfloat16 (``x = hi + mid + lo`` exactly) before the kernel: the one-hot
side is exact in bfloat16, the MXU accumulates in float32, and the three
partial results are added. The gradient is written lane-major —
``[F, rows]``, and the linear table's as the 1-D ``[rows]`` — which is
the layout XLA keeps a narrow ``[rows, F]`` float32 table in on a TPU, so
the optimizer reads both in place (a ``[1, rows]`` output cost two
re-layout passes of 3 ms each).

**Non-finite gradients.** A one-hot contraction multiplies every slot of
a chunk into every lane of a block (0 * inf is NaN): one non-finite
cotangent value turns its column non-finite in all ``T`` table rows of
every block that its chunk of ``C`` sorted slots reaches, where a
scatter-add poisons one row. Callers that must localise a non-finite
gradient stay on the XLA route.

:func:`dense_table_grad` is the entry point: it picks the route from what
it can observe (backend, dtype, shapes) and counts it in the telemetry
counter ``grad_scatter_route``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from dmlc_tpu.ops.pallas_sparse import _on_tpu_backend
from dmlc_tpu.utils import telemetry as _telemetry

# table ids a block, sorted slots a chunk: sized on a v5e at the KDD12 shape
# (PERF.md §6, PR 25: 4,096 x 128 is the fastest of nine pairs at 1,048,576
# slots, 18.4 ms, and within 0.8 ms of the fastest at 262,144). The
# kernel's compares and MXU rows are (blocks + N / C) * C * T, its grid
# steps rows / T.
BLOCK_IDS = 4096
CHUNK_SLOTS = 128
# bfloat16 packs 16 rows a tile: each of the three splits is padded to it
_SPLIT_ROWS = 16

# the cost model behind the route, nanoseconds on a v5e (PERF.md §6, PR 25).
# Steps A + B alone took 26.55 ms at 1,048,576 slots and 18.96 ms at
# 262,144 into 54,686,453 rows: 0.30 ns a table row (every block is
# written, and every one-hot row streamed through the MXU, once) and
# 9.7 ns a slot (the sort, the permute, the chunk's share of a block).
# XLA's scatter-add inside the step (ledger, PR 24): 10.4 ns a float32
# element of the updates and 2.7 ms to zero-fill 492 M elements.
_KERNEL_NS_PER_TABLE_ROW = 0.30
_KERNEL_NS_PER_SLOT = 9.7
_XLA_NS_PER_ELEMENT = 10.4
_XLA_FILL_NS_PER_ELEMENT = 0.0055
# the kernel has to be predicted this much faster before it is taken: for
# F = 8 that is a table of up to 250 rows a slot
_ROUTE_MARGIN = 1.25


def grad_scatter_route(num_rows: int, num_slots: int, num_factors: int,
                       dtype) -> str:
    """``"kernel"`` or ``"xla"`` for a table of ``num_rows`` rows of
    ``num_factors`` factors (and the linear column) receiving
    ``num_slots`` gradient rows: the kernel on a TPU backend, for float32,
    for a table of at least as many rows as it receives slots (where the
    cost model was measured), where that model predicts the kernel faster
    than XLA's scatter-add by ``_ROUTE_MARGIN``; XLA everywhere else (small
    tables, the CPU, other dtypes)."""
    if not _on_tpu_backend() or jnp.dtype(dtype) != jnp.float32:
        return "xla"
    if num_slots < CHUNK_SLOTS or num_rows < max(num_slots, BLOCK_IDS):
        return "xla"
    kernel_ns = (_KERNEL_NS_PER_TABLE_ROW * num_rows
                 + _KERNEL_NS_PER_SLOT * num_slots)
    xla_ns = (num_factors + 1) * (_XLA_NS_PER_ELEMENT * num_slots
                                  + _XLA_FILL_NS_PER_ELEMENT * num_rows)
    return "kernel" if kernel_ns * _ROUTE_MARGIN < xla_ns else "xla"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def sorted_payload(ids: jax.Array, g_w: jax.Array, g_v: jax.Array,
                   num_rows: int, block_ids: int = BLOCK_IDS,
                   chunk_slots: int = CHUNK_SLOTS,
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Step A. ``ids`` [N] int32, ``g_w`` [N], ``g_v`` [N, F] ->
    ``(bounds [2, chunks + 1] int32, sorted ids [1, Np] int32, payload
    [3 * R, Np] bfloat16)`` with Np = N rounded up to whole chunks and R =
    F + 1 rounded up to 16. ``bounds[0, j]`` / ``bounds[1, j]`` are the
    first / last id of chunk ``j`` (one sentinel chunk appended), which is
    all the kernel needs to walk blocks and chunks in step. Payload row
    ``s * R + c`` holds split ``s`` (hi, mid, lo) of column ``c`` (the F
    factor columns, then the linear one). Negative ids count from the end
    as in ``jnp.take``; ids outside the table take the sentinel
    ``blocks * T``, sort last and reach no block.

    The payload does not travel through the sort: the ids are sorted with
    their positions (two operands) and the F + 1 columns are then permuted
    by one gather, 1.9 + 7.5 ms at 1,048,576 slots on a v5e. One sort of
    id + F + 1 operands runs in 7.3 ms and compiles for 99 s; one
    two-operand sort batched over the columns takes 39 ms (PERF.md §6,
    PR 25).
    """
    n, f = g_v.shape
    sentinel = _round_up(num_rows, block_ids)
    ids = ids.astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + num_rows, ids)
    ids = jnp.where((ids < 0) | (ids >= num_rows), sentinel, ids)
    cols = jnp.concatenate([g_v.T, g_w[None, :]]).astype(jnp.float32)
    pad = _round_up(n, chunk_slots) - n
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=sentinel)
        cols = jnp.pad(cols, ((0, 0), (0, pad)))
    ids_s, perm = jax.lax.sort(
        (ids, jax.lax.iota(jnp.int32, ids.shape[0])), num_keys=1,
        is_stable=False)
    cols = cols.at[:, perm].get(mode="promise_in_bounds",
                                unique_indices=True)          # [F + 1, Np]
    ids_s = ids_s[None, :]                                    # [1, Np]
    per_chunk = ids_s.reshape(-1, chunk_slots)
    bounds = jnp.pad(jnp.stack([per_chunk[:, 0], per_chunk[:, -1]]),
                     ((0, 0), (0, 1)), constant_values=sentinel)
    # x = hi + mid + lo exactly: three bfloat16 significands hold float32's
    rows = _round_up(f + 1, _SPLIT_ROWS)
    cols = jnp.pad(cols, ((0, rows - (f + 1)), (0, 0)))
    hi = _bfloat16_part(cols)
    mid = _bfloat16_part(cols - hi)
    lo = cols - hi - mid
    return bounds, ids_s, jnp.concatenate([hi, mid, lo]).astype(jnp.bfloat16)


def _bfloat16_part(x: jax.Array) -> jax.Array:
    """``x`` with the low 16 bits of its float32 pattern cleared: the
    bfloat16 value next towards zero, still as float32. By bits and not by
    a round trip through ``astype``, which a compiler allowed excess
    precision may drop."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


_CUR, _FETCHED, _READY = 0, 1, 2


def _scatter_kernel(bounds_ref, ids_hbm, pay_hbm, dv_ref, dw_ref,
                    ids_buf, pay_buf, sem, acc_ref, state, *,
                    block_ids: int, chunk_slots: int, num_factors: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = acc_ref.shape[0]
    chunks = bounds_ref.shape[1] - 1
    t = pl.program_id(0)
    base = t * block_ids
    upper = base + block_ids

    def copies(c):
        slot = c % 2
        at = pl.ds(pl.multiple_of(c * chunk_slots, chunk_slots), chunk_slots)
        return (pltpu.make_async_copy(ids_hbm.at[:, at], ids_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(pay_hbm.at[:, at], pay_buf.at[slot],
                                      sem.at[1, slot]))

    # chunk c lives in slot c % 2; state holds the chunk the walk stands on
    # and the highest chunk started / waited for. A chunk is started while
    # its predecessor is contracted, whichever block that falls in, and
    # waited for when it is first needed.
    @pl.when(t == 0)
    def _first():
        for cp in copies(0):
            cp.start()
        state[_CUR] = 0
        state[_FETCHED] = 0
        state[_READY] = -1

    acc_ref[...] = jnp.zeros_like(acc_ref)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_ids, chunk_slots), 0)

    def more(carry):
        j, go = carry
        return go & (bounds_ref[0, j] < upper)

    def contract(carry):
        j, _ = carry
        nxt = j + 1

        @pl.when((nxt < chunks) & (nxt > state[_FETCHED]))
        def _prefetch():
            for cp in copies(nxt):
                cp.start()
            state[_FETCHED] = nxt

        @pl.when(j > state[_READY])
        def _arrived():
            for cp in copies(j):
                cp.wait()
            state[_READY] = j

        slot = j % 2
        local = ids_buf[slot] - base                          # [1, C]
        onehot = (iota == local).astype(jnp.bfloat16)         # [T, C]
        d = jax.lax.dot_general(
            pay_buf[slot], onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [3R, T]
        acc_ref[...] += d[:rows] + d[rows:2 * rows] + d[2 * rows:]
        # slots for a later block left in this chunk: stay on it
        done = bounds_ref[1, j] < upper
        return jnp.where(done, nxt, j), done

    j, _ = jax.lax.while_loop(more, contract, (state[_CUR], True))
    state[_CUR] = j

    @pl.when((t == pl.num_programs(0) - 1)
             & (state[_FETCHED] > state[_READY]))
    def _drain():
        for cp in copies(state[_FETCHED]):
            cp.wait()

    dv_ref[...] = acc_ref[:num_factors]
    dw_ref[...] = acc_ref[num_factors]


@functools.partial(jax.jit, static_argnames=(
    "num_rows", "num_factors", "block_ids", "chunk_slots", "interpret"))
def grad_scatter_pallas(bounds: jax.Array, ids_sorted: jax.Array,
                        payload: jax.Array, *, num_rows: int,
                        num_factors: int, block_ids: int = BLOCK_IDS,
                        chunk_slots: int = CHUNK_SLOTS,
                        interpret: bool = False,
                        ) -> Tuple[jax.Array, jax.Array]:
    """Step B: ``(dw [num_rows], dv_t [F, num_rows])`` from
    :func:`sorted_payload`'s outputs (same ``block_ids`` /
    ``chunk_slots``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blocks = -(-num_rows // block_ids)
    split_rows = payload.shape[0]
    rows = split_rows // 3
    assert rows * 3 == split_rows and rows >= num_factors + 1
    assert ids_sorted.shape[1] % chunk_slots == 0
    assert bounds.shape == (2, ids_sorted.shape[1] // chunk_slots + 1)
    kernel = functools.partial(
        _scatter_kernel, block_ids=block_ids, chunk_slots=chunk_slots,
        num_factors=num_factors)
    dv_t, dw = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((num_factors, block_ids),
                             lambda t, bounds: (0, t)),
                pl.BlockSpec((block_ids,), lambda t, bounds: (t,)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, 1, chunk_slots), jnp.int32),
                pltpu.VMEM((2, split_rows, chunk_slots), jnp.bfloat16),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, block_ids), jnp.float32),
                pltpu.SMEM((3,), jnp.int32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((num_factors, num_rows), jnp.float32),
            jax.ShapeDtypeStruct((num_rows,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="grad_scatter",
        interpret=interpret,
    )(bounds, ids_sorted, payload)
    return dw, dv_t


def table_grad_kernel(ids: jax.Array, g_w: jax.Array, g_v: jax.Array,
                      num_rows: int) -> Tuple[jax.Array, jax.Array]:
    """Steps A and B: ``(dw [num_rows], dv [num_rows, F])``."""
    bounds, ids_s, payload = sorted_payload(ids, g_w, g_v, num_rows)
    dw, dv_t = grad_scatter_pallas(
        bounds, ids_s, payload, num_rows=num_rows,
        num_factors=g_v.shape[1])
    return dw, dv_t.T


def table_grad_xla(ids: jax.Array, g_w: jax.Array, g_v: jax.Array,
                   num_rows: int) -> Tuple[jax.Array, jax.Array]:
    """What autodiff makes of the two gathers: XLA's scatter-adds (``ids``
    of any shape [...], ``g_v`` [..., F])."""
    dw = jnp.zeros((num_rows,), g_w.dtype).at[ids].add(g_w)
    dv = jnp.zeros((num_rows, g_v.shape[-1]), g_v.dtype).at[ids].add(g_v)
    return dw, dv


def dense_table_grad(indices: jax.Array, g_w: jax.Array, g_v: jax.Array,
                     num_rows: int, mesh=None, data_axis: str = "data",
                     ) -> Tuple[jax.Array, jax.Array]:
    """``(dw [num_rows], dv [num_rows, F])``: the transpose of gathering
    rows ``indices`` [...] of a linear table and a factor table, given the
    cotangents ``g_w`` [...] and ``g_v`` [..., F]. Called while the
    backward is traced: picks the route (:func:`grad_scatter_route`) and
    counts it in ``grad_scatter_route{route=}``.

    With a ``mesh`` the tables are replicated and the leading (batch)
    dimension is sharded over ``data_axis``: the kernel route sorts and
    builds each shard's dense gradient under ``shard_map`` and sums the
    shards' results, the bytes XLA all-reduces on its own route."""
    f = g_v.shape[-1]
    shards = 1 if mesh is None else mesh.shape[data_axis]
    n_local = indices.size // shards
    route = grad_scatter_route(num_rows, n_local, f, g_v.dtype)
    _telemetry.REGISTRY.counter(_telemetry.GRAD_SCATTER_ROUTE_METRIC,
                                route=route).inc(1)
    if route == "xla":
        return table_grad_xla(indices, g_w, g_v, num_rows)

    def local(idx, gw, gv):
        return table_grad_kernel(idx.reshape(-1), gw.reshape(-1),
                                 gv.reshape(-1, f), num_rows)

    if mesh is None:
        return local(indices, g_w, g_v)
    from jax.sharding import PartitionSpec as P

    # each shard's dense gradient, stacked along the mesh axis; the sum over
    # that axis is then XLA's own all-reduce, under the name and with the
    # bytes of the XLA route's
    lead = P(data_axis)
    dw, dv = jax.shard_map(
        lambda *args: tuple(x[None] for x in local(*args)), mesh=mesh,
        in_specs=(lead, lead, lead), out_specs=(lead, lead),
        check_vma=False)(indices, g_w, g_v)
    return dw.sum(axis=0), dv.sum(axis=0)
