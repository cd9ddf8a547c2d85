"""The dense gradient of a table gather, built block by block.

``jnp.take(table, ids)`` transposes into a scatter-add of the cotangent
rows into a zero table, and XLA's TPU scatter-add walks its updates one
element at a time: 10.4 ns per float32 element on a v5e, 100 ms for the
1,048,576 nine-wide rows of a KDD12 factorization-machine step (PERF.md
§5, PR 24). This module builds the same dense gradient another way:

A. **Sort once in batch space** (:func:`sorted_payload`): the N = B*K
   slots are sorted by table id carrying their payload (the columns of
   every table that shares the id space: an FM's F factor columns and its
   linear column, a field-aware FM's m * k), in aligned chunks of ``C``
   slots whose first and last ids are kept apart.
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
``[F, rows]`` for a ``[rows, F]`` table, a 1-D table's as the 1-D
``[rows]`` — which is
the layout XLA keeps a narrow ``[rows, F]`` float32 table in on a TPU, so
the optimizer reads them in place (a ``[1, rows]`` output cost two
re-layout passes of 3 ms each).

**Non-finite gradients.** A one-hot contraction multiplies every slot of
a chunk into every lane of a block (0 * inf is NaN): one non-finite
cotangent value turns its column non-finite in all ``T`` table rows of
every block that its chunk of ``C`` sorted slots reaches, where a
scatter-add poisons one row. Callers that must localise a non-finite
gradient stay on the XLA route.

:func:`dense_table_grad` is the entry point: it picks the route from what
it can observe (backend, dtype, shapes, the mesh's shard count) and counts
it in the telemetry counter ``grad_scatter_route``. Under a mesh that
replicates the tables and shards the batch, the kernel route lets the
batch's cotangent rows cross the chips (an all-gather of N * (width + 1)
words) and every chip build the whole gradient, where a table that is
large against the batch would make the all-reduce of the dense gradient
(rows * width words a chip) the larger part of the step.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from dmlc_tpu.ops.pallas_sparse import _on_tpu_backend
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import check

# table ids a block, sorted slots a chunk: sized on a v5e at the KDD12 shape
# (PERF.md §6, PR 25: 4,096 x 128 is the fastest of nine pairs at 1,048,576
# slots, 18.4 ms, and within 0.8 ms of the fastest at 262,144). The
# kernel's compares and MXU rows are (blocks + N / C) * C * T, its grid
# steps rows / T.
BLOCK_IDS = 4096
CHUNK_SLOTS = 128
# bfloat16 packs 16 rows a tile: each of the three splits is padded to it
_SPLIT_ROWS = 16
# payloads up to this width are permuted in place, as lane-major columns
# (the FM's 9: 6.4 ms a step); wider ones as row-major rows (sorted_payload)
_PERMUTE_BY_COLUMNS = 16

# the cost model behind the route, nanoseconds on a v5e, from the pieces
# alone (benchmarks/bench_grad_scatter.py; PERF.md §6, PR 25 and PR 26) at
# two shapes: 9 columns in two tables of 54,686,453 rows (steps A + B 26.55
# ms at 1,048,576 slots, 18.96 at 262,144; XLA's two scatter-adds 106.63
# and 28.47) and 44 columns in one table of 13,671,614 rows (30.33 and
# 12.00; XLA 174.24 and 45.19). The kernel route pays a table row once
# (its block is written and its one-hot rows streamed through the MXU) and
# a slot once (sort, permute, its chunk's share of a block), both growing
# with the payload's width; XLA's scatter-add pays every slot once a table
# and once an element, and a zero fill.
_KERNEL_NS_PER_TABLE_ROW = (0.267, 0.0037)     # + per column
_KERNEL_NS_PER_SLOT = (6.2, 0.389)             # + per column
_XLA_NS_PER_SLOT_AND_TABLE = 37.7
_XLA_NS_PER_ELEMENT = 2.9
_XLA_FILL_NS_PER_ELEMENT = 0.0055
# XLA's all-reduce of a dense float32 gradient over the four chips of a v5e
# 2x2, a table element: 37.2 ms alone for 9 x 54,686,453 elements and 34.98
# in the step (benchmarks/bench_grad_scatter.py --mesh, `all_reduce_alone`;
# PERF.md §6, PR 27; a table of 4,194,304 rows reads 0.106). The two
# all-gathers that take its place (1.9 ms alone at 1,048,576 slots, 0.9 of
# them exposed in the step) are left to the margin.
_ALLREDUCE_NS_PER_ELEMENT = 0.076
# the kernel has to be predicted this much faster before it is taken, and
# gathered rows this much faster than a reduced table
_ROUTE_MARGIN = 1.25


def grad_scatter_route(num_rows: int, num_slots: int, width: int,
                       dtype, tables: int = 1, shards: int = 1,
                       ) -> Tuple[str, str]:
    """``(route, collective)`` for ``tables`` tables of ``num_rows`` rows
    and ``width`` columns in all (an FM's linear column and 8 factors: two
    tables, 9) receiving ``num_slots`` gradient rows, ``num_slots /
    shards`` of them on each of ``shards`` chips that hold the tables whole.

    ``route`` is ``"kernel"`` on a TPU backend, for float32, for a table
    of at least as many rows as a chip has slots (where the cost model was
    measured), where that model predicts the kernel faster than XLA's
    scatter-add by ``_ROUTE_MARGIN``; ``"xla"`` everywhere else (small
    tables, the CPU, other dtypes).

    ``collective`` says what crosses the chips: ``"none"`` on one shard;
    ``"table"`` where every shard builds the dense gradient of its own
    slots and the tables are all-reduced (always on the XLA route);
    ``"rows"`` where the slots are all-gathered and every chip runs the
    kernel on all ``num_slots`` of them. Rows cost each chip the kernel's
    per-slot time for the other shards' slots, the table costs the
    all-reduce: rows are taken where the model predicts them faster by
    ``_ROUTE_MARGIN``: from 16 table rows a slot at 9 columns on four
    chips (measured: rows 14.7 ms against the table's 9.2 at 4 rows a slot,
    26.4 against 53.2 at 52)."""
    local_slots = num_slots // shards
    reduced = "none" if shards == 1 else "table"
    if not _on_tpu_backend() or jnp.dtype(dtype) != jnp.float32:
        return "xla", reduced
    if local_slots < CHUNK_SLOTS or num_rows < max(local_slots, BLOCK_IDS):
        return "xla", reduced
    per_row, per_slot = (c + w * width for c, w in (
        _KERNEL_NS_PER_TABLE_ROW, _KERNEL_NS_PER_SLOT))
    kernel_ns = per_row * num_rows + per_slot * local_slots
    xla_ns = (local_slots * (_XLA_NS_PER_SLOT_AND_TABLE * tables
                             + _XLA_NS_PER_ELEMENT * width)
              + _XLA_FILL_NS_PER_ELEMENT * width * num_rows)
    route = "kernel" if kernel_ns * _ROUTE_MARGIN < xla_ns else "xla"
    if shards > 1 and num_rows >= num_slots:
        rows_ns = per_row * num_rows + per_slot * num_slots
        table_ns = ((kernel_ns if route == "kernel" else xla_ns)
                    + _ALLREDUCE_NS_PER_ELEMENT * width * num_rows)
        if rows_ns * _ROUTE_MARGIN < table_ns:
            return "kernel", "rows"
    return route, reduced


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def sort_slots(ids: jax.Array, num_rows: int, block_ids: int = BLOCK_IDS,
               chunk_slots: int = CHUNK_SLOTS,
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The sort of step A, which the forward kernel of
    ``ops/table_gather.py`` shares: ``ids`` [N] int32 -> ``(bounds [2,
    chunks + 1] int32, sorted ids [1, Np] int32, permutation [Np] int32)``
    with Np = N rounded up to whole chunks of ``chunk_slots``.
    ``bounds[0, j]`` / ``bounds[1, j]`` are the first / last id of chunk
    ``j`` (one sentinel chunk appended), which is all a kernel needs to
    walk blocks and chunks in step; sorted slot ``s`` is slot
    ``permutation[s]`` of the batch (the padding's positions are N and
    up). Negative ids count from the end as in ``jnp.take``; ids outside
    the table and the padding take the sentinel ``blocks * block_ids``,
    sort last and reach no block.

    The ids are sorted with their positions (two operands, 0.9 ms at
    1,048,576 slots on a v5e) and whatever travels with them is permuted
    afterwards by one gather. One sort of id + 9 operands runs in 7.3 ms
    and compiles for 99 s; one two-operand sort batched over the columns
    takes 39 ms (PERF.md §6, PR 25).
    """
    sentinel = _round_up(num_rows, block_ids)
    ids = ids.astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + num_rows, ids)
    ids = jnp.where((ids < 0) | (ids >= num_rows), sentinel, ids)
    pad = _round_up(ids.shape[0], chunk_slots) - ids.shape[0]
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=sentinel)
    ids_s, perm = jax.lax.sort(
        (ids, jax.lax.iota(jnp.int32, ids.shape[0])), num_keys=1,
        is_stable=False)
    per_chunk = ids_s.reshape(-1, chunk_slots)
    bounds = jnp.pad(jnp.stack([per_chunk[:, 0], per_chunk[:, -1]]),
                     ((0, 0), (0, 1)), constant_values=sentinel)
    return bounds, ids_s[None, :], perm


def permute_columns(cols: jax.Array, index: jax.Array) -> jax.Array:
    """``cols[:, index]`` for ``cols`` [width, M] float32 and a
    permutation's ``index`` [n] (in bounds, no repeats): one XLA gather,
    7.5 ms at 1,048,576 slots of 9 columns on a v5e. A payload wider than
    ``_PERMUTE_BY_COLUMNS`` is permuted as rows of whole 128-lane lines:
    XLA's gather moves a slot's 44 columns in 12 ns as one row-major row
    and in 57 ns as 44 strided words of the lane-major columns (13.8
    against 59.4 ms at 1,048,576 slots with both transposes; PERF.md §6,
    PR 26)."""
    width = cols.shape[0]
    if width <= _PERMUTE_BY_COLUMNS:
        return cols.at[:, index].get(mode="promise_in_bounds",
                                     unique_indices=True)
    # (the barriers keep XLA from moving the padding past the gather,
    # which would leave it 44-wide rows again)
    rows = jax.lax.optimization_barrier(
        jnp.pad(cols.T, ((0, 0), (0, _round_up(width, 128) - width))))
    rows = jax.lax.optimization_barrier(
        rows.at[index].get(mode="promise_in_bounds", unique_indices=True))
    return rows.T[:width]


def permuted_payload(cols: jax.Array, perm: jax.Array) -> jax.Array:
    """The rest of step A. ``cols`` [width, N] (the cotangent columns of
    every table, one row a column) in the order ``perm`` [Np] of
    :func:`sort_slots`, split three ways: ``[3 * R, Np]`` bfloat16 with R =
    width rounded up to 16, row ``s * R + c`` holding split ``s`` (hi,
    mid, lo) of column ``c``; the padding's slots are zeros."""
    width, n = cols.shape
    cols = jnp.pad(cols.astype(jnp.float32),
                   ((0, 0), (0, perm.shape[0] - n)))
    cols = permute_columns(cols, perm)                        # [width, Np]
    rows = _round_up(width, _SPLIT_ROWS)
    cols = jnp.pad(cols, ((0, rows - width), (0, 0)))
    return jnp.concatenate(_bfloat16_parts(cols)).astype(jnp.bfloat16)


def sorted_payload(ids: jax.Array, cols: jax.Array,
                   num_rows: int, block_ids: int = BLOCK_IDS,
                   chunk_slots: int = CHUNK_SLOTS,
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Step A: :func:`sort_slots` of ``ids`` [N] and
    :func:`permuted_payload` of ``cols`` [width, N] in that order:
    ``(bounds, sorted ids [1, Np], payload [3 * R, Np] bfloat16)``. The
    payload does not travel through the sort: 1.9 + 7.5 ms at 1,048,576
    slots of 9 columns on a v5e."""
    bounds, ids_s, perm = sort_slots(ids, num_rows, block_ids, chunk_slots)
    return bounds, ids_s, permuted_payload(cols, perm)


def _bfloat16_part(x: jax.Array) -> jax.Array:
    """``x`` with the low 16 bits of its float32 pattern cleared: the
    bfloat16 value next towards zero, still as float32. By bits and not by
    a round trip through ``astype``, which a compiler allowed excess
    precision may drop."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _bfloat16_parts(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(hi, mid, lo)`` with ``x = hi + mid + lo`` exactly, each a
    bfloat16 value held as float32: three bfloat16 significands hold
    float32's."""
    hi = _bfloat16_part(x)
    mid = _bfloat16_part(x - hi)
    return hi, mid, x - hi - mid


_CUR, _FETCHED, _READY = 0, 1, 2


def _scatter_kernel(bounds_ref, ids_hbm, pay_hbm, *refs,
                    block_ids: int, chunk_slots: int,
                    trailing: Tuple[Tuple[int, ...], ...]):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    out_refs = refs[:len(trailing)]
    ids_buf, pay_buf, sem, acc_ref, state = refs[len(trailing):]
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

    for ref, tail, at in zip(out_refs, trailing, _column_starts(trailing)):
        if tail:
            ref[...] = acc_ref[at:at + tail[0]]
        else:
            ref[...] = acc_ref[at]


def _widths(trailing) -> Tuple[int, ...]:
    """Columns a table: 1 for a ``[rows]`` table, F for ``[rows, F]``."""
    return tuple(tail[0] if tail else 1 for tail in trailing)


def _column_starts(trailing) -> Tuple[int, ...]:
    """The payload row at which each table's columns start. The tables are
    laid widest first (ties in their own order), so that a wide table's
    rows start on a sublane tile: an FM's ``(w, v)`` puts ``v`` in rows
    0..F-1 and ``w`` in row F."""
    widths = _widths(trailing)
    order = sorted(range(len(widths)), key=lambda i: -widths[i])
    starts, at = [0] * len(widths), 0
    for i in order:
        starts[i], at = at, at + widths[i]
    return tuple(starts)


@functools.partial(jax.jit, static_argnames=(
    "num_rows", "trailing", "block_ids", "chunk_slots", "interpret"))
def grad_scatter_pallas(bounds: jax.Array, ids_sorted: jax.Array,
                        payload: jax.Array, *, num_rows: int,
                        trailing: Tuple[Tuple[int, ...], ...],
                        block_ids: int = BLOCK_IDS,
                        chunk_slots: int = CHUNK_SLOTS,
                        interpret: bool = False,
                        ) -> Tuple[jax.Array, ...]:
    """Step B: one dense gradient a table from :func:`sorted_payload`'s
    outputs (same ``block_ids`` / ``chunk_slots``). ``trailing`` holds each
    table's shape after its id axis, ``()`` or ``(F,)``; the gradient of a
    ``[num_rows]`` table comes as ``[num_rows]``, that of a ``[num_rows,
    F]`` table lane-major as ``[F, num_rows]``. The payload's columns are
    the tables' in the order of :func:`_column_starts`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blocks = -(-num_rows // block_ids)
    split_rows = payload.shape[0]
    rows = split_rows // 3
    assert rows * 3 == split_rows and rows >= sum(_widths(trailing))
    assert ids_sorted.shape[1] % chunk_slots == 0
    assert bounds.shape == (2, ids_sorted.shape[1] // chunk_slots + 1)
    kernel = functools.partial(
        _scatter_kernel, block_ids=block_ids, chunk_slots=chunk_slots,
        trailing=trailing)
    return tuple(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((tail[0], block_ids), lambda t, bounds: (0, t))
                if tail else pl.BlockSpec((block_ids,),
                                          lambda t, bounds: (t,))
                for tail in trailing],
            scratch_shapes=[
                pltpu.VMEM((2, 1, chunk_slots), jnp.int32),
                pltpu.VMEM((2, split_rows, chunk_slots), jnp.bfloat16),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, block_ids), jnp.float32),
                pltpu.SMEM((3,), jnp.int32),
            ]),
        out_shape=[jax.ShapeDtypeStruct(tail + (num_rows,), jnp.float32)
                   for tail in trailing],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="grad_scatter",
        interpret=interpret,
    )(bounds, ids_sorted, payload))


def _trailing(cotangents, indices) -> Tuple[Tuple[int, ...], ...]:
    return tuple(tuple(g.shape[indices.ndim:]) for g in cotangents)


def table_grad_kernel(ids: jax.Array, cotangents: Tuple[jax.Array, ...],
                      num_rows: int, gather_axis=None, sorted_slots=None,
                      ) -> Tuple[jax.Array, ...]:
    """Steps A and B for flat ``ids`` [N] and cotangents ``[N]`` or
    ``[N, F]``: a ``[num_rows]`` or ``[num_rows, F]`` gradient a table.
    Under ``shard_map``, ``gather_axis`` names the mesh axis whose shards'
    slots are all-gathered first (the ids and the payload's columns, two
    collectives): every shard then builds the gradient of all of them.
    ``sorted_slots`` is :func:`sort_slots` of these very ``ids`` where the
    forward has made it already (ops/table_gather.py): nothing is sorted
    again."""
    trailing = _trailing(cotangents, ids)
    starts = _column_starts(trailing)
    by_start = sorted(range(len(cotangents)), key=lambda i: starts[i])
    cols = jnp.concatenate([
        cotangents[i].T if trailing[i] else cotangents[i][None, :]
        for i in by_start])
    if gather_axis is not None:
        ids = jax.lax.all_gather(ids, gather_axis, tiled=True)
        cols = jax.lax.all_gather(cols, gather_axis, axis=1, tiled=True)
    check(sorted_slots is None or gather_axis is None,
          "table_grad_kernel: sorted_slots are one shard's, not the "
          "gathered slots'")
    if sorted_slots is None:
        sorted_slots = sort_slots(ids, num_rows)
    bounds, ids_s, perm = sorted_slots
    out = grad_scatter_pallas(bounds, ids_s, permuted_payload(cols, perm),
                              num_rows=num_rows, trailing=trailing)
    return tuple(d.T if tail else d for d, tail in zip(out, trailing))


def table_grad_xla(ids: jax.Array, cotangents: Tuple[jax.Array, ...],
                   num_rows: int) -> Tuple[jax.Array, ...]:
    """What autodiff makes of the gathers: XLA's scatter-adds (``ids`` of
    any shape [...], cotangents [...] or [..., F])."""
    return tuple(
        jnp.zeros((num_rows,) + g.shape[ids.ndim:], g.dtype).at[ids].add(g)
        for g in cotangents)


def dense_table_grad(indices: jax.Array, cotangents: Tuple[jax.Array, ...],
                     num_rows: int, mesh=None, data_axis: str = "data",
                     sorted_slots=None) -> Tuple[jax.Array, ...]:
    """One dense gradient a table (``[num_rows]`` or ``[num_rows, F]``):
    the transpose of gathering rows ``indices`` [...] of tables that share
    an id space, given the cotangents ``[...]`` / ``[..., F]`` of the
    gathered rows. Called while the backward is traced: picks the route
    (:func:`grad_scatter_route`) and counts it in
    ``grad_scatter_route{route=, width=, collective=}``, ``width`` the
    columns of all the tables together. ``sorted_slots`` is
    :func:`sort_slots` of the flat ``indices`` where the forward kept it
    (one chip only): the kernel route then sorts nothing.

    With a ``mesh`` the tables are replicated and the leading (batch)
    dimension is sharded over ``data_axis``. The kernel route runs under
    ``shard_map`` and lets one of two things cross the chips
    (``collective``): the batch's *rows* -- every shard all-gathers the
    flat ids and cotangent columns and builds the whole gradient from all
    of them, as one chip would, so every replica computes the same float32
    sums in the same order from the same inputs; or the *table* -- every
    shard builds the dense gradient of its own slots and the shards'
    results are summed, XLA's all-reduce of ``num_rows * width`` words, as
    on the XLA route."""
    trailing = _trailing(cotangents, indices)
    check(all(len(tail) <= 1 for tail in trailing),
          "dense_table_grad: a table is [rows] or [rows, F]")
    width = sum(_widths(trailing))
    shards = 1 if mesh is None else mesh.shape[data_axis]
    route, collective = grad_scatter_route(
        num_rows, indices.size, width, cotangents[0].dtype, len(cotangents),
        shards)
    _telemetry.REGISTRY.counter(
        _telemetry.GRAD_SCATTER_ROUTE_METRIC, route=route, width=str(width),
        collective=collective).inc(1)
    if route == "xla":
        return table_grad_xla(indices, cotangents, num_rows)

    def local(idx, *gs, **how):
        return table_grad_kernel(
            idx.reshape(-1),
            tuple(g.reshape((-1,) + tail) for g, tail in zip(gs, trailing)),
            num_rows, **how)

    if mesh is None:
        return local(indices, *cotangents, sorted_slots=sorted_slots)
    from jax.sharding import PartitionSpec as P

    lead = P(data_axis)
    if collective == "rows":
        return jax.shard_map(
            functools.partial(local, gather_axis=data_axis), mesh=mesh,
            in_specs=(lead,) * (1 + len(cotangents)),
            out_specs=(P(),) * len(cotangents),
            check_vma=False)(indices, *cotangents)
    # each shard's dense gradient, stacked along the mesh axis; the sum over
    # that axis is XLA's own all-reduce
    stacked = jax.shard_map(
        lambda *args: tuple(x[None] for x in local(*args)), mesh=mesh,
        in_specs=(lead,) * (1 + len(cotangents)),
        out_specs=(lead,) * len(cotangents),
        check_vma=False)(indices, *cotangents)
    return tuple(d.sum(axis=0) for d in stacked)
