"""Sums over each row's run of slots, and their transpose: the two ops a
ragged (``bcoo``) batch needs that an ELL batch gets from its shape.

An ELL batch sums a row's slots along an axis, ``[B, K, F] -> [B, F]``, and
spreads a row's cotangent back by a broadcast. A ragged batch has its N
slots flat, row after row, each with its row id: the sum is
``jax.ops.segment_sum`` and the way back ``jnp.take(rows, row_ids)``. On a
TPU those are XLA's scatter-add and gather, which walk their indices one at
a time (10.4 and 15 to 17 ns an element on a v5e: PERF.md §5), the two
routes ``ops/grad_scatter.py`` and ``ops/table_gather.py`` exist to avoid.

Here the batch's rows are the "table": ``B`` rows, far fewer than slots,
the other side of those modules' cost models. The slots arrive sorted by
row (:class:`~dmlc_tpu.data.device.DeviceIter` emits them so, and so does
``BCOO.fromdense``), so nothing is sorted and nothing permuted: the same
two one-hot kernels of the sorted walk (``ops/sorted_walk.py``) run over
the slots as they lie (``sorted_walk.presorted_slots``), with blocks of
``ROW_BLOCK`` rows in the place of blocks of 4,096 table ids.

- :func:`slot_rows_sum`: ``[N] / [N, F]`` per-slot arrays -> ``[B] /
  [B, F]`` sums (:func:`~dmlc_tpu.ops.grad_scatter.grad_scatter_pallas`
  with no epilogue: the payload split three ways into bfloat16, so the
  float32 sums are exact products summed in float32);
- :func:`slot_rows_take`: ``[B] / [B, F]`` per-row arrays -> the row's
  value at each of its slots
  (:func:`~dmlc_tpu.ops.table_gather.table_gather_pallas`).

Each is the other's transpose and carries it as its VJP. A slot whose row
id lies outside ``[0, B)`` (the padding of a batch's slot count) adds to no
row and reads zeros. :func:`slot_rows_route` picks the route from what it
can observe and counts it in ``slot_rows_route{route=, op=, width=}``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops import table_gather as tg
from dmlc_tpu.utils import telemetry as _telemetry

# rows a block, slots a chunk, sized on a v5e at 2,097,152 slots of 9
# columns over 65,536 rows (benchmarks/bench_slot_rows.py; PERF.md §6, PR
# 37). The kernels' one-hot is [ROW_BLOCK, ROW_CHUNK] a chunk, and a run of
# slots spans few rows, so a block far smaller than the tables' 4,096 ids
# does the same work in fewer compares.
ROW_BLOCK = 256
ROW_CHUNK = 1024
# XLA's segment_sum and take below this many slots: the kernels' fixed
# cost (a grid step a block, a DMA a chunk) is not measured under it
_MIN_SLOTS = 8 * ROW_CHUNK
# the kernels' names in a device trace: the tables' own two keep theirs
SUM_KERNEL, TAKE_KERNEL = "slot_rows_sum", "slot_rows_take"


def slot_rows_route(num_rows: int, num_slots: int, dtype) -> str:
    """``"kernel"`` on a TPU backend, for float32, for at least one block
    of rows and ``_MIN_SLOTS`` slots; ``"xla"`` everywhere else (the CPU,
    other dtypes, small batches)."""
    if not gs._on_tpu_backend() or jnp.dtype(dtype) != jnp.float32:
        return "xla"
    if num_rows < ROW_BLOCK or num_slots < _MIN_SLOTS:
        return "xla"
    return "kernel"


def _trailing(arrays) -> Tuple[Tuple[int, ...], ...]:
    return tuple(tuple(a.shape[1:]) for a in arrays)


def _count(route: str, op: str, arrays) -> None:
    _telemetry.REGISTRY.counter(
        _telemetry.SLOT_ROWS_ROUTE_METRIC, route=route, op=op,
        width=str(sum(sw.widths(_trailing(arrays))))).inc(1)


def _as_cols(arrays) -> jax.Array:
    """``[width, n]`` float32: the arrays' columns one row each, in their
    order (a ``[n]`` array is one column). The kernels take them as one
    table of ``width`` columns: a 1-D operand's XLA tile is 1,024 lanes,
    which would hold ``ROW_BLOCK`` to its multiples."""
    return jnp.concatenate([x.T if x.ndim == 2 else x[None, :]
                            for x in arrays]).astype(jnp.float32)


def _of_cols(cols: jax.Array, like) -> Tuple[jax.Array, ...]:
    """:func:`_as_cols` undone: one ``[n]`` or ``[n, F]`` array for each
    of ``like``."""
    out, at = [], 0
    for tail in _trailing(like):
        out.append(cols[at:at + tail[0]].T if tail else cols[at])
        at += tail[0] if tail else 1
    return tuple(out)


def rows_sum_kernel(slots, row_ids, num_rows, block=None, chunk=None):
    """:func:`slot_rows_sum` on the kernel, whatever the route says; blocks
    of ``block`` rows and chunks of ``chunk`` slots (``ROW_BLOCK``,
    ``ROW_CHUNK``)."""
    block, chunk = block or ROW_BLOCK, chunk or ROW_CHUNK
    cols = _as_cols(slots)
    bounds, ids = sw.presorted_slots(row_ids, num_rows, block, chunk)
    out, = gs.grad_scatter_pallas(
        bounds, ids, sw.split_payload(cols, ids.shape[1]), num_rows=num_rows,
        trailing=((cols.shape[0],),), block_ids=block, chunk_slots=chunk,
        name=SUM_KERNEL)
    return _of_cols(out, slots)


def rows_take_kernel(rows, row_ids, block=None, chunk=None):
    """:func:`slot_rows_take` on the kernel, as :func:`rows_sum_kernel`."""
    block, chunk = block or ROW_BLOCK, chunk or ROW_CHUNK
    num_rows, n = rows[0].shape[0], row_ids.shape[0]
    table = _as_cols(rows)
    bounds, ids = sw.presorted_slots(row_ids, num_rows, block, chunk)
    cols = tg.table_gather_pallas(
        bounds, ids, table, num_rows=num_rows,
        trailing=((table.shape[0],),), block_ids=block, chunk_slots=chunk,
        name=TAKE_KERNEL)
    return _of_cols(cols[:, :n], rows)


def _sum(slots, row_ids, num_rows):
    route = slot_rows_route(num_rows, row_ids.shape[0], slots[0].dtype)
    _count(route, "sum", slots)
    if route == "kernel":
        return rows_sum_kernel(slots, row_ids, num_rows)
    return tuple(jax.ops.segment_sum(x, row_ids, num_segments=num_rows,
                                     indices_are_sorted=True)
                 for x in slots)


def _take(rows, row_ids):
    num_rows = rows[0].shape[0]
    route = slot_rows_route(num_rows, row_ids.shape[0], rows[0].dtype)
    _count(route, "take", rows)
    if route == "kernel":
        return rows_take_kernel(rows, row_ids)
    return tuple(jnp.take(r, row_ids, axis=0, mode="fill", fill_value=0)
                 for r in rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def slot_rows_sum(slots: Tuple[jax.Array, ...], row_ids: jax.Array,
                  num_rows: int) -> Tuple[jax.Array, ...]:
    """For every array of ``slots`` (``[N]`` or ``[N, F]``, one slot a
    leading index), the sum over each row's slots: ``[num_rows]`` or
    ``[num_rows, F]``. ``row_ids`` [N] int32 says which row a slot belongs
    to and is **ascending**; ids outside ``[0, num_rows)`` come last and
    add to no row. Called while a step is traced: picks the route
    (:func:`slot_rows_route`) and counts it. Its VJP is
    :func:`slot_rows_take` of the cotangents."""
    return _sum(slots, row_ids, num_rows)


def _sum_fwd(slots, row_ids, num_rows):
    return _sum(slots, row_ids, num_rows), row_ids


def _sum_bwd(num_rows, row_ids, g):
    return slot_rows_take(tuple(g), row_ids), None


slot_rows_sum.defvjp(_sum_fwd, _sum_bwd)


@jax.custom_vjp
def slot_rows_take(rows: Tuple[jax.Array, ...], row_ids: jax.Array,
                   ) -> Tuple[jax.Array, ...]:
    """For every array of ``rows`` (``[B]`` or ``[B, F]``), the row's value
    at each slot: ``[N]`` or ``[N, F]`` for ``row_ids`` [N], ascending as
    for :func:`slot_rows_sum`; a slot whose id lies outside ``[0, B)``
    reads zeros. Its VJP is :func:`slot_rows_sum` of the cotangents."""
    return _take(rows, row_ids)


def _take_fwd(rows, row_ids):
    # (the first array rides along for its shape only)
    return _take(rows, row_ids), (row_ids, rows[0])


def _take_bwd(res, g):
    row_ids, like = res
    return slot_rows_sum(tuple(g), row_ids, like.shape[0]), None


slot_rows_take.defvjp(_take_fwd, _take_bwd)
