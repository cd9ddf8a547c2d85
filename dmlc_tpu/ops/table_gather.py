"""Rows of tables that share an id space, read block by block: the forward
op on the sorted walk (``ops/sorted_walk.py``; docs/ops.md has the account
of both), the twin of ``ops/grad_scatter.py``.

``jnp.take(table, ids)`` is XLA's gather, and on a TPU it is bound by the
index, not by the bytes: 15.3 ns an index from a 1-D float32 table, 17 from
eight lane-major columns, 62 from 44 (a v5e; PERF.md §5). Here the slots
are sorted by id (``sorted_walk.sort_slots``, step 1; the backward takes
the sort and sorts nothing) and

2. **every block of the tables is read once**
   (:func:`table_gather_pallas`): block ``t`` of every table arrives
   lane-major, is split into three bfloat16 parts in VMEM and, for every
   chunk the walk brings, is contracted with the chunk's one-hot over the
   chunk's tile window (``sorted_walk.ladder``): ``rows[R, C] += block[R,
   window] @ (t * T + iota == ids)[window, C]``. A chunk's rows accumulate
   over the blocks it spans and leave by DMA when the walk leaves the
   chunk. A grid step takes about a mebibyte of table: the step's one DMA
   a table is bound by its latency below that.
3. **Back to batch order**: the permutation inverted by a second sort and
   one permute of the sorted rows (``sorted_walk.permute_columns``); told
   which slots are a batch's padding (``real=``), the sort sends those to
   the sentinel, so that no row is read for them, and the permute leaves
   out the runs of slots behind the last real one
   (``sorted_walk.permute_live``).

**Values.** For finite tables the rows equal ``jnp.take``'s value for
value (but a zero's sign, and low bits under 2**-110); an id outside the
table reads 0 where ``jnp.take`` gives NaN; negative ids count from the
end. 0 * inf is NaN: a non-finite table value reaches the slots that name
it and every other slot of the chunks whose window holds its tile. Callers
that must localise one stay on XLA's route (docs/ops.md has both in full).

:func:`table_rows` is the entry point: it picks the route from what it can
observe and counts it (``table_gather_route``). On tables dealt by rows the
slots' ids cross the chips to their owners and their rows come back, once;
on tables laid in ranges every chip reads the slots of all that it owns.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dmlc_tpu.ops import grad_scatter as gs        # the routes' probe only
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import check

# the cost model behind the route, nanoseconds on a v5e, from the pieces
# alone (benchmarks/bench_grad_scatter.py --gather; PERF.md §6, PR 29) at
# two shapes: 9 columns in two tables of 54,686,453 rows (steps 1 to 3 take
# 22.61 ms at 1,048,576 slots and 13.69 at 262,144) and 44 columns in one
# table of 13,671,614 rows (31.37 and 12.64). The kernel route pays a table
# row once (its block is read, split and contracted at least once) and a
# slot once (two sorts, its chunk's share of a block, the way back to batch
# order), both growing with the width.
_KERNEL_NS_PER_TABLE_ROW = (0.126, 0.0077)     # + per column
_KERNEL_NS_PER_SLOT = (8.13, 0.357)            # + per column
# XLA's gather, an index, by the columns of the table it reads, as the
# learners' steps run it (ledger, PR 28: 16.1 and 17.7 ms for the FM's two
# tables, 65.1 for the field-aware FM's, at 1,048,576 slots; alone the
# three read 17.1, 24.6 and 57.8 ns). Between the readings a straight
# line; beyond the last, the last line goes on.
_XLA_NS_PER_INDEX = ((1, 15.3), (8, 17.0), (44, 62.0))


def _xla_ns_per_index(width: int) -> float:
    pts = _XLA_NS_PER_INDEX
    for (w0, y0), (w1, y1) in zip(pts, pts[1:]):
        if width <= w1:
            break
    return y0 + (y1 - y0) * (width - w0) / (w1 - w0)


def table_gather_route(num_rows: int, num_slots: int,
                       widths: Tuple[int, ...], dtype) -> str:
    """``"kernel"`` or ``"xla"`` for reading ``num_slots`` rows on one chip
    from tables (or a chip's shards of them) of ``num_rows`` rows and
    ``widths`` columns (an FM's linear column and 8 factors: ``(1, 8)``).

    The kernel is taken on a TPU backend, for float32, for a table of at
    least as many rows as there are slots (where the cost model was
    measured), where that model predicts it faster than one XLA gather a
    table by the backward's ``ROUTE_MARGIN``; XLA's gather everywhere
    else."""
    if not gs._on_tpu_backend() or jnp.dtype(dtype) != jnp.float32:
        return "xla"
    if num_slots < sw.CHUNK_SLOTS or num_rows < max(num_slots, sw.BLOCK_IDS):
        return "xla"
    width = sum(widths)
    per_row, per_slot = (c + w * width for c, w in (
        _KERNEL_NS_PER_TABLE_ROW, _KERNEL_NS_PER_SLOT))
    kernel_ns = per_row * num_rows + per_slot * num_slots
    xla_ns = num_slots * sum(_xla_ns_per_index(w) for w in widths)
    return "kernel" if kernel_ns * gs.ROUTE_MARGIN < xla_ns else "xla"


# a grid step reads the fewest whole blocks of the tables that reach this
# many bytes: at 4,096 rows of 9 columns a step (147 KB) the kernel with no
# slot takes 9.6 ms for 54,686,453 rows, at 8 blocks a step 4.9; of 44
# columns (721 KB) 6.2 ms for 13,671,614 rows, at 2 blocks 5.6 (PERF.md §6,
# PR 29)
_GRID_STEP_BYTES = 1 << 20


def _blocks_a_step(num_rows: int, width: int, block_ids: int) -> int:
    fill = -(-_GRID_STEP_BYTES // (4 * width * block_ids))
    return max(1, min(fill, num_rows // block_ids))


def _gather_kernel(bounds_ref, ids_hbm, *refs, block_ids: int,
                   chunk_slots: int, num_rows: int, blocks_a_step: int,
                   trailing: Tuple[Tuple[int, ...], ...], lanes: int = 0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    table_refs, out_hbm = refs[:len(trailing)], refs[len(trailing)]
    (ids_buf, block_ref, split_ref, acc_ref, out_buf, sem, out_sem,
     state) = refs[len(trailing) + 1:]
    rows = acc_ref.shape[0]
    t = pl.program_id(0)

    def ids_copy(c):
        slot, at = sw.chunk_window(c, chunk_slots)
        return pltpu.make_async_copy(ids_hbm.at[:, at], ids_buf.at[slot],
                                     sem.at[slot])

    def out_copy(c):
        # (on the line side, ``lanes`` of them a slot, a chunk's slots are
        # rows of the output)
        slot, at = sw.chunk_window(c, chunk_slots)
        return pltpu.make_async_copy(
            out_buf.at[slot],
            out_hbm.at[at, :] if lanes else out_hbm.at[:, at],
            out_sem.at[slot])

    # a chunk is its ids alone; its rows leave the way the ids came, from
    # slot c % 2 when the walk leaves it, waited for before chunk c + 2
    walk = sw.Walk(bounds_ref, state, lambda c: (ids_copy(c),))

    @pl.when(t == 0)
    def _first():
        walk.begin()
        acc_ref[...] = jnp.zeros_like(acc_ref)
        block_ref[...] = jnp.zeros_like(block_ref)    # the padding rows

    ladder = sw.ladder(block_ids)
    lane = jax.lax.broadcasted_iota(jnp.int32, block_ref.shape, 1)

    def emit(j):
        slot = j % 2

        @pl.when(j >= 2)
        def _slot_free():
            out_copy(j - 2).wait()

        if lanes:
            # the chunk's rows as lines: the columns past the tables' as
            # zeros, slots to sublanes
            out_buf[slot] = jnp.concatenate([acc_ref[...], jnp.zeros(
                (lanes - rows, chunk_slots), jnp.float32)]).T
        else:
            out_buf[slot] = acc_ref[...]
        out_copy(j).start()
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(b):
        base = (t * blocks_a_step + b) * block_ids
        upper = base + block_ids
        # this block of every table, one row a column (column_starts'
        # order), rows past the tables' end as zeros, in three bfloat16
        # parts
        at = pl.ds(pl.multiple_of(b * block_ids, block_ids), block_ids)
        for ref, tail, row in zip(table_refs, trailing,
                                  sw.column_starts(trailing)):
            if tail:
                block_ref[row:row + tail[0], :] = ref[:, at]
            else:
                block_ref[row, :] = ref[at]
        x = jnp.where(lane < num_rows - base, block_ref[...], 0.0)
        for part, value in enumerate(sw.bfloat16_parts(x)):
            split_ref[part * rows:(part + 1) * rows, :] = value.astype(
                jnp.bfloat16)

        def contract(j):
            # sorted slots: the chunk names nothing of this block outside
            # the tiles of its first and last id, and the smallest rung
            # that holds them, pulled back to end inside the block, is
            # contracted; the tiles it takes beside them multiply zeros
            first, last = sw.tile_window(bounds_ref, j, base, upper)
            local = ids_buf[j % 2] - base                     # [1, C]

            def rung(r):
                def tiles():
                    if r == ladder[-1]:
                        at, here = slice(None), local
                    else:
                        s = jnp.minimum(first, ladder[-1] - r) * sw.TILE_IDS
                        at = pl.ds(pl.multiple_of(s, sw.TILE_IDS),
                                   r * sw.TILE_IDS)
                        here = local - s
                    iota = jax.lax.broadcasted_iota(
                        jnp.int32, (r * sw.TILE_IDS, chunk_slots), 0)
                    onehot = (iota == here).astype(jnp.bfloat16)
                    d = jnp.dot(split_ref[:, at], onehot,
                                preferred_element_type=jnp.float32)
                    acc_ref[...] += d[:rows] + d[rows:2 * rows] + d[2 * rows:]
                return tiles

            sw.on_first_rung_that_holds(last - first + 1, ladder, rung)

        # (every block of a grid step runs: the last step's may lie past)
        walk.block(upper, contract, leave=emit, may_pass_the_sentinel=True)

    jax.lax.fori_loop(0, blocks_a_step, lambda b, _: block(b), None)

    @pl.when(t == pl.num_programs(0) - 1)
    def _last():
        # slots with the sentinel id read 0, chunks of them alone too
        walk.leave_the_rest(emit)
        for c in range(max(walk.chunks - 2, 0), walk.chunks):
            out_copy(jnp.int32(c)).wait()
        walk.drain()


@functools.partial(jax.jit, static_argnames=(
    "num_rows", "trailing", "block_ids", "chunk_slots", "blocks_a_step",
    "interpret", "name", "layout"))
def table_gather_pallas(bounds: jax.Array, ids_sorted: jax.Array,
                        *tables: jax.Array, num_rows: int,
                        trailing: Tuple[Tuple[int, ...], ...],
                        block_ids: int = sw.BLOCK_IDS,
                        chunk_slots: int = sw.CHUNK_SLOTS,
                        blocks_a_step: Optional[int] = None,
                        interpret: bool = False,
                        name: str = "table_gather",
                        layout: str = "columns") -> jax.Array:
    """Step 2: the rows of the sorted slots, ``[R, Np]`` float32 with R the
    tables' columns together rounded up to 16, from
    :func:`~dmlc_tpu.ops.sorted_walk.sort_slots`' outputs (same
    ``block_ids`` / ``chunk_slots``) and the ``tables`` lane-major: a
    ``[num_rows]`` table as it is, a ``[num_rows, F]`` table as ``[F,
    num_rows]``. ``trailing`` holds each table's shape after its id axis.
    Row ``c`` holds column ``c`` in the order of
    :func:`~dmlc_tpu.ops.sorted_walk.column_starts`, rows past the
    tables' columns and slots with the sentinel id are zeros. A grid step
    walks ``blocks_a_step`` blocks (by default :func:`_blocks_a_step`'s
    mebibyte of table). ``name`` is the ``pallas_call``'s, which a device
    trace shows.

    With ``layout="lines"`` (the caller's to say, as
    :func:`~dmlc_tpu.ops.sorted_walk.slot_layout` picks it from the
    tables' width) the same rows leave as ``[Np, lanes]`` lines, slot
    ``s`` on row ``s`` with column ``c`` on lane ``c``: a chunk's ``[R,
    C]`` is transposed in VMEM as the walk leaves it, and XLA's gather
    takes the lines as they are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    width = sum(sw.widths(trailing))
    rows = sw.round_up(width, sw.SPLIT_ROWS)
    if blocks_a_step is None:
        blocks_a_step = _blocks_a_step(num_rows, width, block_ids)
    step_ids = blocks_a_step * block_ids
    padded = ids_sorted.shape[1]
    assert padded % chunk_slots == 0
    assert bounds.shape == (2, padded // chunk_slots + 1)
    assert all(t.shape == tail + (num_rows,)
               for t, tail in zip(tables, trailing))
    how = {}
    out_buf, out_shape = (rows, chunk_slots), (rows, padded)
    if layout == "lines":
        how["lanes"] = lanes = sw.line_lanes(width)
        out_buf, out_shape = (chunk_slots, lanes), (padded, lanes)
    kernel = functools.partial(
        _gather_kernel, block_ids=block_ids, chunk_slots=chunk_slots,
        num_rows=num_rows, blocks_a_step=blocks_a_step, trailing=trailing,
        **how)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-num_rows // step_ids),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [
                pl.BlockSpec((tail[0], step_ids), lambda t, bounds: (0, t))
                if tail else pl.BlockSpec((step_ids,),
                                          lambda t, bounds: (t,))
                for tail in trailing],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, 1, chunk_slots), jnp.int32),
                pltpu.VMEM((rows, block_ids), jnp.float32),
                pltpu.VMEM((3 * rows, block_ids), jnp.bfloat16),
                pltpu.VMEM((rows, chunk_slots), jnp.float32),
                pltpu.VMEM((2,) + out_buf, jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((sw.STATE_WORDS,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
        interpret=interpret,
    )(bounds, ids_sorted, *tables)


def table_gather_tile_counts(ids: jax.Array, num_rows: int,
                             block_ids: int = sw.BLOCK_IDS,
                             chunk_slots: int = sw.CHUNK_SLOTS,
                             blocks_a_step: int = 1,
                             ) -> Tuple[jax.Array, jax.Array]:
    """``(performed, whole_block)``: the tile-products (one ``[3R, 128] @
    [128, C]`` with its one-hot) :func:`table_gather_pallas` performs to
    read rows ``ids`` [...] of tables of ``num_rows`` rows at these tile
    sizes, and those of contracting every (block, chunk) pair over its
    whole block, which the kernel did until PR 43. Counted from the sorted
    ids as the kernel's walk meets them, outside any step:
    ``blocks_a_step`` only decides how far past the tables' end the grid
    reaches, where chunks of sentinels alone are contracted with zeros."""
    bounds, _, _ = sw.sort_slots(ids.reshape(-1), num_rows, block_ids,
                                 chunk_slots)
    return sw.tile_counts(
        bounds, sw.round_up(num_rows, blocks_a_step * block_ids), block_ids,
        sw.ladder(block_ids))


def _trailing(tables) -> Tuple[Tuple[int, ...], ...]:
    return tuple(tuple(t.shape[1:]) for t in tables)


def _table_slots_kernel(ids, tables, sorted_slots=None, real=None,
                        received: str = ""):
    """Steps 1 to 3 for flat ``ids`` [N]: ``(slots, sorted_slots)``, the
    rows in batch order on the side the tables' width takes
    (:func:`~dmlc_tpu.ops.sorted_walk.slot_layout`): lane-major columns
    ``[width, N]`` or lines ``[N, lanes]``, as the kernel wrote and XLA's
    gather moved them; and the sort, made here unless the caller hands it
    in. Slots whose ``real`` [N] is false read zeros: they take the
    sentinel in the sort, and the runs of slots behind the last real one
    (``received``, as :func:`table_cols_kernel` has it: the runs that hold
    no real one) are not brought back to batch order
    (:func:`~dmlc_tpu.ops.sorted_walk.permute_live`)."""
    num_rows, trailing = tables[0].shape[0], _trailing(tables)
    if sorted_slots is None:
        sorted_slots = sw.sort_slots(ids, num_rows, real=real)
    bounds, ids_s, perm = sorted_slots
    width = sum(sw.widths(trailing))
    layout = sw.slot_layout(width)
    lane_major = tuple(t.T if tail else t for t, tail in zip(tables, trailing))
    with jax.named_scope(sw.GATHER_KERNEL_SCOPE):
        rows_s = table_gather_pallas(
            bounds, ids_s, *lane_major, num_rows=num_rows, trailing=trailing,
            layout=layout)
    with jax.named_scope(sw.SORT_SCOPE):
        inverse = sw.inverse_permutation(perm)
    with jax.named_scope(sw.GATHER_PERMUTE_SCOPE):
        return _to_batch_order(rows_s, inverse, perm, ids.shape[0], width,
                               layout, real, received), sorted_slots


def _to_batch_order(rows_s, inverse, perm, n: int, width: int, layout: str,
                    real, received: str):
    """Step 3: the kernel's sorted rows ``rows_s`` as the first ``n`` slots
    of the batch had them, on the side they came. ``received``: the slots
    are those a chip of a dealt table was handed, whose padding is no one
    tail (of an ``"owner"`` the tail of every worker's bucket, of a
    ``"shard"`` the columns of the batch that other chips own)."""
    if real is not None and not sw.permutes_in_groups(width, perm.shape[0]):
        _telemetry.count_table_slot_groups(
            received + "_gather" if received else "gather",
            sw.permute_groups(n))
        return sw.permute_live(
            rows_s if layout == "lines" else rows_s[:width], inverse[:n],
            (sw.live_runs if received else sw.live_batch_slots)(real), layout)
    if layout == "lines":
        return sw.permute_lines(rows_s, inverse[:n])
    if sw.permutes_in_groups(width, perm.shape[0]):
        # too large an operand for one gather of XLA's
        return sw.permute_wide_columns(
            rows_s[:width], inverse, perm)[:, :n]
    return sw.permute_columns(rows_s[:width], inverse[:n])


def table_cols_kernel(ids: jax.Array, tables: Tuple[jax.Array, ...],
                      sorted_slots: Optional[tuple] = None,
                      received: str = "") -> Tuple[jax.Array, tuple]:
    """Steps 1 to 3 for flat ``ids`` [N]: ``(cols, sorted_slots)`` with
    the rows lane-major, ``[width, N]`` with one row a column of the tables
    in the order of :func:`~dmlc_tpu.ops.sorted_walk.column_starts`
    (:func:`~dmlc_tpu.ops.sorted_walk.rows_of_cols` cuts them apart), and
    the sort (made here unless the caller hands it in), for the backward
    (``table_grad_kernel(sorted_slots=)``). ``received`` says that ``ids``
    are rows of a chip's shard of a dealt table with the row one past it
    where the chip has nothing to read, and whose they are: ``"owner"``,
    the slots the exchange sent it, a bucket a worker with the padding
    behind each bucket's count; ``"shard"``, every chip's slots on the road
    with no buckets (``table_exchange.open_slots``). The runs of slots that
    name no row of the shard are not brought back to the order received
    (they read zeros either way; counted in ``table_slot_groups{op=
    "owner_gather" | "shard_gather"}``): an owner's on the line side, a
    shard's on both."""
    width = sum(sw.widths(_trailing(tables)))
    lines = sw.slot_layout(width) == "lines"
    by_runs = received == "shard" or (received and lines)
    slots, sorted_slots = _table_slots_kernel(
        ids, tables, sorted_slots,
        ids < tables[0].shape[0] if by_runs else None, received)
    if lines:
        with jax.named_scope(sw.GATHER_PERMUTE_SCOPE):
            return slots.T[:width], sorted_slots
    return slots, sorted_slots


def table_rows_kernel(ids: jax.Array, tables: Tuple[jax.Array, ...],
                      lines: bool = False, real: Optional[jax.Array] = None,
                      ) -> Tuple[Tuple[jax.Array, ...], tuple]:
    """Steps 1 to 3 for flat ``ids`` [N]: ``(rows, sorted_slots)`` with one
    ``[N]`` or ``[N, F]`` array of rows a table and the sort, for the
    backward (``table_grad_kernel(sorted_slots=)``). With ``lines``, one
    table on the line side comes as its lines ``[N, lanes]`` uncut. Slots
    whose ``real`` [N] is false read zeros."""
    trailing = _trailing(tables)
    slots, sorted_slots = _table_slots_kernel(ids, tables, real=real)
    with jax.named_scope(sw.GATHER_PERMUTE_SCOPE):
        if sw.slot_layout(sum(sw.widths(trailing))) != "lines":
            return sw.rows_of_cols(slots, trailing), sorted_slots
        if lines and len(tables) == 1:
            return (slots,), sorted_slots
        return sw.rows_of_lines(slots, trailing), sorted_slots


def table_rows(tables: Tuple[jax.Array, ...], indices: jax.Array,
               deal=None, real=None, lines: bool = False,
               ) -> Tuple[Tuple[jax.Array, ...], Optional[tuple]]:
    """``(rows, sorted_slots)``: rows ``indices`` [...] of every table
    (``[W]`` or ``[W, F]``, one id space), as one ``jnp.take`` a table
    gives them, and :func:`~dmlc_tpu.ops.sorted_walk.sort_slots` of the
    flat indices where the kernel route made it on one chip (``None``
    otherwise). Called while a forward is traced: picks the route
    (:func:`table_gather_route`) and counts it in
    ``table_gather_route{route=, width=}``, ``width`` the columns of all
    the tables together.

    ``lines``: the caller takes one table's rows as the kernel route's
    line side leaves them (:func:`~dmlc_tpu.ops.sorted_walk.slot_layout`),
    ``[..., lanes]`` with the columns on a line's first lanes and zeros
    behind them, where that is how they come (one chip, no mesh; the
    result's last axis says so), and hands the backward
    (``fused_table_update``) their cotangent in the same form: no pass of
    XLA's cuts the lines to the columns and pads them again.

    With a ``deal`` (:class:`dmlc_tpu.parallel.mesh.RowDeal`) the call is
    made inside ``shard_map`` over ``deal.axis``: ``tables`` are this
    chip's shards of tables dealt by rows, ``indices`` this chip's slots,
    ids in ``[0, deal.num_rows)``. Every slot's id goes to the chip that
    owns it, the owner reads what it received from its shard on the route
    of one chip (that of its shard's rows and the slots of all chips, the
    most it can be handed), and every row comes home once
    (ops/table_exchange.py, which also says what a step does whose buckets
    overflow: nothing is dropped under any skew). The counter gains
    ``shards=``; in ``sorted_slots``' place comes the
    :class:`~dmlc_tpu.ops.table_exchange.Exchange`, which the backward on
    this chip takes. A deal that is not ``even``
    (:class:`~dmlc_tpu.parallel.mesh.RowRanges`) takes the road with no
    buckets instead, always: every chip reads the slots of all that it
    owns (``indices`` K-major, ``[K, B]``), and what comes in
    ``sorted_slots``' place is that road's
    :class:`~dmlc_tpu.ops.table_exchange.Slots`.

    Slots whose ``real`` [...] is false read zeros on the kernel route,
    whatever id they carry: an ELL batch's padding, whose value 0 makes
    zeros of any finite row. With a ``deal`` they are not sent (their one
    sink id would hand one chip 5 slots of every 16); on one chip they
    take the sort's sentinel, so that the kernel reads no row for them,
    and the runs of slots behind the last real one are not brought back
    to batch order (:func:`~dmlc_tpu.ops.sorted_walk.permute_live`,
    counted in ``table_slot_groups{op="gather", groups=}``). **An ELL
    caller hands its slots K-major**, ``indices`` and ``real`` ``[K, B]``:
    its padding then lies behind its real slots in the flat order, whole
    columns of it in runs of their own. Any other order reads the same
    rows and skips less. XLA's route reads ``real`` slots and padding
    alike (``jnp.take`` of the id they carry)."""
    check(all(t.ndim <= 2 for t in tables),
          "table_rows: a table is [rows] or [rows, F]")
    widths = sw.widths(_trailing(tables))
    if deal is not None:
        return (_dealt_rows if deal.even else _rows_of_every_slot)(
            tables, indices, widths, deal, real)
    route = table_gather_route(tables[0].shape[0], indices.size, widths,
                               tables[0].dtype)
    _telemetry.REGISTRY.counter(
        _telemetry.TABLE_GATHER_ROUTE_METRIC, route=route,
        width=str(sum(widths))).inc(1)
    if route == "xla":
        return tuple(jnp.take(t, indices, axis=0) for t in tables), None
    _count_slot_layout(widths)
    rows, sorted_slots = table_rows_kernel(
        indices.reshape(-1), tables, lines,
        None if real is None else real.reshape(-1))
    return tuple(r.reshape(indices.shape + r.shape[1:])
                 for r in rows), sorted_slots


def _count_slot_layout(widths) -> None:
    _telemetry.REGISTRY.counter(
        _telemetry.TABLE_SLOT_LAYOUT_METRIC, op="gather",
        layout=sw.slot_layout(sum(widths))).inc(1)


def _shard_route(tables, num_slots: int, widths, deal) -> str:
    """The route of a chip of ``deal`` reading its shards ``tables``: that
    of one chip with the shard's rows and the ``num_slots`` slots of all
    chips, the most it can be handed; counted with ``shards=``."""
    route = table_gather_route(tables[0].shape[0], num_slots, widths,
                               tables[0].dtype)
    _telemetry.REGISTRY.counter(
        _telemetry.TABLE_GATHER_ROUTE_METRIC, route=route,
        width=str(sum(widths)), shards=str(deal.shards)).inc(1)
    if route == "kernel":
        _count_slot_layout(widths)
    return route


def _shard_cols(tables, route: str, ids, sorted_slots, received: str):
    """Rows ``ids`` [M] of this chip's shards ``tables`` as one chip reads
    them, lane-major ``[width, M]`` (as the kernel's permute leaves them:
    the slots on the lanes, 44 columns on 48 sublanes and not on 128
    lanes); one past the shard reads 0."""
    if route == "kernel":
        return table_cols_kernel(ids, tables, sorted_slots, received)[0]
    return sw.cols_of_rows(tuple(
        jnp.take(t, ids, axis=0, mode="fill", fill_value=0)
        for t in tables), _trailing(tables))


def _rows_of_every_slot(tables, indices, widths, deal, real):
    """:func:`table_rows` for tables laid in ranges, inside ``shard_map``:
    the road with no buckets (ops/table_exchange.py, "Every slot to every
    chip")."""
    from dmlc_tpu.ops import table_exchange as tx

    route = _shard_route(tables, indices.size * deal.shards, widths, deal)
    with jax.named_scope(tx.EXCHANGE_SCOPE):
        slots = tx.open_slots(deal, indices, real)
    if route == "kernel":
        slots = slots._replace(
            sorted_slots=sw.sort_slots(slots.rows, tables[0].shape[0]))
    cols = _shard_cols(tables, route, slots.rows, slots.sorted_slots,
                       "shard")
    with jax.named_scope(tx.EXCHANGE_SCOPE):
        cols = tx.slots_home(deal, cols, tx.slot_columns(indices))
    with jax.named_scope(sw.GATHER_PERMUTE_SCOPE):
        return tuple(
            r.reshape(indices.shape + r.shape[1:])
            for r in sw.rows_of_cols(cols, _trailing(tables))), slots


def _dealt_rows(tables, indices, widths, deal, real):
    """:func:`table_rows` for tables dealt by rows, inside ``shard_map``."""
    from dmlc_tpu.ops import table_exchange as tx

    flat, num_rows = indices.reshape(-1), tables[0].shape[0]
    route = _shard_route(tables, flat.size * deal.shards, widths, deal)
    with jax.named_scope(tx.EXCHANGE_SCOPE):
        exchange = tx.open_exchange(deal, indices, real)
    if route == "kernel":
        exchange = exchange._replace(
            sorted_slots=sw.sort_slots(exchange.received, num_rows))
    shard_cols = functools.partial(_shard_cols, tables, route)

    def owned():
        cols = shard_cols(exchange.received, exchange.sorted_slots, "owner")
        with jax.named_scope(tx.EXCHANGE_SCOPE):
            return tx.rows_home(deal, exchange.buckets, cols)

    def whole():
        with jax.named_scope(tx.EXCHANGE_SCOPE):
            ids = deal.local_slots(flat)
        cols = shard_cols(ids, None, "")
        with jax.named_scope(tx.EXCHANGE_SCOPE):
            # the reduce-scatter as an all-to-all of the chips' blocks and
            # a sum here: XLA writes psum_scatter as an all-reduce of the
            # whole [width, slots] in rows of 128 lanes (PERF.md §6, PR 32)
            blocks = cols.reshape(cols.shape[0], deal.shards, -1)
            return jnp.sum(jax.lax.all_to_all(
                jnp.moveaxis(blocks, 1, 0), deal.axis, 0, 0), axis=0)

    cols = jax.lax.cond(exchange.buckets.overflow, whole, owned)
    with jax.named_scope(sw.GATHER_PERMUTE_SCOPE):
        return tuple(
            r.reshape(indices.shape + r.shape[1:])
            for r in sw.rows_of_cols(cols, _trailing(tables))), exchange
