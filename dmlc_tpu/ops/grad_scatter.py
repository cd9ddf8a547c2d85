"""The dense gradient of a table gather, built block by block, and the
optimizer's step that can take its place: the backward op on the sorted
walk (``ops/sorted_walk.py``; docs/ops.md has the account of both).

``jnp.take(table, ids)`` transposes into a scatter-add of the cotangent
rows, and XLA's TPU scatter-add walks its updates one element at a time:
10.4 ns a float32 element on a v5e, 100 ms for the 1,048,576 nine-wide rows
of a KDD12 FM step (PERF.md §5, PR 24). Here the slots are sorted by id
with their cotangent columns as the payload
(``sorted_walk.sorted_payload``, step A) and

B. **every block of the gradient is written once**
   (:func:`grad_scatter_pallas`): for block ``t`` and every chunk the walk
   brings, ``payload[R, C] @ (t * T + iota == ids)[window, C].T -> [R,
   window]`` on the MXU, the payload's three bfloat16 parts added in
   float32 into the window's lanes of the block's accumulator. The window
   is the rung of 128-id tiles (``sorted_walk.ladder``) that holds the
   chunk's first to last id inside the block: its slots are sorted, so it
   names no other tile (PR 46; every pair took the block's 32 tiles until
   then). Duplicates are summed by the contraction; a block no slot hits
   is written as zeros. The gradient is lane-major (``[F, rows]`` for a
   ``[rows, F]`` table), the layout XLA keeps a narrow float32 table in on
   a TPU: the optimizer reads it in place.
C. **Or the optimizer's step is finished on the block instead**
   (``grad_scatter_pallas(epilogue=)``, :func:`fused_table_update`): the
   block of the gradient is whole in VMEM when step B would write it out,
   and with an :class:`AdamEpilogue` (PR 31) or :class:`AdaGradEpilogue`
   (libffm's, PR 34) the same body reads the block's leaves, takes optax's
   arithmetic in float32 and writes them back over themselves: no dense
   gradient reaches HBM and no second sweep reads it. A block no slot hits
   takes the step with a zero gradient. On a table dealt by rows
   (``deal=``) a chip does so on its shard, from the slots it owns (PR 42;
   on a table laid in ranges from every chip's slots, of which what it
   does not own takes the sort's sentinel: PR 54).

**The window changes no bit.** A tile outside a pair's window meets an
all-zero one-hot: contracted, it would add ``+0.0`` or ``-0.0`` to an
accumulator that starts a block at ``+0.0``, and ``+0.0 + -0.0 = +0.0``, ``x
+ -0.0 = x`` in round-to-nearest, so the accumulator never holds ``-0.0``
with or without it. An id inside the window takes the same sum over the
chunk's ``C`` slots (``payload[3R, C]`` against its one-hot row) whichever
rung holds its tile: a rung sets how many output columns a matmul has, not
how one is summed. For finite cotangents the gradient, ``W`` / ``G`` and
``p`` / ``m`` / ``n`` are those of the whole-block contraction bit for bit
(``_scatter_call(rungs=)`` with the last rung alone is that contraction;
tests/test_grad_scatter.py holds the ladder to it).

**Non-finite gradients.** 0 * inf is NaN: one non-finite cotangent value
turns its column non-finite in the rows of the tiles its chunk's window
holds, in every block its chunk reaches (128 to ``T`` rows of each; all
``T`` until PR 46), where a scatter-add poisons one row; with an epilogue,
in those rows of the block's parameters and state, with no gradient to look
at first. Callers that must localise or inspect one stay on XLA's route or
keep the dense gradient.

:func:`dense_table_grad` is the entry point: it picks the route from what
it can observe and counts it in ``grad_scatter_route``. ``_on_tpu_backend``
is the one probe every route of ``ops/`` consults, through this module's
attribute at call time: the benchmark's ahead-of-time compiles assign to it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from dmlc_tpu.ops.pallas_sparse import _on_tpu_backend
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import check

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
# the kernel has to be predicted this much faster before it is taken
ROUTE_MARGIN = 1.25


def grad_scatter_route(num_rows: int, num_slots: int, width: int,
                       dtype, tables: int = 1) -> str:
    """``"kernel"`` or ``"xla"`` for ``tables`` tables (or a chip's shards
    of them) of ``num_rows`` rows and ``width`` columns in all (an FM's
    linear column and 8 factors: two tables, 9) receiving ``num_slots``
    gradient rows on one chip.

    The kernel is taken on a TPU backend, for float32, for a table of at
    least as many rows as there are slots (where the cost model was
    measured), where that model predicts the kernel faster than XLA's
    scatter-add by ``ROUTE_MARGIN``; XLA's route everywhere else (small
    tables, the CPU, other dtypes)."""
    if not _on_tpu_backend() or jnp.dtype(dtype) != jnp.float32:
        return "xla"
    if num_slots < sw.CHUNK_SLOTS or num_rows < max(num_slots, sw.BLOCK_IDS):
        return "xla"
    per_row, per_slot = (c + w * width for c, w in (
        _KERNEL_NS_PER_TABLE_ROW, _KERNEL_NS_PER_SLOT))
    kernel_ns = per_row * num_rows + per_slot * num_slots
    xla_ns = (num_slots * (_XLA_NS_PER_SLOT_AND_TABLE * tables
                           + _XLA_NS_PER_ELEMENT * width)
              + _XLA_FILL_NS_PER_ELEMENT * width * num_rows)
    return "kernel" if kernel_ns * ROUTE_MARGIN < xla_ns else "xla"


class AdamEpilogue(NamedTuple):
    """``optax.adam``'s hyper-parameters as the kernel's epilogue: what
    :func:`grad_scatter_pallas` does with a finished block of the gradient
    in place of writing it out. Static (the numbers are compiled in); the
    step count arrives as :meth:`bias`.

    An epilogue declares what the kernel keeps books for: ``leaves`` state
    arrays a table (here ``p, m, n``), ``scalars`` float32 numbers a step
    (here :meth:`bias`'s two), the ``kernel_name`` of its ``pallas_call``
    and ``apply(g, *leaves, *scalars) -> leaves``."""
    learning_rate: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8

    leaves = 3
    scalars = 2
    kernel_name = "grad_scatter_adam"

    def bias(self, count: jax.Array) -> jax.Array:
        """``[1 - b1**count, 1 - b2**count]`` float32 for the step that
        ``count`` (already incremented) numbers: optax's two bias
        corrections, which :meth:`apply` divides by."""
        return jnp.stack([1.0 - self.b1 ** count,
                          1.0 - self.b2 ** count]).astype(jnp.float32)

    def apply(self, g, p, m, n, bias_m, bias_n):
        """One float32 Adam step on arrays of one shape, in optax's order
        (``scale_by_adam``, ``scale_by_learning_rate``, ``apply_updates``):
        ``(p, m, n)`` after the gradient ``g``. Runs on a block in VMEM
        inside the kernel and on a scalar parameter outside it. (Its
        arithmetic is 1.1 of the kernel's 24.5 ms on a v5e, and the two
        scalar divides cost what products with reciprocals would:
        PERF.md §6, PR 31.)"""
        m = (1.0 - self.b1) * g + self.b1 * m
        n = (1.0 - self.b2) * (g * g) + self.b2 * n
        update = (m / bias_m) / (jnp.sqrt(n / bias_n) + self.eps)
        return p + update * (-self.learning_rate), m, n


class AdaGradEpilogue(NamedTuple):
    """libffm's AdaGrad as the kernel's epilogue: ``optax.chain(
    scale_by_rss(initial_accumulator_value=1.0, eps=0.0), scale(-lr))``
    with the accumulators ``G`` as the caller started them (at 1: ``G >=
    1`` always, so nothing is divided by zero). ``leaves`` are ``W, G`` a
    table, and there is no scalar: the step has no count.

    The ``pallas_call`` keeps the name ``grad_scatter``: the benchmark's
    ``ffm_grad_scatter_kernel_roofline`` finds the kernel in a trace by
    that name, and a cell whose traced run lacks the metric is refused
    (PERF.md §6, PR 34; tests/test_ffm.py holds the name to the pattern)."""
    learning_rate: float

    leaves = 2
    scalars = 0
    kernel_name = "grad_scatter"

    def apply(self, g, w, acc):
        """One float32 AdaGrad step on arrays of one shape, in optax's
        order (``scale_by_rss``, ``scale``, ``apply_updates``): ``(w, G)``
        after the gradient ``g``. Where ``g == 0`` both come back bit for
        bit (``G + 0`` and ``w - 0``): a coordinate no slot of the batch
        uses never moves, with no guard."""
        acc = g * g + acc
        update = jax.lax.rsqrt(acc) * g
        return w + update * (-self.learning_rate), acc


Epilogue = Union[AdamEpilogue, AdaGradEpilogue]


def _scatter_kernel(bounds_ref, *refs, block_ids: int, chunk_slots: int,
                    trailing: Tuple[Tuple[int, ...], ...],
                    rungs: Tuple[int, ...],
                    epilogue: Optional[Epilogue] = None,
                    blocks_a_step: int = 1, num_blocks: int = 0,
                    lines: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # with an epilogue: its scalars first (if it has any), and its leaves
    # of every table in and, aliased, out; with none: one gradient a table
    # out
    tables = len(trailing)
    if epilogue is None:
        (ids_hbm, pay_hbm), in_refs, refs = refs[:2], (), refs[2:]
        out_refs, refs = refs[:tables], refs[tables:]
    else:
        per = epilogue.leaves
        if epilogue.scalars:
            scalars_ref, refs = refs[0], refs[1:]
        (ids_hbm, pay_hbm), refs = refs[:2], refs[2:]
        in_refs, out_refs, refs = (refs[:per * tables],
                                   refs[per * tables:2 * per * tables],
                                   refs[2 * per * tables:])
    if lines:
        line_buf, refs = refs[-1], refs[:-1]
    ids_buf, pay_buf, sem, acc_ref, state = refs
    rows = acc_ref.shape[0]
    t = pl.program_id(0)
    # of the grid step's first block
    base = t * (blocks_a_step * block_ids)
    upper = base + block_ids

    def copies(c):
        # a chunk is its ids and its payload
        slot, at = sw.chunk_window(c, chunk_slots)
        return (pltpu.make_async_copy(ids_hbm.at[:, at], ids_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(pay_hbm.at[at, :], line_buf.at[slot],
                                      sem.at[1, slot]) if lines else
                pltpu.make_async_copy(pay_hbm.at[:, at], pay_buf.at[slot],
                                      sem.at[1, slot]))

    def split_lines(c):
        # the line side: a chunk comes as its slots' float32 lines and is
        # laid as the payload here, once, when it arrives (a chunk is
        # contracted with every block it spans): slots to lanes, the lines'
        # first ``rows`` lanes in three bfloat16 parts
        slot = c % 2
        x = line_buf[slot].T[:rows]
        for part, value in enumerate(sw.bfloat16_parts(x)):
            pay_buf[slot, part * rows:(part + 1) * rows, :] = value.astype(
                jnp.bfloat16)

    walk = sw.Walk(bounds_ref, state, copies, split_lines if lines else None)
    pl.when(t == 0)(walk.begin)

    def finish(lanes):
        # the block's gradient is whole in acc_ref: write it out, or take
        # the epilogue's step on the block of every leaf
        for i, (tail, row) in enumerate(zip(trailing,
                                            sw.column_starts(trailing))):
            g = acc_ref[row:row + tail[0]] if tail else acc_ref[row]
            at = (slice(None), lanes) if tail and lanes is not ... else lanes
            if epilogue is None:
                out_refs[i][at] = g
                continue
            new = epilogue.apply(
                g, *(ref[at] for ref in in_refs[per * i:per * (i + 1)]),
                *(scalars_ref[k] for k in range(epilogue.scalars)))
            for ref, x in zip(out_refs[per * i:per * (i + 1)], new):
                ref[at] = x

    def block(base, upper, lanes, last_of_step=None):
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def contract(j):
            # sorted slots: the chunk names nothing of this block outside
            # the tiles of its first and last id, and the smallest rung
            # that holds them, pulled back to end inside the block, is
            # contracted and added into its own lanes of the block; the
            # tiles it takes beside them multiply zeros
            slot = j % 2
            first, last = sw.tile_window(bounds_ref, j, base, upper)
            local = ids_buf[slot] - base                          # [1, C]

            def rung(r):
                def tiles():
                    if r == rungs[-1]:
                        at, here = slice(None), local
                    else:
                        s = jnp.minimum(first, rungs[-1] - r) * sw.TILE_IDS
                        at = pl.ds(pl.multiple_of(s, sw.TILE_IDS),
                                   r * sw.TILE_IDS)
                        here = local - s
                    iota = jax.lax.broadcasted_iota(
                        jnp.int32, (r * sw.TILE_IDS, chunk_slots), 0)
                    onehot = (iota == here).astype(jnp.bfloat16)
                    d = jax.lax.dot_general(
                        pay_buf[slot], onehot, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)   # [3R, r * 128]
                    acc_ref[:, at] += (d[:rows] + d[rows:2 * rows]
                                       + d[2 * rows:])
                return tiles

            sw.on_first_rung_that_holds(last - first + 1, rungs, rung)

        walk.block(upper, contract)
        last = t == pl.num_programs(0) - 1
        if last_of_step is not None:
            last = last & last_of_step
        walk.drain(last)
        finish(lanes)

    if blocks_a_step == 1:
        block(base, upper, Ellipsis)
        return
    # only the blocks the table has: the last step's may lie past its end
    live = jnp.minimum(blocks_a_step, num_blocks - t * blocks_a_step)

    def nth(b):
        off = pl.multiple_of(b * block_ids, block_ids)
        block(base + off, upper + off, pl.ds(off, block_ids), b == live - 1)

    jax.lax.fori_loop(0, live, lambda b, _: nth(b), None)


# with an epilogue a grid step takes as many blocks as bring every leaf
# (the columns of all the tables together) to about this many bytes: the
# pipeline's DMAs, one in and one out a leaf and table, are bound by their
# latency under it, and over it a step's slots wait longer for its blocks.
# 4 blocks at the KDD12 FM's 9 columns (590 KB: 26.6 / 24.9 / 24.5 / 25.0
# ms at 1 / 2 / 4 / 8 blocks on a v5e, 18.8 with no slot; PERF.md §6, PR
# 31), 1 at the field-aware FM's 44 (721 KB: 23.9 / 24.8 / 26.3 ms at 1 /
# 2 / 4, 16.8 with no slot whichever; PR 34)
_EPILOGUE_STEP_BYTES = 640 << 10


def _epilogue_blocks_a_step(width: int, block_ids: int, blocks: int) -> int:
    return max(1, min(_EPILOGUE_STEP_BYTES // (4 * width * block_ids), blocks))


@functools.partial(jax.jit, static_argnames=(
    "num_rows", "trailing", "block_ids", "chunk_slots", "epilogue",
    "blocks_a_step", "interpret", "name"))
def grad_scatter_pallas(bounds: jax.Array, ids_sorted: jax.Array,
                        payload: jax.Array, *state: jax.Array,
                        num_rows: int,
                        trailing: Tuple[Tuple[int, ...], ...],
                        block_ids: int = sw.BLOCK_IDS,
                        chunk_slots: int = sw.CHUNK_SLOTS,
                        epilogue: Optional[Epilogue] = None,
                        blocks_a_step: Optional[int] = None,
                        interpret: bool = False,
                        name: Optional[str] = None,
                        ) -> Tuple[jax.Array, ...]:
    """Step B: one dense gradient a table from the outputs of
    :func:`~dmlc_tpu.ops.sorted_walk.sorted_payload` (same ``block_ids`` /
    ``chunk_slots``). ``trailing`` holds each
    table's shape after its id axis, ``()`` or ``(F,)``; the gradient of a
    ``[num_rows]`` table comes as ``[num_rows]``, that of a ``[num_rows,
    F]`` table lane-major as ``[F, num_rows]``. The payload's columns are
    the tables' in the order of
    :func:`~dmlc_tpu.ops.sorted_walk.column_starts`.

    With an ``epilogue`` no gradient is written. ``state`` is then the
    epilogue's scalars where it has any (``AdamEpilogue.bias(count)``)
    and, table by table, its leaves (Adam: the parameters and both moments
    ``p, m, n``; AdaGrad: ``W, G``) in the gradient's lane-major layout.
    Every block of them is read while the block of the gradient is built
    in VMEM, takes the epilogue's step there and is written back over
    itself: the results, a table's leaves as they came, are aliased to the
    operands. A block no slot hits takes the step with a zero gradient. A
    grid step then walks ``blocks_a_step`` blocks. ``name`` is the
    ``pallas_call``'s, which a device trace shows: ``grad_scatter`` or the
    epilogue's ``kernel_name`` unless a caller that is not a table's
    gradient says otherwise (ops/slot_rows.py)."""
    return _scatter_call(
        bounds, ids_sorted, payload, *state, num_rows=num_rows,
        trailing=trailing, block_ids=block_ids, chunk_slots=chunk_slots,
        epilogue=epilogue, blocks_a_step=blocks_a_step, interpret=interpret,
        name=name, rungs=sw.ladder(block_ids))


def _scatter_call(bounds, ids_sorted, payload, *state, num_rows, trailing,
                  block_ids, chunk_slots, epilogue, blocks_a_step, interpret,
                  name, rungs):
    """:func:`grad_scatter_pallas`' ``pallas_call`` with a pair's tile
    window taken from ``rungs`` (a :func:`~dmlc_tpu.ops.sorted_walk.ladder`
    of ``block_ids``). The ladder of the last rung alone contracts every
    pair over its whole block, as the kernel did until PR 46:
    tests/test_grad_scatter.py holds the window to it bit for bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blocks = -(-num_rows // block_ids)
    # the payload says which side it is laid on: [3R, Np] bfloat16 columns
    # or [Np, lanes] float32 lines (sorted_walk.slot_layout)
    lines = payload.dtype == jnp.float32
    if lines:
        rows = sw.round_up(sum(sw.widths(trailing)), sw.SPLIT_ROWS)
        split_rows, lanes = 3 * rows, payload.shape[1]
        assert payload.shape[0] == ids_sorted.shape[1] and lanes >= rows
    else:
        split_rows = payload.shape[0]
        rows = split_rows // 3
    assert rows * 3 == split_rows and rows >= sum(sw.widths(trailing))
    assert ids_sorted.shape[1] % chunk_slots == 0
    assert bounds.shape == (2, ids_sorted.shape[1] // chunk_slots + 1)
    params = dict(dimension_semantics=("arbitrary",))
    if epilogue is None:
        assert not state and blocks_a_step in (None, 1)
        scalars, leaves, per_table = (), (), 1
        name = name or "grad_scatter"
        blocks_a_step, how = 1, {}
    else:
        per_table, name = epilogue.leaves, name or epilogue.kernel_name
        scalars = state[:1] if epilogue.scalars else ()
        leaves = state[len(scalars):]
        assert [x.shape for x in state] == [
            (epilogue.scalars,) for _ in scalars] + [
            tail + (num_rows,) for tail in trailing for _ in range(per_table)]
        if blocks_a_step is None:
            blocks_a_step = _epilogue_blocks_a_step(
                sum(sw.widths(trailing)), block_ids, blocks)
        how = dict(epilogue=epilogue, blocks_a_step=blocks_a_step,
                   num_blocks=blocks)
        # the pipeline holds every table block twice in and twice out
        step_bytes = 4 * per_table * sum(sw.widths(trailing)) * (
            blocks_a_step * block_ids)
        params["vmem_limit_bytes"] = 4 * step_bytes + (24 << 20)
    step_ids = blocks_a_step * block_ids
    scratch = []
    if lines:
        how["lines"] = True
        scratch = [pltpu.VMEM((2, chunk_slots, lanes), jnp.float32)]
    kernel = functools.partial(
        _scatter_kernel, block_ids=block_ids, chunk_slots=chunk_slots,
        trailing=trailing, rungs=rungs, **how)
    table_specs = [
        pl.BlockSpec((tail[0], step_ids), lambda t, *_: (0, t))
        if tail else pl.BlockSpec((step_ids,), lambda t, *_: (t,))
        for tail in trailing for _ in range(per_table)]
    # operands: bounds, (scalars,) ids, payload, then the tables' leaves
    first_leaf = 3 + len(scalars)
    return tuple(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(scalars),
            grid=(-(-num_rows // step_ids),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)]
            + table_specs[:len(leaves)],
            out_specs=table_specs,
            scratch_shapes=[
                pltpu.VMEM((2, 1, chunk_slots), jnp.int32),
                pltpu.VMEM((2, split_rows, chunk_slots), jnp.bfloat16),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, block_ids), jnp.float32),
                pltpu.SMEM((sw.STATE_WORDS,), jnp.int32),
            ] + scratch),
        out_shape=[jax.ShapeDtypeStruct(tail + (num_rows,), jnp.float32)
                   for tail in trailing for _ in range(per_table)],
        input_output_aliases={first_leaf + i: i for i in range(len(leaves))},
        compiler_params=pltpu.CompilerParams(**params),
        name=name,
        interpret=interpret,
    )(bounds, *scalars, ids_sorted, payload, *leaves))


def grad_scatter_tile_counts(ids: jax.Array, num_rows: int,
                             block_ids: int = sw.BLOCK_IDS,
                             chunk_slots: int = sw.CHUNK_SLOTS,
                             ) -> Tuple[jax.Array, jax.Array]:
    """``(performed, whole_block)``: the tile-products (one ``[3R, C] @
    [C, 128]`` with its one-hot) :func:`grad_scatter_pallas` performs, with
    or without an epilogue, to add the cotangent rows of slots ``ids``
    [...] into tables of ``num_rows`` rows at these tile sizes, and those
    of contracting every (block, chunk) pair over its whole block, which
    the kernel did until PR 46. Counted from the sorted ids as the kernel's
    walk meets them, outside any step; the walk stops at the tables' last
    block however many blocks a grid step takes. Two of
    :func:`~dmlc_tpu.ops.sorted_walk.walk_books`' counts."""
    books = sw.walk_books(ids, num_rows, None, block_ids, chunk_slots)
    return books["tile_products"], books["whole_block_tile_products"]


def _trailing(cotangents, indices) -> Tuple[Tuple[int, ...], ...]:
    return tuple(tuple(g.shape[indices.ndim:]) for g in cotangents)


def _sorted_slots_payload(ids, cotangents, num_rows, sorted_slots,
                          trailing=None, real=None, received: str = ""):
    """Step A for flat ``ids`` [N] and cotangents ``[N]`` / ``[N, F]``:
    ``(bounds, sorted ids, payload)``, the payload's columns in the order
    of :func:`~dmlc_tpu.ops.sorted_walk.column_starts`. ``trailing``: the
    tables' where the caller knows them; one table's cotangent wider than
    its table is lines already (``table_rows(lines=True)``). Slots whose
    ``real`` [N] is false take the sort's sentinel (in ``sorted_slots``
    they have it already), no block's walk reaches them, and the runs of
    sorted slots that hold nothing else are not permuted
    (:func:`~dmlc_tpu.ops.sorted_walk.permute_live`; counted in
    ``table_slot_groups{op="update"}``). ``received``
    (``table_gather.table_cols_kernel`` says of whom): ``ids`` are rows of
    a chip's shard of a dealt table, the row one past the shard where the
    chip has nothing to add: those are the slots that are not real, for an
    ``"owner"`` on the line side, for a ``"shard"`` on both
    (``op="owner_update" | "shard_update"``)."""
    trailing = trailing or _trailing(cotangents, ids)
    lines = sw.slot_layout(sum(sw.widths(trailing))) == "lines"
    if received == "shard" or (received and lines):
        real = ids < num_rows
    # (the slots along axis 0 of lines, along axis 1 of columns)
    with jax.named_scope(sw.UPDATE_PERMUTE_SCOPE):
        if _trailing(cotangents, ids) != trailing:
            (slots,) = cotangents
            assert lines and slots.shape[1] == sw.line_lanes(trailing[0][0])
        else:
            slots = (sw.lines_of_rows(cotangents, trailing) if lines else
                     sw.cols_of_rows(cotangents, trailing))
    if real is not None and sorted_slots is None:
        # an id outside the tables takes the sort's sentinel
        with jax.named_scope(sw.SORT_SCOPE):
            ids = jnp.where(real, ids, num_rows)
    if sorted_slots is None:
        sorted_slots = sw.sort_slots(ids, num_rows)
    bounds, ids_s, perm = sorted_slots
    with jax.named_scope(sw.UPDATE_PERMUTE_SCOPE):
        live = None
        if real is not None:
            live = sw.live_sorted_slots(bounds, sw.CHUNK_SLOTS)
            _telemetry.count_table_slot_groups(
                received + "_update" if received else "update",
                sw.permute_groups(perm.shape[0]))
        if live is not None and received and lines:
            slots = sw.row_major_lines(slots)
        return bounds, ids_s, (sw.permuted_lines if lines else
                               sw.permuted_payload)(slots, perm, live)


def table_grad_kernel(ids: jax.Array, cotangents: Tuple[jax.Array, ...],
                      num_rows: int, sorted_slots=None, real=None,
                      received: str = "") -> Tuple[jax.Array, ...]:
    """Steps A and B for flat ``ids`` [N] and cotangents ``[N]`` or
    ``[N, F]``: a ``[num_rows]`` or ``[num_rows, F]`` gradient a table.
    ``sorted_slots`` is ``sorted_walk.sort_slots`` of these very ``ids``
    where the forward has made it already (ops/table_gather.py): nothing is
    sorted again. Slots whose ``real`` [N] is false add nothing, whatever
    their cotangent, nor does the padding of the slots a chip of a dealt
    table ``received``: :func:`_sorted_slots_payload`."""
    trailing = _trailing(cotangents, ids)
    walked = _sorted_slots_payload(ids, cotangents, num_rows, sorted_slots,
                                   real=real, received=received)
    with jax.named_scope(sw.UPDATE_KERNEL_SCOPE):
        out = grad_scatter_pallas(*walked, num_rows=num_rows,
                                  trailing=trailing)
    return tuple(d.T if tail else d for d, tail in zip(out, trailing))


def table_update_kernel(ids: jax.Array, cotangents: Tuple[jax.Array, ...],
                        leaves: Tuple[jax.Array, ...],
                        scalars: Tuple[jax.Array, ...], epilogue: Epilogue,
                        sorted_slots=None, real=None,
                        received: str = "") -> Tuple[jax.Array, ...]:
    """Step A and the kernel with ``epilogue`` for flat ``ids`` [N]:
    ``leaves`` are the epilogue's of every table in turn (Adam's ``p, m,
    n``, AdaGrad's ``W, G``), ``[num_rows]`` or ``[num_rows, F]``, and come
    back updated in place; ``scalars`` is ``(bias,)`` or ``()``. The kernel
    takes and gives the tables lane-major; ``x.T`` is a bitcast of how XLA
    keeps a narrow float32 table on a TPU, both ways. ``sorted_slots``,
    ``real`` and ``received`` as in :func:`table_grad_kernel`."""
    # (the tables' own shapes: a cotangent may come as lines)
    trailing = tuple(tuple(x.shape[1:]) for x in leaves[::epilogue.leaves])
    tails = [tail for tail in trailing for _ in range(epilogue.leaves)]
    num_rows = leaves[0].shape[0]
    walked = _sorted_slots_payload(ids, cotangents, num_rows, sorted_slots,
                                   trailing, real, received)
    lane_major = tuple(x.T if tail else x for x, tail in zip(leaves, tails))
    with jax.named_scope(sw.UPDATE_KERNEL_SCOPE):
        out = grad_scatter_pallas(
            *walked, *scalars, *lane_major, num_rows=num_rows,
            trailing=trailing, epilogue=epilogue)
    return tuple(x.T if tail else x for x, tail in zip(out, tails))


def _on_owners(deal, indices, cotangents, real, exchange, apply):
    """One chip's gradient or update on a table dealt by rows, inside
    ``shard_map`` over ``deal.axis``: ``apply(rows of this shard [M],
    cotangents [M] / [M, F], sorted_slots, received)`` of the slots this
    chip owns, whose cotangent rows their chips send it
    (ops/table_exchange.py; the forward's ``exchange``, or one opened here
    from ``real``), or, on a step whose buckets overflow, of every chip's
    slots all-gathered, the others' lying one past the shard. ``indices``
    [...] and cotangents ``[...]`` / ``[..., F]`` are this chip's. On a
    deal that is not ``even`` there are no buckets: every chip's slots,
    always (``exchange`` is then the forward's
    :class:`~dmlc_tpu.ops.table_exchange.Slots`, with its sort)."""
    from dmlc_tpu.ops import table_exchange as tx

    trailing = _trailing(cotangents, indices)
    ids = indices.reshape(-1)
    with jax.named_scope(sw.UPDATE_PERMUTE_SCOPE):
        cols = sw.cols_of_rows(tuple(
            g.reshape((-1,) + tail) for g, tail in zip(cotangents, trailing)),
            trailing)
    if not deal.even:
        with jax.named_scope(tx.EXCHANGE_SCOPE):
            slots = exchange or tx.open_slots(deal, indices, real)
            got = tx.slots_to_all(deal, cols, tx.slot_columns(indices))
        with jax.named_scope(sw.UPDATE_PERMUTE_SCOPE):
            rows = sw.rows_of_cols(got, trailing)
        return apply(slots.rows, rows, slots.sorted_slots, "shard")
    if exchange is None:
        with jax.named_scope(tx.EXCHANGE_SCOPE):
            exchange = tx.open_exchange(deal, indices, real)

    def owned():
        with jax.named_scope(tx.EXCHANGE_SCOPE):
            got = tx.to_owners(deal, exchange.buckets, cols)
        with jax.named_scope(sw.UPDATE_PERMUTE_SCOPE):
            rows = sw.rows_of_cols(got, trailing)
        return apply(exchange.received, rows, exchange.sorted_slots, "owner")

    def whole():
        with jax.named_scope(tx.EXCHANGE_SCOPE):
            stacked = jax.lax.all_gather(cols, deal.axis)   # [shards, W, n]
            got = jnp.moveaxis(stacked, 0, 1).reshape(cols.shape[0], -1)
            slots = deal.local_slots(ids)
        with jax.named_scope(sw.UPDATE_PERMUTE_SCOPE):
            rows = sw.rows_of_cols(got, trailing)
        return apply(slots, rows, None, "")

    return jax.lax.cond(exchange.buckets.overflow, whole, owned)


def table_grad_xla(ids: jax.Array, cotangents: Tuple[jax.Array, ...],
                   num_rows: int) -> Tuple[jax.Array, ...]:
    """What autodiff makes of the gathers: XLA's scatter-adds (``ids`` of
    any shape [...], cotangents [...] or [..., F])."""
    return tuple(
        jnp.zeros((num_rows,) + g.shape[ids.ndim:], g.dtype).at[ids].add(g)
        for g in cotangents)


def _counted_route(indices, cotangents, num_rows, deal=None, trailing=None):
    """``(route, trailing)`` of :func:`grad_scatter_route` for these
    cotangents (of tables of ``trailing``, where the caller knows them),
    counted in ``grad_scatter_route{route=, width=, collective=}``. A chip
    of a ``deal`` takes the route of one chip with its shard's rows and the
    slots of all (the most it can be handed); ``collective`` says what
    crosses the chips: ``none``, a deal's ``owned_rows``, or ``all_slots``
    of a deal that is not ``even``."""
    trailing = trailing or _trailing(cotangents, indices)
    check(all(len(tail) <= 1 for tail in trailing),
          "dense_table_grad: a table is [rows] or [rows, F]")
    width = sum(sw.widths(trailing))
    route = grad_scatter_route(
        num_rows, indices.size * (deal.shards if deal else 1), width,
        cotangents[0].dtype, len(cotangents))
    _telemetry.REGISTRY.counter(
        _telemetry.GRAD_SCATTER_ROUTE_METRIC, route=route, width=str(width),
        collective="none" if deal is None else
        "owned_rows" if deal.even else "all_slots").inc(1)
    if route == "kernel":
        _telemetry.REGISTRY.counter(
            _telemetry.TABLE_SLOT_LAYOUT_METRIC, op="scatter",
            layout=sw.slot_layout(width)).inc(1)
    return route, trailing


def dense_table_grad(indices: jax.Array, cotangents: Tuple[jax.Array, ...],
                     num_rows: int, sorted_slots=None, deal=None, real=None,
                     ) -> Tuple[jax.Array, ...]:
    """One dense gradient a table (``[num_rows]`` or ``[num_rows, F]``):
    the transpose of gathering rows ``indices`` [...] of tables that share
    an id space, given the cotangents ``[...]`` / ``[..., F]`` of the
    gathered rows. Called while the backward is traced: picks the route
    (:func:`grad_scatter_route`) and counts it in
    ``grad_scatter_route{route=, width=, collective=}``, ``width`` the
    columns of all the tables together. ``sorted_slots`` is
    ``sorted_walk.sort_slots`` of the flat ``indices`` where the forward
    kept it: the kernel route then sorts nothing.

    With a ``deal`` (:class:`dmlc_tpu.parallel.mesh.RowDeal`) the tables
    are *dealt by rows* and the call is made inside ``shard_map`` over
    ``deal.axis``: ``num_rows`` is this chip's shard's, ``indices`` and
    ``cotangents`` this chip's slots'. Every slot's cotangent row goes to
    the chip that owns its id and the owner adds what it received into the
    gradient of its shard as one chip would (``collective="owned_rows"``;
    ops/table_exchange.py, which also says what a step does whose buckets
    overflow). ``sorted_slots`` is then the forward's
    :class:`~dmlc_tpu.ops.table_exchange.Exchange`; without it the buckets
    are made here, and slots whose ``real`` [...] is false (an ELL batch's
    padding) are not sent: their cotangent must be zero. On a deal that is
    not ``even`` (:class:`~dmlc_tpu.parallel.mesh.RowRanges`) every chip's
    cotangent rows are all-gathered instead and a chip adds the ones it
    owns (``collective="all_slots"``; ``sorted_slots`` is that road's
    :class:`~dmlc_tpu.ops.table_exchange.Slots`).

    Without a deal, slots whose ``real`` [...] is false add nothing on the
    kernel route, whatever id and cotangent they carry: they take the
    sort's sentinel (``sorted_slots`` made with the same ``real`` has it
    so already), the kernel's walk stops before them, and the runs of
    sorted slots that hold nothing else are not permuted
    (:func:`~dmlc_tpu.ops.sorted_walk.permute_live`). XLA's route adds
    every slot's cotangent at the id it carries."""
    route, trailing = _counted_route(indices, cotangents, num_rows, deal)
    if deal is not None:
        def grad(ids, cots, sorted_slots, received):
            if route == "xla":     # a slot one past the shard is dropped
                return table_grad_xla(ids, cots, num_rows)
            return table_grad_kernel(ids, cots, num_rows,
                                     sorted_slots=sorted_slots,
                                     received=received)

        return _on_owners(deal, indices, cotangents, real, sorted_slots,
                          grad)
    if route == "xla":
        return table_grad_xla(indices, cotangents, num_rows)
    return table_grad_kernel(
        indices.reshape(-1),
        tuple(g.reshape((-1,) + tail)
              for g, tail in zip(cotangents, trailing)),
        num_rows, real=None if real is None else real.reshape(-1),
        sorted_slots=sorted_slots)


def fused_table_update(indices: jax.Array, cotangents: Tuple[jax.Array, ...],
                       state: Tuple[Tuple[jax.Array, ...], ...],
                       bias: Optional[jax.Array], epilogue: Epilogue,
                       sorted_slots=None, deal=None, real=None,
                       ) -> Tuple[Tuple[jax.Array, ...], ...]:
    """The optimizer's step on tables that share an id space, without
    their dense gradient: ``state`` holds the epilogue's leaves a table
    (``(p, m, n)`` for :class:`AdamEpilogue`, ``(W, G)`` for
    :class:`AdaGradEpilogue`; ``[num_rows]`` or ``[num_rows, F]``),
    ``cotangents`` the gradient with respect to the *gathered rows*
    ``indices`` [...] of each (``[...]`` / ``[..., F]``; of one table read
    by ``table_rows(lines=True)``, the lines ``[..., lanes]`` as they came),
    ``bias`` is
    ``epilogue.bias(count)``, or ``None`` for an epilogue with no scalar.
    The kernel builds every block of the gradient in VMEM and finishes the
    step on that block there (:func:`grad_scatter_pallas`); the results
    take the operands' buffers where the caller donates them.

    For callers on the route ``"kernel"`` of :func:`grad_scatter_route`
    only, which is checked, and counted in ``grad_scatter_route`` as
    :func:`dense_table_grad` counts it: a gradient that XLA scatters has to
    exist. ``sorted_slots`` and ``real`` as there.

    With a ``deal`` the call is made inside ``shard_map`` over
    ``deal.axis``, as :func:`dense_table_grad`'s, ``state`` holding this
    chip's shards: the kernel finishes the step on the shard from the
    slots this chip received (``collective="owned_rows"``; the route is
    that of one chip with the shard's rows and the slots of all), or, on a
    deal that is not ``even``, from every chip's slots, of which the ones
    it does not own take the sort's sentinel (``"all_slots"``). No
    gradient of the shard's size is made on any of these roads."""
    num_rows = state[0][0].shape[0]
    route, trailing = _counted_route(
        indices, cotangents, num_rows, deal,
        tuple(tuple(table[0].shape[1:]) for table in state))
    check(route == "kernel",
          f"fused_table_update: the route is {route!r}; build the dense "
          "gradient (dense_table_grad)")
    scalars = () if bias is None else (bias,)
    check(len(scalars) == (epilogue.scalars > 0)
          and all(len(table) == epilogue.leaves for table in state),
          "fused_table_update: the state is not this epilogue's")

    leaves = tuple(x for table in state for x in table)
    if deal is not None:
        out = _on_owners(
            deal, indices, cotangents, real, sorted_slots,
            lambda ids, cots, sorted_slots, received: table_update_kernel(
                ids, cots, leaves, scalars, epilogue,
                sorted_slots=sorted_slots, received=received))
    else:
        out = table_update_kernel(
            indices.reshape(-1),
            tuple(g.reshape((-1,) + g.shape[indices.ndim:])
                  for g in cotangents),
            leaves, scalars, epilogue,
            real=None if real is None else real.reshape(-1),
            sorted_slots=sorted_slots)
    per = epilogue.leaves
    return tuple(out[per * i:per * (i + 1)] for i in range(len(trailing)))
