"""The sorted walk: what ``ops/grad_scatter.py`` (the backward),
``ops/table_gather.py`` (the forward) and ``ops/slot_rows.py`` (a ragged
batch's row sums) stand on, and the only module that knows

1. the slot layout: blocks of ids, chunks of sorted slots, every table's
   columns as rows of one lane-major array, float32 as three bfloat16 parts;
2. the way to sorted order and back, outside a kernel (:func:`sort_slots`,
   :func:`chunk_bounds`, :func:`sorted_payload`, XLA's permutes, and
   :func:`permute_live`, which does not move a batch's padding);
3. the walk inside a kernel (:class:`Walk`): blocks and chunks in step, a
   chunk's DMAs double-buffered;
4. the tile window (:func:`tile_window`, :func:`ladder`,
   :func:`on_first_rung_that_holds`): the tiles of a block a chunk can
   name, which a (block, chunk) pair of either kernel is contracted over,
   and the count of such tile-products outside a kernel
   (:func:`tile_counts`).

A kernel supplies what differs: a chunk's DMAs, what a block does around
its chunks, what a pair contracts, what happens when the walk leaves a
chunk. docs/ops.md, "The sorted walk", is the account.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# table ids a block, sorted slots a chunk: sized on a v5e at the KDD12 shape
# (PERF.md §6, PR 25: 4,096 x 128 is the fastest of nine pairs at 1,048,576
# slots and within 0.8 ms of the fastest at 262,144). The scatter's compares
# and MXU rows are (blocks + N / C) * C * T, its grid steps rows / T.
BLOCK_IDS = 4096
CHUNK_SLOTS = 128
# bfloat16 packs 16 rows a tile: each of the three splits is padded to it
SPLIT_ROWS = 16
# payloads up to this width are permuted in place, as lane-major columns
# (the FM's 9: 6.4 ms a step); wider ones as row-major rows (permute_columns)
PERMUTE_BY_COLUMNS = 16
# the ``jax.named_scope`` of each piece of the walk on a table, inside
# whatever scope the caller is in (``fm_gather``, ``ffm_optimizer``,
# ``table_exchange``, a ``cond``'s branch), on every route that takes the
# kernels: a device trace reads the pieces by these names whatever XLA
# numbered their fusions (docs/observability.md; the benchmark's
# ``walk_*_device_ms``). Metadata only. An operation is in one of them or in
# none: the permutes are shared, so their callers name them
# (ops/table_gather.py, ops/grad_scatter.py), and the row sums' two kernels
# (ops/slot_rows.py) stay under their caller's ``fm_rowsum`` alone.
SORT_SCOPE = "walk_sort"                      # sort_slots, presorted_slots,
#                                               the forward's inverse
GATHER_KERNEL_SCOPE = "walk_gather_kernel"    # table_gather's pallas_call
GATHER_PERMUTE_SCOPE = "walk_gather_permute"  # sorted rows to batch order
UPDATE_PERMUTE_SCOPE = "walk_update_permute"  # cotangent rows to sorted order
UPDATE_KERNEL_SCOPE = "walk_update_kernel"    # grad_scatter's pallas_call
WALK_SCOPES = (SORT_SCOPE, GATHER_KERNEL_SCOPE, GATHER_PERMUTE_SCOPE,
               UPDATE_PERMUTE_SCOPE, UPDATE_KERNEL_SCOPE)


def slot_layout(width: int) -> str:
    """How a kernel's slot side is laid for a payload of ``width`` columns,
    which is how XLA's gather permutes it fastest (:func:`permute_columns`):
    ``"columns"``, lane-major ``[R, Np]`` with the slots on the lanes, up
    to ``PERMUTE_BY_COLUMNS``; ``"lines"`` above, row-major ``[Np,
    line_lanes(width)]`` float32 with a slot's columns on the first lanes
    of its own line and zeros behind them. A kernel on the line side moves
    a chunk as its ``[C, lanes]`` lines and transposes it in VMEM: the
    gather's operand and result are the kernels' own, with no pass of
    XLA's between (docs/ops.md, "The two layouts of a kernel's slot
    side")."""
    return "lines" if width > PERMUTE_BY_COLUMNS else "columns"


def line_lanes(width: int) -> int:
    """The lanes of a slot's line: ``width`` in whole float32 tile rows."""
    return round_up(width, 128)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def widths(trailing) -> Tuple[int, ...]:
    """Columns a table: 1 for a ``[rows]`` table, F for ``[rows, F]``."""
    return tuple(tail[0] if tail else 1 for tail in trailing)


def column_starts(trailing) -> Tuple[int, ...]:
    """The payload row at which each table's columns start. The tables are
    laid widest first (ties in their own order), so that a wide table's
    rows start on a sublane tile: an FM's ``(w, v)`` puts ``v`` in rows
    0..F-1 and ``w`` in row F."""
    each = widths(trailing)
    order = sorted(range(len(each)), key=lambda i: -each[i])
    starts, at = [0] * len(each), 0
    for i in order:
        starts[i], at = at, at + each[i]
    return tuple(starts)


def cols_of_rows(rows: Tuple[jax.Array, ...], trailing) -> jax.Array:
    """``[width, N]``: the slots' rows ``[N]`` / ``[N, F]`` of every table
    lane-major, one row a column in the order of :func:`column_starts`."""
    starts = column_starts(trailing)
    by_start = sorted(range(len(rows)), key=lambda i: starts[i])
    return jnp.concatenate([
        rows[i].T if trailing[i] else rows[i][None, :] for i in by_start])


def rows_of_cols(cols: jax.Array, trailing) -> Tuple[jax.Array, ...]:
    """:func:`cols_of_rows` back: ``[N]`` / ``[N, F]`` rows a table."""
    return tuple(
        cols[at:at + tail[0]].T if tail else cols[at]
        for tail, at in zip(trailing, column_starts(trailing)))


def lines_of_rows(rows: Tuple[jax.Array, ...], trailing) -> jax.Array:
    """``[N, line_lanes(width)]``: the slots' rows ``[N]`` / ``[N, F]`` of
    every table as lines, lane ``c`` holding column ``c`` in the order of
    :func:`column_starts`, zeros past the columns."""
    starts = column_starts(trailing)
    by_start = sorted(range(len(rows)), key=lambda i: starts[i])
    lines = jnp.concatenate([
        rows[i] if trailing[i] else rows[i][:, None] for i in by_start],
        axis=1)
    return jnp.pad(
        lines, ((0, 0), (0, line_lanes(lines.shape[1]) - lines.shape[1])))


def lines_of_cols(cols: jax.Array) -> jax.Array:
    """``[N, line_lanes(width)]``: lane-major ``cols`` [width, N] as lines,
    one transposition and the zeros behind the columns."""
    width = cols.shape[0]
    return jnp.pad(cols.T, ((0, 0), (0, line_lanes(width) - width)))


def rows_of_lines(lines: jax.Array, trailing) -> Tuple[jax.Array, ...]:
    """:func:`lines_of_rows` back: ``[N]`` / ``[N, F]`` rows a table."""
    return tuple(
        lines[:, at:at + tail[0]] if tail else lines[:, at]
        for tail, at in zip(trailing, column_starts(trailing)))


def bfloat16_part(x: jax.Array) -> jax.Array:
    """``x`` with the low 16 bits of its float32 pattern cleared: the
    bfloat16 value next towards zero, still as float32. By bits and not by
    a round trip through ``astype``, which a compiler allowed excess
    precision may drop."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def bfloat16_parts(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(hi, mid, lo)`` with ``x = hi + mid + lo`` exactly, each a
    bfloat16 value held as float32: three bfloat16 significands hold
    float32's."""
    hi = bfloat16_part(x)
    mid = bfloat16_part(x - hi)
    return hi, mid, x - hi - mid


def split_payload(cols: jax.Array, num_slots: int) -> jax.Array:
    """``cols`` [width, n] float32 as a kernel's payload: ``[3 * R,
    num_slots]`` bfloat16 with R = width rounded up to ``SPLIT_ROWS``, row
    ``s * R + c`` holding part ``s`` (hi, mid, lo) of column ``c``; zeros
    past the columns and past the ``n`` slots."""
    width, n = cols.shape
    cols = jnp.pad(cols, ((0, round_up(width, SPLIT_ROWS) - width),
                          (0, num_slots - n)))
    return jnp.concatenate(bfloat16_parts(cols)).astype(jnp.bfloat16)


def _in_whole_chunks(ids: jax.Array, num_rows: int, block_ids: int,
                     chunk_slots: int) -> Tuple[jax.Array, int]:
    """``(ids [Np], sentinel)``: ids outside ``[0, num_rows)`` and the
    padding to whole chunks of ``chunk_slots`` take the sentinel ``blocks *
    block_ids``, which sorts last and reaches no block."""
    sentinel = round_up(num_rows, block_ids)
    ids = jnp.where((ids < 0) | (ids >= num_rows), sentinel, ids)
    pad = round_up(ids.shape[0], chunk_slots) - ids.shape[0]
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=sentinel)
    return ids, sentinel


def chunk_bounds(ids_sorted: jax.Array, chunk_slots: int,
                 sentinel: int) -> jax.Array:
    """``[2, chunks + 1]``: the first and the last id of every chunk of
    ``chunk_slots`` sorted ids, and one sentinel chunk."""
    per_chunk = ids_sorted.reshape(-1, chunk_slots)
    return jnp.pad(jnp.stack([per_chunk[:, 0], per_chunk[:, -1]]),
                   ((0, 0), (0, 1)), constant_values=sentinel)


def sort_slots(ids: jax.Array, num_rows: int, block_ids: int = BLOCK_IDS,
               chunk_slots: int = CHUNK_SLOTS,
               real: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``ids`` [N] int32 -> ``(bounds [2, chunks + 1] int32, sorted ids [1,
    Np] int32, permutation [Np] int32)`` with Np = N rounded up to whole
    chunks of ``chunk_slots``. ``bounds[0, j]`` / ``bounds[1, j]`` are the
    first / last id of chunk ``j`` (one sentinel chunk appended), which is
    all a kernel needs to walk blocks and chunks in step; sorted slot ``s``
    is slot ``permutation[s]`` of the batch (the padding's positions are N
    and up). Negative ids count from the end as in ``jnp.take``; ids
    outside the table, the padding and the slots whose ``real`` [N] is
    false (a batch's own padding, whatever id it carries) take the
    sentinel: the forward's kernel reads them as zeros and the backward's
    stops before them. The forward makes the sort and hands it to the
    backward, which then sorts nothing.

    Two operands, 0.9 ms at 1,048,576 slots on a v5e; what travels with the
    slots is permuted afterwards by one gather (one sort of id + 9 operands
    runs in 7.3 ms and compiles for 99 s, one two-operand sort batched over
    the columns takes 39 ms: PERF.md §6, PR 25)."""
    with jax.named_scope(SORT_SCOPE):
        ids = ids.astype(jnp.int32)
        ids = jnp.where(ids < 0, ids + num_rows, ids)
        if real is not None:
            ids = jnp.where(real, ids, num_rows)   # outside: the sentinel
        ids, sentinel = _in_whole_chunks(ids, num_rows, block_ids,
                                         chunk_slots)
        ids_s, perm = jax.lax.sort(
            (ids, jax.lax.iota(jnp.int32, ids.shape[0])), num_keys=1,
            is_stable=False)
        return (chunk_bounds(ids_s, chunk_slots, sentinel), ids_s[None, :],
                perm)


def presorted_slots(ids: jax.Array, num_rows: int, block_ids: int,
                    chunk_slots: int) -> Tuple[jax.Array, jax.Array]:
    """``(bounds [2, chunks + 1], ids [1, Np])`` of :func:`sort_slots` for
    ``ids`` [N] that are ascending already (a ragged batch's row ids):
    nothing is sorted and no negative id counts from the end; ids outside
    ``[0, num_rows)``, which must come last, and the padding take the
    sentinel."""
    with jax.named_scope(SORT_SCOPE):
        ids, sentinel = _in_whole_chunks(ids.astype(jnp.int32), num_rows,
                                         block_ids, chunk_slots)
        return chunk_bounds(ids, chunk_slots, sentinel), ids[None, :]


def permute_columns(cols: jax.Array, index: jax.Array) -> jax.Array:
    """``cols[:, index]`` for ``cols`` [width, M] float32 and a
    permutation's ``index`` [n] (in bounds, no repeats): one XLA gather,
    7.5 ms at 1,048,576 slots of 9 columns on a v5e. A payload wider than
    ``PERMUTE_BY_COLUMNS`` is permuted as rows of whole 128-lane lines:
    XLA's gather moves a slot's 44 columns in 12 ns as one row-major row
    and in 57 ns as 44 strided words of the lane-major columns (13.8
    against 59.4 ms at 1,048,576 slots with both transposes; PERF.md §6,
    PR 26)."""
    width = cols.shape[0]
    if slot_layout(width) == "columns":
        return cols.at[:, index].get(mode="promise_in_bounds",
                                     unique_indices=True)
    return permute_lines(lines_of_cols(cols), index).T[:width]


def permute_lines(lines: jax.Array, index: jax.Array) -> jax.Array:
    """``lines[index]`` for ``lines`` [M, lanes] float32
    (:func:`line_lanes`) and a permutation's ``index`` [n] (in bounds, no
    repeats): XLA's gather of whole lines, 9.5 ns a line on a v5e. (The
    barriers keep XLA from moving
    a producer's padding or a consumer's slice past the gather, which would
    leave it rows of the payload's width again.)"""
    lines = jax.lax.optimization_barrier(lines)
    return jax.lax.optimization_barrier(
        lines.at[index].get(mode="promise_in_bounds", unique_indices=True))


def row_major_lines(lines: jax.Array) -> jax.Array:
    """``lines`` [M, lanes] (M in whole sublane tiles of 8) for
    :func:`permute_live` where XLA makes them of lane-major columns (the
    cotangent columns an owner of a dealt table received): it lays such
    lines column-major, the columns padded and not moved, and leaves the
    transposition to whoever reads them, which is every run's gather in
    its own conditional: 0.5 ms a live run for 0.19 (PERF.md §6, PR 51).
    A ``[M / 8, 8, lanes]`` view of them behind a barrier is a bitcast of
    row-major lines only, so the lines are transposed once, before the
    runs."""
    return jax.lax.optimization_barrier(
        lines.reshape(-1, 8, lines.shape[1])).reshape(lines.shape)


# a permute that may skip its tail cuts its indices into this many equal
# runs where they divide (an ELL batch's 16 columns of slots)
PERMUTE_GROUPS = 16


def permute_groups(n: int) -> int:
    """The equal runs :func:`permute_live` cuts ``n`` indices into."""
    return math.gcd(n, PERMUTE_GROUPS)


def live_sorted_slots(bounds: jax.Array, chunk_slots: int) -> jax.Array:
    """The sorted slots, in whole chunks, that may carry an id under the
    sentinel (``bounds`` of :func:`sort_slots`): every one behind them
    carries the sentinel."""
    return chunk_slots * jnp.sum(bounds[0, :-1] < bounds[0, -1],
                                 dtype=jnp.int32)


def live_batch_slots(real: jax.Array) -> jax.Array:
    """One past the last slot of flat ``real`` [N] that is true: an ELL
    batch handed K-major (column after column of its rows' slots) has its
    padding behind it."""
    at = jax.lax.iota(jnp.int32, real.shape[0])
    return jnp.max(jnp.where(real, at + 1, 0))


def live_runs(real: jax.Array) -> jax.Array:
    """``[permute_groups(N)]`` bool: whether each of the equal runs
    :func:`permute_live` cuts an index of flat ``real`` [N]'s length into
    holds a slot that is true. For slots whose padding is no one tail: an
    owner's received slots are a bucket a worker, each real up to its own
    count, so the dead runs are the tail of every bucket."""
    return jnp.any(real.reshape(permute_groups(real.shape[0]), -1), axis=1)


def permute_live(slots: jax.Array, index: jax.Array, live: jax.Array,
                 layout: str) -> jax.Array:
    """:func:`permute_lines` (``layout="lines"``, ``slots`` [M, lanes]) or
    :func:`permute_columns` (``"columns"``, ``slots`` [width, M]) for an
    ``index`` [n] of which only the first ``live`` (an int32 scalar, found
    on the device from the batch) name anything a reader needs: ``index``
    is cut into ``PERMUTE_GROUPS`` equal runs (one run where ``n`` does not
    divide), a run that starts at or past ``live`` is not gathered and
    yields zeros, every other run is XLA's gather of it exactly as the
    whole permute's. XLA's gather is bound by its count of indices (9.5 ns
    a line, 6.1 ns an index of 9 columns on a v5e), so the time falls with
    the runs skipped; an index with nothing to skip (``live >= n``) pays
    for the grouping alone (PERF.md §6, PR 49). ``live`` may also say run
    by run which to gather (:func:`live_runs`, bool ``[runs]``), where what
    nobody reads is not one tail: the line side gathers exactly those runs,
    the column side every run from the first live one to the last (a chip
    of a table laid in ranges owns neighbouring fields: PR 54).

    How the runs land in one result differs by what XLA does with each
    side. *Lines*: a ``cond`` a run carries the result through and writes
    its run into it in place, over a buffer nobody has filled
    (``lax.empty``): a run's 32 MB leave the gather in fast memory and
    reach HBM once (branches that return their run are concatenated by a
    second pass over the result: 9.85 ms for 7.6 with 11 runs live).
    *Columns*: the gather of ``[width, M]`` reads 6.9 ns an index only
    while its operand lies in fast memory, where XLA prefetches the whole
    permute's; an operand that enters a conditional stays in HBM (22 ns).
    So one ``switch`` on the count of runs to gather makes its own copy of
    the columns inside the branch taken (0.1 ms) and gathers that count of
    runs at once."""
    n = index.shape[0]
    groups = permute_groups(n)
    run = n // groups
    first = None
    if jnp.ndim(live):
        assert live.shape == (groups,)
        gathered = lambda g: live[g]                            # noqa: E731
        if layout == "columns":
            # the runs [first, first + count) brought to the front of the
            # index, gathered as a count of runs and put back behind
            at = jax.lax.iota(jnp.int32, groups)
            first = jnp.min(jnp.where(live, at, groups))
            count = jnp.max(jnp.where(live, at + 1 - first, 0))
            index = jax.lax.dynamic_slice(
                jnp.concatenate([index, index]), (first * run,), (n,))
    else:
        count = jnp.clip(-(-live // run), 0, groups).astype(jnp.int32)
        gathered = lambda g: g < count                          # noqa: E731
    if layout == "lines":
        out = jax.lax.empty((n, slots.shape[1]), slots.dtype)
        for g in range(groups):
            out = jax.lax.cond(
                gathered(g),
                lambda out, at: jax.lax.dynamic_update_slice(
                    out, permute_lines(slots, at), (g * run, 0)),
                lambda out, at: jax.lax.dynamic_update_slice(
                    out, jnp.zeros((run, out.shape[1]), out.dtype),
                    (g * run, 0)),
                out, index[g * run:(g + 1) * run])
        return out

    def gather(runs: int):
        def branch(cols, index, taken):
            if not runs:
                return jnp.zeros((cols.shape[0], n), cols.dtype)
            # (``taken`` holds in this branch; XLA cannot tell, and makes
            # the copy)
            got = permute_columns(jnp.where(taken, cols, 0.0),
                                  index[:runs * run])
            return jnp.pad(got, ((0, 0), (0, n - runs * run)))
        return branch

    out = jax.lax.switch(count, [gather(r) for r in range(groups + 1)],
                         slots, index, count > 0)
    if first is None:
        return out
    return jax.lax.dynamic_slice(
        jnp.pad(out, ((0, 0), (n, 0))), (0, n - first * run), out.shape)


# XLA's gather of lane-major columns falls off a cliff where its operand,
# the columns padded to whole 8-row tiles, passes about 100 MB: [9, N]
# float32 takes 10.6 ms at N = 1,572,864 (101 MB) and 43.6 at 1,929,216
# (123 MB), [8, N] 10.3 there (62 MB); a ragged batch of 65,536 rows is
# past it (benchmarks/bench_slot_rows.py --permute; PERF.md §6, PR 37).
# Over this size the columns are permuted eight at a time
GATHER_OPERAND_BYTES = 96 << 20


def _gather_operand_bytes(width: int, n: int) -> int:
    return 4 * round_up(width, 8) * n


def permutes_in_groups(width: int, n: int) -> bool:
    """Whether ``[width, n]`` float32 columns are too large an operand for
    :func:`permute_columns`' one gather (``GATHER_OPERAND_BYTES``)."""
    return (8 < width <= PERMUTE_BY_COLUMNS
            and _gather_operand_bytes(width, n) > GATHER_OPERAND_BYTES)


def scatter_columns_by_sort(cols: jax.Array, index: jax.Array) -> jax.Array:
    """``out[:, index[s]] = cols[:, s]`` for a permutation ``index`` [n] of
    ``cols`` [width, n]'s columns: one two-operand sort on ``index`` a
    column (the keys are distinct, so it need not be stable), 2.2 ms a
    column at 1,929,216 slots. The inverse of ``permute_columns(cols,
    index)``. The columns go through one sort in a loop: XLA merges sorts
    that share their key into one sort of every operand, which runs in 1.2
    ms a column and compiles for 90 s at 9 columns of 1,929,216 slots,
    where the loop's compiles in 9 (PERF.md §6, PR 37 and PR 25)."""
    return jax.lax.map(
        lambda col: jax.lax.sort((index, col), num_keys=1,
                                 is_stable=False)[1], cols)


def inverse_permutation(perm: jax.Array) -> jax.Array:
    """``inverse[perm[s]] = s``, by a sort (a scatter walks its updates)."""
    return jax.lax.sort((perm, jax.lax.iota(jnp.int32, perm.shape[0])),
                        num_keys=1, is_stable=False)[1]


def permute_wide_columns(cols: jax.Array, index: jax.Array,
                         inverse: jax.Array) -> jax.Array:
    """:func:`permute_columns` for columns past ``GATHER_OPERAND_BYTES``:
    ``cols[:, index]`` with ``index`` [n] a whole permutation of ``cols``
    [width, n]'s columns and ``inverse`` its inverse. Eight columns (one
    row of tiles) at a time by the gather, 10.3 ms at 1,929,216 slots; a
    group of one column, or one still past the cliff, by
    :func:`scatter_columns_by_sort` on ``inverse``."""
    out = []
    for at in range(0, cols.shape[0], 8):
        group = cols[at:at + 8]
        if group.shape[0] == 1 or _gather_operand_bytes(
                group.shape[0], group.shape[1]) > GATHER_OPERAND_BYTES:
            out.append(scatter_columns_by_sort(group, inverse))
        else:
            out.append(permute_columns(group, index))
    return jnp.concatenate(out)


def permute_whole(cols: jax.Array, index: jax.Array) -> jax.Array:
    """``cols[:, index]`` for ``index`` [n] a whole permutation of ``cols``
    [width, n]'s columns, by whichever of the two permutes its size takes;
    the inverse is made here where the wide one needs it."""
    if permutes_in_groups(*cols.shape):
        return permute_wide_columns(cols, index, inverse_permutation(index))
    return permute_columns(cols, index)


def permuted_payload(cols: jax.Array, perm: jax.Array,
                     live: Optional[jax.Array] = None) -> jax.Array:
    """``cols`` [width, N] (the cotangent columns of every table, one row a
    column) in the order ``perm`` [Np] of :func:`sort_slots`, as the
    backward's kernel takes a payload of that width (:func:`slot_layout`):
    as :func:`split_payload` lays the columns, or as float32 lines
    (:func:`permuted_lines`); the padding's slots are zeros. With ``live``
    (:func:`live_sorted_slots`) the runs of sorted slots behind it, which
    carry the sentinel and which no block's walk reaches, are not gathered
    (:func:`permute_live`)."""
    if slot_layout(cols.shape[0]) == "lines":
        return permuted_lines(lines_of_cols(cols), perm, live)
    cols = jnp.pad(cols.astype(jnp.float32),
                   ((0, 0), (0, perm.shape[0] - cols.shape[1])))
    if live is None or permutes_in_groups(*cols.shape):
        return split_payload(permute_whole(cols, perm), perm.shape[0])
    return split_payload(permute_live(cols, perm, live, "columns"),
                         perm.shape[0])


def permuted_lines(lines: jax.Array, perm: jax.Array,
                   live: Optional[jax.Array] = None) -> jax.Array:
    """``lines`` [N, lanes] (:func:`lines_of_rows` of the cotangent rows) in
    the order ``perm`` [Np] of :func:`sort_slots`: the line side's payload,
    ``[Np, lanes]`` float32, which the kernel transposes and splits chunk
    by chunk; the padding's slots are zeros. ``live`` as in
    :func:`permuted_payload`."""
    lines = jnp.pad(lines.astype(jnp.float32), (
        (0, perm.shape[0] - lines.shape[0]), (0, 0)))
    if live is None:
        return permute_lines(lines, perm)
    return permute_live(lines, perm, live, "lines")


def sorted_payload(ids: jax.Array, cols: jax.Array,
                   num_rows: int, block_ids: int = BLOCK_IDS,
                   chunk_slots: int = CHUNK_SLOTS,
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`sort_slots` of ``ids`` [N] and :func:`permuted_payload` of
    ``cols`` [width, N] in that order: ``(bounds, sorted ids [1, Np],
    payload)``, the payload ``[3 * R, Np]`` bfloat16 or, on the line side,
    ``[Np, lanes]`` float32. The payload does not travel through
    the sort: 1.9 + 7.5 ms at 1,048,576 slots of 9 columns on a v5e."""
    bounds, ids_s, perm = sort_slots(ids, num_rows, block_ids, chunk_slots)
    return bounds, ids_s, permuted_payload(cols, perm)


# the words of a walk's state, int32 in SMEM, which outlive a grid step: the
# chunk the walk stands on, the highest chunk whose DMAs were started, the
# highest waited for
_CUR, _FETCHED, _READY = 0, 1, 2
STATE_WORDS = 3


def chunk_window(c, chunk_slots: int):
    """``(slot, lanes)`` of chunk ``c``: the buffer slot ``c % 2`` it lives
    in and where its ``chunk_slots`` slots lie along the sorted slots."""
    from jax.experimental import pallas as pl

    return c % 2, pl.ds(pl.multiple_of(c * chunk_slots, chunk_slots),
                        chunk_slots)


class Walk:
    """Blocks of table ids and chunks of sorted slots walked in step, inside
    a Pallas kernel whose sequential grid takes the blocks in order.
    ``bounds_ref`` [2, chunks + 1] (SMEM) is :func:`chunk_bounds`, ``state``
    an int32 scratch of ``STATE_WORDS`` words in SMEM, ``copies(c)`` the
    async copies that bring chunk ``c`` into buffer slot ``c % 2``
    (:func:`chunk_window`). A chunk is started while its predecessor is
    contracted, whichever block or grid step that falls in, and waited for
    when it is first needed; ``arrive(c)``, if given, runs then, once a
    chunk however many blocks it spans. The kernel calls :meth:`begin` in
    its first grid step, :meth:`block` once a block and :meth:`drain` at
    its end."""

    def __init__(self, bounds_ref, state, copies: Callable,
                 arrive: Optional[Callable] = None):
        self.bounds, self.state, self.copies = bounds_ref, state, copies
        self.arrive = arrive
        self.chunks = bounds_ref.shape[1] - 1

    def begin(self) -> None:
        """The first grid step: chunk 0 on its way, the walk on it."""
        for cp in self.copies(0):
            cp.start()
        self.state[_CUR] = 0
        self.state[_FETCHED] = 0
        self.state[_READY] = -1

    def block(self, upper, contract: Callable,
              leave: Optional[Callable] = None,
              may_pass_the_sentinel: bool = False) -> None:
        """The block of ids below ``upper``: ``contract(j)`` for every
        chunk ``j`` from the one the walk stands on that holds an id below
        ``upper``, its DMAs arrived in slot ``j % 2``. A chunk whose last
        id is below ``upper`` is done: ``leave(j)`` runs and the walk moves
        on; one with slots of a later block left keeps the walk on it.
        ``may_pass_the_sentinel``: the kernel runs a grid step's last
        blocks though they lie past the table's end and the sentinel id,
        so the walk also stops at the last chunk."""
        from jax.experimental import pallas as pl

        bounds, state, chunks = self.bounds, self.state, self.chunks

        def more(carry):
            j, go = carry
            if may_pass_the_sentinel:
                go = go & (j < chunks)
            return go & (bounds[0, j] < upper)

        def step(carry):
            j, _ = carry
            nxt = j + 1

            @pl.when((nxt < chunks) & (nxt > state[_FETCHED]))
            def _prefetch():
                for cp in self.copies(nxt):
                    cp.start()
                state[_FETCHED] = nxt

            @pl.when(j > state[_READY])
            def _arrived():
                for cp in self.copies(j):
                    cp.wait()
                state[_READY] = j
                if self.arrive is not None:
                    self.arrive(j)

            contract(j)
            # slots for a later block left in this chunk: stay on it
            done = bounds[1, j] < upper
            if leave is not None:
                pl.when(done)(lambda: leave(j))
            return jnp.where(done, nxt, j), done

        j, _ = jax.lax.while_loop(more, step, (state[_CUR], True))
        state[_CUR] = j

    def leave_the_rest(self, leave: Callable) -> None:
        """After the last block: ``leave(c)`` for what no block finished,
        the chunk the walk stands on and the chunks of sentinels alone."""
        jax.lax.fori_loop(self.state[_CUR], self.chunks,
                          lambda c, _: leave(c), None)

    def drain(self, last=None) -> None:
        """After the last block (where ``last``, if given, holds): wait for
        a chunk that was started and never needed."""
        from jax.experimental import pallas as pl

        state = self.state
        pending = state[_FETCHED] > state[_READY]

        @pl.when(pending if last is None else last & pending)
        def _drain():
            for cp in self.copies(state[_FETCHED]):
                cp.wait()


# a (block, chunk) pair is contracted over whole tiles of this many table
# ids, one [3R, 128] @ [128, C] product each, as the MXU takes them
TILE_IDS = 128
TILE_SHIFT = TILE_IDS.bit_length() - 1
# the tiles a pair may contract: the smallest of these that holds the
# chunk's window, so that a pair stays one matmul of a static shape. These
# read 0.02-0.23 ms a step under (1, 2, 3, 4, 6, 8, 12, 16, 24) at the cells'
# three shapes; a rung for every width is no faster (PERF.md §6, PR 43)
RUNGS = (1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16, 20, 24, 28)


def ladder(block_ids: int) -> Tuple[int, ...]:
    """The rungs of a block of ``block_ids`` ids, the last the whole block."""
    tiles = block_ids // TILE_IDS
    return tuple(r for r in RUNGS if r < tiles) + (tiles,)


def tile_window(bounds_ref, j, base, upper):
    """``(first, last)``: the tiles of the block of ids ``[base, upper)``
    between which chunk ``j`` can name an id. Its slots are sorted, so
    inside the block they lie between its first and its last id
    (``bounds_ref``, :func:`chunk_bounds`); every other tile meets an
    all-zero one-hot."""
    first = jax.lax.shift_right_logical(
        jnp.maximum(bounds_ref[0, j], base) - base, TILE_SHIFT)
    last = jax.lax.shift_right_logical(
        jnp.minimum(bounds_ref[1, j], upper - 1) - base, TILE_SHIFT)
    return first, last


def rung_index(need, rungs: Tuple[int, ...]):
    """The first of ``rungs`` (a :func:`ladder`) to hold ``need`` tiles."""
    return sum((need > r).astype(jnp.int32) for r in rungs[:-1])


def on_first_rung_that_holds(need, rungs: Tuple[int, ...], rung) -> None:
    """Inside a kernel: run ``rung(r)()`` for the first ``r`` of ``rungs``
    that holds ``need`` tiles (a scalar, at most the last rung), found by
    halving: a pair pays four branches for sixteen rungs. A ``switch``
    or a ``when`` a rung costs a branch a rung, 6 ns each on a v5e where
    a tile-product costs 8 (PERF.md §6, PR 43)."""
    def among(lo: int, hi: int):
        if hi - lo == 1:
            return rung(rungs[lo])
        mid = (lo + hi) // 2
        return lambda: jax.lax.cond(need <= rungs[mid - 1],
                                    among(lo, mid), among(mid, hi))

    among(0, len(rungs))()


def tile_counts(bounds: jax.Array, walked: int, block_ids: int,
                rungs: Tuple[int, ...]) -> Tuple[jax.Array, jax.Array]:
    """``(performed, whole_block)``: the tile-products (one ``[3R, 128]``
    by ``[128, C]`` with its one-hot) a kernel on the walk performs for the
    chunks of ``bounds`` (:func:`chunk_bounds`) against the blocks of the
    ids below ``walked``, each pair on the first of ``rungs`` that holds
    its :func:`tile_window`, and those of contracting every pair over its
    whole block. Counted as the walk meets the pairs, outside any kernel;
    what both kernels' counts stand on."""
    tiles, of_rung = rungs[-1], jnp.asarray(rungs, jnp.int32)
    first, last = bounds[0, :-1], jnp.minimum(bounds[1, :-1], walked - 1)
    # a chunk is contracted with every block from its first id's to its
    # last's (a chunk that starts at or past ``walked`` with none): the
    # first and the last over the tiles from the id to the block's edge,
    # those between over the whole block
    pairs = jnp.maximum(last // block_ids - first // block_ids + 1, 0)
    tile_of = lambda x: x % block_ids // TILE_IDS               # noqa: E731
    rung = lambda need: of_rung[rung_index(need, rungs)]       # noqa: E731
    one = rung(tile_of(last) - tile_of(first) + 1)
    more = (rung(tiles - tile_of(first)) + rung(tile_of(last) + 1)
            + (pairs - 2) * tiles)
    performed = jnp.where(pairs == 1, one, jnp.where(pairs > 1, more, 0))
    return jnp.sum(performed), jnp.sum(pairs) * tiles


def walk_books(ids: jax.Array, num_rows: int, real: Optional[jax.Array] = None,
               block_ids: int = BLOCK_IDS, chunk_slots: int = CHUNK_SLOTS,
               ) -> Dict[str, jax.Array]:
    """What the update's kernel walks for the slots ``ids`` [...] (with the
    step's ``real`` [...], where it names its padding) of tables of
    ``num_rows`` rows, counted outside any step from :func:`sort_slots` of
    them as the step makes it: ``slots`` (as handed in), ``real_slots``
    (under the sentinel), ``chunks`` (live: holding such a slot), ``pairs``
    ((block, chunk) pairs the walk meets), ``tile_products`` and
    ``whole_block_tile_products`` (:func:`tile_counts` on the
    :func:`ladder` of ``block_ids``, up to the tables' last block) and
    ``blocks_touched`` (blocks that hold a real slot's id), int32 scalars.
    The forward's kernel walks the same sort: its counts differ only where
    its grid reaches past the tables' last block
    (``table_gather_tile_counts``)."""
    flat = ids.reshape(-1)
    bounds, ids_s, _ = sort_slots(
        flat, num_rows, block_ids, chunk_slots,
        None if real is None else real.reshape(-1))
    sentinel, rungs = round_up(num_rows, block_ids), ladder(block_ids)
    made, whole = tile_counts(bounds, sentinel, block_ids, rungs)
    under, block = ids_s[0] < sentinel, ids_s[0] // block_ids
    first_of_block = jnp.concatenate([
        jnp.ones(1, bool), block[1:] != block[:-1]])
    count = lambda x: jnp.sum(x, dtype=jnp.int32)            # noqa: E731
    return {"slots": jnp.int32(flat.shape[0]), "real_slots": count(under),
            "chunks": count(bounds[0, :-1] < sentinel),
            "pairs": whole // rungs[-1], "tile_products": made,
            "whole_block_tile_products": whole,
            "blocks_touched": count(under & first_of_block)}
