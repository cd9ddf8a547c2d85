"""What crosses the chips for a table dealt by rows
(:class:`dmlc_tpu.parallel.mesh.RowDeal`): the slots a chip owns, and no
others.

Every chip of the deal is a worker (it holds ``n`` slots of the batch) and
a server (it holds a shard of the table). A slot's row lives on one chip,
``deal.place(id)``. So a worker sends each owner the ids of the slots that
owner holds, the owner reads those from its shard as one chip reads a
batch (``ops/table_gather.py``: sort, walk, un-permute; on fewer slots),
and sends the rows back; backward, the cotangent rows go the same road the
other way and the owner adds them into its shard (``ops/grad_scatter.py``).
All inside the caller's ``shard_map`` over ``deal.axis``:

1. **Bucket by owner** (:func:`bucket_slots`): one sort of the ``n`` slots
   by owning chip, carrying their rows at the owner and their positions.
   Slots the caller marks not ``real`` (an ELL batch's padding: value 0,
   all of them the one sink id, so all owned by one chip) sort last and
   are not sent: they read zeros, which is what ``x = 0`` makes of any
   finite row, and carry no cotangent. The send buffer is ``[shards,
   cap]`` rows, past a bucket's count the row one past the shard (a
   gather reads 0 there, a scatter drops it).
2. **All-to-all the rows' ids** (:func:`open_exchange`): an owner receives
   ``shards * cap`` slots, all its own.
3. **Rows home** (:func:`rows_home`): the owner's rows, lane-major in the
   order received, go back as one all-to-all of ``[shards, width, cap]``
   blocks (never ``psum_scatter`` on a v5e 2x2: PERF.md §6, PR 32); the
   worker lays the blocks end to end in its bucketed order and inverts
   the bucketing. No sum: a row arrives once.
4. **Cotangents to owners** (:func:`to_owners`): the worker permutes its
   cotangent columns into the bucketed order, cuts them into the same
   blocks, one all-to-all.

**Live runs** (PR 51). The slots a worker sent lie first in its bucketed
order (``sum(counts)`` of them) and, handed K-major, first in its batch's
order too; an owner's received slots are real up to each bucket's count and
its sort sends the rest to the sentinel. On the line side (a payload of
over 16 columns) all four permutes of this road therefore go run by run
(:func:`~dmlc_tpu.ops.sorted_walk.permute_live`) and gather only the runs
that hold a slot somebody reads: a worker's two here, under the scope
``exchange_permute`` (live up to the slots sent); the owner's un-permute by
:func:`~dmlc_tpu.ops.sorted_walk.live_runs` of the ids it received (the
tail of every bucket is dead) and its update permute up to its sort's
sentinel (``ops/table_gather.py``, ``ops/grad_scatter.py``:
``received=True``). A skipped run yields the zeros the whole gather found
there: the step is the same bits. ``table_slot_groups{op="rows_home" |
"to_owners" | "owner_gather" | "owner_update"}`` counts each once a trace.
The road of a step that overflows keeps its whole permutes.

**Capacity.** ``cap`` is :func:`capacity`: 1.25 times a chip's even share
``ceil(n / shards)``, in whole chunks of the kernels' slots; a constant of
the shapes. Whether any (worker, owner) bucket holds more is counted on the
device every step and agreed by a ``psum`` (``Buckets.overflow``): a step
that overflows takes the route that needs no capacity, whole (every chip
all-gathers every slot and reads or adds the ones it owns: the callers'
``lax.cond``). Nothing is dropped, truncated or approximated under any
skew, a hot id in every row included.

**Every slot to every chip** (PR 54). A table laid in contiguous ranges
(:class:`dmlc_tpu.parallel.mesh.RowRanges`: ``deal.even`` is false) hands
one chip most of a click log's slots, whatever the batch: every step would
overflow. Its road builds no bucket and has no other road beside it
(:func:`open_slots`): the chips' slot ids are all-gathered, **K-major over
the whole batch** (``[K][chip][rows]``: a column of the batch, which is a
field of the log and so a range of ids, is one run of the permutes'
sixteen), a slot another chip owns or that is the batch's padding becomes
the row one past the shard, and every chip runs the one-chip walk on all
of them: one sort, shared by forward and update, in which what it does not
own takes the sentinel; the forward's rows leave the kernel in sorted
order, go back to batch order run by run (:func:`~dmlc_tpu.ops.sorted_walk.
live_runs`: a run no slot of which this chip owns is skipped and reads
zeros) and are summed home (:func:`slots_home`: an all-to-all of the
chips' blocks and a sum of ``shards`` terms of which one is not zero);
the cotangent rows are all-gathered into the same order
(:func:`slots_to_all`) and permuted up to the sort's sentinel. The skew
costs the per-slot work; the stream of the table, which is what a step
pays most for, is a chip's share. ``table_slot_groups{op="shard_gather" |
"shard_update"}`` counts the two permutes.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.utils import telemetry as _telemetry

# a bucket's room over the even share, as a ratio of integers
_SLACK = (5, 4)
# the scope of what crosses the chips, forward and backward: slot ids out,
# rows back, cotangent rows out (docs/observability.md)
EXCHANGE_SCOPE = "table_exchange"
# inside it, a worker's own two permutes of its ``n`` slots: the rows home
# into batch order, the cotangent rows out of it
PERMUTE_SCOPE = "exchange_permute"


def capacity(num_slots: int, shards: int) -> int:
    """Slots a chip may send one owner: ``_SLACK`` times its even share of
    ``num_slots``, rounded up to whole ``CHUNK_SLOTS``."""
    share = -(-num_slots // shards)
    return sw.round_up(-(-share * _SLACK[0] // _SLACK[1]), sw.CHUNK_SLOTS)


class Buckets(NamedTuple):
    """A chip's ``n`` slots in the order of their owners
    (:func:`bucket_slots`)."""
    order: jax.Array      # [n] int32: bucketed position p holds slot order[p]
    starts: jax.Array     # [shards] int32: where owner d's bucket starts
    counts: jax.Array     # [shards] int32: the real slots owner d holds
    overflow: jax.Array   # bool: a bucket of some chip is over the capacity


class Exchange(NamedTuple):
    """What a forward hands its backward in ``sorted_slots``' place on a
    dealt table: the worker's bucketing, the rows of this shard it
    received as an owner (``[shards * cap]``, in the order received), and
    :func:`~dmlc_tpu.ops.sorted_walk.sort_slots` of those where the
    kernel route made it."""
    buckets: Buckets
    received: jax.Array
    sorted_slots: Optional[tuple] = None


class Slots(NamedTuple):
    """What a forward hands its backward in ``sorted_slots``' place on the
    road with no buckets (:func:`open_slots`)."""
    rows: jax.Array       # [shards * n] int32: every chip's slots as rows of
    #                       this shard, K-major over the whole batch; the row
    #                       one past the shard where this chip owns none
    sorted_slots: Optional[tuple] = None   # sort_slots of them (kernel route)


def slot_columns(ids) -> int:
    """The columns of the batch a chip's ``ids`` [K, ...] lie in, K-major
    (flat ``[n]``: one)."""
    return ids.shape[0] if ids.ndim > 1 else 1


def open_slots(deal, ids, real=None) -> Slots:
    """This chip's ``ids`` [K, n / K] (or flat ``[n]``: one column) in
    ``[0, deal.num_rows)`` -> every chip's, as rows of this chip's shard in
    the order ``[K][chip][n / K]``: one all-gather. A slot whose ``real`` is
    false crosses as an id no chip owns."""
    ids = ids.astype(jnp.int32).reshape(slot_columns(ids), -1)
    if real is not None:
        ids = jnp.where(real.reshape(ids.shape), ids, deal.padded_rows)
    return Slots(deal._rows_here(
        jax.lax.all_gather(ids, deal.axis, axis=1).reshape(-1)))


def slots_to_all(deal, cols, columns: int):
    """This chip's ``cols`` [width, n] (its slots in ``columns`` columns of
    the batch, K-major: :func:`slot_columns`) -> every chip's ``[width, shards * n]`` in
    :func:`open_slots`' order: one all-gather."""
    blocks = cols.reshape(cols.shape[0], columns, -1)
    return jax.lax.all_gather(blocks, deal.axis, axis=2).reshape(
        cols.shape[0], -1)


def slots_home(deal, cols, columns: int):
    """``cols`` [width, shards * n], what this chip read for every chip's
    slots in :func:`open_slots`' order (zeros where it owns none) -> this
    chip's own ``[width, n]``, the chips' readings summed: an all-to-all of
    the chips' blocks and a sum here, exact because one term a slot is not
    zero (never ``psum_scatter`` on a v5e 2x2: PERF.md §6, PR 32)."""
    blocks = cols.reshape(cols.shape[0], columns, deal.shards, -1)
    return jnp.sum(jax.lax.all_to_all(blocks, deal.axis, 2, 0),
                   axis=0).reshape(cols.shape[0], -1)


def _owners(deal, ids, real):
    """``(owner [n], row at the owner [n], count an owner [shards])`` of
    ``ids`` [...], flat; a slot whose ``real`` [...] is false has the owner
    ``shards``."""
    chip, row = deal.place(ids.reshape(-1).astype(jnp.int32))
    if real is not None:
        chip = jnp.where(real.reshape(-1), chip, deal.shards)
    counts = jnp.sum(chip[None, :] == jnp.arange(deal.shards)[:, None],
                     axis=1, dtype=jnp.int32)
    return chip, row, counts


def _agreed(deal, counts, num_slots: int):
    """Whether any chip's ``counts`` pass the capacity: the same on all."""
    over = jnp.any(counts > capacity(num_slots, deal.shards))
    return jax.lax.psum(over.astype(jnp.int32), deal.axis) > 0


def overflows(deal, ids, real=None):
    """``Buckets.overflow`` alone, for a caller that keeps books: whether
    the step on these ``ids`` [...] (this chip's) takes the route with no
    capacity."""
    return _agreed(deal, _owners(deal, ids, real)[2], ids.size)


def _cut(x, buckets: Buckets, cap: int, fill):
    """``[shards, ..., cap]``: owner ``d``'s bucket of ``x`` [..., n] (in
    the bucketed order), ``fill`` past its count."""
    lead = x.shape[:-1]
    x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, cap)])
    lane = jnp.arange(cap)
    return jnp.stack([
        jnp.where(lane < buckets.counts[d], jax.lax.dynamic_slice(
            x, (0,) * len(lead) + (buckets.starts[d],), lead + (cap,)), fill)
        for d in range(buckets.starts.shape[0])])


def bucket_slots(deal, ids, real=None):
    """Step 1 for this chip's ``ids`` [...], ``n`` in all: ``(buckets,
    rows [shards, cap])``, the send buffer holding the rows at their
    owners. The bucketed order is of the flat ids."""
    owner, row, counts = _owners(deal, ids, real)
    _, row, order = jax.lax.sort(
        (owner, row, jax.lax.iota(jnp.int32, ids.size)), num_keys=1,
        is_stable=False)
    buckets = Buckets(order, jnp.cumsum(counts) - counts, counts,
                      _agreed(deal, counts, ids.size))
    return buckets, _cut(row, buckets, capacity(ids.size, deal.shards),
                         deal.local_rows)


def open_exchange(deal, ids, real=None) -> Exchange:
    """Steps 1 and 2 for this chip's ``ids`` [...] in ``[0,
    deal.num_rows)``: the bucketing and the slots this chip received."""
    buckets, rows = bucket_slots(deal, ids, real)
    return Exchange(buckets, jax.lax.all_to_all(
        rows, deal.axis, 0, 0).reshape(-1))


def _permute_live_columns(cols, index, live, op: str):
    """``cols[:, index]`` as :func:`~dmlc_tpu.ops.sorted_walk.permute_whole`
    gives it wherever a reader looks, for ``index`` [n] whose entries past
    the first ``live`` name nothing one does. A payload on the line side
    (the field-aware FM's 44 columns) is gathered run by run and only up to
    ``live`` (:func:`~dmlc_tpu.ops.sorted_walk.permute_live`; counted in
    ``table_slot_groups{op=}``); lane-major columns keep their one
    gather."""
    width = cols.shape[0]
    if sw.slot_layout(width) != "lines":
        return sw.permute_whole(cols, index)
    _telemetry.count_table_slot_groups(op, sw.permute_groups(index.shape[0]))
    lines = sw.lines_of_cols(cols)
    with jax.named_scope(PERMUTE_SCOPE):
        lines = sw.permute_live(lines, index, live, "lines")
    return lines.T[:width]


def rows_home(deal, buckets: Buckets, cols):
    """Step 3: ``cols`` [width, shards * cap], an owner's columns in the
    order it received the slots -> this chip's ``[width, n]`` in the order
    of its batch; a slot that was not sent reads 0."""
    n, cap = buckets.order.shape[0], cols.shape[1] // deal.shards
    blocks = jax.lax.all_to_all(jnp.moveaxis(
        cols.reshape(cols.shape[0], deal.shards, cap), 1, 0), deal.axis, 0, 0)
    # end to end: an owner's block runs into the next bucket with the
    # zeros of the rows nobody asked for, and the next block overwrites them
    out = jnp.zeros((cols.shape[0], n + cap), cols.dtype)
    for d in range(deal.shards):
        out = jax.lax.dynamic_update_slice(out, blocks[d],
                                           (0, buckets.starts[d]))
    inverse = sw.inverse_permutation(buckets.order)
    # (the slots that were sent lie first in the bucketed order)
    return _permute_live_columns(
        out[:, :n], inverse,
        sw.live_batch_slots(inverse < jnp.sum(buckets.counts)), "rows_home")


def to_owners(deal, buckets: Buckets, cols):
    """Step 4: this chip's ``cols`` [width, n] in the order of its batch ->
    ``[width, shards * cap]`` in the order this chip, as an owner,
    received the slots; zeros where a worker sent none."""
    cap = capacity(cols.shape[1], deal.shards)
    blocks = jax.lax.all_to_all(
        _cut(_permute_live_columns(cols, buckets.order,
                                   jnp.sum(buckets.counts), "to_owners"),
             buckets, cap, 0.0),
        deal.axis, 0, 0)
    return jnp.moveaxis(blocks, 0, 1).reshape(cols.shape[0], -1)
