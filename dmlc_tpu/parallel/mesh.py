"""Mesh construction + sharding helpers.

The tracker's tree/ring topology maps (tracker.py:186-261) have no socket
analog on TPU: the ICI torus plus XLA collectives replace them. What remains
is (a) building the mesh, (b) placing per-host batches into a global sharded
array — the TPU equivalent of per-rank InputSplit shards feeding one logical
dataset (SURVEY.md §2.3 row 1) — and (c) laying the rows of a table over a
mesh axis, a share a chip: what the tracker's ``--num-servers`` parameter
servers did with a model's keys. Two rules say where id ``i`` lives, and
each is right for one kind of reader:

- :class:`RowDeal`, **cyclic** (chip ``i % shards``): every chip gets its
  share of every field of a click log, so the slots a chip owns are even
  and only those cross the chips (``ops/table_exchange.py``'s buckets). The
  dealt array is *not* in id order: a reader finds a row through the deal
  (:meth:`RowDeal.take`, :meth:`RowDeal.physical_row`). Right where every
  reader is the learner's own (``FFMLearner(mesh=)``, whose ``rows()`` is
  the one way in).
- :class:`RowRanges`, **contiguous** (chip ``i // local_rows``): the laid
  array *is* the table in id order, padded to a multiple of the shards, so
  ``table[i]`` and ``jnp.take(table, ids)`` read id ``i`` whoever asks and
  XLA partitions them. Right where the tables are public
  (``FMLearner(mesh=)``: ``params.w[i]`` is the row of id ``i``). The price
  is the id space's own skew: fields are ranges of ids, so one chip may own
  most of a row's slots, no bucket of an even share holds a step, and the
  table ops let every chip see every slot and work on the ones it owns.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    axes: Optional[Dict[str, int]] = None, *, devices=None
) -> Mesh:
    """Build a Mesh from an axis->size dict, e.g. ``{"data": 4, "model": 2}``.

    Defaults to a 1-D data mesh over all devices. Axis sizes must multiply to
    the device count; pass ``-1`` for one axis to infer it.
    """
    devices = list(devices if devices is not None else jax.devices())
    ndev = len(devices)
    if not axes:
        axes = {"data": ndev}
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = ndev // known
    total = int(np.prod(sizes))
    if total != ndev:
        raise ValueError(f"mesh axes {dict(zip(names, sizes))} != {ndev} devices")
    dev_array = np.array(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=names)


def data_sharding(mesh: Mesh, *, axis: str = "data", ndim: int = 1) -> NamedSharding:
    """Batch-dim sharding over the data axis, rest replicated."""
    spec = [axis] + [None] * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


@dataclass(frozen=True)
class RowDeal:
    """The rows of a ``[num_rows, ...]`` table dealt over the ``shards``
    chips of mesh axis ``axis``, parameter-server fashion (ps-lite gives
    each server a range of keys; every chip here is a server and a
    worker). :meth:`place` is the one function that says where id ``i``
    lives; everything else follows from it.

    The deal is **cyclic**: id ``i`` lives on chip ``i % shards`` at local
    row ``i // shards``. Click-log ids are dense within a field and fields
    differ in size by orders of magnitude, so contiguous ranges would hand
    one chip most of a row's slots; a cyclic deal gives every chip its
    share of every field. A chip holds ``local_rows = ceil(num_rows /
    shards)`` rows; where ``shards`` does not divide ``num_rows`` the last
    local row of the later chips stands for no id (``padded_rows -
    num_rows`` inert rows in all).

    The dealt table is one global array ``[padded_rows, ...]`` sharded by
    :meth:`sharding`: chip ``c`` holds physical rows ``[c * local_rows,
    (c + 1) * local_rows)``, so id ``i`` is physical row
    :meth:`physical_row`. Inside ``jax.shard_map`` over ``axis`` a chip
    sees its ``[local_rows, ...]`` shard. What crosses the chips in a step
    is ``ops/table_exchange.py``'s: a chip places its slots' ids with
    :meth:`place` and sends each owner the ones it holds, local rows out
    and table rows back, one all-to-all each way (cotangent rows likewise),
    so a chip works on the slots it owns. :meth:`local_slots`, every
    chip's ids all-gathered as rows of this chip's shard, is the road of a
    step whose slots do not fit that exchange's buckets."""

    num_rows: int
    shards: int
    axis: str = "data"

    # whether a dense id space's slots fall evenly on the chips under this
    # rule, so that buckets of an even share (ops/table_exchange.py) hold a
    # step; where they do not, the table ops take the road with no bucket
    even = True

    @property
    def local_rows(self) -> int:
        return -(-self.num_rows // self.shards)

    @property
    def padded_rows(self) -> int:
        return self.local_rows * self.shards

    def place(self, ids):
        """``(chip, local row)`` of ids in ``[0, num_rows)``: numpy or jax
        integers of any shape."""
        return ids % self.shards, ids // self.shards

    def owned(self, chip: int):
        """``(first id, id stride, rows)`` of the ids ``chip`` holds, in
        the order of its local rows: how a checkpoint's file of that
        shard addresses its rows (local row ``r`` is id ``first + r *
        stride``; the shard's last local row may stand for no id)."""
        return chip, self.shards, len(range(chip, self.num_rows,
                                            self.shards))

    def describe(self) -> dict:
        """The deal as a checkpoint's header records it, :meth:`place`
        spelled out, so that another deal (or a plain reader) finds a
        row by its id."""
        return {"num_rows": self.num_rows, "shards": self.shards,
                "axis": self.axis, "rule": "cyclic",
                "place": {"chip": "id % shards", "row": "id // shards"}}

    def physical_row(self, ids):
        """The row of the dealt global array that holds id ``ids``."""
        chip, row = self.place(ids)
        return chip * self.local_rows + row

    def sharding(self, mesh: Mesh, ndim: int = 2) -> NamedSharding:
        """How the dealt ``[padded_rows, ...]`` array lies on ``mesh``."""
        return NamedSharding(mesh, P(self.axis, *([None] * (ndim - 1))))

    def _rows_here(self, ids):
        """Inside ``shard_map`` over ``axis``: ``ids`` as rows of this
        chip's shard: the local row where this chip owns the id,
        ``local_rows`` (one past the shard: a gather reads 0 there, a
        scatter drops it) where another does."""
        import jax.numpy as jnp

        chip, row = self.place(ids)
        return jnp.where(chip == jax.lax.axis_index(self.axis), row,
                         self.local_rows)

    def local_slots(self, ids):
        """Inside ``shard_map`` over ``axis``: this chip's flat ``ids``
        [n] -> the slots of **all** chips ``[shards * n]`` (an all-gather,
        chip-major) as rows of this chip's shard (``local_rows``, one past
        it, where another chip owns the id)."""
        return self._rows_here(jax.lax.all_gather(ids, self.axis, tiled=True))

    def owned_slots(self, ids, real):
        """Inside ``shard_map`` over ``axis``: how many of the chips'
        slots ``ids`` [...] with ``real`` [...] true each chip owns,
        ``[shards]`` uint32, the same on every chip (a psum of the chips'
        own counts). Largest over mean is the deal's skew."""
        return jax.lax.psum(self.count_owned(ids, real), self.axis)

    def count_owned(self, ids, real):
        """:meth:`owned_slots` of the ``ids`` at hand alone, no collective:
        ``[shards]`` uint32."""
        import jax.numpy as jnp

        chip, _ = self.place(ids)
        mine = (chip[..., None] == jnp.arange(self.shards)) & real[..., None]
        return jnp.sum(mine, axis=tuple(range(ids.ndim)), dtype=jnp.uint32)

    def take(self, mesh: Mesh, table, ids):
        """Rows ``ids`` [n] of a dealt ``table``, whole on every chip:
        each chip reads the rows it owns and zeros elsewhere, and the
        chips' results are summed (exact: one term a row is not zero).
        For readers outside the step (a probe, an export)."""
        import jax.numpy as jnp

        def local(shard, ids):
            return jax.lax.psum(jnp.take(
                shard, self._rows_here(ids), axis=0, mode="fill",
                fill_value=0), self.axis)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(self.axis, *([None] * (table.ndim - 1))), P()),
            out_specs=P(), check_vma=False))(table, ids)


def count_up(books, more):
    """``books`` [n, 2] uint32 (a 64-bit count in two words a row: low,
    high) with ``more`` [n] uint32 added: how a learner keeps
    :meth:`RowDeal.owned_slots` over a run, inside its step."""
    import jax.numpy as jnp

    low = books[:, 0] + more
    high = books[:, 1] + (low < more).astype(jnp.uint32)
    return jnp.stack([low, high], axis=1)


def counts_of(books) -> list:
    """:func:`count_up`'s books read off the device: Python ints."""
    books = jax.device_get(books).astype("uint64")
    return [int(lo + (hi << 32)) for lo, hi in books]


@dataclass(frozen=True)
class RowRanges(RowDeal):
    """:class:`RowDeal` by the **contiguous** rule: id ``i`` lives on chip
    ``i // local_rows`` at local row ``i % local_rows``, so the laid global
    array ``[padded_rows, ...]`` is the table itself in id order
    (:meth:`physical_row` is the id) with ``padded_rows - num_rows`` inert
    rows behind it on the last chip, which no id names. Everything a
    :class:`RowDeal` answers holds here (:meth:`owned`, :meth:`describe`,
    :meth:`take`, :meth:`sharding`, the books of :meth:`owned_slots`); a
    checkpoint written under either rule restores under the other.

    The chips' shares of a batch's slots are as skewed as its ids (module
    docstring), which :attr:`even` tells the table ops: they build no
    buckets for this rule, all-gather every chip's slots and let each chip
    read and update the ones in its range (``ops/table_exchange.py``,
    "Every slot to every chip")."""

    even = False

    def place(self, ids):
        return ids // self.local_rows, ids % self.local_rows

    def owned(self, chip: int):
        first = chip * self.local_rows
        return first, 1, max(0, min(self.local_rows, self.num_rows - first))

    def describe(self) -> dict:
        return {"num_rows": self.num_rows, "shards": self.shards,
                "axis": self.axis, "rule": "ranges",
                "place": {"chip": "id // local_rows",
                          "row": "id % local_rows"}}


def host_shard_info(
    num_parts_hint: Optional[int] = None,
) -> Tuple[int, int]:
    """(part_index, num_parts) for this host's InputSplit shard.

    Multi-host: each process reads its own partition
    (``jax.process_index()/process_count()``), the direct analog of per-rank
    ``InputSplit::Create(uri, rank, world)`` (src/io.cc:74-130).
    """
    if num_parts_hint is not None:
        return 0, num_parts_hint
    return jax.process_index(), jax.process_count()


def local_batch_to_global(
    mesh: Mesh, local_arrays, *, axis: str = "data"
) -> Tuple[jax.Array, ...]:
    """Assemble per-process host batches into global sharded jax.Arrays.

    Uses ``jax.make_array_from_process_local_data``: each host contributes its
    InputSplit shard; the result is one logical array sharded over ``axis``
    across the pod — no host ever materializes the global batch.
    """
    out = []
    for arr in local_arrays:
        sharding = NamedSharding(mesh, P(axis, *([None] * (arr.ndim - 1))))
        out.append(jax.make_array_from_process_local_data(sharding, np.asarray(arr)))
    return tuple(out)
