"""Factorization machine learner over the device pipeline.

The libfm text format the reference parses (src/data/libfm_parser.h) exists
to feed exactly this model family — second-order FMs (Rendle 2010) over
high-dimensional sparse features. This is the TPU-first formulation:

    margin(x) = w0 + <w, x> + 0.5 * sum_f [ (<V[:,f], x>)^2 - <V[:,f]^2, x^2> ]

- **dense path** (hashed/low-D data): two matmuls on the MXU —
  ``(x @ V)**2`` and ``(x**2) @ (V**2)`` — plus the linear term; everything
  fuses under one jit.
- **ELL path** (true high-D sparse, KDD-shaped): per-row gathers of the
  factor rows ``V[idx]`` (static [B, K, F] shapes; XLA vectorizes the
  gather+reduce), so the [D, F] factor table never materializes per batch.
  Neither the gather nor its backward goes through XLA's where that is
  slow. On a TPU the rows of a large table are read from the sorted slots
  by a one-hot MXU kernel (ops/table_gather.py; the counter
  ``table_gather_route`` says which route a forward took), value for
  value what ``jnp.take`` reads;
  :func:`dmlc_tpu.ops.sparse.ell_table_gather` carries its own VJP, which
  on a TPU builds the dense gradient of a large table from the sorted
  batch rows with a one-hot MXU kernel (ops/grad_scatter.py; the counter
  ``grad_scatter_route`` says which route a step took) and hands
  ``optax`` the same dense float32 gradient either way. Where the
  optimizer is the learner's own Adam (no ``optimizer=``, ``l2 == 0``)
  and the scatter takes that kernel, no dense gradient is made at all:
  the step differentiates the loss with respect to the gathered rows and
  the kernel finishes Adam on every block of the tables in VMEM, in
  place (:meth:`FMLearner.table_update_route`; the counter
  ``table_update_route`` says which way a step went). The arithmetic is
  optax's, every coordinate's moments decay on every step, and
  ``opt_state`` keeps ``optax.adam``'s pytree.

- **bcoo path** (ragged rows: lengths that differ, real values): the
  batch's slots lie flat, row after row, pad-free but for the tail of the
  slot count's bucket: ``N`` ids with a value and a row id each (a
  ``(BCOO, label, weight)`` batch of ``DeviceIter(layout="bcoo")``; the
  rows ascending). The table rows are read and updated by the ELL path's
  one op on the flat ids (``table_rows`` / ``ell_table_gather``,
  ``dense_table_grad`` / ``fused_table_update``: the same kernels, the same
  routes and counters, told ``N`` slots), and a row's sums over its run of
  slots and the way back are :mod:`dmlc_tpu.ops.slot_rows` (scope
  ``fm_rowsum``). No ``bcoo_dot_general`` and no scatter of XLA's on a TPU.

Params are a pytree under ``jax.jit``. **Under a mesh the tables are laid
by rows, never replicated**: ``w``, ``v`` and Adam's moments of both are
global arrays *in id order*, padded to a multiple of the shards (the
padding rows zero and inert: no id names them; the sink stays row
``num_col``) and sharded over ``data_axis``, chip ``c`` holding the
contiguous rows ``[c * L, (c + 1) * L)``
(:class:`dmlc_tpu.parallel.mesh.RowRanges`); ``w0`` and the step count
are replicated, batches shard over the same axis. ``params.w[i]`` is the
row of id ``i`` whoever reads it, which is why the ranges are contiguous
and not the field-aware FM's cyclic deal (a reader outside the learner
would find another id's row there); the price is that a click log's
fields are ranges of ids too, so one chip owns most of a row's slots. The
start is value for value the one-device draw, made on the shards.

A step on the fused route (:meth:`FMLearner.table_update_route`) runs
under ``shard_map``: every chip sees every slot of the global batch (one
all-gather of the ids, K-major over the whole batch), sorts them once with
what it does not own at the sentinel, reads the rows it owns from its
shard with the forward's kernel, and the chips' readings are summed home
(an all-to-all and a sum); margin, loss and the gradient with respect to
the gathered rows are each chip's own rows'; the cotangent rows are
all-gathered and the gradient kernel finishes Adam on the chip's shard of
``(p, m, n)``, in place (``ops/table_exchange.py``, "Every slot to every
chip"; ``table_rows`` / ``fused_table_update`` with ``deal=``). A chip
streams its quarter of the tables and walks only its own slots; no
bucket, no capacity, nothing a skew can overflow. Every other route (the
CPU, a small table, ``l2``, a caller's optimizer, the ``dense`` layout)
is the one-device step under ``jit`` on the row-sharded operands:
``jnp.take``, its scatter-add and optax's sweep, partitioned by XLA. The
result of a step is that of the undivided tables either way; the counter
``table_shard_route`` counts a traced step, :meth:`FMLearner.shard_slots`
says how the batches' slots fell on the chips. The SPMD shape is
:class:`dmlc_tpu.models.LinearLearner`'s, including the
``steps_per_epoch`` / ``max_steps`` collective step-count contract.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from dmlc_tpu.models._loop import TrainLoopMixin
from dmlc_tpu.ops import grad_scatter, sorted_walk, table_exchange
from dmlc_tpu.ops.slot_rows import slot_rows_sum
from dmlc_tpu.ops.sparse import EllBatch, ell_table_gather
from dmlc_tpu.ops.table_gather import table_rows
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import check


class FMParams(NamedTuple):
    w0: jax.Array       # scalar bias
    w: jax.Array        # [W] linear weights; last slot = ELL padding sink
    v: jax.Array        # [W, F] factor rows; sink row pinned to 0


# The step's stages carry fixed ``jax.named_scope`` names, so a device
# trace can say which part of the step an XLA fusion belongs to whatever
# number XLA gave it (docs/observability.md; TrainLoopMixin.hlo_scopes):
# fm_gather (table rows brought to the batch: the gathers, or the
# contractions that stand for them), fm_interaction, fm_loss,
# fm_optimizer, fm_sink; on a ragged batch also fm_rowsum (a row's sums
# over its run of slots, and their transpose in the backward: both read
# ``fm_rowsum``). The gradient's scatter is the transpose of the
# gather and reads ``transpose(jvp(fm_gather))``; on the fused route there
# is none, and the permute of the cotangent rows and the kernel that
# updates the tables read ``fm_optimizer``. Inside those two the table ops
# name the sorted walk's five pieces (``walk_sort``, ``walk_gather_kernel``,
# ``walk_gather_permute``, ``walk_update_permute``, ``walk_update_kernel``:
# ops/sorted_walk.py). Scopes are HLO metadata only: the compiled step is
# the same program with or without them.

def _margin_dense(params: FMParams, x: jax.Array) -> jax.Array:
    with jax.named_scope("fm_gather"):
        linear = x @ params.w + params.w0
        xv = x @ params.v                       # [B, F] — MXU
        x2v2 = (x * x) @ (params.v * params.v)  # [B, F] — MXU
    with jax.named_scope("fm_interaction"):
        return linear + 0.5 * jnp.sum(xv * xv - x2v2, axis=-1)


def _flat_slots(mat) -> Tuple[jax.Array, jax.Array, jax.Array]:
    # (ids [N], values [N], row ids [N]) of a BCOO batch whose slots lie
    # row after row. The padding of the slot count carries coordinates one
    # past both ends, (rows, num_col): its id is the sink row, its row id
    # reaches no row, and its value (ones, where the values were elided)
    # is masked here
    rows, ids = mat.indices[:, 0], mat.indices[:, 1]
    return ids, jnp.where(rows < mat.shape[0], mat.data, 0.0), rows


def _margin_of_slots(w0: jax.Array, w_g: jax.Array, v_g: jax.Array,
                     val: jax.Array, rows: jax.Array,
                     num_rows: int) -> jax.Array:
    # the gathered rows [N] and [N, F] of the flat slots, their values [N]
    # and row ids [N] ascending. One column carries the linear term and the
    # squares together: sum_k (w_k x_k - 1/2 sum_f (v_kf x_k)^2)
    with jax.named_scope("fm_interaction"):
        a = v_g * val[:, None]                              # v_k x_k
        q = w_g * val - 0.5 * jnp.sum(a * a, axis=-1)
    with jax.named_scope("fm_rowsum"):
        q, s = slot_rows_sum((q, a), rows, num_rows)        # [B], [B, F]
    with jax.named_scope("fm_interaction"):
        return w0 + q + 0.5 * jnp.sum(s * s, axis=-1)


def _margin_of_rows(w0: jax.Array, w_g: jax.Array, v_g: jax.Array,
                    val: jax.Array) -> jax.Array:
    # the gathered rows [K, B] and [K, B, F] and the slots' values [K, B],
    # K-major; padding slots carry value 0 so they contribute nothing to
    # any sum
    with jax.named_scope("fm_interaction"):
        linear = jnp.sum(w_g * val, axis=0) + w0
        s = jnp.einsum("kbf,kb->bf", v_g, val)             # sum_k v_k x_k
        # sum_k v_k^2 x_k^2
        s2 = jnp.einsum("kbf,kb->bf", v_g * v_g, val * val)
        return linear + 0.5 * jnp.sum(s * s - s2, axis=-1)


def _margin_ell(params: FMParams, batch: EllBatch, sharded: bool
                ) -> jax.Array:
    # gathers over the factor table, the slots K-major ``[K, B]``: the
    # batch's padding (value 0) then lies behind its real slots and is
    # neither read nor permuted (``table_rows(real=)``). The op's own VJP
    # builds the dense gradient (ops/grad_scatter.py); inside the scope, so
    # the backward reads transpose(jvp(fm_gather)) whichever route it takes
    indices, values = batch.indices.T, batch.values.T
    with jax.named_scope("fm_gather"):
        if sharded:
            # tables laid by rows over a mesh, outside ``shard_map``:
            # XLA's gather and its transpose, which XLA partitions
            w_g, v_g = (jnp.take(t, indices, axis=0)
                        for t in (params.w, params.v))
        else:
            w_g, v_g = ell_table_gather((params.w, params.v), indices, None,
                                        values != 0)
    return _margin_of_rows(params.w0, w_g, v_g, values)


class FMLearner(TrainLoopMixin):
    """Second-order factorization machine (logistic or squared objective).

    ``layout`` matches the DeviceIter layout ('dense', 'ell', or 'bcoo' —
    the last single-device: ragged rows as flat slots on the ELL path's
    table kernels, their sums by :mod:`dmlc_tpu.ops.slot_rows`); factors
    initialize to small gaussian noise (all-zero factors have zero gradient
    through the interaction term). With ``mesh``, batches shard over
    ``data_axis`` and the tables and Adam's moments are laid by rows over
    it in id order, a contiguous share a chip (:attr:`deal`; module
    docstring: the road of a step, and what crosses the chips).
    """

    def __init__(
        self,
        num_col: int,
        num_factors: int = 8,
        objective: str = "logistic",
        layout: str = "dense",
        optimizer: Optional[optax.GradientTransformation] = None,
        learning_rate: float = 0.05,
        init_scale: float = 0.01,
        l2: float = 0.0,
        seed: int = 0,
        mesh=None,
        data_axis: str = "data",
    ):
        check(layout in ("dense", "ell", "bcoo"),
              "FMLearner: layout must be dense|ell|bcoo")
        check(layout != "bcoo" or mesh is None,
              "FMLearner: layout='bcoo' takes no mesh: a ragged batch's "
              "slots are one flat list with no axis to shard a row's run "
              "along (DeviceIter emits the kind on one device only)")
        check(objective in ("logistic", "squared"),
              f"FMLearner: unknown objective {objective!r}")
        check(num_factors >= 1, "FMLearner: num_factors must be >= 1")
        self.num_col = num_col
        self.num_factors = num_factors
        self.objective = objective
        self.layout = layout
        self.l2 = l2
        self.data_axis = data_axis
        # +1 = the padding sink: ELL's pad id, and the id that a BCOO
        # batch's pad coordinates (rows, num_col) carry
        self.weight_dim = num_col + 1
        self.opt = optimizer or optax.adam(learning_rate)
        self._lay_over(mesh)
        key = jax.random.PRNGKey(seed)
        if mesh is None:
            v = init_scale * jax.random.normal(
                key, (self.weight_dim, num_factors), jnp.float32)
            v = v.at[-1].set(0.0)  # sink row inert
            self.params = FMParams(
                w0=jnp.zeros((), jnp.float32),
                w=jnp.zeros(self.weight_dim, jnp.float32),
                v=v,
            )
            self.opt_state = self.opt.init(self.params)
        else:
            self.params, self.opt_state = self._start_fn(init_scale)(key)
        self._opt_meta = ({"name": "caller"} if optimizer is not None
                          or callable(learning_rate) else
                          {"name": "adam", "learning_rate": learning_rate})
        # the learner's own optimizer is one the gradient kernel can finish
        # (its numbers are known here); one a caller passes in is opaque,
        # and so is a schedule in the learning rate's place
        own = optimizer is None and not callable(learning_rate)
        self._adam = (grad_scatter.AdamEpilogue(float(learning_rate)) if own
                      else None)
        self._step = self._build_step()
        self._accuracy = self._build_accuracy()
        self._predict = jax.jit(lambda params, batch: self._margin(params, batch)[0])

    def device_num_col(self) -> int:
        """The ``num_col`` a DeviceIter must use to feed this learner (a
        dense batch is as wide as the tables have rows: under a mesh, with
        the layout's padding)."""
        if self.layout == "dense":
            return (self.weight_dim if self.deal is None
                    else self.deal.padded_rows)
        return self.weight_dim - 1

    def _lay_over(self, mesh) -> None:
        """Lay the learner out on ``mesh``: the ranges of ``weight_dim``
        rows over its ``data_axis`` and the shardings that follow
        (``None``: one device, no deal)."""
        self.mesh, self.deal = mesh, None
        if mesh is not None:
            from dmlc_tpu.parallel.mesh import RowRanges

            self.deal = RowRanges(self.weight_dim,
                                  mesh.shape[self.data_axis], self.data_axis)
            self._shardings = self._state_shardings()
            self._specs = jax.tree_util.tree_map(lambda sh: sh.spec,
                                                 self._shardings)

    def _start_fn(self, init_scale: float):
        """The jitted start ``key -> (params, opt_state)`` under a mesh,
        value for value the one-device start and made on the shards (no
        chip draws the whole): jax's default ``threefry_partitionable``
        bits depend on an element's flat index alone, so the laid
        ``[padded_rows, k]`` holds the rows of ``[weight_dim, k]`` and the
        compiler draws every shard where it lives. The sink and the rows
        past it (the layout's padding) are zero. The optimizer's state is
        born on the shards with the tables; its last leaf is the learner's
        own books, ``[shards, 2]`` uint32 (low word, high word): the real
        slots every chip has owned so far (:meth:`shard_slots`)."""
        rows, sink = self.deal.padded_rows, self.weight_dim - 1

        def start(key):
            # (drawn, then scaled: folded into the normal transform's
            # constants the rows come out an ulp off the one-device start)
            v = init_scale * jax.lax.optimization_barrier(jax.random.normal(
                key, (rows, self.num_factors), jnp.float32))
            ids = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            params = FMParams(w0=jnp.zeros((), jnp.float32),
                              w=jnp.zeros(rows, jnp.float32),
                              v=jnp.where(ids < sink, v, 0.0))
            return params, self._with_books(
                self.opt.init(params),
                jnp.zeros((self.deal.shards, 2), jnp.uint32))

        return jax.jit(start, out_shardings=self._shardings[:2])

    @staticmethod
    def _with_books(opt_state, books):
        """The optimizer's state with the learner's books as its last
        leaf: a chain's tuple (optax.adam's) one longer, which keeps
        ``opt_state[0]`` Adam's; any other state beside them."""
        if type(opt_state) is tuple:
            return opt_state + (books,)
        return (opt_state, books)

    def _without_books(self, opt_state):
        """``(the optimizer's own state, the books)`` of
        :meth:`_with_books`' result."""
        if self._chain_state:
            return opt_state[:-1], opt_state[-1]
        return opt_state

    def batch_shardings(self):
        return None if self.mesh is None else self._shardings[2]

    def shard_slots(self):
        """Real slots (value not 0) every chip has owned over all steps so
        far, ``[shards]`` Python ints; ``None`` without a mesh or on the
        ``dense`` layout, which has no slots. Read outside a step: the
        counts ride in the optimizer's state and no step waits for them.
        Largest over mean is the layout's skew."""
        if self.deal is None or self.layout == "dense":
            return None
        from dmlc_tpu.parallel.mesh import counts_of

        return counts_of(self._without_books(self.opt_state)[1])

    def _checkpoint_spec(self):
        """What a checkpoint holds (docs/checkpoint.md): the parameters
        and the optimiser's whole state, whichever ``layout`` feeds them;
        the tables by global row id, so a state saved under a mesh
        restores on one device and the reverse (the layout's padding rows
        are no ids; the per-chip books are the layout's own)."""
        from dmlc_tpu.models._checkpoint import CheckpointSpec

        return CheckpointSpec(
            meta={"class": "FMLearner", "num_col": self.num_col,
                  "num_factors": self.num_factors,
                  "objective": self.objective, "layout": self.layout,
                  "l2": self.l2, "optimizer": self._opt_meta},
            tree={"params": self.params, "opt_state": self.opt_state},
            deal=self.deal,
            layout_bound=() if self.deal is None else (
                f"opt_state.{len(self.opt_state) - 1}",))

    def _state_shardings(self):
        """``(params, opt_state, batch, replicated)`` shardings under the
        mesh: a leaf of the tables' rows is laid by rows, every other one
        (``w0``, the count, the books) is whole on every chip."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        rows = self.deal.padded_rows

        def laid(x):
            if x.ndim and x.shape[0] == rows:
                return self.deal.sharding(self.mesh, x.ndim)
            return rep

        sds = jax.ShapeDtypeStruct
        params = FMParams(w0=sds((), jnp.float32),
                          w=sds((rows,), jnp.float32),
                          v=sds((rows, self.num_factors), jnp.float32))
        opt_state = jax.eval_shape(self.opt.init, params)
        self._chain_state = type(opt_state) is tuple
        opt_sh = self._with_books(jax.tree_util.tree_map(laid, opt_state),
                                  rep)
        vec = NamedSharding(self.mesh, P(self.data_axis))
        row = NamedSharding(self.mesh, P(self.data_axis, None))
        if self.layout == "ell":
            batch_sh = EllBatch(indices=row, values=row, label=vec, weight=vec)
        else:
            batch_sh = (row, vec, vec)
        return jax.tree_util.tree_map(laid, params), opt_sh, batch_sh, rep

    # ---------------- jitted functions ----------------

    def _pred_from_margin(self, margin: jax.Array) -> jax.Array:
        return (margin > 0).astype(jnp.float32)

    def _margin(self, params: FMParams, batch):
        if self.layout == "ell":
            return (_margin_ell(params, batch, self.deal is not None),
                    batch.label, batch.weight)
        if self.layout == "bcoo":
            # the ELL path's op on the flat ids: its VJP builds the dense
            # gradient
            ids, _, label, weight, margin = self._slots_view(batch)
            with jax.named_scope("fm_gather"):
                w_g, v_g = ell_table_gather((params.w, params.v), ids)
            return margin(params.w0, w_g, v_g), label, weight
        x, label, weight = batch
        return _margin_dense(params, x), label, weight

    def _loss_of_margin(self, margin, label, weight, axis=None) -> jax.Array:
        """The batch's mean loss; inside ``shard_map`` over ``axis``, this
        chip's rows' share of the whole batch's (the shares sum to it)."""
        with jax.named_scope("fm_loss"):
            if self.objective == "logistic":
                per = optax.sigmoid_binary_cross_entropy(margin, label)
            else:
                per = 0.5 * (margin - label) ** 2
            rows = weight.sum()
            if axis is not None:
                rows = jax.lax.psum(rows, axis)
            den = jnp.maximum(rows, 1.0)
            return (per * weight).sum() / den

    def loss_fn(self, params: FMParams, batch) -> jax.Array:
        loss = self._loss_of_margin(*self._margin(params, batch))
        if self.l2 > 0.0:
            with jax.named_scope("fm_loss"):
                loss = loss + 0.5 * self.l2 * (
                    jnp.sum(params.w ** 2) + jnp.sum(params.v ** 2))
        return loss

    def _table_rows(self) -> int:
        """The rows of the tables one chip holds."""
        return self.weight_dim if self.deal is None else self.deal.local_rows

    def table_update_route(self, num_slots: int) -> Tuple[str, str]:
        """``(route, reason)`` of a step on a batch of ``num_slots`` slots
        (ELL's ``B * K``, a ragged batch's flat count), from what the
        learner can observe. ``"fused"``: the loss is
        differentiated with respect to the gathered rows and the gradient
        kernel finishes Adam on the tables block by block
        (:func:`dmlc_tpu.ops.grad_scatter.fused_table_update`); no dense
        gradient exists. ``"dense"``: autodiff hands ``self.opt`` a dense
        gradient, because (``reason``) the ``layout`` gathers no rows, the
        ``optimizer`` is the caller's, ``l2`` puts a term into the
        gradient that is not in the rows, or the gradient is scattered by
        XLA (``scatter_xla``: the CPU, a small table, another dtype). A
        mesh is no reason: a chip of the laid tables takes the route of
        one chip with its shard's rows and the whole batch's slots, which
        are all gathered to it."""
        if self.layout == "dense":
            return "dense", "layout"
        if self._adam is None:
            return "dense", "optimizer"
        if self.l2 > 0.0:
            return "dense", "l2"
        route = grad_scatter.grad_scatter_route(
            self._table_rows(), num_slots, self.num_factors + 1,
            self.params.v.dtype, 2)
        if route != "kernel":
            return "dense", "scatter_xla"
        return "fused", "adam"

    def _slots_view(self, batch):
        """``(indices, real, label, weight, margin)`` of a batch whose
        table rows are gathered: the ids as the table ops take them (ELL's
        slots K-major, ``[K, B]``; a ragged batch's flat ``[N]``), which of
        them are not the batch's padding (``None``: the ops are not told;
        a ragged batch's padding is the tail of its bucket, 2% of the
        slots) and ``margin(w0, w_g, v_g)`` of the rows gathered at
        them."""
        if self.layout == "ell":
            indices, values = batch.indices.T, batch.values.T
            return (indices, values != 0, batch.label, batch.weight,
                    lambda w0, w_g, v_g: _margin_of_rows(
                        w0, w_g, v_g, values))
        mat, label, weight = batch
        ids, val, rows = _flat_slots(mat)
        return (ids, None, label, weight,
                lambda w0, w_g, v_g: _margin_of_slots(
                    w0, w_g, v_g, val, rows, mat.shape[0]))

    def _walk_books_of(self, batch):
        """:meth:`TrainLoopMixin.walk_books` of ``batch``: the slots as
        the update's walk sorts them. Under a mesh every chip walks the
        whole batch's slots with the ones it does not own at the sentinel:
        a count a chip."""
        if self.layout == "dense":
            return {}
        indices, real = self._slots_view(batch)[:2]
        rows = self._table_rows()
        route = grad_scatter.grad_scatter_route(
            rows, indices.size, self.num_factors + 1, self.params.v.dtype, 2)
        if route != "kernel":
            return {}
        if self.deal is None:
            return sorted_walk.walk_books(indices, rows, real)
        from jax.sharding import PartitionSpec as P

        def on_chip(batch):
            indices, real = self._slots_view(batch)[:2]
            books = sorted_walk.walk_books(table_exchange.open_slots(
                self.deal, indices, real).rows, rows)
            return {what: x[None] for what, x in books.items()}

        return jax.shard_map(
            on_chip, mesh=self.mesh, in_specs=(self._specs[2],),
            out_specs=P(self.data_axis), check_vma=False)(batch)

    def _fused_step(self, params, opt_state, batch):
        """The step with no dense gradient: of one device, or, inside
        ``shard_map`` over the mesh, of one chip on its shards of the
        tables and its rows of the batch (module docstring)."""
        adam, rest = opt_state[0], opt_state[1:]
        axis = None if self.deal is None else self.data_axis
        indices, real, label, weight, margin = self._slots_view(batch)
        with jax.named_scope("fm_gather"):
            (w_g, v_g), sorted_slots = table_rows(
                (params.w, params.v), indices, deal=self.deal, real=real)

        def loss_of(w0, w_g, v_g):
            return self._loss_of_margin(margin(w0, w_g, v_g), label, weight,
                                        axis)

        loss, (g_w0, g_w, g_v) = jax.value_and_grad(
            loss_of, argnums=(0, 1, 2))(params.w0, w_g, v_g)
        if axis is not None:
            with jax.named_scope("fm_loss"):
                loss, g_w0 = jax.lax.psum((loss, g_w0), axis)
        with jax.named_scope("fm_optimizer"):
            count = optax.safe_increment(adam.count)
            bias = self._adam.bias(count)
            w0 = self._adam.apply(g_w0, params.w0, adam.mu.w0, adam.nu.w0,
                                  bias[0], bias[1])
            w, v = grad_scatter.fused_table_update(
                indices, (g_w, g_v),
                ((params.w, adam.mu.w, adam.nu.w),
                 (params.v, adam.mu.v, adam.nu.v)),
                bias, self._adam, sorted_slots, deal=self.deal, real=real)
        params, mu, nu = (FMParams(*leaves) for leaves in zip(w0, w, v))
        return params, (adam._replace(count=count, mu=mu, nu=nu),
                        ) + tuple(rest), loss

    def _counted_route(self, batch) -> str:
        """:meth:`table_update_route` of ``batch``, counted in
        ``table_update_route`` (while a step is traced)."""
        route, reason = self.table_update_route(
            batch.indices.size if self.layout == "ell"
            else batch[0].nse if self.layout == "bcoo" else 0)
        _telemetry.REGISTRY.counter(
            _telemetry.TABLE_UPDATE_ROUTE_METRIC, route=route,
            reason=reason).inc(1)
        return route

    def _dense_step(self, params, opt_state, batch):
        loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
        with jax.named_scope("fm_optimizer"):
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def _build_step(self):
        if self.deal is not None:
            return self._build_laid_step()

        def step(params, opt_state, batch):
            if self._counted_route(batch) == "fused":
                params, opt_state, loss = self._fused_step(
                    params, opt_state, batch)
            else:
                params, opt_state, loss = self._dense_step(
                    params, opt_state, batch)
            # keep the padding sink inert
            with jax.named_scope("fm_sink"):
                params = params._replace(
                    w=params.w.at[-1].set(0.0),
                    v=params.v.at[-1].set(0.0),
                )
            return params, opt_state, loss

        return self._jit_step(step)

    def _build_laid_step(self):
        """The step under a mesh: on the fused route one chip's
        (:meth:`_fused_step`) under ``shard_map``, elsewhere the
        one-device step on the row-sharded operands, partitioned by XLA."""
        from jax.sharding import PartitionSpec as P

        from dmlc_tpu.parallel.mesh import count_up

        deal, axis, sink = self.deal, self.data_axis, self.weight_dim - 1
        sink_chip, sink_row = (int(x) for x in deal.place(sink))

        def inert(table):
            # the padding sink lives on one chip; a row written in place
            mine = jax.lax.axis_index(axis) == sink_chip
            return table.at[sink_row].set(
                jnp.where(mine, 0.0, table[sink_row]))

        def on_chip(params, opt_state, books, batch):
            params, opt_state, loss = self._fused_step(
                params, opt_state, batch)
            with jax.named_scope("fm_sink"):
                params = params._replace(w=inert(params.w),
                                         v=inert(params.v))
            with jax.named_scope("fm_shard_books"):
                books = count_up(books, deal.owned_slots(
                    batch.indices, batch.values != 0))
            return params, opt_state, books, loss

        def step(params, opt_state, batch):
            fused = self._counted_route(batch) == "fused"
            _telemetry.REGISTRY.counter(
                _telemetry.TABLE_SHARD_ROUTE_METRIC, learner="fm",
                shards=str(deal.shards), deal="ranges",
                collective="all_slots" if fused else "xla").inc(1)
            opt_state, books = self._without_books(opt_state)
            if fused:
                params_sp, opt_sp, batch_sp, _ = self._specs
                opt_sp, books_sp = self._without_books(opt_sp)
                params, opt_state, books, loss = jax.shard_map(
                    on_chip, mesh=self.mesh, check_vma=False,
                    in_specs=(params_sp, opt_sp, books_sp, batch_sp),
                    out_specs=(params_sp, opt_sp, books_sp, P()))(
                    params, opt_state, books, batch)
            else:
                params, opt_state, loss = self._dense_step(
                    params, opt_state, batch)
                with jax.named_scope("fm_sink"):
                    params = params._replace(w=params.w.at[sink].set(0.0),
                                             v=params.v.at[sink].set(0.0))
                if self.layout == "ell":
                    with jax.named_scope("fm_shard_books"):
                        books = count_up(books, deal.count_owned(
                            batch.indices, batch.values != 0))
            return params, self._with_books(opt_state, books), loss

        params_sh, opt_sh, batch_sh, rep = self._shardings
        return self._jit_step(step, params_sh=params_sh, batch_sh=batch_sh,
                              opt_sh=opt_sh, loss_sh=rep)

    def predict(self, batch) -> jax.Array:
        """Raw margin for a batch (apply sigmoid for probabilities)."""
        return self._predict(self.params, batch)
