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

Params are a pytree under ``jax.jit``; with a mesh, batches shard over the
``data`` axis and the tables and optimizer state are replicated. For the
``dense`` layout XLA inserts the gradient psum over ICI. For ``ell`` the
op's VJP chooses what crosses the chips: where the table is large against
the batch, the batch's cotangent rows are all-gathered and every chip
builds the whole dense gradient itself, or on the fused route updates its
replica in place from them (no table is all-reduced; the
replicas stay bit-identical because they run the same arithmetic on the
same inputs), otherwise the dense gradient is all-reduced as XLA would
(ops/grad_scatter.py; ``grad_scatter_route{collective=}``). Either way the
SPMD shape is :class:`dmlc_tpu.models.LinearLearner`'s, including the
``steps_per_epoch`` / ``max_steps`` collective step-count contract.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from dmlc_tpu.models._loop import TrainLoopMixin
from dmlc_tpu.ops import grad_scatter, sorted_walk
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
                    val: jax.Array, k_major: bool) -> jax.Array:
    # the gathered rows [K, B] and [K, B, F] and the slots' values [K, B]
    # (``k_major``; else [B, K], [B, K, F] and [B, K]); padding slots carry
    # value 0 so they contribute nothing to any sum
    slots, rows = (0, "kbf,kb->bf") if k_major else (1, "bkf,bk->bf")
    with jax.named_scope("fm_interaction"):
        linear = jnp.sum(w_g * val, axis=slots) + w0
        s = jnp.einsum(rows, v_g, val)                     # sum_k v_k x_k
        # sum_k v_k^2 x_k^2
        s2 = jnp.einsum(rows, v_g * v_g, val * val)
        return linear + 0.5 * jnp.sum(s * s - s2, axis=-1)


def _ell_slots(batch: EllBatch, mesh):
    """``(indices, values, k_major)`` of an ELL batch as the table ops take
    its slots: K-major ``[K, B]`` on one chip, where the batch's padding
    (value 0) then lies behind its real slots and is neither read nor
    permuted (``table_rows(real=)``); ``[B, K]`` as it came under a mesh,
    whose shards cut the leading axis."""
    if mesh is None:
        return batch.indices.T, batch.values.T, True
    return batch.indices, batch.values, False


def _margin_ell(params: FMParams, batch: EllBatch, mesh=None,
                data_axis: str = "data") -> jax.Array:
    # gathers over the factor table. The op's own VJP builds the dense
    # gradient (ops/grad_scatter.py); inside the scope, so the backward
    # reads transpose(jvp(fm_gather)) whichever route it takes
    indices, values, k_major = _ell_slots(batch, mesh)
    with jax.named_scope("fm_gather"):
        w_g, v_g = ell_table_gather((params.w, params.v), indices, mesh,
                                    data_axis, None, values != 0)
    return _margin_of_rows(params.w0, w_g, v_g, values, k_major)


class FMLearner(TrainLoopMixin):
    """Second-order factorization machine (logistic or squared objective).

    ``layout`` matches the DeviceIter layout ('dense', 'ell', or 'bcoo' —
    the last single-device: ragged rows as flat slots on the ELL path's
    table kernels, their sums by :mod:`dmlc_tpu.ops.slot_rows`); factors
    initialize to small gaussian noise (all-zero factors have zero gradient
    through the interaction term). With ``mesh``, batches shard over
    ``data_axis`` and every chip applies the global batch's gradient to its
    replica (module docstring: what crosses the chips).
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
        self.mesh = mesh
        self.data_axis = data_axis
        # +1 = the padding sink: ELL's pad id, and the id that a BCOO
        # batch's pad coordinates (rows, num_col) carry
        self.weight_dim = num_col + 1
        key = jax.random.PRNGKey(seed)
        v = init_scale * jax.random.normal(
            key, (self.weight_dim, num_factors), jnp.float32)
        v = v.at[-1].set(0.0)  # sink row inert
        self.params = FMParams(
            w0=jnp.zeros((), jnp.float32),
            w=jnp.zeros(self.weight_dim, jnp.float32),
            v=v,
        )
        self.opt = optimizer or optax.adam(learning_rate)
        self.opt_state = self.opt.init(self.params)
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
        """The ``num_col`` a DeviceIter must use to feed this learner."""
        if self.layout == "dense":
            return self.weight_dim
        return self.weight_dim - 1

    def batch_shardings(self):
        return self._shardings()[1]

    def _checkpoint_spec(self):
        """What a checkpoint holds (docs/checkpoint.md): the parameters
        and the optimiser's whole state, whichever ``layout`` feeds them."""
        from dmlc_tpu.models._checkpoint import CheckpointSpec

        return CheckpointSpec(
            meta={"class": "FMLearner", "num_col": self.num_col,
                  "num_factors": self.num_factors,
                  "objective": self.objective, "layout": self.layout,
                  "l2": self.l2, "optimizer": self._opt_meta},
            tree={"params": self.params, "opt_state": self.opt_state})

    # ---------------- jitted functions ----------------

    def _pred_from_margin(self, margin: jax.Array) -> jax.Array:
        return (margin > 0).astype(jnp.float32)

    def _margin(self, params: FMParams, batch):
        if self.layout == "ell":
            return (_margin_ell(params, batch, self.mesh, self.data_axis),
                    batch.label, batch.weight)
        if self.layout == "bcoo":
            # the ELL path's op on the flat ids: its VJP builds the dense
            # gradient
            ids, _, label, weight, margin = self._slots_view(batch)
            with jax.named_scope("fm_gather"):
                w_g, v_g = ell_table_gather((params.w, params.v), ids, None,
                                            self.data_axis)
            return margin(params.w0, w_g, v_g), label, weight
        x, label, weight = batch
        return _margin_dense(params, x), label, weight

    def _loss_of_margin(self, margin, label, weight) -> jax.Array:
        with jax.named_scope("fm_loss"):
            if self.objective == "logistic":
                per = optax.sigmoid_binary_cross_entropy(margin, label)
            else:
                per = 0.5 * (margin - label) ** 2
            den = jnp.maximum(weight.sum(), 1.0)
            return (per * weight).sum() / den

    def loss_fn(self, params: FMParams, batch) -> jax.Array:
        loss = self._loss_of_margin(*self._margin(params, batch))
        if self.l2 > 0.0:
            with jax.named_scope("fm_loss"):
                loss = loss + 0.5 * self.l2 * (
                    jnp.sum(params.w ** 2) + jnp.sum(params.v ** 2))
        return loss

    def _shardings(self):
        if self.mesh is None:
            return None, None
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        params_sh = FMParams(w0=rep, w=rep, v=rep)
        vec = NamedSharding(mesh, P(self.data_axis))
        row = NamedSharding(mesh, P(self.data_axis, None))
        if self.layout == "ell":
            batch_sh = EllBatch(indices=row, values=row, label=vec, weight=vec)
        else:
            batch_sh = (row, vec, vec)
        return params_sh, batch_sh

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
        gradient that is not in the rows, the gradient is scattered by XLA
        (``scatter_xla``: the CPU, a small table, another dtype) or is
        all-reduced over the mesh (``collective_table``)."""
        if self.layout == "dense":
            return "dense", "layout"
        if self._adam is None:
            return "dense", "optimizer"
        if self.l2 > 0.0:
            return "dense", "l2"
        shards = 1 if self.mesh is None else self.mesh.shape[self.data_axis]
        route, collective = grad_scatter.grad_scatter_route(
            self.weight_dim, num_slots, self.num_factors + 1,
            self.params.v.dtype, 2, shards)
        if route != "kernel":
            return "dense", "scatter_xla"
        if collective == "table":
            return "dense", "collective_table"
        return "fused", "adam"

    def _slots_view(self, batch):
        """``(indices, real, label, weight, margin)`` of a batch whose
        table rows are gathered: the ids as the table ops take them (ELL's
        slots as :func:`_ell_slots` lays them, a ragged batch's flat
        ``[N]``), which of them are not the batch's padding (``None``: the
        ops are not told; a ragged batch's padding is the tail of its
        bucket, 2% of the slots) and ``margin(w0, w_g, v_g)`` of the
        rows gathered at them."""
        if self.layout == "ell":
            indices, values, k_major = _ell_slots(batch, self.mesh)
            return (indices, values != 0, batch.label, batch.weight,
                    lambda w0, w_g, v_g: _margin_of_rows(
                        w0, w_g, v_g, values, k_major))
        mat, label, weight = batch
        ids, val, rows = _flat_slots(mat)
        return (ids, None, label, weight,
                lambda w0, w_g, v_g: _margin_of_slots(
                    w0, w_g, v_g, val, rows, mat.shape[0]))

    def _walk_books_of(self, batch):
        """:meth:`TrainLoopMixin.walk_books` of ``batch``: the slots as
        the update's walk sorts them, the whole batch's under a mesh that
        all-gathers its rows (``collective="rows"``). Not counted where
        the table is all-reduced: every chip then walks its own shard of
        the slots, which no cell does."""
        if self.layout == "dense":
            return {}
        indices, real = self._slots_view(batch)[:2]
        shards = 1 if self.mesh is None else self.mesh.shape[self.data_axis]
        route, collective = grad_scatter.grad_scatter_route(
            self.weight_dim, indices.size, self.num_factors + 1,
            self.params.v.dtype, 2, shards)
        if route != "kernel" or collective == "table":
            return {}
        return sorted_walk.walk_books(indices, self.weight_dim, real)

    def _fused_step(self, params, opt_state, batch):
        adam, rest = opt_state[0], opt_state[1:]
        indices, real, label, weight, margin = self._slots_view(batch)
        with jax.named_scope("fm_gather"):
            (w_g, v_g), sorted_slots = table_rows(
                (params.w, params.v), indices, self.mesh, self.data_axis,
                real=real)

        def loss_of(w0, w_g, v_g):
            return self._loss_of_margin(margin(w0, w_g, v_g), label, weight)

        loss, (g_w0, g_w, g_v) = jax.value_and_grad(
            loss_of, argnums=(0, 1, 2))(params.w0, w_g, v_g)
        with jax.named_scope("fm_optimizer"):
            count = optax.safe_increment(adam.count)
            bias = self._adam.bias(count)
            w0 = self._adam.apply(g_w0, params.w0, adam.mu.w0, adam.nu.w0,
                                  bias[0], bias[1])
            w, v = grad_scatter.fused_table_update(
                indices, (g_w, g_v),
                ((params.w, adam.mu.w, adam.nu.w),
                 (params.v, adam.mu.v, adam.nu.v)),
                bias, self._adam, self.mesh, self.data_axis, sorted_slots,
                real=real)
        params, mu, nu = (FMParams(*leaves) for leaves in zip(w0, w, v))
        return params, (adam._replace(count=count, mu=mu, nu=nu),
                        ) + tuple(rest), loss

    def _build_step(self):
        def step(params, opt_state, batch):
            route, reason = self.table_update_route(
                batch.indices.size if self.layout == "ell"
                else batch[0].nse if self.layout == "bcoo" else 0)
            _telemetry.REGISTRY.counter(
                _telemetry.TABLE_UPDATE_ROUTE_METRIC, route=route,
                reason=reason).inc(1)
            if route == "fused":
                params, opt_state, loss = self._fused_step(
                    params, opt_state, batch)
            else:
                loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
                with jax.named_scope("fm_optimizer"):
                    updates, opt_state = self.opt.update(grads, opt_state,
                                                         params)
                    params = optax.apply_updates(params, updates)
            # keep the padding sink inert
            with jax.named_scope("fm_sink"):
                params = params._replace(
                    w=params.w.at[-1].set(0.0),
                    v=params.v.at[-1].set(0.0),
                )
            return params, opt_state, loss

        params_sh, batch_sh = self._shardings()
        if params_sh is None:
            return self._jit_step(step)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        opt_sh = jax.tree_util.tree_map(lambda _: rep, self.opt_state)
        return self._jit_step(step, params_sh=params_sh, batch_sh=batch_sh,
                              opt_sh=opt_sh, loss_sh=rep)

    def predict(self, batch) -> jax.Array:
        """Raw margin for a batch (apply sigmoid for probabilities)."""
        return self._predict(self.params, batch)
