"""Linear learners (logistic / least-squares) on the device pipeline.

TPU-first design:
- pure functional step (params pytree in, params out) under ``jax.jit``,
- batch sharded over the mesh ``data`` axis, params replicated; XLA inserts
  the gradient ``psum`` over ICI (no hand-written allreduce — the tracker's
  ring topology, tracker.py:202-234, has no code analog here by design),
- optional feature-dim sharding of the weight vector over a ``model`` axis
  for very wide models (the dense path shards the [B, D] batch's D too),
- dense path hits the MXU via a plain matmul; sparse path uses the ELL
  gather (ops/sparse.ell_matvec).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dmlc_tpu.models._loop import TrainLoopMixin
from dmlc_tpu.ops.sparse import EllBatch, ell_matvec
from dmlc_tpu.utils.check import check


class LinearParams(NamedTuple):
    weight: jax.Array  # [W]; dense/ell: last slot is the padding sink,
    #                    pinned to 0 — for bcoo it is a real feature weight
    bias: jax.Array    # scalar


def init_params(weight_dim: int, num_class: int = 1,
                dtype=jnp.float32) -> LinearParams:
    if num_class > 1:
        # multinomial: weight [W, C], per-class bias (softmax objective)
        return LinearParams(
            weight=jnp.zeros((weight_dim, num_class), dtype=dtype),
            bias=jnp.zeros(num_class, dtype=dtype),
        )
    return LinearParams(
        weight=jnp.zeros(weight_dim, dtype=dtype),
        bias=jnp.zeros((), dtype=dtype),
    )


def _margin_dense(params: LinearParams, x: jax.Array) -> jax.Array:
    # x is [B, W] (features padded to the weight width): full-width matmul,
    # no slicing — keeps the model-axis sharding of both operands aligned.
    # No precision= here: for the [W] vector this is a matrix-VECTOR
    # product, which a TPU v5e runs in float32 at default precision
    # (default and HIGHEST gave identical margins, 2.6e-6 from float64,
    # and the 28-column flagship's loss stays within 5e-6 of the float32
    # reference — chip_smoke.py phase 1). The multinomial [W, C] matmul
    # has not been compared on the chip.
    return x @ params.weight + params.bias


def _margin_ell(params: LinearParams, batch: EllBatch,
                use_auto: bool = True) -> jax.Array:
    if use_auto:
        # single-device / replicated-weight case (the default): route
        # through the auto entry, which picks the pallas one-hot kernel
        # in its measured win band — lane-aligned D in [512, 4096] on a
        # TPU backend (SPARSE_TPU_r05.json; ell_matvec_auto's docstring
        # carries the A/B numbers and the one known in-band anomaly) —
        # and the XLA gather everywhere else. Sharded weights stay on
        # ell_matvec — pallas_call is not shard_map-aware here.
        from dmlc_tpu.ops.pallas_sparse import ell_matvec_auto

        return ell_matvec_auto(params.weight, batch) + params.bias
    return ell_matvec(params.weight, batch) + params.bias


def _loss_from_margin(margin, label, weight, objective: str, l2: float, params):
    if objective == "logistic":
        per = optax.sigmoid_binary_cross_entropy(margin, label)
    elif objective == "squared":
        per = 0.5 * (margin - label) ** 2
    elif objective == "softmax":
        # margin is [B, C]; labels are class ids carried in the float label
        per = optax.softmax_cross_entropy_with_integer_labels(
            margin, label.astype(jnp.int32))
    else:
        raise ValueError(f"unknown objective {objective!r}")
    den = jnp.maximum(weight.sum(), 1.0)
    loss = (per * weight).sum() / den
    if l2 > 0.0:
        # the padding sink is pinned to 0, so regularizing the full vector
        # adds nothing for it
        loss = loss + 0.5 * l2 * jnp.sum(params.weight ** 2)
    return loss


class LinearLearner(TrainLoopMixin):
    """Logistic / least-squares / multinomial-softmax learner with optax
    updates (the learner family the reference's Row::SDot was built for,
    data.h:146-161, widened to multi-class).

    ``layout`` must match the DeviceIter layout ('dense', 'ell', or
    'bcoo' — the last single-device, margin via bcoo_dot_general);
    ``objective='softmax'`` needs ``num_class >= 2`` and works on any
    layout — the ELL path gathers rows of the [W, C] table (labels are
    integer class ids carried in the float label column).
    """

    def __init__(
        self,
        num_col: int,
        objective: str = "logistic",
        layout: str = "dense",
        optimizer: Optional[optax.GradientTransformation] = None,
        learning_rate: float = 0.1,
        l2: float = 0.0,
        mesh=None,
        data_axis: str = "data",
        model_axis: Optional[str] = None,
        num_class: int = 1,
    ):
        check(layout in ("dense", "ell", "bcoo"),
              "LinearLearner: layout must be dense|ell|bcoo")
        check(layout != "bcoo" or mesh is None,
              "layout='bcoo' is single-device (matches DeviceIter bcoo)")
        check((objective == "softmax") == (num_class > 1),
              "softmax objective iff num_class > 1")
        self.num_class = num_class
        self.num_col = num_col
        self.objective = objective
        self.layout = layout
        self.l2 = l2
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        # weight length: num_col features + 1 padding sink, rounded up so a
        # model-axis sharding divides it evenly. BCOO batches carry real
        # coordinates only (pad entries are out-of-bounds and masked), so
        # no sink slot is needed there.
        if layout == "bcoo":
            self.weight_dim = num_col
        else:
            model_size = 1
            if mesh is not None and model_axis is not None:
                model_size = mesh.shape[model_axis]
            self.weight_dim = -(-(num_col + 1) // model_size) * model_size
        self.opt = optimizer or optax.sgd(learning_rate)
        self._opt_meta = ({"name": "caller"} if optimizer is not None
                          or callable(learning_rate) else
                          {"name": "sgd", "learning_rate": learning_rate})
        self.params = init_params(self.weight_dim, num_class)
        self.opt_state = self.opt.init(self.params)
        self._step = self._build_step()
        self._predict = self._build_predict()
        self._accuracy = self._build_accuracy()

    def _checkpoint_spec(self):
        """What a checkpoint holds (docs/checkpoint.md)."""
        from dmlc_tpu.models._checkpoint import CheckpointSpec

        return CheckpointSpec(
            meta={"class": "LinearLearner", "num_col": self.num_col,
                  "num_class": self.num_class, "objective": self.objective,
                  "layout": self.layout, "l2": self.l2,
                  "weight_dim": self.weight_dim,
                  "optimizer": self._opt_meta},
            tree={"params": self.params, "opt_state": self.opt_state})

    def batch_shardings(self):
        """Batch placement for a DeviceIter feeding this learner (or None)."""
        return self._shardings()[1]

    def device_num_col(self) -> int:
        """The ``num_col`` a DeviceIter must use to feed this learner.

        dense: batches are [B, weight_dim] (zero columns beyond the data's
        features); ell: pad index = weight_dim - 1, the pinned-zero sink;
        bcoo: the true column count (OOB pad coords are masked).
        """
        if self.layout == "ell":
            return self.weight_dim - 1
        return self.weight_dim

    # ---------------- jitted functions ----------------

    def _margin(self, params: LinearParams, batch):
        if self.layout == "ell":
            return (_margin_ell(params, batch, use_auto=self.mesh is None),
                    batch.label, batch.weight)
        # dense and bcoo share one margin: _margin_dense's `x @ weight` is
        # bcoo_dot_general when x is a BCOO batch (AD-complete wrt weights)
        x, label, weight = batch
        return _margin_dense(params, x), label, weight

    def _pred_from_margin(self, margin: jax.Array) -> jax.Array:
        if self.num_class > 1:
            return jnp.argmax(margin, axis=-1).astype(jnp.float32)
        return (margin > 0).astype(jnp.float32)

    def loss_fn(self, params: LinearParams, batch) -> jax.Array:
        # the scope names are the FM learner's (models/fm.py), stage for
        # stage: one vocabulary for every learner's device trace
        with jax.named_scope("fm_gather"):
            margin, label, weight = self._margin(params, batch)
        with jax.named_scope("fm_loss"):
            return _loss_from_margin(margin, label, weight, self.objective,
                                     self.l2, params)

    def _shardings(self):
        """(params, batch) shardings for pjit when a mesh is present."""
        if self.mesh is None:
            return None, None
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        if self.model_axis is not None:
            # feature-sharded weights (the TP analog for very wide models)
            if self.num_class > 1:
                p_w = NamedSharding(mesh, P(self.model_axis, None))
            else:
                p_w = NamedSharding(mesh, P(self.model_axis))
        else:
            p_w = NamedSharding(mesh, P())
        p_scalar = NamedSharding(mesh, P())
        params_sh = LinearParams(weight=p_w, bias=p_scalar)
        if self.layout == "ell":
            row = NamedSharding(mesh, P(self.data_axis, None))
            vec = NamedSharding(mesh, P(self.data_axis))
            batch_sh = EllBatch(indices=row, values=row, label=vec, weight=vec)
        else:
            if self.model_axis is not None:
                x_sh = NamedSharding(mesh, P(self.data_axis, self.model_axis))
            else:
                x_sh = NamedSharding(mesh, P(self.data_axis, None))
            vec = NamedSharding(mesh, P(self.data_axis))
            batch_sh = (x_sh, vec, vec)
        return params_sh, batch_sh

    def _build_step(self):
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
            with jax.named_scope("fm_optimizer"):
                updates, opt_state = self.opt.update(grads, opt_state,
                                                     params)
                params = optax.apply_updates(params, updates)
            if self.layout != "bcoo":
                # keep the padding sink at zero so ELL gathers of pad slots
                # are inert (bcoo has no sink: its last weight is real)
                with jax.named_scope("fm_sink"):
                    params = params._replace(
                        weight=params.weight.at[-1].set(0.0))
            return params, opt_state, loss

        params_sh, batch_sh = self._shardings()
        return self._jit_step(step, params_sh=params_sh, batch_sh=batch_sh)

    def _build_predict(self):
        def predict(params, batch):
            if self.layout == "ell":
                return _margin_ell(params, batch, use_auto=self.mesh is None)
            return _margin_dense(params, batch[0])  # dense or bcoo operand

        return jax.jit(predict)

    # ---------------- public API ----------------

    def predict(self, batch) -> jax.Array:
        return self._predict(self.params, batch)

