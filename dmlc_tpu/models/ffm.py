"""Field-aware factorization machine learner over the device pipeline.

The libfm text format (``label field:index:value``, src/data/libfm_parser.h)
carries a *field* id beside every feature, and the model that reads it is
the field-aware factorization machine (Juan, Zhuang, Chin, Lin, RecSys
2016; libffm): every feature keeps one latent vector **per field**, and a
pair of features interacts through the vector each holds for the other's
field. With slots ``s = 1..K`` of a row holding id ``i_s``, field ``f_s``
and value ``x_s`` (padding: the sink id, value 0):

    r    = 1 / sum_s x_s^2                      (instance-wise normalisation)
    phi  = r * sum_{s<t} <W[i_s, f_t, :], W[i_t, f_s, :]> x_s x_t
    loss = weight * [ log(1 + exp(-y phi))
                      + l2/2 * sum_{s != t, x_s x_t != 0} |W[i_s, f_t, :]|^2 ]

with ``y = 2 * label - 1``, no bias and no linear term: libffm's objective,
a *sum* over instances. One AdaGrad update is made per batch on the summed
gradient, in libffm's form (accumulators start at 1, no epsilon):
``G += g^2; W -= lr * g / sqrt(G)``. A coordinate no row of the batch uses
has ``g == 0`` exactly, so neither it nor its accumulator changes:
regularisation touches used coordinates only, as in libffm.

The table is ``[num_col + 1, num_fields * num_factors]`` float32 (row
``num_col`` the padding sink, zero and inert; column ``f * num_factors +
d`` is factor ``d`` for field ``f``). Its rows are gathered by
:func:`dmlc_tpu.ops.sparse.ell_table_gather`, the op the plain FM gathers
its two tables with: where that is faster the forward reads them from
the sorted slots with a one-hot MXU kernel (ops/table_gather.py, value for
value what ``jnp.take`` reads; counter ``table_gather_route``) and the
backward builds the dense gradient from the sorted batch rows with its
twin (ops/grad_scatter.py; the counter ``grad_scatter_route`` says which
route a step took). No ``(x @ V)^2`` trick applies to this model: the step
works on a ``[factors, K, K, B]`` pair tensor, written batch-minor so that
every elementwise operation fills the TPU's lanes.

Batches come from ``DeviceIter(layout="ell", fields=True)``. A ``mesh`` is
refused: the published deployment shards the table by rows, which
``parallel/mesh.py`` cannot do yet (ROADMAP).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax

from dmlc_tpu.models._loop import TrainLoopMixin
from dmlc_tpu.ops.sparse import EllBatch, ell_table_gather
from dmlc_tpu.utils.check import check


class FFMParams(NamedTuple):
    w: jax.Array        # [W, m * k]; last row = ELL padding sink, pinned to 0


# The step's stages carry fixed ``jax.named_scope`` names, as the FM's do
# (models/fm.py; docs/observability.md): ffm_gather (table rows brought to
# the batch; the gradient's scatter is its transpose and reads
# ``transpose(jvp(ffm_gather))``), ffm_interaction, ffm_loss,
# ffm_optimizer, ffm_sink.

def _pair_terms(params: FFMParams, batch: EllBatch, num_fields: int,
                num_factors: int):
    """``(phi [B], reg [B])``: the interaction of every row and the sum of
    squares its regulariser takes, both before ``weight``."""
    check(batch.fields is not None,
          "FFMLearner: the batch carries no field plane; build the "
          "DeviceIter with fields=True")
    m, k = num_fields, num_factors
    slots, rows = batch.indices.shape[1], batch.indices.shape[0]
    # slot-major, batch-minor: [K, B] planes, so that a pair tensor is
    # [.., K, K, B] with the batch on the lanes
    with jax.named_scope("ffm_gather"):
        (got,) = ell_table_gather((params.w,), batch.indices.T)  # [K, B, m*k]
    with jax.named_scope("ffm_interaction"):
        wg = jnp.moveaxis(got, -1, 0).reshape(m, k, slots, rows)
        f = batch.fields.T.astype(jnp.int32)                  # [K, B]
        x = batch.values.T                                    # [K, B]
        # a[d, s, t, b] = W[i_s, f_t, d] and c[d, s, t, b] = W[i_t, f_s, d]:
        # selects over the m fields, exact in float32 (a one-hot
        # contraction would round the table to the MXU's bfloat16)
        a = c = jnp.zeros((k, slots, slots, rows), params.w.dtype)
        for field in range(m):
            here = f == field
            a = a + jnp.where(here[None, None, :, :],
                              wg[field][:, :, None, :], 0.0)
            c = c + jnp.where(here[None, :, None, :],
                              wg[field][:, None, :, :], 0.0)
        s_id = jax.lax.broadcasted_iota(jnp.int32, (slots, slots, 1), 0)
        t_id = jax.lax.broadcasted_iota(jnp.int32, (slots, slots, 1), 1)
        xx = x[:, None, :] * x[None, :, :]                    # [K, K, B]
        pairs = jnp.sum(a * c, axis=0) * xx
        norm = jnp.sum(x * x, axis=0)
        r = jnp.where(norm > 0, 1.0 / norm, 0.0)    # an empty row: phi = 0
        phi = r * jnp.sum(jnp.where(s_id < t_id, pairs, 0.0), axis=(0, 1))
        used = (xx != 0) & (s_id != t_id)
        reg = jnp.sum(jnp.where(used, jnp.sum(a * a, axis=0), 0.0),
                      axis=(0, 1))
    return phi, reg


class FFMLearner(TrainLoopMixin):
    """Field-aware factorization machine, logistic loss, exact float32
    AdaGrad in libffm's form (module docstring). ``num_col`` is the
    feature-id space (``DeviceIter``'s ``num_col``), ``num_fields`` the
    number of field ids; batches are ``EllBatch`` with a ``fields`` plane.
    ``learning_rate`` / ``l2`` / ``num_factors`` default to libffm's
    ``-r 0.2 -l 0.00002 -k 4``. The start is ``U[0, 1 / sqrt(num_factors))``
    from ``seed``, the sink row zero."""

    layout = "ell"

    def __init__(
        self,
        num_col: int,
        num_fields: int,
        num_factors: int = 4,
        learning_rate: float = 0.2,
        l2: float = 2e-5,
        seed: int = 0,
        mesh=None,
    ):
        check(mesh is None,
              "FFMLearner: no mesh — the deployment shards the table by "
              "rows, which parallel/mesh.py cannot do yet (ROADMAP)")
        check(num_fields >= 1 and num_factors >= 1,
              "FFMLearner: num_fields and num_factors must be >= 1")
        self.num_col = num_col
        self.num_fields = num_fields
        self.num_factors = num_factors
        self.learning_rate = learning_rate
        self.l2 = l2
        self.mesh = None
        self.weight_dim = num_col + 1           # +1 = the ELL padding sink
        width = num_fields * num_factors
        scale = 1.0 / float(num_factors) ** 0.5

        def start(key):
            w = jax.random.uniform(key, (self.weight_dim, width),
                                   jnp.float32) * scale
            return w.at[-1].set(0.0)            # sink row inert

        self.params = FFMParams(w=jax.jit(start)(jax.random.PRNGKey(seed)))
        # AdaGrad as libffm has it: G starts at 1, the update is
        # g / sqrt(G) with no epsilon
        self.opt = optax.chain(
            optax.scale_by_rss(initial_accumulator_value=1.0, eps=0.0),
            optax.scale(-learning_rate))
        self.opt_state = self.opt.init(self.params)
        self._step = self._build_step()
        self._accuracy = self._build_accuracy()
        self._predict = jax.jit(
            lambda params, batch: self._margin(params, batch)[0])

    def device_num_col(self) -> int:
        """The ``num_col`` a DeviceIter must use to feed this learner."""
        return self.weight_dim - 1

    def batch_shardings(self):
        return None

    @property
    def accumulators(self) -> jax.Array:
        """AdaGrad's sums of squared gradients ``G``, shaped as the
        table (1 where a coordinate never had a gradient)."""
        return self.opt_state[0].sum_of_squares.w

    # ---------------- jitted functions ----------------

    def _pred_from_margin(self, margin: jax.Array) -> jax.Array:
        return (margin > 0).astype(jnp.float32)

    def _margin(self, params: FFMParams, batch: EllBatch):
        phi, _ = _pair_terms(params, batch, self.num_fields,
                             self.num_factors)
        return phi, batch.label, batch.weight

    def loss_sum(self, params: FFMParams, batch: EllBatch) -> jax.Array:
        """libffm's objective over the batch: the *sum* over its rows."""
        phi, reg = _pair_terms(params, batch, self.num_fields,
                               self.num_factors)
        with jax.named_scope("ffm_loss"):
            y = 2.0 * batch.label - 1.0
            per = jnp.logaddexp(0.0, -y * phi) + (0.5 * self.l2) * reg
            return jnp.sum(per * batch.weight)

    def _build_step(self):
        def step(params, opt_state, batch):
            total, grads = jax.value_and_grad(self.loss_sum)(params, batch)
            with jax.named_scope("ffm_optimizer"):
                updates, opt_state = self.opt.update(grads, opt_state,
                                                     params)
                params = optax.apply_updates(params, updates)
            with jax.named_scope("ffm_sink"):
                params = params._replace(w=params.w.at[-1].set(0.0))
            with jax.named_scope("ffm_loss"):
                # the mean over the batch's rows, for a reader; the
                # update above is on the sum
                loss = total / jnp.maximum(batch.weight.sum(), 1.0)
            return params, opt_state, loss

        return self._jit_step(step)

    def predict(self, batch) -> jax.Array:
        """Raw interaction ``phi`` for a batch (apply sigmoid for click
        probabilities)."""
        return self._predict(self.params, batch)
