"""ALX-style sharded alternating least squares on the device pipeline.

ALX (arXiv:2112.02194) trains large-scale matrix factorization on TPU pods
by sharding the factor tables and turning the per-row least-squares solve
into dense batched linear algebra — gathers of the fixed side's factors,
normal equations on the MXU, `jnp.linalg.solve`, scatter of the solved
side. This learner is that recipe adapted to stream off dmlc_tpu's ingest
stack instead of a pre-materialized embedding layout:

* **Data encoding.** Each corpus row is one user's rating list in libsvm
  form — the float *label* carries the user/row id, the ``item:rating``
  features carry the observed entries. That means the whole existing
  parse / block-cache / shuffle / service stack moves ratings without a
  single new wire type: `EllBatch.label` delivers row ids to the jitted
  step, `indices`/`values` deliver the rated items.
* **User half-step, per batch.** For every row in the batch the normal
  equations ``A_u = V_u^T V_u + reg*I`` and ``b_u = V_u^T r_u`` are formed
  from gathers of the (fixed) item table — the right-hand side goes
  through :func:`dmlc_tpu.ops.pallas_sparse.ell_matvec_auto`, the
  sanctioned sparse hot-path entry that picks the Pallas one-hot kernel
  in its measured win band and the XLA gather elsewhere — then a batched
  ``jnp.linalg.solve`` and a row scatter update the user table exactly.
* **Item half-step, per epoch.** The item side's normal equations
  accumulate across the epoch inside ``opt_state`` (a ``[D+1, F, F]``
  gram and ``[D+1, F]`` rhs, scatter-added per batch) and are solved in
  one donated jitted :meth:`AlsLearner.finalize_items` at the epoch
  boundary — the streaming-friendly shape of ALX's alternation: each
  epoch is one full user sweep *and* one item solve.
* **Padding discipline.** ELL pad slots carry index ``num_items`` — the
  item table's sink row, pinned to zero. Pad gathers therefore contribute
  nothing to ``A_u``/``b_u``/the loss for free; pad scatter-adds land in
  the sink row and are zeroed again by ``finalize_items``.
* **Sharding.** Batches shard over the mesh data axis; both factor
  tables and the normal-equation accumulators stay replicated, so the
  per-device scatters reconcile through XLA's SPMD lowering (the pod
  story: `pod_sharding=` hands each host a disjoint set of user rows, so
  row scatters never conflict across hosts). The loss comes back
  replicated — addressable on every process. The step is compiled by
  :meth:`TrainLoopMixin._jit_step`, so the ``(params, opt_state)``
  buffers are donated: the big tables update in place.

The loss reported per step is the weighted mean squared error of the
freshly solved user rows against their observed ratings — with fixed
inputs and a fixed schedule the trajectory is fully deterministic, which
is what the mid-train checkpoint/restore byte-identity tests pin.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_tpu.models._loop import TrainLoopMixin
from dmlc_tpu.ops.sparse import EllBatch
from dmlc_tpu.utils.check import check


class AlsParams(NamedTuple):
    users: jax.Array  # [num_users, F] row factors, solved exactly per batch
    items: jax.Array  # [num_items + 1, F]; last row = ELL pad sink, pinned 0


class AlsOptState(NamedTuple):
    # epoch-accumulated item-side normal equations (sink row included so
    # pad scatters have somewhere inert to land)
    gram: jax.Array  # [num_items + 1, F, F]  sum of u u^T per observation
    rhs: jax.Array   # [num_items + 1, F]     sum of r * u per observation


class AlsLearner(TrainLoopMixin):
    """Sharded ALS / embedding-table factorization fed by DeviceIter.

    Feed it ELL batches whose ``label`` column carries integer user/row
    ids (``DeviceIter(layout='ell', num_col=model.device_num_col(), ...)``)
    — one corpus row per user per epoch. ``fit_epoch`` runs the user sweep
    and then :meth:`finalize_items`, so ``fit(epochs=N)`` performs N full
    alternations.
    """

    def __init__(
        self,
        num_users: int,
        num_items: int,
        num_factors: int = 8,
        reg: float = 0.1,
        init_scale: float = 0.1,
        seed: int = 0,
        mesh=None,
        data_axis: str = "data",
    ):
        check(num_users > 0 and num_items > 0 and num_factors > 0,
              "AlsLearner: num_users/num_items/num_factors must be positive")
        # user ids travel as the batch's float32 label, which holds every
        # integer exactly only up to 2^24: past that, rows would be
        # scattered into their neighbours' slots without an error
        check(num_users <= 1 << 24,
              f"AlsLearner: num_users={num_users} exceeds 2^24 — row ids "
              "ride the float32 label column and would lose exactness")
        self.num_users = num_users
        self.num_items = num_items
        self.num_factors = num_factors
        self.reg = float(reg)
        self.mesh = mesh
        self.data_axis = data_axis
        key = jax.random.PRNGKey(seed)
        items = init_scale * jax.random.normal(
            key, (num_items + 1, num_factors), dtype=jnp.float32)
        self.params = AlsParams(
            users=jnp.zeros((num_users, num_factors), dtype=jnp.float32),
            items=items.at[-1].set(0.0),
        )
        self.opt_state = AlsOptState(
            gram=jnp.zeros((num_items + 1, num_factors, num_factors),
                           dtype=jnp.float32),
            rhs=jnp.zeros((num_items + 1, num_factors), dtype=jnp.float32),
        )
        self._step = self._build_step()
        self._finalize = self._build_finalize()
        self._eval = self._build_eval()

    # ---------------- DeviceIter surface ----------------

    def device_num_col(self) -> int:
        """The ``num_col`` a DeviceIter must use: pad index == num_items,
        the item table's pinned-zero sink row."""
        return self.num_items

    def batch_shardings(self):
        """ELL batch placement for a DeviceIter feeding this learner."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        row = NamedSharding(self.mesh, P(self.data_axis, None))
        vec = NamedSharding(self.mesh, P(self.data_axis))
        return EllBatch(indices=row, values=row, label=vec, weight=vec)

    def _rep_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        params_sh = jax.tree_util.tree_map(lambda _: rep, self.params)
        opt_sh = jax.tree_util.tree_map(lambda _: rep, self.opt_state)
        return rep, params_sh, opt_sh

    # ---------------- jitted functions ----------------

    def _build_step(self):
        reg_eye = self.reg * jnp.eye(self.num_factors, dtype=jnp.float32)
        num_items = self.num_items

        def step(params, opt_state, batch):
            from dmlc_tpu.ops.pallas_sparse import ell_matvec_auto

            idx = batch.indices                        # [B, K], pad = D
            vals = batch.values                        # [B, K], 0 at pads
            uid = batch.label.astype(jnp.int32)        # [B] user/row ids
            w = batch.weight                           # [B]
            v_g = jnp.take(params.items, idx, axis=0)  # [B, K, F]; 0 at pads
            # normal-equation RHS b_u = V_u^T r_u through the sparse
            # hot-path entry (Pallas in its band, XLA gather elsewhere)
            b = ell_matvec_auto(params.items, batch)   # [B, F]
            # float32 normal equations: at the TPU's default precision
            # (one bfloat16 pass) A came out 1.6e-3 off and the solved
            # rows 1.2e-2 off a float64 solve; at HIGHEST, 1.3e-7 and
            # 1.6e-6 (B=512, K=64, F=128 on a TPU v5e). jnp.linalg.solve
            # needs no such help: it was as exact at default precision.
            a = jnp.einsum("bkf,bkg->bfg", v_g, v_g,
                           precision=jax.lax.Precision.HIGHEST) + reg_eye
            u = jnp.linalg.solve(a, b[..., None])[..., 0]  # [B, F]
            users = params.users.at[uid].set(u)
            # item-side normal equations: pad slots scatter into the sink
            # row (masked for the gram, rating 0 for the rhs) and are
            # zeroed again by finalize_items
            mask = (idx != num_items).astype(jnp.float32)      # [B, K]
            wk = mask * w[:, None]                             # [B, K]
            outer = u[:, None, :, None] * u[:, None, None, :]  # [B, 1, F, F]
            gram = opt_state.gram.at[idx].add(wk[..., None, None] * outer)
            rhs = opt_state.rhs.at[idx].add(
                (w[:, None] * vals)[..., None] * u[:, None, :])
            # weighted MSE of the freshly solved rows (pads are exact
            # zeros on both sides, so only the count needs the mask)
            pred = jnp.einsum("bkf,bf->bk", v_g, u)
            err = pred - vals
            den = jnp.maximum((wk).sum(), 1.0)
            loss = ((err * err) * wk).sum() / den
            return (AlsParams(users=users, items=params.items),
                    AlsOptState(gram=gram, rhs=rhs), loss)

        if self.mesh is None:
            return self._jit_step(step)
        rep, params_sh, opt_sh = self._rep_shardings()
        return self._jit_step(step, params_sh=params_sh,
                              batch_sh=self.batch_shardings(),
                              opt_sh=opt_sh, loss_sh=rep)

    def _build_finalize(self):
        reg_eye = self.reg * jnp.eye(self.num_factors, dtype=jnp.float32)

        def finalize(params, opt_state):
            a = opt_state.gram + reg_eye               # [D+1, F, F]
            items = jnp.linalg.solve(a, opt_state.rhs[..., None])[..., 0]
            items = items.at[-1].set(0.0)              # re-pin the pad sink
            return (AlsParams(users=params.users, items=items),
                    AlsOptState(gram=jnp.zeros_like(opt_state.gram),
                                rhs=jnp.zeros_like(opt_state.rhs)))

        if self.mesh is None:
            fn = jax.jit(finalize, donate_argnums=(0, 1))
        else:
            _, params_sh, opt_sh = self._rep_shardings()
            fn = jax.jit(finalize, donate_argnums=(0, 1),
                         in_shardings=(params_sh, opt_sh),
                         out_shardings=(params_sh, opt_sh))
        fn._donate_argnums = (0, 1)
        return fn

    def _build_eval(self):
        num_items = self.num_items

        def eval_fn(params, batch):
            idx = batch.indices
            vals = batch.values
            uid = batch.label.astype(jnp.int32)
            u = jnp.take(params.users, uid, axis=0)      # [B, F]
            v_g = jnp.take(params.items, idx, axis=0)    # [B, K, F]
            pred = jnp.einsum("bkf,bf->bk", v_g, u)
            wk = ((idx != num_items).astype(jnp.float32)
                  * batch.weight[:, None])
            err = pred - vals
            return ((err * err) * wk).sum(), wk.sum()

        if self.mesh is None:
            return jax.jit(eval_fn)
        from jax.sharding import NamedSharding, PartitionSpec as P

        # replicated scalar outputs: the cross-device reduction of the
        # sharded batch is the one psum XLA inserts for the whole pass
        rep = NamedSharding(self.mesh, P())
        return jax.jit(eval_fn, out_shardings=(rep, rep))

    # ---------------- training surface ----------------

    def finalize_items(self) -> None:
        """Solve the item half from the epoch's accumulated normal
        equations and reset the accumulators (donated — in place)."""
        self.params, self.opt_state = self._finalize(
            self.params, self.opt_state)

    def fit_epoch(self, device_iter, max_steps=None) -> Tuple[float, int]:
        """User sweep (inherited loop: device-side loss accumulation, one
        host sync) followed by the epoch-boundary item solve."""
        loss, n = super().fit_epoch(device_iter, max_steps=max_steps)
        self.finalize_items()
        return loss, n

    def eval_loss(self, device_iter, max_steps=None) -> float:
        """Weighted MSE over one pass. Per-host/per-device partials stay
        on device and reduce replicated; two host syncs total."""
        from dmlc_tpu.models._loop import host_scalar

        se, wsum, n = None, None, 0
        for batch in device_iter:
            s, t = self._eval(self.params, batch)
            se = s if se is None else se + s
            wsum = t if wsum is None else wsum + t
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        device_iter.reset()
        if n == 0:
            return 0.0
        return host_scalar(se) / max(host_scalar(wsum), 1.0)

    # ---------------- checkpoint surface ----------------

    def state_dict(self) -> dict:
        """Host-side snapshot of the full training state — pairs with
        ``DeviceIter.state_dict()`` for mid-epoch checkpoints; restoring
        both reproduces the loss trajectory byte-identically."""
        return {
            "users": np.asarray(self.params.users),
            "items": np.asarray(self.params.items),
            "gram": np.asarray(self.opt_state.gram),
            "rhs": np.asarray(self.opt_state.rhs),
        }

    def load_state_dict(self, state: dict) -> None:
        self.params = AlsParams(users=jnp.asarray(state["users"]),
                                items=jnp.asarray(state["items"]))
        self.opt_state = AlsOptState(gram=jnp.asarray(state["gram"]),
                                     rhs=jnp.asarray(state["rhs"]))
