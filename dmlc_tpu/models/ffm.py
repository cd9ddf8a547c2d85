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
route a step took). Where the scatter takes that kernel, a chip makes no
dense gradient at all, of the table or of its shard of one: the step
differentiates the loss with respect to the gathered rows and the kernel
finishes AdaGrad on every block of ``W`` and ``G`` in VMEM, in place
(:meth:`FFMLearner.table_update_route`; the counter ``table_update_route``
says which way a step went). The arithmetic
is optax's, and ``opt_state`` keeps its pytree. No ``(x @ V)^2`` trick
applies to this model: a row's two sums run over a ``[factors, K, K]`` pair
tensor, ``a[d, s, t] = W[i_s, f_t, d]``. They are an op of their own
(:func:`dmlc_tpu.ops.ffm_pairs.ffm_pair_terms`; the counter
``ffm_interaction_route`` says which route a step took and where a slot's
field came from). On a chip, an ELL batch's field plane is data and two
kernels select the pair tensor on it once a block of 1,024 rows, forward
and backward, and it lives in VMEM only:

    a[d, s, t] = wg[f_t * k + d, s]                 (m selects a value)
    d wg[f * k + d, s] += [f_t == f] da[d, s, t]    (m masked adds a value)

``layout="dense"`` hands the op no plane: column ``t`` is field ``t``,
known when the step is traced, and the positional kernels read the pair
tensor where it lies in the gathered rows, no select, no mask, no tensor:

    a[d, s, t] = wg[t * k + d, s]
    d wg[t * k + d, s] = g_phi x_s x_t wg[s * k + d, t]
                         + g_reg [x_s x_t != 0] wg[t * k + d, s]   (t != s)

A grid step of the general kernels holds its block of ``wg`` twice and the
pair tensor: 10.1 MB forward and 15.9 backward at 11 fields and 16 slots,
83 MB and (cut to four lines) 99 MB at 39 and 39; one of the positional
kernels holds a slot's share, 0.8 MB forward and 2.0 backward at 11
fields, 2.9 and 5.6 MB at 39. Everywhere else the pair tensor is a
``[factors, K, K, B]`` array of plain ``jax.numpy``, batch-minor so that
every elementwise operation fills the TPU's lanes, and autodiff's to
transpose.

Batches come from ``DeviceIter(layout="ell", fields=True)``, or, with
``FFMLearner(layout="dense", column_offsets=)``, from ``DeviceIter(layout=
"dense", x_dtype="int32")`` over a delimited table of id columns (a CSV
parsed with ``dtype=int32``): the batch is the file's columns, ``(x [B, C]
int32, label, weight)``, every column an id space of its own, and the
step itself does what an offline conversion to ``field:id:1`` text would
have: under the scope ``ffm_columns`` slot ``(b, c)`` becomes field ``c``
(by its place: no plane of fields is made), table row ``column_offsets[c]
+ x[b, c]``, value 1 (a padded row, weight 0: the sink and value 0, as ELL
pads). From there it is the step above, on ``C`` slots a row with none
padded, its pair terms on the positional kernels.

**Under a mesh the table is dealt by rows, never replicated**: libffm's
KDD2012 table and its accumulators are 19.25 GB and no chip holds them.
``FFMLearner(mesh=)`` deals the rows of ``W`` and ``G`` cyclically over
the mesh's ``data_axis`` (:class:`dmlc_tpu.parallel.mesh.RowDeal`: id
``i`` on chip ``i % shards``), parameter-server fashion with a worker and
a server on every chip: the batch is sharded over the same axis, the step
runs under ``shard_map``, the forward reads every slot's row from the chip
that owns it and the backward adds every slot's cotangent row into the
owner's shard (``ops/table_gather.py`` / ``ops/grad_scatter.py`` with
``deal=``; scope ``table_exchange``). Only the slots a chip owns reach it
(``ops/table_exchange.py``: an all-to-all by owner with a capacity; the
batch's padding, value 0, is not sent), and a step whose slots do not fit
all-gathers them instead, with the same result. The update is the one-chip
step's, chosen the same way from a shard's rows and the batch's slots: on
the kernel route every chip finishes AdaGrad on its shard inside the
gradient kernel and no gradient of a shard's size exists
(``fused_table_update(deal=)``); elsewhere optax sweeps the shard with its
dense gradient. The start is drawn on the shards, value for value the
one-chip draw, so ``params.w`` is never whole anywhere; ``params.w`` and
:attr:`accumulators` are the *dealt* arrays (``[deal.padded_rows, m * k]``:
:meth:`rows` reads them by id). The result of a step is that of the
undivided table; the counter ``table_shard_route`` counts a traced step,
:meth:`shard_slots` says how evenly the batches' slots fell and
:meth:`fallback_steps` how many steps did not fit the exchange.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dmlc_tpu.models._loop import TrainLoopMixin
from dmlc_tpu.ops import grad_scatter, sorted_walk, table_exchange
from dmlc_tpu.ops.ffm_pairs import ffm_pair_terms
from dmlc_tpu.ops.sparse import EllBatch, ell_table_gather
from dmlc_tpu.ops.table_gather import table_rows
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import check


class FFMParams(NamedTuple):
    w: jax.Array        # [W, m * k]; last row = ELL padding sink, pinned to 0


# The step's stages carry fixed ``jax.named_scope`` names, as the FM's do
# (models/fm.py; docs/observability.md): ffm_gather (table rows brought to
# the batch; the gradient's scatter is its transpose and reads
# ``transpose(jvp(ffm_gather))``), ffm_interaction, ffm_loss,
# ffm_optimizer, ffm_sink. On the fused route there is no scatter: the
# permute of the cotangent rows and the kernel that updates ``W`` and ``G``
# read ``ffm_optimizer``.

def _pair_terms(params: FFMParams, batch: EllBatch, num_fields: int,
                deal=None):
    """``(phi [B], reg [B])``: the interaction of every row and the sum of
    squares its regulariser takes, both before ``weight``. With a ``deal``
    the call is one chip's inside ``shard_map``: its shard of the table
    and its rows of the batch."""
    with jax.named_scope("ffm_gather"):
        # (K-major, its padding named: table_rows says what that saves)
        (got,) = ell_table_gather(
            (params.w,), batch.indices.T, deal, _real(batch).T)  # [K, B, m*k]
    return _terms_of_rows(got, batch, num_fields)


def _real(batch: EllBatch) -> jax.Array:
    """The slots that are not the batch's padding, ``[B, K]`` bool."""
    return batch.values != 0


def _check_fields(batch: EllBatch) -> None:
    check(batch.fields is not None,
          "FFMLearner: the batch carries no field plane; build the "
          "DeviceIter with fields=True")


def _terms_of_rows(got: jax.Array, batch: EllBatch, num_fields: int,
                   num_factors: Optional[int] = None):
    """:func:`_pair_terms` from the gathered rows ``got`` [K, B, m * k]
    (with ``num_factors`` said, possibly as lines: ``ffm_pair_terms``). A
    batch of :meth:`FFMLearner._slots` with no field plane is a table's
    columns: slot ``t`` is field ``t``."""
    with jax.named_scope("ffm_interaction"):
        return ffm_pair_terms(
            got, None if batch.fields is None else batch.fields.T,
            batch.values.T, num_fields, num_factors)


class FFMLearner(TrainLoopMixin):
    """Field-aware factorization machine, logistic loss, exact float32
    AdaGrad in libffm's form (module docstring). ``num_col`` is the
    feature-id space (``DeviceIter``'s ``num_col``), ``num_fields`` the
    number of field ids; batches are ``EllBatch`` with a ``fields`` plane.
    ``learning_rate`` / ``l2`` / ``num_factors`` default to libffm's
    ``-r 0.2 -l 0.00002 -k 4``. The start is ``U[0, 1 / sqrt(num_factors))``
    from ``seed``, the sink row zero. With a ``mesh`` the table and its
    accumulators are dealt by rows over ``data_axis`` (:attr:`deal`) and
    the batch is sharded over it (module docstring). ``layout="dense"``
    with ``column_offsets`` [num_fields] takes the dense batches of id
    columns instead (module docstring): column ``c`` is field ``c`` and
    its ids start at table row ``column_offsets[c]``."""

    def __init__(
        self,
        num_col: int,
        num_fields: int,
        num_factors: int = 4,
        learning_rate: float = 0.2,
        l2: float = 2e-5,
        seed: int = 0,
        mesh=None,
        data_axis: str = "data",
        layout: str = "ell",
        column_offsets=None,
    ):
        check(num_fields >= 1 and num_factors >= 1,
              "FFMLearner: num_fields and num_factors must be >= 1")
        check(layout in ("ell", "dense"),
              "FFMLearner: layout must be ell|dense")
        check((layout == "dense") == (column_offsets is not None),
              "FFMLearner: layout='dense' reads id columns and needs their "
              "column_offsets=; layout='ell' takes none")
        check(layout == "ell" or mesh is None,
              "FFMLearner: layout='dense' takes no mesh yet: the dealt "
              "step shards an ELL batch (batch_shardings())")
        self.layout = layout
        self.column_offsets = None
        if layout == "dense":
            offsets = np.asarray(column_offsets)
            check(offsets.shape == (num_fields,)
                  and offsets.dtype.kind in "iu"
                  and 0 <= int(offsets.min())
                  and int(offsets.max()) < num_col,
                  "FFMLearner: column_offsets must be num_fields whole "
                  "numbers inside [0, num_col)")
            self.column_offsets = offsets.astype(np.int32)
        self.num_col = num_col
        self.num_fields = num_fields
        self.num_factors = num_factors
        self.learning_rate = learning_rate
        self.l2 = l2
        self.data_axis = data_axis
        self.weight_dim = num_col + 1           # +1 = the ELL padding sink
        # AdaGrad as libffm has it: G starts at 1, the update is
        # g / sqrt(G) with no epsilon
        self.opt = optax.chain(
            optax.scale_by_rss(initial_accumulator_value=1.0, eps=0.0),
            optax.scale(-learning_rate))
        # the same numbers as the gradient kernel's epilogue, for as long
        # as ``self.opt`` is the chain above (table_update_route)
        self._own_opt = self.opt
        self._adagrad = grad_scatter.AdaGradEpilogue(float(learning_rate))
        self._deal_over(mesh)
        self.params = FFMParams(
            w=self._start_fn()(jax.random.PRNGKey(seed)))
        if mesh is None:
            self.opt_state = self.opt.init(self.params)
        else:
            # the accumulators are born on the shards as the table is; the
            # last leaf is the learner's own books, [shards + 1, 2] uint32
            # (low word, high word): real slots every chip has owned so
            # far, then the steps that did not fit the exchange
            self.opt_state = jax.jit(
                lambda params: self.opt.init(params) + (
                    jnp.zeros((self.deal.shards + 1, 2), jnp.uint32),),
                out_shardings=self._shardings[1])(self.params)
        self._step = self._build_step()
        self._accuracy = self._build_accuracy()
        self._predict = jax.jit(
            lambda params, batch: self._margin(params, batch)[0])

    def _deal_over(self, mesh) -> None:
        """Lay the learner out on ``mesh``: the deal of ``weight_dim`` rows
        over its ``data_axis`` and the shardings that follow (``None``: one
        device, no deal)."""
        self.mesh, self.deal = mesh, None
        if mesh is not None:
            from dmlc_tpu.parallel.mesh import RowDeal

            self.deal = RowDeal(self.weight_dim, mesh.shape[self.data_axis],
                                self.data_axis)
            self._shardings = self._state_shardings()
            self._specs = jax.tree_util.tree_map(lambda sh: sh.spec,
                                                 self._shardings)

    def _start_fn(self):
        """The jitted draw of the seeded start from a key. Dealt, it is
        the same draw on the shards: jax's default
        ``threefry_partitionable`` bits depend on an element's flat index
        alone, so ``[local_rows, shards, width]`` holds at ``[r, c]`` the
        row that ``[weight_dim, width]`` holds at ``r * shards + c``, and
        the compiler makes every shard where it lives. Rows past
        ``weight_dim`` (the deal's padding) and the sink are zero."""
        width = self.num_fields * self.num_factors
        scale = 1.0 / float(self.num_factors) ** 0.5
        sink = self.weight_dim - 1
        if self.deal is None:
            def start(key):
                w = jax.random.uniform(key, (self.weight_dim, width),
                                       jnp.float32) * scale
                return w.at[-1].set(0.0)            # sink row inert

            return jax.jit(start)
        deal = self.deal

        def start(key):
            # RowDeal's cyclic rule: [r, c] is id r * shards + c
            w = jax.random.uniform(
                key, (deal.local_rows, deal.shards, width),
                jnp.float32) * scale
            ids = (jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
                   * deal.shards
                   + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1))
            w = jnp.where(ids < sink, w, 0.0)
            return jnp.swapaxes(w, 0, 1).reshape(deal.padded_rows, width)

        return jax.jit(start, out_shardings=deal.sharding(self.mesh))

    def device_num_col(self) -> int:
        """The ``num_col`` a DeviceIter must use to feed this learner."""
        return self.weight_dim - 1

    def _checkpoint_spec(self):
        """What a checkpoint holds (docs/checkpoint.md): ``W`` and ``G``
        by global row id, so a table saved under one deal restores under
        another; the dealt learner's per-chip books are the layout's own."""
        from dmlc_tpu.models._checkpoint import CheckpointSpec

        own = self.opt is self._own_opt
        return CheckpointSpec(
            meta={"class": "FFMLearner", "num_col": self.num_col,
                  "num_fields": self.num_fields,
                  "num_factors": self.num_factors, "l2": self.l2,
                  "dtype": "float32",
                  "optimizer": {"name": "adagrad", "eps": 0.0,
                                "learning_rate": self.learning_rate,
                                "initial_accumulator": 1.0}
                  if own else {"name": "caller"}},
            tree={"params": self.params, "opt_state": self.opt_state},
            deal=self.deal,
            layout_bound=() if self.deal is None else (
                f"opt_state.{len(self.opt_state) - 1}",))

    def _state_shardings(self):
        """``(params, opt_state, batch, replicated)`` shardings under the
        mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        table = self.deal.sharding(self.mesh)
        params_sh = FFMParams(w=table)
        # every leaf of AdaGrad's state is shaped as the table; the
        # learner's own last leaf (shard_slots) is whole on every chip
        opt_sh = jax.tree_util.tree_map(
            lambda _: table, jax.eval_shape(self.opt.init, FFMParams(
                w=jax.ShapeDtypeStruct((1, 1), jnp.float32)))) + (rep,)
        vec = NamedSharding(self.mesh, P(self.data_axis))
        row = NamedSharding(self.mesh, P(self.data_axis, None))
        batch_sh = EllBatch(indices=row, values=row, label=vec, weight=vec,
                            fields=row)
        return params_sh, opt_sh, batch_sh, rep

    def batch_shardings(self):
        return None if self.mesh is None else self._shardings[2]

    @property
    def accumulators(self) -> jax.Array:
        """AdaGrad's sums of squared gradients ``G``, shaped as the
        table (1 where a coordinate never had a gradient)."""
        return self.opt_state[0].sum_of_squares.w

    def rows(self, ids):
        """``(W rows, G rows)`` at feature ids ``ids`` [n], whole on every
        chip: the one way to read a table that may be dealt."""
        ids = jnp.asarray(ids, jnp.int32)
        if self.deal is None:
            return (jnp.take(self.params.w, ids, axis=0),
                    jnp.take(self.accumulators, ids, axis=0))
        return tuple(self.deal.take(self.mesh, table, ids)
                     for table in (self.params.w, self.accumulators))

    def shard_slots(self):
        """Real slots (value not 0) every chip has owned over all steps so
        far, ``[shards]`` Python ints; ``None`` without a mesh. Read
        outside a step: the counts ride in the optimizer's state and no
        step waits for them."""
        return None if self.deal is None else self._books()[:-1]

    def fallback_steps(self):
        """Steps so far in which some chip held more real slots of one
        owner than the exchange has room for
        (:func:`dmlc_tpu.ops.table_exchange.capacity`), so that every chip
        all-gathered all slots instead; ``None`` without a mesh. Read
        outside a step, as :meth:`shard_slots`; the reading is kept in the
        gauge ``table_shard_fallback_steps`` for
        ``pod_snapshot()["table_shard_routes"]``."""
        if self.deal is None:
            return None
        steps = self._books()[-1]
        _telemetry.REGISTRY.gauge(
            _telemetry.TABLE_SHARD_FALLBACK_METRIC).set(steps)
        return steps

    def _books(self):
        from dmlc_tpu.parallel.mesh import counts_of

        return counts_of(self.opt_state[-1])

    # ---------------- jitted functions ----------------

    def _pred_from_margin(self, margin: jax.Array) -> jax.Array:
        return (margin > 0).astype(jnp.float32)

    def _on_shards(self, fn, out_specs, state=False):
        """``fn(params, [opt_state,] batch)`` of one chip's shards, under
        ``shard_map`` over the mesh."""
        params_sp, opt_sp, batch_sp, _ = self._specs
        return jax.shard_map(
            fn, mesh=self.mesh, out_specs=out_specs, check_vma=False,
            in_specs=(params_sp,) + ((opt_sp,) if state else ())
            + (batch_sp,))

    def _slots(self, batch) -> EllBatch:
        """The batch as the step's slots. ``layout="ell"``: the batch
        itself. ``layout="dense"``: the columns ``(x [B, C] int32, label,
        weight)`` with the learner's offsets, under the scope
        ``ffm_columns`` (module docstring)."""
        if self.layout == "ell":
            _check_fields(batch)
            return batch
        x, label, weight = batch
        check(jnp.issubdtype(x.dtype, jnp.integer)
              and x.shape[1:] == (self.num_fields,),
              "FFMLearner(layout='dense'): the batch's x must be "
              f"[B, {self.num_fields}] integer id columns "
              "(DeviceIter(layout='dense', x_dtype='int32')), not "
              f"{x.dtype}{list(x.shape)}")
        with jax.named_scope("ffm_columns"):
            live = (weight > 0)[:, None]
            ids = jnp.where(live, x.astype(jnp.int32) + self.column_offsets,
                            self.weight_dim - 1)
            # no field plane: column c is field c, which the pair terms
            # read off a slot's position (ops/ffm_pairs.py)
            return EllBatch(ids, jnp.broadcast_to(
                live.astype(jnp.float32), ids.shape), label, weight, None)

    def _margin(self, params: FFMParams, batch):
        batch = self._slots(batch)
        if self.deal is None:
            phi, _ = _pair_terms(params, batch, self.num_fields)
        else:
            from jax.sharding import PartitionSpec as P

            phi = self._on_shards(
                lambda params, batch: _pair_terms(
                    params, batch, self.num_fields, self.deal)[0],
                P(self.data_axis))(params, batch)
        return phi, batch.label, batch.weight

    def loss_sum(self, params: FFMParams, batch: EllBatch) -> jax.Array:
        """libffm's objective over the batch: the *sum* over its rows (on
        a chip of the mesh: over its rows of the batch, from its shard)."""
        return self._loss_of_terms(*_pair_terms(
            params, batch, self.num_fields, self.deal), batch)

    def _loss_of_terms(self, phi, reg, batch: EllBatch) -> jax.Array:
        with jax.named_scope("ffm_loss"):
            y = 2.0 * batch.label - 1.0
            per = jnp.logaddexp(0.0, -y * phi) + (0.5 * self.l2) * reg
            return jnp.sum(per * batch.weight)

    def _update(self, params, opt_state, batch, sink):
        """The step of one chip: ``(params, opt_state, summed loss)``.
        ``sink`` sets the padding row of the table to zero."""
        total, grads = jax.value_and_grad(self.loss_sum)(params, batch)
        with jax.named_scope("ffm_optimizer"):
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        with jax.named_scope("ffm_sink"):
            params = params._replace(w=sink(params.w))
        return params, opt_state, total

    def table_update_route(self, num_slots: int,
                           num_rows: Optional[int] = None) -> Tuple[str, str]:
        """``(route, reason)`` of a step on a batch of ``num_slots`` ELL
        slots into a table of ``num_rows`` rows (the learner's own, or the
        traced table's), from what the learner can observe, as
        :meth:`FMLearner.table_update_route`. ``"fused"``: the loss is
        differentiated with respect to the gathered rows and the gradient
        kernel finishes AdaGrad on ``W`` and ``G`` block by block
        (:func:`dmlc_tpu.ops.grad_scatter.fused_table_update`); no dense
        gradient exists. ``"dense"``: autodiff hands ``self.opt`` a dense
        gradient, because (``reason``) the ``optimizer`` is no longer the
        learner's own, or the gradient is scattered by XLA
        (``scatter_xla``: the CPU, a small table). A mesh is no reason: a
        chip of a dealt table takes the route of one chip with its shard's
        rows and the whole batch's slots, which are all gathered to it."""
        if self.opt is not self._own_opt:
            return "dense", "optimizer"
        rows = num_rows or self.weight_dim
        if self.deal is not None:
            rows = -(-rows // self.deal.shards)
        route = grad_scatter.grad_scatter_route(
            rows, num_slots, self.num_fields * self.num_factors,
            self.params.w.dtype)
        if route != "kernel":
            return "dense", "scatter_xla"
        return "fused", "adagrad"

    def _updater(self, params, batch: EllBatch):
        """:meth:`_fused_update` or :meth:`_update`, as
        :meth:`table_update_route` says for the traced table and batch
        (whole, outside ``shard_map``); counted in ``table_update_route``."""
        route, reason = self.table_update_route(batch.indices.size,
                                                params.w.shape[0])
        _telemetry.REGISTRY.counter(
            _telemetry.TABLE_UPDATE_ROUTE_METRIC, route=route,
            reason=reason).inc(1)
        return self._fused_update if route == "fused" else self._update

    def _walk_books_of(self, batch):
        """:meth:`TrainLoopMixin.walk_books` of ``batch``: the slots as
        the update's walk sorts them. On a dealt table an owner walks the
        slots it received (all the chips' on a step whose buckets
        overflow), found by the exchange's own bucketing: a count a chip."""
        batch = self._slots(batch)
        rows = self.weight_dim if self.deal is None else self.deal.local_rows
        route = grad_scatter.grad_scatter_route(
            rows, batch.indices.size, self.num_fields * self.num_factors,
            self.params.w.dtype)
        if route != "kernel":
            return {}
        if self.deal is None:
            return sorted_walk.walk_books(batch.indices.T, rows,
                                          _real(batch).T)
        from jax.sharding import PartitionSpec as P

        deal = self.deal

        def on_chip(batch):
            slots = batch.indices.T
            exchange = table_exchange.open_exchange(deal, slots,
                                                    _real(batch).T)
            books = jax.lax.cond(
                exchange.buckets.overflow,
                lambda: sorted_walk.walk_books(
                    deal.local_slots(slots.reshape(-1)), rows),
                lambda: sorted_walk.walk_books(exchange.received, rows))
            return {what: x[None] for what, x in books.items()}

        return jax.shard_map(
            on_chip, mesh=self.mesh, in_specs=(self._specs[2],),
            out_specs=P(self.data_axis), check_vma=False)(batch)

    def _fused_update(self, params, opt_state, batch, sink):
        """:meth:`_update` of one chip with no dense gradient (of a dealt
        table: none of its shard)."""
        rss, rest = opt_state[0], opt_state[1:]
        with jax.named_scope("ffm_gather"):
            # (the rows as lines where the gather leaves them so: their
            # cotangent goes back to the update's kernel in that form;
            # K-major with the padding named: table_rows says what for)
            slots, real = batch.indices.T, _real(batch).T
            (got,), sorted_slots = table_rows(
                (params.w,), slots, deal=self.deal, real=real, lines=True)

        def loss_of(got):
            # libffm's regulariser is a sum over the rows' own squares
            # (_terms_of_rows' a * a): the cotangent rows carry it
            return self._loss_of_terms(*_terms_of_rows(
                got, batch, self.num_fields, self.num_factors), batch)

        total, g = jax.value_and_grad(loss_of)(got)
        with jax.named_scope("ffm_optimizer"):
            ((w, acc),) = grad_scatter.fused_table_update(
                batch.indices.T, (g,), ((params.w, rss.sum_of_squares.w),),
                None, self._adagrad, sorted_slots=sorted_slots,
                deal=self.deal, real=real)
        with jax.named_scope("ffm_sink"):
            w = sink(w)
        return FFMParams(w=w), (rss._replace(
            sum_of_squares=FFMParams(w=acc)),) + tuple(rest), total

    def _build_step(self):
        if self.deal is None:
            def step(params, opt_state, batch):
                batch = self._slots(batch)
                params, opt_state, total = self._updater(params, batch)(
                    params, opt_state, batch, lambda w: w.at[-1].set(0.0))
                with jax.named_scope("ffm_loss"):
                    # the mean over the batch's rows, for a reader; the
                    # update above is on the sum
                    loss = total / jnp.maximum(batch.weight.sum(), 1.0)
                return params, opt_state, loss

            return self._jit_step(step)
        from jax.sharding import PartitionSpec as P

        from dmlc_tpu.parallel.mesh import count_up

        deal, axis = self.deal, self.data_axis
        sink_chip, sink_row = (int(x) for x in deal.place(self.weight_dim - 1))

        def sink(w):
            # the padding sink lives on one chip; a row written in place
            mine = jax.lax.axis_index(axis) == sink_chip
            return w.at[sink_row].set(jnp.where(mine, 0.0, w[sink_row]))

        def on_chip(update, params, opt_state, batch):
            params, adagrad, total = update(
                params, opt_state[:-1], batch, sink)
            with jax.named_scope("ffm_loss"):
                total, rows = jax.lax.psum(
                    (total, batch.weight.sum()), axis)
                loss = total / jnp.maximum(rows, 1.0)
            with jax.named_scope("ffm_shard_books"):
                # 64-bit counts in two words
                real = _real(batch)
                more = jnp.append(
                    deal.owned_slots(batch.indices, real),
                    table_exchange.overflows(
                        deal, batch.indices, real).astype(jnp.uint32))
                books = count_up(opt_state[-1], more)
            return params, adagrad + (books,), loss

        def step(params, opt_state, batch):
            batch = self._slots(batch)
            update = self._updater(params, batch)
            _telemetry.REGISTRY.counter(
                _telemetry.TABLE_SHARD_ROUTE_METRIC, shards=str(deal.shards),
                deal="cyclic", collective="owned_slots").inc(1)
            params_sp, opt_sp, _, _ = self._specs
            return self._on_shards(
                functools.partial(on_chip, update),
                (params_sp, opt_sp, P()), state=True)(
                params, opt_state, batch)

        params_sh, opt_sh, batch_sh, rep = self._shardings
        return self._jit_step(step, params_sh=params_sh, batch_sh=batch_sh,
                              opt_sh=opt_sh, loss_sh=rep)

    def predict(self, batch) -> jax.Array:
        """Raw interaction ``phi`` for a batch (apply sigmoid for click
        probabilities)."""
        return self._predict(self.params, batch)
