"""Adapter between the harness and ``dmlc_tpu.models.FFMLearner`` fed in
the epoch plan's order (PR 45, configuration ``kdd12_ffm_rand``).

The mathematics, the reference (``reference/ffm_adagrad.py``), the
comparison's six numbers and the bfloat16 control are ``learners/ffm.py``'s.
What differs is the order of the rows, and the reference follows it on its
own (``reference/epoch_plan_plain.py``, which imports nothing of the
program): it cuts the corpus text into the parser's blocks, works out the
plan's order for ``(the run's seed, epoch)`` from the contract in
``docs/data.md``, writes the rows of the first planned batches to a file of
their own in that order and hands that file to ``learners/ffm.py``'s
``reference_digest``. All of that runs where the harness runs a
reference, before the program's tables exist and outside ``setup_s``; the
cache does not exist yet, which is why the blocks' rows come from the text.
After the window the same cut is held against the published cache's index.

Three more numbers join the comparison, each 0 in a sound run:

* ``order_gap``: rows of the first three batches the harness stepped
  whose label, ids or fields are not the reference's row at that position;
* ``epoch_order_gap``: the verification epoch's order-sensitive sum
  (``sum((position + 1) * hash(row))`` modulo 2**32, folded on the device
  beside the harness's four checksums) against the plain plan's for the
  epoch the program says it served, which must be the epoch the adapter
  counted to (or the one after: a window that closes between two epochs
  makes the harness reset twice, and the program then skips an order);
* ``order_repeat``: 1 if two of the epochs the adapter saw begin (set-up's,
  the window's, the verification's), or one of them and the file, hold the
  same row at a hundredth or more of their first batch's positions.

The plan's seed is the run's ``--seed``, whole. It reaches the reference
through ``reference_digest(config, seed, ...)`` and the feed through
``device_iter_kwargs()["plan"]``, a dict the feed removes before it builds
``DeviceIter`` and into which it puts the iterator it opened, so that the
adapter can ask which epoch the program says it serves. In the window the
adapter keeps references to batches it is handed and computes nothing.
"""

from __future__ import annotations

import gc

import numpy as np

from cellbench.learners import ffm as _ffm
from cellbench.reference import epoch_plan_plain as plain

ORDER_NUMBERS = ("order_gap", "epoch_order_gap", "order_repeat")
FIRST_STEPS = 3        # run.FIRST_STEPS: the batches order_gap reads
REPEAT_SHARE = 0.01    # of a batch's positions: above it two orders repeat
_RUN: dict = {}        # the run's reference of the order, and its adapter


def _head_path(corpus_path: str) -> str:
    return corpus_path + ".plan_head"


def reference_digest(config: dict, seed: int, corpus_path: str,
                     steps: int = FIRST_STEPS, **how) -> dict:
    """The reference over the first ``steps`` batches **of the plan's first
    epoch**, and what the three order numbers are read against."""
    how_plan, batch = config["plan"], config["batch_size"]
    with open(corpus_path, "rb") as f:
        data = f.read()
    block_rows, starts = plain.cut_text(data, how_plan["chunk_bytes"])
    head = plain.epoch_rows(seed, how_plan["first_planned_epoch"], block_rows,
                            how_plan["shuffle_window"], limit=steps * batch)
    with open(_head_path(corpus_path), "wb") as f:
        f.write(plain.read_rows(data, starts, head))
    ref = _ffm.reference_digest(config, seed, _head_path(corpus_path),
                                steps=steps, **how)
    ids, fields, labels = plain.parse_libfm(data, config["max_nnz"])
    _RUN.update(seed=int(seed), block_rows=block_rows, head_rows=head,
                rows=(ids, fields, labels),
                hashes=plain.row_hashes(ids, fields, labels))
    return ref


def compare(ref: dict, losses: list, grad_norms: list, update_norms: list,
            touched: dict, untouched: dict) -> dict:
    out = _ffm.compare(ref, losses, grad_norms, update_norms, touched,
                       untouched)
    adapter = _RUN.get("adapter")
    out.update(adapter.order_numbers() if adapter is not None
               else {k: float("inf") for k in ORDER_NUMBERS})
    return out


def control_numbers(config: dict, seed: int, corpus_path: str,
                    ref: dict) -> dict:
    """``learners/ffm.py``'s two controls over the planned head, and for
    the order's own numbers a feed with no plan armed, put in the
    program's place: the file's order against the plan's."""
    out = _ffm.control_numbers(config, seed, _head_path(corpus_path), ref)
    how, hashes = config["plan"], _RUN["hashes"]
    head = _RUN["head_rows"]
    in_file = np.arange(len(head))
    planned = plain.epoch_rows(seed, how["first_planned_epoch"] + 1,
                               _RUN["block_rows"], how["shuffle_window"])
    out.update(
        order_gap=float(_rows_differ(_take(in_file), _take(head))),
        epoch_order_gap=float(abs(plain.order_sum(hashes)
                                  - plain.order_sum(hashes[planned]))),
        order_repeat=1.0)
    return out


def _take(rows):
    return tuple(x[rows] for x in _RUN["rows"])


def _rows_differ(got, want) -> int:
    """Positions at which two ``(ids, fields, labels)`` differ, a missing
    position counted too."""
    n = min(len(got[2]), len(want[2]))
    same = (got[2][:n] == want[2][:n])
    for a, b in zip(got[:2], want[:2]):
        same &= (a[:n] == b[:n]).all(axis=1)
    return int(max(len(got[2]), len(want[2])) - same.sum())


def _host_rows(batches):
    """``(ids, fields, labels)`` of ELL batches as the plain reference
    holds rows: a slot with no value is id -1 and field -1; rows of no
    weight (a short last batch's tail) are left out."""
    ids, fields, labels = [], [], []
    for b in batches:
        live = np.asarray(b.weight) > 0
        real = np.asarray(b.values)[live] != 0
        ids.append(np.where(real, np.asarray(b.indices, np.int64)[live], -1))
        fields.append(np.where(real, np.asarray(b.fields, np.int64)[live], -1))
        labels.append(np.asarray(b.label)[live].astype(np.int64))
    return np.concatenate(ids), np.concatenate(fields), np.concatenate(labels)


class Adapter(_ffm.Adapter):
    def __init__(self, config: dict, seed: int, mesh=None):
        # an adapter built before this one (tools/limits.py builds one a
        # seed) gives its 4.8 GB back only to the collector
        _RUN.pop("adapter", None)
        gc.collect()
        super().__init__(config, seed, mesh=mesh)
        # the iterator the feed opens lands in this dict ("opened")
        self.plan = {"shuffle_seed": int(seed),
                     "shuffle_window": config["plan"]["shuffle_window"]}
        self.steps_per_epoch = -(-config["rows"] // config["batch_size"])
        self.steps = 0
        self.first = []        # the first batches stepped: order_gap's
        self.heads = []        # the first batch of every epoch stepped
        self.verified = None   # (claimed epoch, order, first batch, fold)
        _RUN["adapter"] = self

    def device_iter_kwargs(self) -> dict:
        return dict(super().device_iter_kwargs(), plan=self.plan)

    def step(self, batch):
        if self.steps < FIRST_STEPS:
            self.first.append(batch)
        if self.steps % self.steps_per_epoch == 0:
            self.heads.append(batch)
        self.steps += 1
        return self.learner.step(batch)

    def checksum_fold(self):
        """``learners/ffm.py``'s four sums, and beside them the epoch's
        order-sensitive sum, kept here: the harness compares four."""
        import jax
        import jax.numpy as jnp

        zero, sums = super().checksum_fold()
        slots = self.config["max_nnz"]

        def order_fold(acc, batch):
            count, total = acc
            real = batch.values != 0
            live = batch.weight > 0
            h = batch.label.astype(jnp.uint32) + 1
            for k in range(slots):
                term = ((batch.indices[:, k].astype(jnp.uint32) + 1)
                        * (2 * batch.fields[:, k].astype(jnp.uint32) + 3))
                h = jnp.where(real[:, k], h * jnp.uint32(1000003) + term, h)
            at = count + jnp.cumsum(live, dtype=jnp.uint32)
            return (count + jnp.sum(live, dtype=jnp.uint32),
                    total + jnp.sum(jnp.where(live, at * h, 0),
                                    dtype=jnp.uint32))

        order_fold = jax.jit(order_fold)
        state = [(jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.uint32))]

        def fold(acc, batch):
            if self.verified is None:
                # the source's own account of what it serves now (the
                # property is older than stats()["plan"], which reports it)
                live = getattr(self.plan["opened"].source, "plan_state",
                               None) or {}
                self.verified = (live.get("epoch"), live.get("order"),
                                 batch, state)
            state[0] = order_fold(state[0], batch)
            return sums(acc, batch)

        return zero, fold

    # ---- the numbers ----
    def order_numbers(self) -> dict:
        from cellbench import run

        how, ref = self.config["plan"], _RUN
        seed, window = ref["seed"], how["shuffle_window"]
        block_rows = ref["block_rows"]
        run.log(f"plan: {len(block_rows)} blocks of {block_rows.min()} to "
                f"{block_rows.max()} rows by the reference's cut of the "
                f"text, shuffle_window {window}")
        opened = self.plan.get("opened")
        cache = getattr(getattr(opened, "source", None), "cache_file", None)
        try:
            indexed = plain.cache_block_rows(cache)
            run.log("plan: the published cache's index "
                    + ("agrees with that cut" if np.array_equal(
                        indexed, block_rows) else
                       f"has {len(indexed)} blocks of {indexed.min()} to "
                       f"{indexed.max()} rows: NOT that cut"))
        except (OSError, ValueError, TypeError, KeyError) as exc:
            run.log(f"plan: no cache index to hold the cut against: {exc!r}")
        out = {"order_gap": float(_rows_differ(
            _host_rows(self.first), _take(ref["head_rows"])))}
        # ---- the verification epoch against the plan for its own index ----
        epochs_stepped = len(self.heads)
        counted = how["first_planned_epoch"] + epochs_stepped
        if self.verified is None:
            # tools/limits.py drives the first steps and no epoch beyond
            out["epoch_order_gap"] = (
                0.0 if self.steps <= FIRST_STEPS else float("inf"))
            heads = list(self.heads)
        else:
            claimed, order, first, state = self.verified
            got = int(state[0][1])
            heads = self.heads + [first]
            if order != "plan" or claimed not in (counted, counted + 1):
                run.log(f"plan: the verification epoch was served as "
                        f"epoch {claimed} in {order!r} order; the adapter "
                        f"counted to epoch {counted}")
                out["epoch_order_gap"] = float("inf")
            else:
                want = plain.order_sum(ref["hashes"][plain.epoch_rows(
                    seed, claimed, block_rows, window)])
                run.log(f"plan: epoch {claimed} (the adapter counted to "
                        f"{counted}): order-sensitive sum {got}, the plain "
                        f"plan's {want}")
                out["epoch_order_gap"] = float(abs(got - want))
        # ---- no two epochs alike, and none like the file ----
        batch = self.config["batch_size"]
        orders = [("the file", _take(np.arange(min(
            batch, len(ref["hashes"])))))] + [
            (f"epoch #{n + 1} seen", _host_rows([b]))
            for n, b in enumerate(heads)]
        most, pair = 0.0, None
        for i, (name_a, a) in enumerate(orders):
            for name_b, b in orders[i + 1:]:
                n = max(len(a[2]), len(b[2]))
                share = (n - _rows_differ(a, b)) / n if n else 1.0
                if share >= most:
                    most, pair = share, (name_a, name_b)
        run.log(f"plan: the first batches of {len(orders) - 1} epochs and "
                f"of the file: at most {most:.6f} of the positions hold the "
                f"same row ({pair[0]} and {pair[1]})"
                if pair else "plan: one order seen, nothing to hold it to")
        out["order_repeat"] = float(most >= REPEAT_SHARE)
        return out
