"""Adapter between the harness and ``dmlc_tpu.models.FFMLearner``, and the
comparison of its first steps with ``cellbench/reference/ffm_adagrad.py``.

Shaped as ``learners/fm.py``: the harness builds the learner the
configuration names, asks how a ``DeviceIter`` must be shaped to feed it
(``fields=True``: the libfm field plane), forwards ``step`` untouched, and
reads the few numbers the comparison needs from the learner's own state.
Nothing here computes on the program's behalf inside the measured window.
"""

from __future__ import annotations

import numpy as np

from cellbench.learners import fm as _fm
from cellbench.reference import ffm_adagrad
# at import, not in Adapter: a program without the learner (the parent of
# PR 26) fails here, at once, before the corpus's reference is run
from dmlc_tpu.models import FFMLearner

SAMPLE_ROWS = 256  # touched and untouched rows compared one by one


class Adapter:
    def __init__(self, config: dict, seed: int, mesh=None):
        self.config = config
        self.seed = int(seed) % (2 ** 31 - 1)
        self.mesh = mesh
        if (config["optimizer"] != "adagrad" or config["dtype"] != "float32"
                or not config["normalize"] or not config["fields"]):
            raise ValueError("ffm adapter: the configuration must state "
                             "float32 tables, AdaGrad, libffm's instance-wise "
                             "normalisation and the field plane")
        self.learner = FFMLearner(
            num_col=config["num_features"], num_fields=config["num_fields"],
            num_factors=config["num_factors"],
            learning_rate=config["learning_rate"], l2=config["l2"],
            seed=self.seed, mesh=mesh)
        self._probes = None

    # ---- how to feed it ----
    def device_iter_kwargs(self) -> dict:
        return dict(num_col=self.learner.device_num_col(),
                    batch_size=self.config["batch_size"],
                    layout=self.config["layout"],
                    max_nnz=self.config["max_nnz"], fields=True,
                    mesh=self.mesh,
                    shardings=self.learner.batch_shardings())

    def step(self, batch):
        return self.learner.step(batch)

    def step_min_bytes(self) -> int:
        from cellbench.costs_ffm import ffm_adagrad_step_min_bytes

        c = self.config
        return ffm_adagrad_step_min_bytes(
            c["num_fields"], c["num_factors"], c["batch_size"], c["max_nnz"])

    # ---- readings for the comparison (outside the window) ----
    def _jitted(self):
        if self._probes is None:
            import jax
            import jax.numpy as jnp

            def norm(x):
                return jnp.sqrt(jnp.sum(x.astype(jnp.float32)))

            def grad_norm(acc):
                # AdaGrad's accumulator after one step is 1 + g^2
                return norm(acc - 1.0)

            def update_norm(w, ids, start):
                return norm(jnp.square(jnp.take(w, ids, axis=0) - start))

            def gather(w, acc, ids):
                return jnp.take(w, ids, axis=0), jnp.take(acc, ids, axis=0)

            self._probes = (jax.jit(grad_norm), jax.jit(update_norm),
                            jax.jit(gather))
        return self._probes

    def first_grad_norms(self) -> list:
        """The norm of the first gradient as AdaGrad got it, from the
        accumulators after one step (one leaf: the table)."""
        return [float(self._jitted()[0](self.learner.accumulators))]

    def update_norms(self, reference: dict) -> list:
        """The norm of the table's change since the seeded start, over
        every row the first batches touched, against the start rows the
        reference drew from the seed (the untouched rows are compared
        apart, exactly)."""
        import jax.numpy as jnp

        return [float(self._jitted()[1](
            self.learner.params.w,
            jnp.asarray(reference["all_touched_ids"], jnp.int32),
            jnp.asarray(reference["w_start_touched"])))]

    def rows(self, ids: np.ndarray) -> dict:
        import jax.numpy as jnp

        w, acc = self._jitted()[2](self.learner.params.w,
                                   self.learner.accumulators,
                                   jnp.asarray(ids, jnp.int32))
        return {"w": np.asarray(w, np.float32),
                "g": np.asarray(acc, np.float32)}

    def checksum_fold(self):
        """``(zero, fold)``: the FM cells' sums over an epoch (rows,
        indices, squared indices, labels), with the field plane held to
        the text as well: the generator gives every field its own range of
        ids, so a real slot whose field is not its id's range's is taken
        off the row count, and the sums no longer match."""
        import jax
        import jax.numpy as jnp

        from cellbench.generators.fields_zipf_libfm import _field_vocabs

        gen = self.config["generator"]
        ends = jnp.asarray(np.cumsum(_field_vocabs(
            gen["num_features"], gen["fields"])), jnp.int32)
        zero, sums = _fm.Adapter.checksum_fold(self)

        def fold(acc, batch):
            acc = sums(acc, batch)
            real = batch.values != 0
            want = jnp.searchsorted(ends, batch.indices, side="right")
            wrong = real & (batch.fields.astype(jnp.int32) != want)
            return (acc[0] - jnp.sum(wrong, dtype=jnp.int32),) + acc[1:]

        return zero, jax.jit(fold)


# ---------------------------------------------------------------------------
# the plain reference's side of the comparison
# ---------------------------------------------------------------------------

def reference_digest(config: dict, seed: int, corpus_path: str,
                     steps: int = 3, dtype: str = "float32",
                     zero_fields: bool = False) -> dict:
    """Run the plain reference over the first ``steps`` batches of the
    corpus and keep what the comparison reads. ``dtype='bfloat16'`` and
    ``zero_fields=True`` (every field read as 0: a factorization machine
    that forgot its fields) are the two controls."""
    seed = int(seed) % (2 ** 31 - 1)
    batch, k = config["batch_size"], config["max_nnz"]
    m, f = config["num_fields"], config["num_factors"]
    w_rows = config["num_features"] + 1
    idx, fld, val, lab = ffm_adagrad.parse_libfm_rows(
        corpus_path, steps * batch, k)
    if zero_fields:
        fld = np.zeros_like(fld)
    touched, counts = np.unique(idx[idx >= 0], return_counts=True)
    rng = np.random.default_rng(seed)
    # the rows compared one by one: a seeded sample of the touched rows,
    # the most often touched among them, and as many untouched rows
    often = touched[np.argsort(counts)[-8:]]
    rest = np.setdiff1d(touched, often)
    sample_t = np.sort(np.concatenate([
        often, rng.choice(rest, min(SAMPLE_ROWS, len(rest)), replace=False)]))
    pool = rng.integers(0, config["num_features"], 4 * SAMPLE_ROWS)
    sample_u = np.setdiff1d(pool, touched)[:SAMPLE_ROWS]
    # a compact table: the touched rows, then rows that nothing touches up
    # to one fixed size (so that every seed compiles the same programs),
    # then the padding sink
    size = steps * batch * k
    sink = w_rows - 1
    compact = np.where(idx >= 0, np.searchsorted(touched, idx), size)
    pad = np.full(size - len(touched), sink, np.int64)
    pool_ids = np.full(SAMPLE_ROWS, sink, np.int64)
    pool_ids[:len(sample_u)] = sample_u
    w0, w0_untouched = ffm_adagrad.initial_rows(
        seed, w_rows, m, f, np.concatenate([touched, pad, [sink]]), pool_ids)
    w0_untouched = w0_untouched[:len(sample_u)]
    cut = lambda x, s: x[s * batch:(s + 1) * batch]  # noqa: E731
    batches = [(cut(compact, s), cut(fld, s), cut(val, s), cut(lab, s))
               for s in range(steps)]
    trace = ffm_adagrad.train(w0, batches, config["learning_rate"],
                              config["l2"], m, f, dtype=dtype)
    norm = lambda x: float(np.sqrt(np.sum(x, dtype=np.float64)))  # noqa: E731
    _, w_end, g_end = trace[-1]
    at = np.searchsorted(touched, sample_t)
    return {
        "losses": [t[0] for t in trace],
        "grad_norms": [norm(trace[0][2].astype(np.float64) - 1.0)],
        "update_norms": [norm(np.square(
            w_end[:len(touched)] - w0[:len(touched)], dtype=np.float64))],
        "all_touched_ids": np.concatenate([touched, pad]),
        "w_start_touched": w0[:-1],
        "touched_ids": sample_t, "untouched_ids": sample_u,
        "touched": {"w": w_end[at], "g": g_end[at]},
        "untouched_w": w0_untouched,
    }


def compare(ref: dict, losses: list, grad_norms: list, update_norms: list,
            touched: dict, untouched: dict) -> dict:
    """The numbers compared, by name. ``touched`` / ``untouched`` are the
    program's (or a control's) rows of ``W`` and ``G`` at
    ``ref['touched_ids']`` / ``ref['untouched_ids']``."""
    rel = lambda got, want: abs(got - want) / max(abs(want), 1e-30)  # noqa: E731
    out = {
        "loss_gap": max(rel(a, b) for a, b in zip(losses, ref["losses"])),
        "grad_norm_gap": rel(grad_norms[0], ref["grad_norms"][0]),
        "update_norm_gap": rel(update_norms[0], ref["update_norms"][0]),
    }
    # the sampled rows of the table and of the accumulators after the last
    # step: the root-mean-square gap of the sample against its root mean
    # square (the accumulators against their growth beyond the start's 1),
    # and the widest gap of one element, with no limit
    for key, name, base in (("w", "table", 0.0), ("g", "accumulator", 1.0)):
        want, got = ref["touched"][key], touched[key]
        gap = np.square(got - want, dtype=np.float64)
        scale = np.square(want - base, dtype=np.float64)
        out[name + "_rms_gap"] = float(np.sqrt(gap.mean() / scale.mean()))
        floor = float(np.median(np.abs(want - base)))
        out[name + "_row_gap"] = float(
            (np.abs(got - want) / np.maximum(np.abs(want - base), floor)
             ).max())
    # rows no batch touched: the seeded start and accumulators of 1, exactly
    out["untouched_gap"] = max(
        float(np.abs(untouched["w"] - ref["untouched_w"]).max()),
        float(np.abs(untouched["g"] - 1.0).max()))
    return out


def _as_control(ref: dict, other: dict, untouched_w: np.ndarray) -> dict:
    return compare(ref, other["losses"], other["grad_norms"],
                   other["update_norms"], other["touched"],
                   {"w": untouched_w, "g": np.ones_like(untouched_w)})


def control_numbers(config: dict, seed: int, corpus_path: str,
                    ref: dict) -> dict:
    """The comparison's numbers for the two controls, each put in the
    program's place: the reference in bfloat16 (under the comparison's own
    names) and the reference with every field read as 0 (under
    ``zero_fields.<name>``)."""
    import jax.numpy as jnp

    low = reference_digest(config, seed, corpus_path, dtype="bfloat16")
    out = _as_control(ref, low, np.asarray(jnp.asarray(
        ref["untouched_w"]).astype(jnp.bfloat16), np.float32))
    flat = reference_digest(config, seed, corpus_path, zero_fields=True)
    out.update({"zero_fields." + k: v for k, v in _as_control(
        ref, flat, ref["untouched_w"]).items()})
    return out
