"""Adapter between the harness and ``dmlc_tpu.models.FMLearner``.

The harness drives the program through this one object: it builds the
learner the configuration names, says how a ``DeviceIter`` must be shaped
to feed it, forwards ``step`` untouched, and reads the few numbers the
comparison with the plain reference needs from the learner's own state.
Nothing here computes on the program's behalf inside the measured window.
"""

from __future__ import annotations

import numpy as np

from cellbench.reference import fm_adam

SAMPLE_ROWS = 256  # touched and untouched rows compared one by one


class Adapter:
    def __init__(self, config: dict, seed: int, mesh=None):
        from dmlc_tpu.models import FMLearner

        self.config = config
        self.seed = int(seed) % (2 ** 31 - 1)
        self.mesh = mesh
        if config["optimizer"] != "adam" or config["dtype"] != "float32":
            raise ValueError("fm adapter: the configuration must state the "
                             "learner's float32 tables and optax.adam")
        self.learner = FMLearner(
            num_col=config["num_features"],
            num_factors=config["num_factors"],
            objective=config["objective"], layout=config["layout"],
            learning_rate=config["learning_rate"],
            init_scale=config["init_scale"], seed=self.seed, mesh=mesh)
        self._probes = None

    # ---- how to feed it ----
    def device_iter_kwargs(self) -> dict:
        return dict(num_col=self.learner.device_num_col(),
                    batch_size=self.config["batch_size"],
                    layout=self.config["layout"],
                    max_nnz=self.config["max_nnz"], mesh=self.mesh,
                    shardings=self.learner.batch_shardings())

    def step(self, batch):
        return self.learner.step(batch)

    def step_min_bytes(self) -> int:
        from cellbench.costs import fm_adam_step_min_bytes

        c = self.config
        return fm_adam_step_min_bytes(c["num_features"], c["num_factors"],
                                      c["batch_size"], c["max_nnz"])

    # ---- readings for the comparison (outside the window) ----
    def _adam(self):
        return self.learner.opt_state[0]

    def _jitted(self):
        if self._probes is None:
            import jax
            import jax.numpy as jnp

            def norm(x):
                return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

            def grad_norms(mu):
                return tuple(norm(m) / (1.0 - fm_adam.B1) for m in mu)

            def update_norms(params, ids, v_start):
                moved = jnp.take(params.v, ids, axis=0) - v_start
                return jnp.abs(params.w0), norm(params.w), norm(moved)

            def gather(params, mu, nu, ids):
                return tuple(jnp.take(t, ids, axis=0)
                             for t in (params.w, params.v, mu.w, mu.v,
                                       nu.w, nu.v))

            self._probes = (jax.jit(grad_norms), jax.jit(update_norms),
                            jax.jit(gather))
        return self._probes

    def first_grad_norms(self) -> list:
        """Per leaf (w0, w, v): the norm of the first gradient as Adam got
        it, from the first moment after one step."""
        return [float(x) for x in self._jitted()[0](self._adam().mu)]

    def update_norms(self, reference: dict) -> list:
        """Per leaf: the norm of the parameters' change since the seeded
        start. ``w0`` and ``w`` start at zero, so theirs is the whole
        table's norm; ``v``'s is taken over every row the first batches
        touched, against the start rows the reference drew from the seed
        (making the whole start again beside the learner would raise the
        peak the run reports; the untouched rows are compared apart)."""
        import jax.numpy as jnp

        return [float(x) for x in self._jitted()[1](
            self.learner.params,
            jnp.asarray(reference["all_touched_ids"], jnp.int32),
            jnp.asarray(reference["v_start_touched"]))]

    def rows(self, ids: np.ndarray) -> dict:
        import jax.numpy as jnp

        got = self._jitted()[2](self.learner.params, self._adam().mu,
                                self._adam().nu, jnp.asarray(ids, jnp.int32))
        names = ("w", "v", "m_w", "m_v", "n_w", "n_v")
        return {k: np.asarray(x, np.float32) for k, x in zip(names, got)}

    def checksum_fold(self):
        """``(zero, fold)``: a jitted consumer that sums, over the real
        slots of an ELL batch, rows, indices, squared indices (uint32
        wrap-around) and labels."""
        import jax
        import jax.numpy as jnp

        zero = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.uint32),
                jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.int32))

        def fold(acc, batch):
            real = batch.values != 0
            idx = jnp.where(real, batch.indices, 0).astype(jnp.uint32)
            live = batch.weight > 0
            return (acc[0] + jnp.sum(live, dtype=jnp.int32),
                    acc[1] + jnp.sum(idx, dtype=jnp.uint32),
                    acc[2] + jnp.sum(idx * idx, dtype=jnp.uint32),
                    acc[3] + jnp.sum(jnp.where(live, batch.label, 0.0)
                                     ).astype(jnp.int32))

        if self.mesh is None:
            return zero, jax.jit(fold)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        return zero, jax.jit(fold, out_shardings=(rep,) * 4)


# ---------------------------------------------------------------------------
# the plain reference's side of the comparison
# ---------------------------------------------------------------------------

def reference_digest(config: dict, seed: int, corpus_path: str,
                     steps: int = 3, dtype: str = "float32") -> dict:
    """Run the plain reference over the first ``steps`` batches of the
    corpus and keep what the comparison reads. ``dtype='bfloat16'`` is the
    control."""
    seed = int(seed) % (2 ** 31 - 1)
    batch, k = config["batch_size"], config["max_nnz"]
    w_rows = config["num_features"] + 1
    idx, val, lab = fm_adam.parse_libfm_rows(corpus_path, steps * batch, k)
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
    # compact tables: the touched rows, then rows that nothing touches up
    # to one fixed size (so that every seed compiles the same programs),
    # then the padding sink
    size = steps * batch * k
    sink = w_rows - 1
    compact = np.where(idx >= 0, np.searchsorted(touched, idx), size)
    pad = np.full(size - len(touched), sink, np.int64)
    pool_ids = np.full(SAMPLE_ROWS, sink, np.int64)
    pool_ids[:len(sample_u)] = sample_u
    v0, v0_untouched = fm_adam.initial_rows(
        seed, w_rows, config["num_factors"], config["init_scale"],
        np.concatenate([touched, pad, [sink]]), pool_ids)
    v0_untouched = v0_untouched[:len(sample_u)]
    batches = [(compact[s * batch:(s + 1) * batch],
                val[s * batch:(s + 1) * batch],
                lab[s * batch:(s + 1) * batch]) for s in range(steps)]
    trace = fm_adam.train(v0, batches, config["learning_rate"], dtype=dtype)
    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    _, p_end, m_end, n_end = trace[-1]
    m_first = trace[0][2]
    at = np.searchsorted(touched, sample_t)
    return {
        "losses": [t[0] for t in trace],
        "grad_norms": [norm(m) / (1.0 - fm_adam.B1) for m in m_first],
        "update_norms": [abs(float(p_end[0])), norm(p_end[1]),
                         norm(p_end[2][:len(touched)]
                              - v0[:len(touched)])],
        "all_touched_ids": np.concatenate([touched, pad]),
        "v_start_touched": v0[:-1],
        "touched_ids": sample_t, "untouched_ids": sample_u,
        "touched": {"w": p_end[1][at], "v": p_end[2][at],
                    "m_w": m_end[1][at], "m_v": m_end[2][at],
                    "n_w": n_end[1][at], "n_v": n_end[2][at]},
        "untouched_v": v0_untouched,
    }


def _worst_leaf_gap(got: list, ref: list) -> float:
    """The gap between two norms by the worst leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    med = float(np.median(ref))
    return max(abs(g - r) / max(r, med, 1e-30) for g, r in zip(got, ref))


def compare(ref: dict, losses: list, grad_norms: list, update_norms: list,
            touched: dict, untouched: dict) -> dict:
    """The numbers compared, by name. ``touched`` / ``untouched`` are the
    program's (or the control's) rows at ``ref['touched_ids']`` /
    ``ref['untouched_ids']``."""
    out = {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(losses, ref["losses"])),
        "grad_norm_gap": _worst_leaf_gap(grad_norms, ref["grad_norms"]),
        "update_norm_gap": _worst_leaf_gap(update_norms,
                                           ref["update_norms"]),
    }
    # rows of the first moment after the last step, one by one: the widest
    # gap of an element, against that element or the sample's median
    # element, whichever is larger; and the root-mean-square gap of the
    # sample against its root mean square
    for key, name in (("m_w", "moment_w"), ("m_v", "moment_v")):
        want, got = ref["touched"][key], touched[key]
        floor = float(np.median(np.abs(want)))
        rel = np.abs(got - want) / np.maximum(np.abs(want), floor)
        out[name + "_row_gap"] = float(rel.max())
        out[name + "_rms_gap"] = float(
            np.sqrt(np.mean(np.square(got - want, dtype=np.float64)))
            / np.sqrt(np.mean(np.square(want, dtype=np.float64))))
    exact = 0.0
    for key in ("w", "m_w", "m_v", "n_w", "n_v"):
        exact = max(exact, float(np.abs(untouched[key]).max()))
    exact = max(exact, float(np.abs(untouched["v"]
                                    - ref["untouched_v"]).max()))
    out["untouched_gap"] = exact
    return out


def control_numbers(config: dict, seed: int, corpus_path: str,
                    ref: dict) -> dict:
    """The comparison's numbers for the control: the reference put in the
    program's place, in bfloat16."""
    import jax.numpy as jnp

    low = reference_digest(config, seed, corpus_path, dtype="bfloat16")
    zeros = {k: np.zeros_like(v) for k, v in low["touched"].items()}
    zeros["v"] = np.asarray(jnp.asarray(ref["untouched_v"]).astype(
        jnp.bfloat16), np.float32)
    return compare(ref, low["losses"], low["grad_norms"],
                   low["update_norms"], low["touched"], zeros)
