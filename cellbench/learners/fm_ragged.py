"""Adapter between the harness and ``dmlc_tpu.models.FMLearner`` on ragged
rows: ``layout="bcoo"``, batches of flat slots from libsvm text (PR 37).

What it shares with ``learners/fm.py`` it takes from there: the readings of
the learner's state, the six numbers of the comparison. Its own: the
learner and the feed's shape (no ``max_nnz``: no row is cut), the checksum
over a ``(BCOO, label, weight)`` batch, and the plain reference's side over
ragged lists (``reference/fm_adam_ragged.py``).

The configuration needs the program's ragged step (flat slots on the table
kernels, a row's sums by ``dmlc_tpu.ops.slot_rows``): a program without it
would run these rows through ``bcoo_dot_general`` and XLA's scatter-add,
which is another deployment, so it is refused at import, at once.
"""

from __future__ import annotations

import numpy as np

import dmlc_tpu.ops.slot_rows  # noqa: F401 - see the module's docstring
from cellbench.learners import fm
from cellbench.learners.fm import SAMPLE_ROWS, compare  # noqa: F401
from cellbench.reference import fm_adam_ragged


def table_ids(config: dict) -> int:
    """Ids the table holds: the file's ids are trained on as printed, so a
    1-based file (``first_id`` 1) leaves id 0 unused, as libFM does."""
    return config["num_features"] + config["first_id"]


class Adapter(fm.Adapter):
    def __init__(self, config: dict, seed: int, mesh=None):
        from dmlc_tpu.models import FMLearner

        if mesh is not None:
            raise ValueError("fm_ragged adapter: the bcoo kind is one chip's")
        if (config["optimizer"] != "adam" or config["dtype"] != "float32"
                or config["layout"] != "bcoo"):
            raise ValueError("fm_ragged adapter: the configuration must "
                             "state float32 tables, optax.adam and the bcoo "
                             "layout")
        self.config = config
        self.seed = int(seed) % (2 ** 31 - 1)
        self.mesh = None
        self.learner = FMLearner(
            num_col=table_ids(config), num_factors=config["num_factors"],
            objective=config["objective"], layout="bcoo",
            learning_rate=config["learning_rate"],
            init_scale=config["init_scale"], seed=self.seed)
        self._probes = None

    def device_iter_kwargs(self) -> dict:
        return dict(num_col=self.learner.device_num_col(),
                    batch_size=self.config["batch_size"], layout="bcoo")

    def step_min_bytes(self) -> int:
        raise NotImplementedError(
            "the bytes of a ragged step depend on the batch's real slots: "
            "cellbench/readers/ragged_hbm_roofline_share.py counts them")

    def checksum_fold(self):
        """``(zero, fold)``: a jitted consumer that sums, over the real
        slots of a ``(BCOO, label, weight)`` batch, rows, indices, squared
        indices (uint32 wrap-around) and labels. A pad slot's coordinates
        lie one past both ends."""
        import jax
        import jax.numpy as jnp

        zero = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.uint32),
                jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.int32))

        def fold(acc, batch):
            mat, label, weight = batch
            real = mat.indices[:, 0] < mat.shape[0]
            idx = jnp.where(real, mat.indices[:, 1], 0).astype(jnp.uint32)
            live = weight > 0
            return (acc[0] + jnp.sum(live, dtype=jnp.int32),
                    acc[1] + jnp.sum(idx, dtype=jnp.uint32),
                    acc[2] + jnp.sum(idx * idx, dtype=jnp.uint32),
                    acc[3] + jnp.sum(jnp.where(live, label, 0.0)
                                     ).astype(jnp.int32))

        return zero, jax.jit(fold)


# ---------------------------------------------------------------------------
# the plain reference's side of the comparison
# ---------------------------------------------------------------------------

def _power_of_two(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length()


def reference_digest(config: dict, seed: int, corpus_path: str,
                     steps: int = 3, dtype: str = "float32") -> dict:
    """Run the plain reference over the first ``steps`` batches of the
    corpus and keep what the comparison reads, as ``fm.reference_digest``
    does. ``dtype='bfloat16'`` is the control."""
    seed = int(seed) % (2 ** 31 - 1)
    batch = config["batch_size"]
    w_rows = table_ids(config) + 1
    lens, ids, val, lab = fm_adam_ragged.parse_libsvm_rows(
        corpus_path, steps * batch)
    touched, counts = np.unique(ids, return_counts=True)
    rng = np.random.default_rng(seed)
    often = touched[np.argsort(counts)[-8:]]
    rest = np.setdiff1d(touched, often)
    sample_t = np.sort(np.concatenate([
        often, rng.choice(rest, min(SAMPLE_ROWS, len(rest)), replace=False)]))
    pool = rng.integers(0, table_ids(config), 4 * SAMPLE_ROWS)
    sample_u = np.setdiff1d(pool, touched)[:SAMPLE_ROWS]
    # compact tables: the touched rows, then rows that nothing touches up
    # to a power of two (so that seeds share compiled programs), then the
    # padding sink
    size = _power_of_two(len(touched) + 1)
    sink = w_rows - 1
    compact = np.searchsorted(touched, ids)
    pad = np.full(size - len(touched), sink, np.int64)
    pool_ids = np.full(SAMPLE_ROWS, sink, np.int64)
    pool_ids[:len(sample_u)] = sample_u
    v0, v0_untouched = fm_adam_ragged.initial_rows(
        seed, w_rows, config["num_factors"], config["init_scale"],
        np.concatenate([touched, pad, [sink]]), pool_ids)
    v0_untouched = v0_untouched[:len(sample_u)]
    # a step's flat lists, padded at the end to one length with entries of
    # no row (the sink's id, value 0, row number `batch`)
    ends = np.cumsum(lens)
    cuts = [0] + [int(ends[(s + 1) * batch - 1]) for s in range(steps)]
    longest = _power_of_two(max(b - a for a, b in zip(cuts, cuts[1:])))
    batches = []
    for s, (a, b) in enumerate(zip(cuts, cuts[1:])):
        fill = longest - (b - a)
        batches.append((
            np.concatenate([compact[a:b], np.full(fill, size)]),
            np.concatenate([val[a:b], np.zeros(fill, np.float32)]),
            np.concatenate([np.repeat(np.arange(batch),
                                      lens[s * batch:(s + 1) * batch]),
                            np.full(fill, batch)]),
            lab[s * batch:(s + 1) * batch]))
    trace = fm_adam_ragged.train(v0, batches, config["learning_rate"],
                                 dtype=dtype)
    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))  # noqa: E731
    _, p_end, m_end, n_end = trace[-1]
    m_first = trace[0][2]
    at = np.searchsorted(touched, sample_t)
    return {
        "losses": [t[0] for t in trace],
        "grad_norms": [norm(m) / (1.0 - fm_adam_ragged.B1) for m in m_first],
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
