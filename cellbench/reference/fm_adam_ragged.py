"""Plain reference: a second-order factorization machine trained by Adam on
ragged rows, read from libsvm text.

Written from the equations of ``fm_adam.py`` (the same margin, loss and
exact dense Adam), importing nothing of the program, for rows whose lengths
differ: a row is its own list of ``(id, value)``, the lists lie one after
another, and every sum over a row's non-zeros is a sum over its run of the
flat list (``jax.ops.segment_sum`` by the row's number): no row is padded
to another's length and none is cut. The three flat lists of a step are
padded at their end to one length, with entries that belong to no row, only
so that the three steps compile once.

As ``fm_adam.py``, the reference holds the table rows that the given
batches touch and no others. ``dtype`` is float32 for the reference proper;
the control runs the same code with bfloat16 tables, moments and margins.
"""

from __future__ import annotations

import numpy as np

from cellbench.reference.fm_adam import B1, B2, EPS, initial_rows  # noqa: F401


def parse_libsvm_rows(path: str, rows: int):
    """The first ``rows`` rows of a libsvm text file, by plain Python:
    ``(lengths [rows] int64, ids [nnz] int64 as printed, values [nnz]
    float32, labels [rows] float32)``."""
    lens = np.zeros(rows, np.int64)
    lab = np.zeros(rows, np.float32)
    ids, vals = [], []
    with open(path, "rb") as f:
        for r in range(rows):
            toks = f.readline().split()
            if not toks:
                raise ValueError(f"{path}: only {r} rows, wanted {rows}")
            lab[r] = float(toks[0])
            lens[r] = len(toks) - 1
            for tok in toks[1:]:
                i, x = tok.split(b":")
                ids.append(int(i))
                vals.append(float(x))
    return lens, np.asarray(ids, np.int64), np.asarray(vals, np.float32), lab


def _margin(w0, w, v, idx, val, row, num_rows):
    import jax
    import jax.numpy as jnp

    def over_rows(x):
        return jax.ops.segment_sum(x, row, num_segments=num_rows)

    w_g = w[idx]                      # [n]
    v_g = v[idx]                      # [n, F]
    linear = over_rows(w_g * val) + w0
    s = over_rows(v_g * val[:, None])
    s2 = over_rows((v_g * v_g) * (val * val)[:, None])
    return linear + 0.5 * jnp.sum(s * s - s2, axis=-1)


def _loss(params, idx, val, row, lab):
    import jax.numpy as jnp

    w0, w, v = params
    margin = _margin(w0, w, v, idx, val, row,
                     lab.shape[0]).astype(jnp.float32)
    per = jnp.logaddexp(0.0, margin) - lab * margin
    return jnp.mean(per)


def _adam_step(params, m, n, t, idx, val, row, lab, learning_rate, dt):
    import jax
    import jax.numpy as jnp

    loss, g = jax.value_and_grad(_loss)(params, idx, val.astype(dt), row, lab)
    # the padding row never learns
    g = (g[0], g[1].at[-1].set(0.0), g[2].at[-1].set(0.0))
    m = tuple((B1 * mi + (1 - B1) * gi).astype(dt) for mi, gi in zip(m, g))
    n = tuple((B2 * ni + (1 - B2) * gi * gi).astype(dt)
              for ni, gi in zip(n, g))
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = tuple(
        (p - learning_rate * (mi / c1) / (jnp.sqrt(ni / c2) + EPS)).astype(dt)
        for p, mi, ni in zip(params, m, n))
    return loss, params, m, n


def train(v_rows, batches, learning_rate: float, dtype="float32"):
    """Adam steps over ``batches``, each ``(idx [n], val [n], row [n], lab
    [B])``: the flat lists of a batch's non-zeros with ``idx`` already
    mapped into the compact tables and ``row`` the number of the row an
    entry belongs to (entries past the batch's last carry the last table
    row, value 0 and the row number B, which no sum holds). ``v_rows``
    [U + 1, F] are the start rows of ``v``. Returns per step the loss and
    the state after it, as ``fm_adam.train`` does."""
    import functools

    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    step = jax.jit(functools.partial(_adam_step, learning_rate=learning_rate,
                                     dt=dt))
    f32 = lambda tree: tuple(np.asarray(x, np.float32) for x in tree)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        v = jnp.asarray(v_rows, jnp.float32).astype(dt)
        params = (jnp.zeros((), dt), jnp.zeros(v.shape[0], dt), v)
        m = tuple(jnp.zeros_like(p) for p in params)
        n = tuple(jnp.zeros_like(p) for p in params)
        out = []
        for t, (idx, val, row, lab) in enumerate(batches, start=1):
            loss, params, m, n = step(
                params, m, n, jnp.float32(t), jnp.asarray(idx, jnp.int32),
                jnp.asarray(val, jnp.float32), jnp.asarray(row, jnp.int32),
                jnp.asarray(lab, jnp.float32))
            out.append((float(loss), f32(params), f32(m), f32(n)))
    return out
