"""Plain reference: a second-order factorization machine trained by Adam.

Written from the equations, importing nothing of the program:

    margin(x) = w0 + sum_k w[i_k] x_k
                + 1/2 sum_f [ (sum_k v[i_k, f] x_k)^2 - sum_k v[i_k, f]^2 x_k^2 ]
    loss      = mean over rows of  log(1 + exp(margin)) - y * margin
    Adam (Kingma & Ba 2015), bias-corrected:
        m <- b1 m + (1 - b1) g,  n <- b2 n + (1 - b2) g^2
        p <- p - lr * (m / (1 - b1^t)) / (sqrt(n / (1 - b2^t)) + eps)

Exact dense Adam from zero moments leaves a coordinate whose gradient has
always been zero exactly where it started: its moments stay 0 and its
update is 0 / (0 + eps). So the reference holds the rows that the given
batches touch and no others ("in blocks of rows"): the compact tables are
what dense Adam would hold at those rows, and the comparison checks that
the program left a sample of the other rows untouched. That keeps the
reference to a few tens of MB on the device, beside a program whose tables
fill a third of it.

``dtype`` is float32 for the reference proper. The control of the
comparison runs the same code with bfloat16 tables, moments and margins:
the nearest precision below the one the configuration states.
"""

from __future__ import annotations

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def parse_libfm_rows(path: str, rows: int, max_nnz: int):
    """The first ``rows`` rows of a libfm text file, by plain Python:
    ``(indices [rows, max_nnz] int64, values float32, labels float32)``,
    short rows padded with index -1 and value 0."""
    idx = np.full((rows, max_nnz), -1, np.int64)
    val = np.zeros((rows, max_nnz), np.float32)
    lab = np.zeros(rows, np.float32)
    with open(path, "rb") as f:
        for r in range(rows):
            toks = f.readline().split()
            if not toks:
                raise ValueError(f"{path}: only {r} rows, wanted {rows}")
            lab[r] = float(toks[0])
            for k, tok in enumerate(toks[1:1 + max_nnz]):
                _, i, x = tok.split(b":")
                idx[r, k] = int(i)
                val[r, k] = float(x)
    return idx, val, lab


def initial_rows(seed: int, rows: int, num_factors: int, init_scale: float,
                 *id_lists):
    """Rows of the configuration's seeded start, one array per list of
    ids: ``init_scale * N(0, 1)`` from ``jax.random.PRNGKey(seed)``, shape
    ``[rows, num_factors]`` float32, with the last (padding sink) row zero.

    The table is drawn and scaled as two separate operations, the way the
    configuration states it. Under one ``jit`` XLA folds the scale into the
    constants of the normal transform and the rows come out an ulp off,
    which the exact comparison of untouched rows would catch."""
    import jax
    import jax.numpy as jnp

    v = init_scale * jax.random.normal(
        jax.random.PRNGKey(seed), (rows, num_factors), jnp.float32)
    v = v.at[-1].set(0.0)
    return [np.asarray(jnp.take(v, jnp.asarray(ids, jnp.int32), axis=0))
            for ids in id_lists]


def _margin(w0, w, v, idx, val):
    import jax.numpy as jnp

    w_g = w[idx]                      # [B, K]
    v_g = v[idx]                      # [B, K, F]
    linear = jnp.sum(w_g * val, axis=-1) + w0
    s = jnp.sum(v_g * val[..., None], axis=1)
    s2 = jnp.sum((v_g * v_g) * (val * val)[..., None], axis=1)
    return linear + 0.5 * jnp.sum(s * s - s2, axis=-1)


def _loss(params, idx, val, lab):
    import jax.numpy as jnp

    w0, w, v = params
    margin = _margin(w0, w, v, idx, val).astype(jnp.float32)
    per = jnp.logaddexp(0.0, margin) - lab * margin
    return jnp.mean(per)


def _adam_step(params, m, n, t, idx, val, lab, learning_rate, dt):
    import jax
    import jax.numpy as jnp

    loss, g = jax.value_and_grad(_loss)(params, idx, val.astype(dt), lab)
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
    """Adam steps over ``batches`` (each ``(idx, val, lab)`` with ``idx``
    already mapped into the compact tables; padding slots point at the
    last row and carry value 0). ``v_rows`` [U + 1, F] are the start rows
    of ``v``. Returns per step the loss and the state after it:
    ``[(loss, (w0, w, v), (m_w0, m_w, m_v), (n_w0, n_w, n_v)), ...]`` as
    float32 numpy. Each step is one jitted call at matmul precision
    highest; callers that pad ``v_rows`` to one size compile it once."""
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
        for t, (idx, val, lab) in enumerate(batches, start=1):
            loss, params, m, n = step(
                params, m, n, jnp.float32(t), jnp.asarray(idx, jnp.int32),
                jnp.asarray(val, jnp.float32), jnp.asarray(lab, jnp.float32))
            out.append((float(loss), f32(params), f32(m), f32(n)))
    return out
