"""Plain reference: a field-aware factorization machine trained by AdaGrad.

Written from the equations of Juan, Zhuang, Chin, Lin (RecSys 2016) and
libffm's ``ffm.cpp``, importing nothing of the program. A row holds slots
``s`` with id ``i_s``, field ``f_s`` and value ``x_s``; the table ``W`` is
``[ids, m, k]``:

    r     = 1 / sum_s x_s^2
    phi   = r * sum_{s<t} <W[i_s, f_t, :], W[i_t, f_s, :]> x_s x_t
    l     = log(1 + exp(-y phi))
            + lambda/2 * sum_{s != t, x_s x_t != 0} |W[i_s, f_t, :]|^2
    g     = sum over the batch's rows of dl/dW              (y = 2 label - 1)
    G    <- G + g^2;   W <- W - eta * g / sqrt(G)           (G starts at 1)

libffm makes that AdaGrad update after every instance; one update per
batch on the summed gradient is the configuration's stated departure.
Per pair libffm's gradient is ``lambda w1 + kappa w2 x_s x_t r`` with
``kappa = dl/dphi``, which is the gradient of ``l`` above.

A coordinate whose gradient has always been zero keeps its start and its
accumulator's 1 exactly, so the reference holds the rows that the given
batches touch and no others: the compact table is what the dense update
would hold at those rows, and the comparison checks that the program left
a sample of the other rows untouched.

``dtype`` is float32 for the reference proper; the control of the
comparison runs the same code with bfloat16 tables, accumulators and pair
products: the nearest precision below the one the configuration states.
"""

from __future__ import annotations

import numpy as np


def parse_libfm_rows(path: str, rows: int, max_nnz: int):
    """The first ``rows`` rows of a libfm text file, by plain Python:
    ``(indices [rows, max_nnz] int64, fields int64, values float32, labels
    float32)``, short rows padded with index -1, field 0 and value 0."""
    idx = np.full((rows, max_nnz), -1, np.int64)
    fld = np.zeros((rows, max_nnz), np.int64)
    val = np.zeros((rows, max_nnz), np.float32)
    lab = np.zeros(rows, np.float32)
    with open(path, "rb") as f:
        for r in range(rows):
            toks = f.readline().split()
            if not toks:
                raise ValueError(f"{path}: only {r} rows, wanted {rows}")
            lab[r] = float(toks[0])
            for k, tok in enumerate(toks[1:1 + max_nnz]):
                field, i, x = tok.split(b":")
                fld[r, k] = int(field)
                idx[r, k] = int(i)
                val[r, k] = float(x)
    return idx, fld, val, lab


def initial_rows(seed: int, rows: int, num_fields: int, num_factors: int,
                 *id_lists):
    """Rows of the configuration's seeded start, one array per list of
    ids: ``U[0, 1) / sqrt(num_factors)`` from ``jax.random.PRNGKey(seed)``
    drawn as ``[rows, num_fields * num_factors]`` float32 (column
    ``f * num_factors + d`` is factor ``d`` for field ``f``), with the last
    (padding sink) row zero."""
    import jax
    import jax.numpy as jnp

    w = jax.random.uniform(jax.random.PRNGKey(seed),
                           (rows, num_fields * num_factors), jnp.float32)
    w = w * (1.0 / float(num_factors) ** 0.5)
    w = w.at[-1].set(0.0)
    return [np.asarray(jnp.take(w, jnp.asarray(ids, jnp.int32), axis=0))
            for ids in id_lists]


def _phi_and_reg(w, idx, fld, val, num_fields: int, num_factors: int):
    import jax.numpy as jnp

    b, k = idx.shape
    rows = w[idx].reshape(b, k, num_fields, num_factors)   # W[i_s, :, :]
    # a[b, s, t, :] = W[i_s, f_t, :]
    a = jnp.take_along_axis(rows, fld[:, None, :, None], axis=2)
    pair = jnp.sum(a * jnp.swapaxes(a, 1, 2), axis=-1)     # <., W[i_t, f_s]>
    xx = val[:, :, None] * val[:, None, :]
    s, t = jnp.arange(k)[:, None], jnp.arange(k)[None, :]
    norm = jnp.sum(val * val, axis=1)
    r = jnp.where(norm > 0, 1.0 / jnp.where(norm > 0, norm, 1.0), 0.0)
    phi = r * jnp.sum(jnp.where(s < t, pair * xx, 0.0), axis=(1, 2))
    used = (xx != 0) & (s != t)
    reg = jnp.sum(jnp.where(used, jnp.sum(a * a, axis=-1), 0.0),
                  axis=(1, 2))
    return phi, reg


def _loss_sum(w, idx, fld, val, lab, l2, num_fields, num_factors):
    import jax.numpy as jnp

    phi, reg = _phi_and_reg(w, idx, fld, val, num_fields, num_factors)
    phi = phi.astype(jnp.float32)
    y = 2.0 * lab - 1.0
    per = jnp.logaddexp(0.0, -y * phi) + 0.5 * l2 * reg.astype(jnp.float32)
    return jnp.sum(per)


def _adagrad_step(w, acc, idx, fld, val, lab, *, learning_rate, l2,
                  num_fields, num_factors, dt):
    import jax

    total, g = jax.value_and_grad(_loss_sum)(
        w, idx, fld, val.astype(dt), lab, l2, num_fields, num_factors)
    g = g.at[-1].set(0.0)                   # the padding row never learns
    acc = (acc + g * g).astype(dt)
    w = (w - learning_rate * g / jax.numpy.sqrt(acc)).astype(dt)
    return total / idx.shape[0], w, acc


def train(w_rows, batches, learning_rate: float, l2: float, num_fields: int,
          num_factors: int, dtype="float32"):
    """AdaGrad steps over ``batches`` (each ``(idx, fld, val, lab)`` with
    ``idx`` already mapped into the compact table; padding slots point at
    the last row and carry value 0). ``w_rows`` [U + 1, m * k] are the
    start rows. Returns per step the mean loss of the batch's rows and the
    state after it: ``[(loss, W, G), ...]`` as float32 numpy. Each step is
    one jitted call at matmul precision highest; callers that pad
    ``w_rows`` to one size compile it once."""
    import functools

    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    step = jax.jit(functools.partial(
        _adagrad_step, learning_rate=learning_rate, l2=l2,
        num_fields=num_fields, num_factors=num_factors, dt=dt))
    with jax.default_matmul_precision("highest"):
        w = jnp.asarray(w_rows, jnp.float32).astype(dt)
        acc = jnp.ones_like(w)
        out = []
        for idx, fld, val, lab in batches:
            loss, w, acc = step(
                w, acc, jnp.asarray(idx, jnp.int32),
                jnp.asarray(fld, jnp.int32), jnp.asarray(val, jnp.float32),
                jnp.asarray(lab, jnp.float32))
            out.append((float(loss), np.asarray(w, np.float32),
                        np.asarray(acc, np.float32)))
    return out
