"""Plain reference: rows of the field-aware FM's seeded start, drawn in
blocks (PR 32; the configuration ``kdd12_ffm_ps4``'s copy of the start).

``ffm_adagrad.initial_rows`` draws the whole ``[rows, m * k]`` start on one
device and takes rows of it: 9.6 GB at libffm's whole KDD2012 table, and
as much again for the scaling. The same values come row by row. Under
jax's default ``jax_threefry_partitionable`` an element of
``jax.random.uniform(key, shape)`` depends on the key and on the
element's flat index ``n`` alone: its 32 random bits are the two output
words of Threefry-2x32 on the counter ``(n >> 32, n & 0xffffffff)``,
exclusive-ored, and the float is ``bitcast(bits >> 9 | 0x3f800000) - 1``.
So the rows asked for are computed directly on the device from their ids,
a block of ids at a time, and nothing else is. Imports nothing of the program;
``cellbench/tests/test_ffm_ps4_cell.py`` and ``tests/test_ffm_ps.py`` hold
it value for value to ``initial_rows`` at a small size.
"""

from __future__ import annotations

import numpy as np

BLOCK_IDS = 1 << 18     # ids a block: 46 MB of float32 at 44 columns


def flat_index_words(ids, width: int):
    """``(hi, lo)`` uint32 ``[width, n]`` (the ids on the minor axis, where
    a TPU has its lanes and a transfer its long runs): the two words of the
    flat index ``id * width + column`` of every element of rows ``ids`` [n]
    (uint32).
    It passes 31 bits at libffm's size (2,406,203,932 elements) and may
    pass 32, so it is made from 16-bit limbs of the id (``width`` is below
    2**16), with no 64-bit integer."""
    import jax.numpy as jnp

    u32 = jnp.uint32
    ids = ids[None, :]
    col = jnp.arange(width, dtype=u32)[:, None]
    low_limb, high_limb = (ids & u32(0xFFFF)) * u32(width), \
        (ids >> u32(16)) * u32(width)
    shifted = high_limb << u32(16)
    partial = shifted + low_limb
    lo = partial + col
    hi = (high_limb >> u32(16)) + (partial < shifted).astype(u32) \
        + (lo < partial).astype(u32)
    return hi, lo


def _uniform_rows(key_data, ids, width: int):
    """Rows ``ids`` [n] (uint32) of ``jax.random.uniform(key, (rows,
    width))``, transposed: ``U[0, 1)`` float32 ``[width, n]``."""
    import jax
    import jax.numpy as jnp
    from jax.extend.random import threefry2x32_p

    u32 = jnp.uint32
    hi, lo = flat_index_words(ids, width)
    a, b = threefry2x32_p.bind(jnp.broadcast_to(key_data[0], lo.shape),
                               jnp.broadcast_to(key_data[1], lo.shape),
                               hi, lo)
    one_to_two = ((a ^ b) >> u32(9)) | u32(0x3F800000)
    return jax.lax.bitcast_convert_type(one_to_two, jnp.float32) - 1.0


def initial_rows(seed: int, rows: int, num_fields: int, num_factors: int,
                 *id_lists, block_ids: int = BLOCK_IDS):
    """As ``ffm_adagrad.initial_rows``, value for value: rows of
    ``U[0, 1) / sqrt(num_factors)`` from ``jax.random.PRNGKey(seed)`` drawn
    as ``[rows, num_fields * num_factors]`` float32 with the last (padding
    sink) row zero, one array per list of ids; only the rows asked for
    are made, ``block_ids`` at a time."""
    import jax
    import jax.numpy as jnp

    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("the start is defined under jax's default "
                           "jax_threefry_partitionable=True")
    width = num_fields * num_factors
    assert rows < 2 ** 32 and width < 2 ** 16
    key = jax.random.key_data(jax.random.PRNGKey(seed))
    scale = 1.0 / float(num_factors) ** 0.5
    draw = jax.jit(lambda ids: _uniform_rows(key, ids, width) * scale)
    out = []
    for ids in id_lists:
        ids = np.asarray(ids).astype(np.uint32)
        got = np.empty((len(ids), width), np.float32)
        for at in range(0, len(ids), block_ids):
            cut = ids[at:at + block_ids]
            if len(cut) < block_ids:        # one compiled shape
                cut = np.concatenate(
                    [cut, np.zeros(block_ids - len(cut), np.uint32)])
            got[at:at + block_ids] = np.asarray(
                draw(jnp.asarray(cut))).T[:len(ids) - at]
        got[ids == rows - 1] = 0.0           # the padding sink
        out.append(got)
    return out
