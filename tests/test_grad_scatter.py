"""ops/grad_scatter.py: the dense gradient of the ELL table gather built
from sorted batch rows by a one-hot kernel, against XLA's scatter-add. On
the CPU backend the kernel runs in Pallas' interpret mode; the routing's
hardware gate is opened the way tests open ``pallas_band``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dmlc_tpu.models import FMLearner
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops.sparse import EllBatch, ell_table_gather
from dmlc_tpu.utils import telemetry

T, C = 256, 128   # small tiles: the interpreter walks every block


def _case(name):
    """``(num_rows, ids, num_factors)`` of one property the kernel must
    hold against ``zeros.at[ids].add(rows)``."""
    rng = np.random.default_rng(sum(map(ord, name)))
    rows, n, f = 4 * T, 6 * C, 8
    ids = rng.integers(0, rows, n)
    if name == "heavy_duplicates":        # one id holds 15% of the slots
        ids[rng.permutation(n)[:n * 15 // 100]] = 300
    elif name == "third_on_the_sink":
        rows = 4 * T + 1
        ids[rng.permutation(n)[:n // 3]] = rows - 1
    elif name == "both_edges_of_a_block":
        ids = np.tile(np.array([T - 1, T, 2 * T - 1, 2 * T, 0, rows - 1]),
                      n // 6)
    elif name == "empty_blocks":          # blocks 1 and 2 see no slot
        rows = 5 * T
        ids = np.where(ids % 2 == 0, ids % T, 3 * T + ids % (2 * T))
    elif name == "rows_not_a_multiple_of_the_block":
        rows = 3 * T + 77
        ids = rng.integers(0, rows, n)
        ids[:4] = rows - 1
    elif name == "slots_not_a_multiple_of_the_chunk":
        ids = ids[:n - 37]
    elif name == "one_chunk_spans_every_block":
        ids = rng.integers(0, rows, C - 5)
    elif name == "one_block_spans_many_chunks":
        ids = rng.integers(T, 2 * T, n)
    elif name == "negative_and_out_of_range_ids":
        ids[:8] = [-1, -rows, -rows - 1, rows, rows + 5, 2 ** 30, -3, 0]
    elif name.startswith("factors_"):
        f = int(name.split("_")[1])
    else:
        assert name == "uniform", name
    return rows, ids.astype(np.int32), f


# (the walk's own corners -- empty_blocks, rows_not_a_multiple_of_the_block,
# one_chunk_spans_every_block -- run for every op on it in
# tests/test_sorted_walk.py; the epilogues' cases below keep them)
CASES = ["uniform", "heavy_duplicates", "third_on_the_sink",
         "both_edges_of_a_block", "slots_not_a_multiple_of_the_chunk",
         "one_block_spans_many_chunks", "negative_and_out_of_range_ids",
         "factors_1", "factors_8", "factors_16"]


def _kernel(ids, g_w, g_v, rows, t=T, c=C):
    trailing = ((), (g_v.shape[1],))
    cols = [g_w[None, :], g_v.T]        # in the kernel's own column order
    starts = sw.column_starts(trailing)
    bounds, ids_s, payload = sw.sorted_payload(
        ids, jnp.concatenate(sorted(cols, key=lambda x: starts[
            0 if x is cols[0] else 1])), rows, t, c)
    dw_t, dv_t = gs.grad_scatter_pallas(
        bounds, ids_s, payload, num_rows=rows, trailing=trailing,
        block_ids=t, chunk_slots=c, interpret=True)
    return np.asarray(dw_t), np.asarray(dv_t.T)


@pytest.mark.parametrize("name", CASES)
def test_kernel_matches_the_scatter_add(name):
    rows, ids, f = _case(name)
    rng = np.random.default_rng(1)
    g_w = rng.normal(size=ids.size).astype(np.float32)
    g_v = rng.normal(size=(ids.size, f)).astype(np.float32)
    dw, dv = _kernel(jnp.asarray(ids), jnp.asarray(g_w), jnp.asarray(g_v),
                     rows)
    # the reference in float64: what both float32 sums round
    want_w, want_v = np.zeros(rows), np.zeros((rows, f))
    at = np.where(ids < 0, ids + rows, ids)
    keep = (at >= 0) & (at < rows)
    np.add.at(want_w, at[keep], g_w[keep].astype(np.float64))
    np.add.at(want_v, at[keep], g_v[keep].astype(np.float64))
    scale = np.zeros(rows)
    np.add.at(scale, at[keep], np.abs(g_v[keep]).max(axis=1))
    tol = 2e-6 * np.maximum(scale, 1.0)
    assert np.all(np.abs(dw - want_w) <= tol)
    assert np.all(np.abs(dv - want_v) <= tol[:, None])
    untouched = np.setdiff1d(np.arange(rows), at[keep])
    assert not dw[untouched].any() and not dv[untouched].any()  # exact 0


@pytest.mark.parametrize("value", [1.0, 1e-30, 3.0000002, -65504.125,
                                   1.1754944e-38])
def test_three_bfloat16_splits_hold_a_float32_exactly(value):
    """A lone slot's gradient comes back bit for bit: hi + mid + lo is the
    float32 itself."""
    ids = jnp.zeros((C,), jnp.int32).at[0].set(5)
    g = jnp.zeros((C,), jnp.float32).at[0].set(value)
    dw, dv = _kernel(ids + 0, g, jnp.stack([g, -g], axis=1), 2 * T)
    assert dw[5] == np.float32(value)
    assert dv[5, 0] == np.float32(value) and dv[5, 1] == -np.float32(value)


def _one_chunk_a_block(tiles_named):
    """Four blocks, a chunk each: chunk ``b`` names ids of the first
    ``tiles_named`` tiles of block ``b`` (their first and last id among
    them), so that its window is those tiles."""
    span = tiles_named * sw.TILE_IDS
    within = np.arange(C) * span // C
    within[-1] = span - 1
    return (np.repeat(np.arange(4) * T, C) + np.tile(within, 4)).astype(
        np.int32)


def _window_of_chunk_2(tiles_named):
    inside = np.zeros(4 * T, bool)
    inside[2 * T:2 * T + tiles_named * sw.TILE_IDS] = True
    return inside


@pytest.mark.parametrize("tiles_named", [1, 2])
def test_a_non_finite_value_poisons_its_column_of_its_window_only(
        tiles_named):
    """The documented caveat, pinned: 0 * inf in the contraction spreads a
    non-finite value over its column in the rows of the tiles its chunk's
    window holds in the block the chunk falls in (a scatter-add would
    poison one row; until PR 46 all T rows of the block were), and over
    nothing else: one tile of the block's two, or both."""
    ids = _one_chunk_a_block(tiles_named)
    g_w = jnp.ones((4 * C,), jnp.float32).at[2 * C + 3].set(jnp.inf)
    dw, dv = _kernel(jnp.asarray(ids), g_w,
                     jnp.ones((4 * C, 2), jnp.float32), 4 * T)
    inside = _window_of_chunk_2(tiles_named)
    assert not np.isfinite(dw[inside]).any()
    assert np.isfinite(dw[~inside]).all() and np.isfinite(dv).all()


# ---------------- the Adam epilogue ----------------

ADAM = gs.AdamEpilogue(0.05)
FUSED_CASES = ["uniform", "heavy_duplicates", "empty_blocks",
               "negative_and_out_of_range_ids",
               "rows_not_a_multiple_of_the_block", "third_on_the_sink",
               "one_chunk_spans_every_block"]


def _dense_grad(ids, g_w, g_v, rows):
    """``table_grad_xla``'s gradient with jnp.take's reading of the ids
    (negative ones count from the end, ids outside the table drop)."""
    ids = jnp.where(ids < 0, ids + rows, ids)
    ids = jnp.where(ids < 0, rows, ids)
    return gs.table_grad_xla(ids, (g_w, g_v), rows)


def _moving_state(rows, f, seed=5):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(                      # noqa: E731
        0.01 * rng.normal(size=shape), jnp.float32)
    return tuple((draw(*shape), draw(*shape), jnp.square(draw(*shape)))
                 for shape in ((rows,), (rows, f)))


def _fused_steps(name, count, blocks_a_step, steps=3):
    """``steps`` consecutive updates from one moving state, by the kernel
    with the epilogue and by ``optax.adam`` on the dense gradient:
    ``(got, want, start, ids)`` with a state as ``((w, m, n), (v, m, n))``."""
    rows, ids, f = _case(name)
    ids = jnp.asarray(ids)
    trailing = ((), (f,))
    start = got = _moving_state(rows, f)
    params, mu, nu = zip(*start)
    opt = optax.adam(ADAM.learning_rate)
    opt_state = (optax.ScaleByAdamState(jnp.asarray(count, jnp.int32), mu,
                                        nu), optax.EmptyState())
    for step in range(steps):
        rng = np.random.default_rng(step)
        g_w = jnp.asarray(rng.normal(size=ids.size), jnp.float32)
        g_v = jnp.asarray(rng.normal(size=(ids.size, f)), jnp.float32)
        bias = ADAM.bias(optax.safe_increment(opt_state[0].count))
        updates, opt_state = opt.update(_dense_grad(ids, g_w, g_v, rows),
                                        opt_state, params)
        params = optax.apply_updates(params, updates)
        bounds, ids_s, payload = sw.sorted_payload(
            ids, jnp.concatenate([g_v.T, g_w[None]]), rows, T, C)
        out = gs.grad_scatter_pallas(
            bounds, ids_s, payload, bias,
            *(x.T if x.ndim == 2 else x for t in got for x in t),
            num_rows=rows, trailing=trailing, block_ids=T, chunk_slots=C,
            epilogue=ADAM, blocks_a_step=blocks_a_step, interpret=True)
        got = (out[:3], tuple(x.T for x in out[3:]))
    want = tuple(zip(params, opt_state[0].mu, opt_state[0].nu))
    return got, want, start, np.asarray(ids)


@pytest.mark.parametrize("blocks_a_step", [1, 3])
@pytest.mark.parametrize("name", FUSED_CASES)
def test_fused_kernel_matches_optax_adam_on_the_dense_gradient(
        name, blocks_a_step):
    """Three consecutive steps: parameters and both moments of both tables
    as ``optax.adam`` leaves them from ``table_grad_xla``'s gradient.
    Three blocks a grid step leave the last step partly (or wholly) past
    the table's end."""
    got, want, _, _ = _fused_steps(name, 0, blocks_a_step)
    for table_got, table_want in zip(got, want):
        for leaf, (a, b) in enumerate(zip(table_got, table_want)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape
            assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max(), (name, leaf)


@pytest.mark.parametrize("count", [0, 1000, 2 ** 31 - 2, 2 ** 31 - 1])
def test_fused_kernel_takes_the_bias_correction_of_any_step(count):
    """``count`` 0 (the first step's corrections, 10 and 1000) to optax's
    saturated counter (both corrections 1)."""
    got, want, _, _ = _fused_steps("uniform", count, 2, steps=2)
    for table_got, table_want in zip(got, want):
        for a, b in zip(table_got, table_want):
            assert np.abs(np.asarray(a) - np.asarray(b)).max() \
                <= 1e-5 * np.abs(np.asarray(b)).max()


@pytest.mark.parametrize("blocks_a_step", [1, 2])
def test_a_block_no_slot_hits_still_takes_its_adam_step(blocks_a_step):
    """Exact dense Adam: blocks 1 and 2 see no slot and their moments
    decay all the same, ``m`` by ``b1`` and ``n`` by ``b2`` a step, and
    their parameters move by what the decayed moments say."""
    got, want, start, ids = _fused_steps("empty_blocks", 3, blocks_a_step,
                                         steps=1)
    assert not ((ids >= T) & (ids < 3 * T)).any()
    quiet = slice(T, 3 * T)
    for (p, m, n), (p_w, _, _), (p0, m0, n0) in zip(got, want, start):
        p, m, n, p0, m0, n0 = (np.asarray(x)[quiet]
                               for x in (p, m, n, p0, m0, n0))
        np.testing.assert_allclose(m, np.float32(ADAM.b1) * m0, rtol=1e-6)
        np.testing.assert_allclose(n, np.float32(ADAM.b2) * n0, rtol=1e-6)
        assert (p != p0).mean() > 0.9
        np.testing.assert_allclose(p, np.asarray(p_w)[quiet], rtol=1e-5,
                                   atol=1e-7)


def test_rows_with_no_gradient_and_no_moments_never_move():
    """What the benchmark's ``untouched_gap`` holds to 0: a zero gradient
    on zero moments leaves the parameter bit for bit."""
    rows, ids, f = _case("empty_blocks")
    bounds, ids_s, payload = sw.sorted_payload(
        jnp.asarray(ids), jnp.ones((f + 1, ids.size), jnp.float32), rows,
        T, C)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=rows), jnp.float32)
    v = jnp.asarray(rng.normal(size=(f, rows)), jnp.float32)
    out = gs.grad_scatter_pallas(
        bounds, ids_s, payload, ADAM.bias(jnp.int32(1)), w,
        jnp.zeros_like(w), jnp.zeros_like(w), v, jnp.zeros_like(v),
        jnp.zeros_like(v), num_rows=rows, trailing=((), (f,)), block_ids=T,
        chunk_slots=C, epilogue=ADAM, blocks_a_step=2, interpret=True)
    rest = np.setdiff1d(np.arange(rows), ids)
    assert rest.size >= 2 * T
    assert np.array_equal(np.asarray(out[0])[rest], np.asarray(w)[rest])
    assert np.array_equal(np.asarray(out[3])[:, rest],
                          np.asarray(v)[:, rest])
    for moment in (out[1], out[2]):
        assert not np.asarray(moment)[rest].any()
    hit = np.unique(ids)
    assert (np.asarray(out[0])[hit] != np.asarray(w)[hit]).all()


@pytest.mark.parametrize("tiles_named", [1, 2])
def test_a_non_finite_cotangent_reaches_parameters_and_moments(tiles_named):
    """The caveat with the epilogue, pinned: the block a non-finite
    cotangent's chunk falls in has its column's parameters and both
    moments non-finite in the rows of the chunk's window, and no other
    row of any block does."""
    ids = _one_chunk_a_block(tiles_named)
    g = jnp.ones((2, 4 * C), jnp.float32).at[0, 2 * C + 3].set(jnp.inf)
    bounds, ids_s, payload = sw.sorted_payload(
        jnp.asarray(ids), g, 4 * T, T, C)
    state = [jnp.full(shape, 0.5, jnp.float32)
             for shape in ((4 * T,), (1, 4 * T)) for _ in range(3)]
    out = gs.grad_scatter_pallas(
        bounds, ids_s, payload, ADAM.bias(jnp.int32(1)), *state,
        num_rows=4 * T, trailing=((), (1,)), block_ids=T, chunk_slots=C,
        epilogue=ADAM, blocks_a_step=1, interpret=True)
    inside = _window_of_chunk_2(tiles_named)
    for leaf in out[:3]:                  # the 1-D table: payload row 0
        assert not np.isfinite(np.asarray(leaf)[inside]).any()
        assert np.isfinite(np.asarray(leaf)[~inside]).all()
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in out[3:])


# ---------------- the AdaGrad epilogue (PR 34) ----------------

ADAGRAD = gs.AdaGradEpilogue(0.2)
LIBFFM = optax.chain(
    optax.scale_by_rss(initial_accumulator_value=1.0, eps=0.0),
    optax.scale(-ADAGRAD.learning_rate))


def _adagrad_case(name):
    """``(num_rows, ids, width)``: :func:`_case`'s ids into one table of
    44 columns (``hot_id``: a third of the slots on id 7, a Zipf head)."""
    rows, ids, _ = _case("uniform" if name == "hot_id" else name)
    if name == "hot_id":
        ids[::3] = 7
    return rows, ids, 44


def _adagrad_steps(name, blocks_a_step, steps=3, width=None):
    """``steps`` consecutive updates of one ``[rows, width]`` table from
    libffm's start (``W`` uniform, ``G`` = 1) by the kernel with the
    AdaGrad epilogue and by optax on the dense gradient: ``(got, want,
    start, ids)``, a state as ``(W, G)``."""
    rows, ids, w44 = _adagrad_case(name)
    f = width or w44
    ids = jnp.asarray(ids)
    rng = np.random.default_rng(11)
    w0 = jnp.asarray(0.5 * rng.uniform(size=(rows, f)), jnp.float32)
    start = got = (w0, jnp.ones_like(w0))
    params, opt_state = (w0,), LIBFFM.init((w0,))
    for step in range(steps):
        g = jnp.asarray(np.random.default_rng(step).normal(
            size=(ids.size, f)), jnp.float32)
        dense = _dense_grad(ids, g[:, 0], g, rows)[1:]
        updates, opt_state = LIBFFM.update(dense, opt_state, params)
        params = optax.apply_updates(params, updates)
        bounds, ids_s, payload = sw.sorted_payload(ids, g.T, rows, T, C)
        out = gs.grad_scatter_pallas(
            bounds, ids_s, payload, *(x.T for x in got), num_rows=rows,
            trailing=((f,),), block_ids=T, chunk_slots=C, epilogue=ADAGRAD,
            blocks_a_step=blocks_a_step, interpret=True)
        got = tuple(x.T for x in out)
    want = (params[0], opt_state[0].sum_of_squares[0])
    return got, want, start, np.asarray(ids)


@pytest.mark.parametrize("blocks_a_step", [1, 3])
@pytest.mark.parametrize("name", FUSED_CASES + ["hot_id"])
def test_fused_kernel_matches_optax_adagrad_on_the_dense_gradient(
        name, blocks_a_step):
    """Three consecutive steps at 44 columns: ``W`` and ``G`` as libffm's
    optax chain leaves them from ``table_grad_xla``'s gradient, within
    1e-6 of the widest element; rows no slot names bit for bit."""
    got, want, start, ids = _adagrad_steps(name, blocks_a_step)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max(), name
    rows = start[0].shape[0]
    at = np.where(ids < 0, ids + rows, ids)
    rest = np.setdiff1d(np.arange(rows), at[(at >= 0) & (at < rows)])
    assert rest.size
    for a, b in zip(got, start):
        assert np.array_equal(np.asarray(a)[rest], np.asarray(b)[rest])


@pytest.mark.parametrize("width", [1, 9, 16])
def test_adagrad_epilogue_takes_a_table_of_any_width(width):
    got, want, _, _ = _adagrad_steps("uniform", 2, steps=2, width=width)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() \
            <= 1e-6 * np.abs(np.asarray(b)).max()


def test_a_repeated_id_is_summed_once_before_it_is_squared():
    """AdaGrad is not additive in the slots: an id in 40 slots takes one
    step on the *sum* of its cotangent rows, ``G += (sum g)^2``."""
    rows, f, n = 2 * T, 44, C
    ids = jnp.full((n,), 300, jnp.int32).at[40:].set(5)
    g = jnp.asarray(np.random.default_rng(0).normal(size=(n, f)),
                    jnp.float32)
    w = jnp.full((f, rows), 0.25, jnp.float32)
    bounds, ids_s, payload = sw.sorted_payload(ids, g.T, rows, T, C)
    w1, acc = gs.grad_scatter_pallas(
        bounds, ids_s, payload, w, jnp.ones_like(w), num_rows=rows,
        trailing=((f,),), block_ids=T, chunk_slots=C, epilogue=ADAGRAD,
        interpret=True)
    total = np.asarray(g[:40], np.float64).sum(axis=0)
    want_acc = 1.0 + total ** 2
    np.testing.assert_allclose(np.asarray(acc)[:, 300], want_acc, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(w1)[:, 300],
        0.25 - ADAGRAD.learning_rate * total / np.sqrt(want_acc),
        rtol=1e-5, atol=1e-6)
    each = 1.0 + (np.asarray(g[:40], np.float64) ** 2).sum(axis=0)
    assert np.abs(np.asarray(acc)[:, 300] - each).max() > 1.0


def test_a_zero_gradient_leaves_table_and_accumulators_bit_for_bit():
    """What ``kdd12_ffm``'s ``untouched_gap`` holds to 0, with no guard
    and no sweep: ``G + 0`` and ``w - 0``, for accumulators that are no
    longer 1 and parameters of either sign (and both zeros)."""
    rows, ids, f = _adagrad_case("empty_blocks")
    bounds, ids_s, payload = sw.sorted_payload(
        jnp.asarray(ids), jnp.ones((f, ids.size), jnp.float32), rows, T, C)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(f, rows)), jnp.float32)
    w = w.at[:, T].set(0.0).at[:, T + 1].set(-0.0)
    acc = jnp.asarray(1.0 + rng.gamma(1.0, size=(f, rows)), jnp.float32)
    w1, acc1 = gs.grad_scatter_pallas(
        bounds, ids_s, payload, w, acc, num_rows=rows, trailing=((f,),),
        block_ids=T, chunk_slots=C, epilogue=ADAGRAD, blocks_a_step=2,
        interpret=True)
    rest = np.setdiff1d(np.arange(rows), ids)
    assert rest.size >= 2 * T and T in rest and T + 1 in rest
    for got, was in ((w1, w), (acc1, acc)):
        assert np.array_equal(np.asarray(got)[:, rest].view(np.uint32),
                              np.asarray(was)[:, rest].view(np.uint32))
    hit = np.unique(ids)
    assert (np.asarray(w1)[:, hit] != np.asarray(w)[:, hit]).all()
    assert (np.asarray(acc1)[:, hit] > np.asarray(acc)[:, hit]).all()


@pytest.mark.parametrize("tiles_named", [1, 2])
def test_a_non_finite_cotangent_reaches_table_and_accumulators(tiles_named):
    """PR 31's caveat holds for this epilogue too: the block a non-finite
    cotangent's chunk falls in has its column of ``W`` and ``G``
    non-finite in the rows of the chunk's window, and no other row of any
    block does."""
    ids = _one_chunk_a_block(tiles_named)
    g = jnp.ones((2, 4 * C), jnp.float32).at[0, 2 * C + 3].set(jnp.inf)
    bounds, ids_s, payload = sw.sorted_payload(
        jnp.asarray(ids), g, 4 * T, T, C)
    state = [jnp.full((2, 4 * T), 0.5, jnp.float32),
             jnp.ones((2, 4 * T), jnp.float32)]
    out = gs.grad_scatter_pallas(
        bounds, ids_s, payload, *state, num_rows=4 * T, trailing=((2,),),
        block_ids=T, chunk_slots=C, epilogue=ADAGRAD, blocks_a_step=1,
        interpret=True)
    inside = _window_of_chunk_2(tiles_named)
    for leaf in out:
        leaf = np.asarray(leaf)
        assert not np.isfinite(leaf[0, inside]).any()
        assert np.isfinite(leaf[0, ~inside]).all()
        assert np.isfinite(leaf[1]).all()     # the other column


def test_an_epilogue_declares_what_the_kernel_keeps_books_for(kernel_route):
    """Adam: ``p, m, n`` and two bias scalars; AdaGrad: ``W, G`` and no
    scalar. State of another epilogue's shape is refused before a trace,
    and only the Adam kernel carries another name than ``grad_scatter``
    (the benchmark's kernel roofline reads the name: PR 33)."""
    assert (ADAM.leaves, ADAM.scalars, ADAM.kernel_name) \
        == (3, 2, "grad_scatter_adam")
    assert (ADAGRAD.leaves, ADAGRAD.scalars, ADAGRAD.kernel_name) \
        == (2, 0, "grad_scatter")
    assert ADAGRAD != ADAM and hash(ADAGRAD) != hash(gs.AdaGradEpilogue(0.1))
    rows, f = 2 * T, 4
    ids = jnp.arange(C, dtype=jnp.int32)
    bounds, ids_s, payload = sw.sorted_payload(
        ids, jnp.ones((f, C), jnp.float32), rows, T, C)
    table = jnp.ones((f, rows), jnp.float32)
    call = functools.partial(
        gs.grad_scatter_pallas, bounds, ids_s, payload, num_rows=rows,
        trailing=((f,),), block_ids=T, chunk_slots=C, interpret=True)
    with pytest.raises(AssertionError):
        call(table, table, table, epilogue=ADAGRAD)
    with pytest.raises(AssertionError):
        call(ADAM.bias(jnp.int32(1)), table, table, epilogue=ADAGRAD)
    with pytest.raises(AssertionError):
        call(table, table, epilogue=ADAM)
    with pytest.raises(Exception, match="not this epilogue's"):
        gs.fused_table_update(ids, (jnp.ones((C, f)),),
                              ((table.T, table.T, table.T),), None, ADAGRAD)


# pinned with the parent's own code (9cdda0e, jax 0.9.0): str(make_jaxpr)
# of the call with no epilogue, sha256, first 16 digits.
# PR 46 (parent 68779f0): the kernel contracts a (block, chunk) pair over
# the tiles the chunk can name (the body of the pallas_call holds a tree of
# ``cond``s over the ladder's rungs, as the forward's has since PR 43), by
# design another program at all three shapes (they read 591c3a295b86bed5,
# a1d5f77b4deb15b1 and 308f21120ebb5cf3): re-pinned from PR 46's own tree.
# What holds the new program to the old one's results is
# test_the_window_is_the_whole_block_bit_for_bit
PARENT_JAXPRS = {
    (54_686_453, 1 << 20, ((), (8,)), 4096, 128): "6e0f9c56ab37abe2",
    (13_671_614, 1 << 20, ((44,),), 4096, 128): "79ab39e220c1d186",
    (1000, 700, ((), (8,)), 256, 128): "cd0db77a2a1dc989",
}


@pytest.mark.parametrize("shape", list(PARENT_JAXPRS),
                         ids=["kdd12_fm", "kdd12_ffm", "tiny"])
def test_no_epilogue_lowers_to_the_jaxpr_it_had_before_the_epilogue(shape):
    """The field-aware FM, the ``table`` collective and every caller with
    an optimizer of its own run the kernel with no epilogue: its program
    is, character for character, the one before the epilogue existed. (A
    new jax may print a jaxpr otherwise: pin again from that commit.)"""
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    rows, n, trailing, t, c = shape
    padded = -(-n // c) * c
    split = 3 * (-(-sum(sw.widths(trailing)) // 16) * 16)
    text = str(jax.make_jaxpr(lambda *a: gs.grad_scatter_pallas(
        *a, num_rows=rows, trailing=trailing, block_ids=t, chunk_slots=c))(
        jax.ShapeDtypeStruct((2, padded // c + 1), jnp.int32),
        jax.ShapeDtypeStruct((1, padded), jnp.int32),
        jax.ShapeDtypeStruct((split, padded), jnp.bfloat16)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_JAXPRS[shape]
    if shape in LINE_JAXPRS:
        assert sw.slot_layout(sum(sw.widths(trailing))) == "lines"
        lines = str(jax.make_jaxpr(lambda *a: gs.grad_scatter_pallas(
            *a, num_rows=rows, trailing=trailing, block_ids=t,
            chunk_slots=c))(
            jax.ShapeDtypeStruct((2, padded // c + 1), jnp.int32),
            jax.ShapeDtypeStruct((1, padded), jnp.int32),
            jax.ShapeDtypeStruct((padded, 128), jnp.float32)))
        assert "name=grad_scatter" in lines
        assert hashlib.sha256(lines.encode()).hexdigest()[:16] \
            == LINE_JAXPRS[shape]


# pinned from PR 47's own tree (jax 0.9.0): the same call fed the field-aware
# FM's payload as it comes since then, ``[Np, 128]`` float32 lines that the
# kernel transposes and splits when a chunk arrives
# (``sorted_walk.slot_layout``). Fed three bfloat16 parts of lane-major
# columns the kernel is the parent's program still, at 44 columns as at 9:
# PARENT_JAXPRS above was not touched
LINE_JAXPRS = {
    (13_671_614, 1 << 20, ((44,),), 4096, 128): "04c7b07826e9beb0",
}


# pinned with the parent's own code (b7fb3af, jax 0.9.0): the call with the
# Adam epilogue, as above (the bookkeeping became the epilogue's own in PR
# 34: leaves and scalars a table; Adam's program is the one it was).
# PR 46 (parent 68779f0): the tile window, as PARENT_JAXPRS says; by design
# another program at both shapes (they read 4235bce6da5c72c2 and
# fdd8e41b364c1857): re-pinned from PR 46's own tree
PARENT_ADAM_JAXPRS = {
    (54_686_453, 1 << 20, ((), (8,)), 4096, 128, None): "dcfbe812f6e30506",
    (1000, 700, ((), (8,)), 256, 128, 3): "2b5e4b0f59b5e84b",
}


def _epilogue_jaxpr(shape, epilogue):
    rows, n, trailing, t, c, blocks = shape
    padded = -(-n // c) * c
    split = 3 * (-(-sum(sw.widths(trailing)) // 16) * 16)
    sds = jax.ShapeDtypeStruct
    state = [sds((epilogue.scalars,), jnp.float32)] * (epilogue.scalars > 0)
    state += [sds(tail + (rows,), jnp.float32) for tail in trailing
              for _ in range(epilogue.leaves)]
    return jax.make_jaxpr(lambda *a: gs.grad_scatter_pallas(
        *a, num_rows=rows, trailing=trailing, block_ids=t, chunk_slots=c,
        epilogue=epilogue, blocks_a_step=blocks))(
        sds((2, padded // c + 1), jnp.int32), sds((1, padded), jnp.int32),
        sds((split, padded), jnp.bfloat16), *state)


@pytest.mark.parametrize("shape", list(PARENT_ADAM_JAXPRS),
                         ids=["kdd12_fm", "tiny"])
def test_adam_epilogue_lowers_to_the_jaxpr_it_had_before_adagrad(shape):
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    text = str(_epilogue_jaxpr(shape, ADAM))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_ADAM_JAXPRS[shape]
    assert "name=grad_scatter_adam" in text


def test_adagrad_epilogue_aliases_both_leaves_and_writes_no_gradient():
    """At kdd12_ffm's shape: two table operands in, the same two out, each
    aliased to its operand, and the call named ``grad_scatter``."""
    jaxpr = _epilogue_jaxpr(
        (13_671_614, 1 << 20, ((44,),), 4096, 128, None), ADAGRAD).jaxpr
    (outer,) = jaxpr.eqns                         # grad_scatter_pallas' jit
    (call,) = [e for e in outer.params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "grad_scatter"
    assert call.params["input_output_aliases"] == ((3, 0), (4, 1))
    assert [v.aval.shape for v in call.outvars] == [(44, 13_671_614)] * 2


# ---------------- the tile window (PR 46) ----------------

# twelve tiles a block, so that the ladder has a gap (1 to 8, 10, 12 tiles)
# and a window can be pulled back; and the cells' own block of 32
T_WIDE = 1536
WINDOW_BLOCKS = [T_WIDE, sw.BLOCK_IDS]
WINDOW_CASES = ["chunk_inside_one_tile", "chunk_across_a_tiles_edge",
                "window_pulled_back", "chunk_spans_three_blocks",
                "chunks_of_one_repeated_id", "chunks_of_sentinels_alone",
                "blocks_past_the_tables_end", "every_rung"]
# a grid step's blocks with each epilogue (the dense gradient takes one):
# at 2 and at 3 ``blocks_past_the_tables_end``'s last step holds a block
# past the table's fifth
WINDOW_EPILOGUES = {"none": (None, 1), "adam": (ADAM, 3),
                    "adagrad": (ADAGRAD, 2)}


def _window_ids(name, t):
    """``(num_rows, ids)`` of one thing a pair's window may get wrong, at
    blocks of ``t`` ids."""
    rng = np.random.default_rng(sum(map(ord, name)))
    tiles = t // sw.TILE_IDS
    rows, n = 4 * t, 6 * C
    if name == "chunk_inside_one_tile":       # tile 5 of block 1
        ids = t + 5 * 128 + rng.integers(0, 128, C)
    elif name == "chunk_across_a_tiles_edge":     # tiles 5 and 6, six chunks
        ids = t + 6 * 128 + rng.integers(-28, 28, n)
    elif name == "window_pulled_back":
        # the block's last nine tiles: the rung of ten starts a tile early
        lo = (tiles - 9) * 128
        ids = t + rng.integers(lo, t, C)
        ids[:2] = [t + lo, 2 * t - 1]
    elif name == "chunk_spans_three_blocks":
        # one chunk: block 0's last tiles, ids all over block 1, block 2's
        # first tiles; and a chunk of block 3's alone after it
        ids = np.concatenate([
            rng.integers(t - 200, t, 40), rng.integers(t, 2 * t, 48),
            rng.integers(2 * t, 2 * t + 200, 40),
            rng.integers(3 * t + 260, 3 * t + 380, C)])
    elif name == "chunks_of_one_repeated_id":
        # an ELL batch's padding: chunk after chunk of the table's last row
        rows = 3 * t + 77
        ids = np.full(n, rows - 1)
        ids[:C // 2] = rng.integers(0, rows, C // 2)
    elif name == "chunks_of_sentinels_alone":
        ids = rng.integers(0, rows, n)
        ids[n // 3:] = rows + rng.integers(0, 50, n - n // 3)
    elif name == "blocks_past_the_tables_end":
        rows = 4 * t + 77
        ids = rng.integers(0, rows, n)
        ids[:4] = rows - 1
    else:
        # a chunk a block, its window as wide as a rung or one tile
        # narrower, ending on the block's edge
        assert name == "every_rung", name
        ladder = sw.ladder(t)
        widths = sorted(set(ladder) | {r - 1 for r in ladder[1:]})
        rows = len(widths) * t
        ids = np.concatenate([
            (b + 1) * t - 1 - np.append(rng.integers(0, w * 128, C - 2),
                                        [0, w * 128 - 1])
            for b, w in enumerate(widths)])
    return rows, ids.astype(np.int32)


def _plain_rungs(ids, rows, t, rungs):
    """The rung every (block, chunk) pair of ``_scatter_kernel``'s walk
    takes, in plain Python: every block of the table against every chunk
    that holds an id of it or of both sides of it."""
    ids = ids.astype(np.int64)
    sentinel = -(-rows // t) * t
    ids = np.sort(np.where((ids < 0) | (ids >= rows), sentinel, ids))
    ids = np.concatenate([ids, np.full(-len(ids) % C, sentinel)])
    taken = []
    for chunk in ids.reshape(-1, C):
        for base in range(0, sentinel, t):
            if chunk[0] < base + t and chunk[-1] >= base:
                first = (max(chunk[0], base) - base) // 128
                last = (min(chunk[-1], base + t - 1) - base) // 128
                taken.append(min(r for r in rungs if r > last - first))
    return taken


def _window_step(name, t, epilogue, rungs):
    """One call of the kernel on ``_window_ids(name, t)`` with a pair's
    window taken from ``rungs``: ``(rows, ids, cols, state, outputs)``,
    the cotangent columns ``cols`` [width, N] in the payload's order."""
    rows, ids = _window_ids(name, t)
    ep, blocks_a_step = WINDOW_EPILOGUES[epilogue]
    trailing = ((44,),) if ep is ADAGRAD else ((), (8,))
    rng = np.random.default_rng(3)
    cols = jnp.asarray(rng.normal(size=(sum(sw.widths(trailing)), ids.size)),
                       jnp.float32)
    draw = lambda tail: jnp.asarray(                        # noqa: E731
        0.01 * rng.normal(size=tail + (rows,)), jnp.float32)
    if ep is None:
        state = ()
    elif ep is ADAM:
        state = (ADAM.bias(jnp.int32(3)),) + tuple(
            x for tail in trailing
            for x in (draw(tail), draw(tail), jnp.square(draw(tail))))
    else:
        state = (draw((44,)), 1.0 + jnp.square(draw((44,))))
    bounds, ids_s, payload = sw.sorted_payload(jnp.asarray(ids), cols, rows,
                                               t, C)
    out = gs._scatter_call(
        bounds, ids_s, payload, *state, num_rows=rows, trailing=trailing,
        block_ids=t, chunk_slots=C, epilogue=ep, blocks_a_step=(
            None if ep is None else blocks_a_step), interpret=True,
        name=None, rungs=rungs)
    return rows, ids, cols, state, [np.asarray(x) for x in out]


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("epilogue", list(WINDOW_EPILOGUES))
@pytest.mark.parametrize("t", WINDOW_BLOCKS)
@pytest.mark.parametrize("name", WINDOW_CASES)
def test_the_window_is_the_whole_block_bit_for_bit(name, t, epilogue):
    """The kernel on its ladder against the same kernel with the ladder of
    the last rung alone, which contracts every pair over its whole block
    as the kernel did until PR 46: every output the same bits (the dense
    gradient; ``p, m, n`` of both tables; ``W, G``), and the XLA
    scatter-add's gradient, through the epilogue's own arithmetic, to a
    few roundings as before."""
    rows, ids, cols, state, got = _window_step(name, t, epilogue,
                                               sw.ladder(t))
    *_, whole = _window_step(name, t, epilogue, sw.ladder(t)[-1:])
    assert len(got) == len(whole)
    for a, b in zip(got, whole):
        assert np.array_equal(_bits(a), _bits(b))
    ep, _ = WINDOW_EPILOGUES[epilogue]
    at = jnp.where(jnp.asarray(ids) >= rows, rows, jnp.asarray(ids))
    if ep is ADAGRAD:
        dense = gs.table_grad_xla(at, (cols.T,), rows)
        want = ep.apply(dense[0].T, *state)
    else:
        dense = gs.table_grad_xla(at, (cols[8], cols[:8].T), rows)
        dense = (dense[0], dense[1].T)
        want = dense if ep is None else [
            x for g, leaves in zip(dense, (state[1:4], state[4:]))
            for x in ep.apply(g, *leaves, *state[0])]
    for a, b in zip(got, want):
        b = np.asarray(b)
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()


# ---------------- the two layouts of the slot side (PR 47) ----------------

def _line_ids(name):
    """``(num_rows, ids)`` of one thing the line side's arrival hook may
    get wrong, at blocks of ``T`` ids."""
    rng = np.random.default_rng(sum(map(ord, name)))
    rows, n = 4 * T + 3, 6 * C
    ids = rng.integers(0, rows, n)
    if name == "a_chunk_spans_every_block":     # contracted with four blocks,
        ids = rng.integers(0, rows, C - 5)      # arrives once; Np: one chunk
    elif name == "chunks_of_sentinels_alone":
        ids[n // 3:] = rows + rng.integers(0, 50, n - n // 3)
    elif name == "slots_not_a_multiple_of_the_chunk":
        ids = ids[:n - 37]
    elif name == "one_slot":
        ids = ids[:1]
    elif name == "chunks_of_one_repeated_id":
        ids[C // 2:] = rows - 1
    else:
        assert name == "uniform", name
    return rows, ids.astype(np.int32)


LINE_CASES = ["uniform", "a_chunk_spans_every_block",
              "chunks_of_sentinels_alone",
              "slots_not_a_multiple_of_the_chunk", "one_slot",
              "chunks_of_one_repeated_id"]


@pytest.mark.parametrize("epilogue", ["gradient", "adagrad", "adam"])
@pytest.mark.parametrize("width", [17, 44, 130])
@pytest.mark.parametrize("name", LINE_CASES)
def test_the_line_side_is_the_column_side_bit_for_bit(name, width, epilogue):
    """The kernel fed a wide payload as float32 lines, which it transposes
    and splits in VMEM when a chunk arrives, against the same kernel fed
    the columns XLA transposed and split, as every payload was until PR
    47: every output the same bits, with and without an epilogue. 130
    columns take two tiles of lanes a line."""
    rows, ids = _line_ids(name)
    ep = {"gradient": None, "adagrad": ADAGRAD, "adam": ADAM}[epilogue]
    trailing = ((width,),)
    rng = np.random.default_rng(5)
    cols = jnp.asarray(rng.normal(size=(width, ids.size)), jnp.float32)
    draw = lambda: jnp.asarray(                             # noqa: E731
        0.01 * rng.normal(size=(width, rows)), jnp.float32)
    state = () if ep is None else (draw(), 1.0 + jnp.square(draw()))
    if ep is ADAM:
        state = (ADAM.bias(jnp.int32(3)), draw()) + state
    bounds, ids_s, perm = sw.sort_slots(jnp.asarray(ids), rows, T, C)
    lines = sw.permuted_payload(cols, perm)
    assert sw.slot_layout(width) == "lines"
    assert lines.dtype == jnp.float32
    assert lines.shape == (perm.shape[0], sw.line_lanes(width))
    columns = sw.split_payload(sw.permute_whole(jnp.pad(cols, (
        (0, 0), (0, perm.shape[0] - ids.size))), perm), perm.shape[0])
    assert columns.dtype == jnp.bfloat16
    got, want = ([np.asarray(x) for x in gs._scatter_call(
        bounds, ids_s, payload, *state, num_rows=rows, trailing=trailing,
        block_ids=T, chunk_slots=C, epilogue=ep, blocks_a_step=None,
        interpret=True, name=None, rungs=sw.ladder(T))]
        for payload in (lines, columns))
    assert len(got) == len(want) == (1 if ep is None else ep.leaves)
    for a, b in zip(got, want):
        assert a.shape == b.shape == (width, rows)
        assert np.array_equal(_bits(a), _bits(b))
    # and the gradient is the scatter-add's
    if ep is None:
        at = jnp.where(jnp.asarray(ids) >= rows, rows, jnp.asarray(ids))
        (dense,) = gs.table_grad_xla(at, (cols.T,), rows)
        assert np.abs(got[0] - np.asarray(dense.T)).max() <= 1e-5 * max(
            np.abs(np.asarray(dense)).max(), 1.0)


@pytest.mark.parametrize("width,want", [(1, "columns"), (9, "columns"),
                                        (16, "columns"), (17, "lines"),
                                        (44, "lines"), (130, "lines")])
def test_the_payloads_width_picks_the_slot_side(width, want):
    """One rule, beside ``PERMUTE_BY_COLUMNS``: the side XLA's gather
    permutes a payload fastest on is the side the kernels take it on."""
    assert sw.slot_layout(width) == want
    perm = jnp.arange(2 * C, dtype=jnp.int32)[::-1]
    payload = sw.permuted_payload(jnp.ones((width, 2 * C - 3)), perm)
    if want == "lines":
        assert payload.shape == (2 * C, sw.line_lanes(width))
        assert payload.dtype == jnp.float32
        # the padding's three slots (positions N and up) first, as zeros
        assert not np.asarray(payload[:3]).any()
        assert np.asarray(payload[3:, :width] == 1).all()
        assert not np.asarray(payload[:, width:]).any()
    else:
        assert payload.shape == (3 * sw.round_up(width, 16), 2 * C)
        assert payload.dtype == jnp.bfloat16


@pytest.mark.parametrize("t", WINDOW_BLOCKS)
@pytest.mark.parametrize("name", WINDOW_CASES)
def test_tile_counts_are_the_backwards_walks(name, t):
    """``grad_scatter_tile_counts`` against the plain count of the pairs'
    rungs; the cases name the windows they are there for."""
    rows, ids = _window_ids(name, t)
    ladder = sw.ladder(t)
    taken = _plain_rungs(ids, rows, t, ladder)
    got = gs.grad_scatter_tile_counts(jnp.asarray(ids), rows, t, C)
    assert tuple(map(int, got)) == (sum(taken), len(taken) * ladder[-1])
    assert 0 < sum(taken) <= len(taken) * ladder[-1]
    if name == "every_rung":
        assert set(taken) == set(ladder)
    elif name == "chunk_inside_one_tile":
        assert taken == [1]
    elif name == "chunk_across_a_tiles_edge":
        assert 2 in taken and set(taken) == {1, 2}
    elif name == "window_pulled_back":
        assert taken == [10]
    elif name == "chunk_spans_three_blocks":
        assert taken == [2, ladder[-1], 2, 1]
    elif name == "chunks_of_one_repeated_id":
        assert taken.count(1) >= 5 and len(taken) >= 6


def test_the_whole_block_ladder_is_the_count_the_kernel_made_before():
    rows, ids = _window_ids("every_rung", T_WIDE)
    bounds, _, _ = sw.sort_slots(jnp.asarray(ids), rows, T_WIDE, C)
    made, whole = sw.tile_counts(bounds, rows, T_WIDE, sw.ladder(T_WIDE)[-1:])
    assert int(made) == int(whole) > 0


@pytest.mark.parametrize("traffic", ["owned", "overflow"])
def test_the_window_under_a_deal_is_the_whole_block_bit_for_bit(
        monkeypatch, traffic):
    """``fused_table_update(deal=)`` on four chips, both roads of
    ``_on_owners`` (the slots a chip owns; every chip's slots all-gathered
    when a bucket overflows): every chip's shard of ``W`` and ``G`` the
    same bits on the ladder and on whole blocks."""
    from jax.sharding import PartitionSpec as P

    from dmlc_tpu.ops import table_exchange as tx
    from dmlc_tpu.parallel import RowDeal, make_mesh

    monkeypatch.setattr(gs, "grad_scatter_route", lambda *a: "kernel")
    mesh = make_mesh(devices=jax.devices()[:4])
    rows, width, k = 9001, 20, 8
    b = 512 if traffic == "overflow" else 64
    deal = RowDeal(rows, 4)
    rng = np.random.default_rng(7)
    idx = rng.integers(0, rows // 2, (k, b)).astype(np.int32)
    if traffic == "overflow":
        idx[:3] = 17
    c = rng.normal(size=(k, b, width)).astype(np.float32)
    w = rng.normal(size=(deal.padded_rows, width)).astype(np.float32)
    acc = 1.0 + rng.uniform(size=w.shape).astype(np.float32)

    def run(rungs):
        monkeypatch.setattr(
            gs, "grad_scatter_pallas",
            lambda *a, block_ids=sw.BLOCK_IDS, chunk_slots=sw.CHUNK_SLOTS,
            epilogue=None, blocks_a_step=None, name=None, **kw:
            gs._scatter_call(*a, block_ids=block_ids, chunk_slots=chunk_slots,
                             epilogue=epilogue, blocks_a_step=blocks_a_step,
                             interpret=True, name=name,
                             rungs=rungs(block_ids), **kw))

        def on_chip(w, acc, idx, c):
            ((w, acc),) = gs.fused_table_update(
                idx, (c,), ((w, acc),), None, ADAGRAD, deal=deal)
            return w, acc, tx.overflows(deal, idx, None)

        table, slots = P("data", None), P(None, "data")
        return jax.jit(jax.shard_map(
            on_chip, mesh=mesh,
            in_specs=(table, table, slots, P(None, "data", None)),
            out_specs=(table, table, P()), check_vma=False))(w, acc, idx, c)

    got_w, got_acc, overflowed = run(sw.ladder)
    whole_w, whole_acc, _ = run(lambda t: sw.ladder(t)[-1:])
    assert bool(overflowed) is (traffic == "overflow")
    assert np.array_equal(_bits(got_w), _bits(whole_w))
    assert np.array_equal(_bits(got_acc), _bits(whole_acc))
    assert (np.asarray(got_w) != w).any()


# ---------------- the route ----------------

KDD12 = dict(num_rows=54_686_453, num_slots=65_536 * 16, width=9, tables=2)
FFM = dict(num_rows=13_671_614, num_slots=65_536 * 16, width=44, tables=1)


def _route(shape):
    shape = dict(shape)
    return gs.grad_scatter_route(
        shape.pop("num_rows"), shape.pop("num_slots"), shape.pop("width"),
        shape.pop("dtype", jnp.float32), shape.pop("tables"), **shape)


@pytest.mark.parametrize("name,on_tpu,shape,want", [
    ("cell_shape_on_the_chip", True, KDD12, "kernel"),
    ("cell_shape_on_the_cpu", False, KDD12, "xla"),
    ("tiny_table", True, dict(KDD12, num_rows=4096), "xla"),
    ("table_as_large_as_the_batch", True,
     dict(KDD12, num_rows=1 << 20), "kernel"),
    ("table_smaller_than_the_batch", True,
     dict(KDD12, num_rows=(1 << 20) - 1), "xla"),
    ("a_few_slots", True, dict(KDD12, num_slots=64), "xla"),
    ("table_huge_against_the_batch", True,
     dict(KDD12, num_slots=8192), "xla"),
    ("bfloat16_tables", True, dict(KDD12, dtype=jnp.bfloat16), "xla"),
    ("one_shard_of_four", True, dict(KDD12, num_slots=16_384 * 16),
     "kernel"),
])
def test_route_is_a_function_of_backend_dtype_and_shapes(
        monkeypatch, name, on_tpu, shape, want):
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    assert _route(shape) == want, name


@pytest.mark.parametrize("cell,shape", [
    ("kdd12_fm_text", KDD12), ("kdd12_fm_snap", KDD12),
    ("kdd12_fm_bcache", KDD12),
    # since PR 54 a chip of the four: its shard's rows, the slots of all
    ("kdd12_fm_dp4_bcache", dict(KDD12, num_rows=13_671_614)),
    ("kdd12_ffm_text", FFM)])
def test_one_shard_routes_every_cell_as_the_parent_did(monkeypatch, cell,
                                                       shape):
    """Pinned: an edit of a constant cannot move a one-chip cell (or a
    chip of the laid tables) off the route the ledger measured."""
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    assert _route(shape) == "kernel", cell


@pytest.mark.parametrize("name,on_tpu,shape,want", [
    # kdd12_fm_dp4_bcache: 13 of a chip's table rows a global slot
    ("the_four_chip_cell", True, KDD12, "kernel"),
    # a shard smaller than the batch has slots: the kernel was not
    # measured there
    ("one_row_a_slot", True, dict(KDD12, num_rows=1 << 20), "xla"),
    ("two_rows_a_slot", True, dict(KDD12, num_rows=2 << 20), "xla"),
    ("four_rows_a_slot", True, dict(KDD12, num_rows=4 << 20), "kernel"),
    ("eight_rows_a_slot", True, dict(KDD12, num_rows=8 << 20), "kernel"),
    ("table_smaller_than_the_batch", True, dict(KDD12, num_rows=1 << 19),
     "xla"),
    ("ffm_table_on_four_chips", True, FFM, "kernel"),
    ("table_huge_against_the_batch", True, dict(KDD12, num_rows=80 << 20),
     "kernel"),
    # a chip streams its shard for a few slots: XLA's scatter wins
    ("a_shard_huge_against_the_batch", True, dict(KDD12, num_slots=32_768),
     "xla"),
    ("the_cpu", False, KDD12, "xla"),
    ("tiny_table", True, dict(KDD12, num_rows=4096), "xla"),
    ("two_chips", True, dict(KDD12, shards=2), "kernel"),
])
def test_a_chip_of_laid_tables_routes_by_its_shard_and_every_slot(
        monkeypatch, name, on_tpu, shape, want):
    """(PR 54; in the place of the replicated tables' collective, which
    went with them.) A chip of tables laid by rows takes the route of one
    chip with its shard's rows and the whole batch's slots."""
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    shape = dict(shape)
    shards = shape.pop("shards", 4)
    shape["num_rows"] = -(-shape["num_rows"] // shards)
    assert _route(shape) == want, name


def test_route_crosses_over_once_as_the_table_grows(monkeypatch):
    """One algorithm chosen by shape: for the cell's batch the kernel is
    taken from some table size up to another, and XLA outside; a chip of
    four takes it from four times that size on."""
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    routes = [gs.grad_scatter_route(1 << p, 1 << 20, 9, jnp.float32, 2)
              for p in range(8, 34)]
    flips = sum(a != b for a, b in zip(routes, routes[1:]))
    assert routes[0] == "xla" and "kernel" in routes and flips <= 2, routes
    on_four = [gs.grad_scatter_route((r << 20) // 4, 1 << 20, 9,
                                     jnp.float32, 2) for r in range(1, 200)]
    crossed = [r for r, (a, b) in enumerate(zip(on_four, on_four[1:]), 2)
               if a != b]
    assert on_four[0] == "xla" and crossed == [4] \
        and on_four[-1] == "kernel"


# ---------------- the op, the learner, the counter ----------------

@pytest.fixture
def kernel_route(monkeypatch):
    """Every ELL backward takes the kernel, interpreted."""
    calls = {"n": 0}
    real = gs.grad_scatter_pallas

    def interpreted(*args, **kw):
        calls["n"] += 1
        return real(*args, **dict(kw, interpret=True))

    monkeypatch.setattr(gs, "grad_scatter_pallas", interpreted)
    monkeypatch.setattr(gs, "grad_scatter_route", lambda *a: "kernel")
    return calls


def _ell(rows, b=64, k=8, seed=0, sink_from=5):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, rows - 1, (b, k)).astype(np.int32)
    val = rng.normal(size=(b, k)).astype(np.float32)
    idx[:, sink_from:], val[:, sink_from:] = rows - 1, 0.0   # padding slots
    return EllBatch(jnp.asarray(idx), jnp.asarray(val),
                    jnp.asarray(rng.integers(0, 2, b), jnp.float32),
                    jnp.ones(b, jnp.float32))


def test_op_gradient_matches_autodiff_of_the_two_gathers(kernel_route):
    rows, f = 3000, 4
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=rows), jnp.float32)
    v = jnp.asarray(rng.normal(size=(rows, f)), jnp.float32)
    idx = _ell(rows).indices

    def through(gather):
        def loss(w, v):
            a, b = gather(w, v)
            return jnp.sum(jnp.sin(a)) + jnp.sum(b * b * a[..., None])
        return jax.grad(loss, argnums=(0, 1))(w, v)

    got = through(lambda w, v: ell_table_gather((w, v), idx))
    want = through(lambda w, v: (jnp.take(w, idx, axis=0),
                                 jnp.take(v, idx, axis=0)))
    assert kernel_route["n"] == 1
    for g, x in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5,
                                   atol=1e-6)


def _routed_since(before):
    """``table_update_route``'s counts by route, less ``before``'s."""
    return {k: v - before.get(k, 0)
            for k, v in telemetry.table_update_routes().items()
            if v != before.get(k, 0)}


def _own_adam(route):
    """The learner's optimizer argument for a route: the ``fused`` update
    engages for the learner's own Adam only; the same Adam handed in as an
    optax transformation keeps the dense gradient (``kernel``)."""
    return None if route == "fused" else optax.adam(0.05)


@functools.lru_cache(maxsize=None)
def _three_steps(route):
    """State of FMLearner(layout='ell') after each of three steps on one
    route (``xla`` / ``kernel``: the dense gradient by either scatter,
    ``fused``: the kernel's Adam epilogue), with the losses."""
    rows = 5000
    model = FMLearner(num_col=rows - 1, num_factors=8, layout="ell", seed=3,
                      optimizer=_own_adam(route))
    before = telemetry.table_update_routes()
    out = {"touched": np.unique(np.concatenate(
        [np.asarray(_ell(rows, seed=s).indices).ravel() for s in range(3)])),
        "steps": []}
    for s in range(3):
        loss = float(model.step(_ell(rows, seed=s)))
        adam = model.opt_state[0]
        out["steps"].append({
            "loss": loss, "count": int(adam.count),
            "w0": np.asarray(model.params.w0),
            "w": np.asarray(model.params.w), "v": np.asarray(model.params.v),
            "mu_w0": np.asarray(adam.mu.w0), "mu_w": np.asarray(adam.mu.w),
            "mu_v": np.asarray(adam.mu.v), "nu_w0": np.asarray(adam.nu.w0),
            "nu_w": np.asarray(adam.nu.w), "nu_v": np.asarray(adam.nu.v)})
    out["routed"] = _routed_since(before)
    out["structure"] = jax.tree_util.tree_structure(model.opt_state)
    out["dtypes"] = [x.dtype for x in jax.tree_util.tree_leaves(
        model.opt_state)]
    return out


@pytest.mark.parametrize("leaf", ["loss", "w0", "w", "v", "mu_w0", "mu_w",
                                  "mu_v", "nu_w0", "nu_w", "nu_v",
                                  "untouched"])
@pytest.mark.parametrize("route", ["kernel", "fused"])
def test_fm_step_on_the_kernel_routes_matches_the_xla_route(
        request, route, leaf):
    """Step for step: the dense gradient built by the kernel, and the
    kernel finishing Adam itself, against XLA's scatter-add and optax."""
    want = _three_steps("xla")
    request.getfixturevalue("kernel_route")
    got = _three_steps(route)
    assert want["routed"] == {"dense": 1}
    assert got["routed"] == {"fused" if route == "fused" else "dense": 1}
    for now, (g, w) in enumerate(zip(got["steps"], want["steps"]), 1):
        assert g["count"] == w["count"] == now
        if leaf == "untouched":      # rows no batch touched: bit for bit
            rest = np.setdiff1d(np.arange(5000), want["touched"])
            assert rest.size > 1000
            for key in ("w", "v", "mu_w", "mu_v", "nu_w", "nu_v"):
                assert np.array_equal(g[key][rest], w[key][rest]), key
            continue
        scale = np.abs(w[leaf]).max()
        assert np.abs(g[leaf] - w[leaf]).max() <= 2e-6 * scale, (leaf, now)


def test_fused_step_keeps_optax_adams_state_as_it_is(kernel_route):
    """The harness, ``state_dict`` and users read ``opt_state[0].mu`` /
    ``.nu`` / ``.count``: the pytree is ``optax.adam(...).init(params)``'s,
    type for type and dtype for dtype, after fused steps too."""
    got = _three_steps("fused")
    model = FMLearner(num_col=4999, num_factors=8, layout="ell", seed=3)
    init = optax.adam(0.05).init(model.params)
    assert got["structure"] == jax.tree_util.tree_structure(init)
    assert got["dtypes"] == [x.dtype
                             for x in jax.tree_util.tree_leaves(init)]
    assert isinstance(init[0], optax.ScaleByAdamState)


# ---------------- how the step updates its tables ----------------

def _routed(monkeypatch, on_tpu=True, **kw):
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    kw = dict(dict(num_col=4999, num_factors=8, layout="ell"), **kw)
    return FMLearner(**kw)


@pytest.mark.parametrize("name,on_tpu,kw,slots,want", [
    ("the_learners_own_adam_on_the_chip", True, {}, 512, ("fused", "adam")),
    ("its_learning_rate_is_the_callers", True, dict(learning_rate=0.5), 512,
     ("fused", "adam")),
    ("an_optax_transformation_handed_in", True,
     dict(optimizer=optax.adam(0.05)), 512, ("dense", "optimizer")),
    ("another_optimizer", True, dict(optimizer=optax.sgd(0.1)), 512,
     ("dense", "optimizer")),
    ("l2_is_not_in_the_rows", True, dict(l2=1e-4), 512, ("dense", "l2")),
    ("the_cpu", False, {}, 512, ("dense", "scatter_xla")),
    ("a_table_smaller_than_the_batch", True, {}, 8192,
     ("dense", "scatter_xla")),
    ("a_few_slots", True, {}, 64, ("dense", "scatter_xla")),
    ("the_dense_layout", True, dict(layout="dense", num_col=64), 0,
     ("dense", "layout")),
    ("the_bcoo_layout_gathers_rows_as_ell_does", True, dict(layout="bcoo"),
     512, ("fused", "adam")),
    ("the_bcoo_layout_on_the_cpu", False, dict(layout="bcoo"), 512,
     ("dense", "scatter_xla")),
])
def test_table_update_route_is_a_function_of_what_the_learner_observes(
        monkeypatch, name, on_tpu, kw, slots, want):
    assert _routed(monkeypatch, on_tpu, **kw).table_update_route(slots) \
        == want, name


@pytest.mark.parametrize("on_tpu,rows,want", [
    (True, 19_999, ("fused", "adam")), (False, 19_999,
                                        ("dense", "scatter_xla")),
    # the route is a chip's: its shard of these is smaller than the batch
    (True, 4999, ("dense", "scatter_xla"))])
def test_table_update_route_under_a_mesh_is_one_chips_with_its_shard(
        monkeypatch, on_tpu, rows, want):
    """A mesh is no reason: a chip takes the route of one chip with its
    shard's rows and the whole batch's slots."""
    from dmlc_tpu.parallel import make_mesh

    model = _routed(monkeypatch, on_tpu, num_col=rows,
                    mesh=make_mesh(devices=jax.devices()[:4]))
    assert model.table_update_route(4096) == want


def test_the_cells_shape_fuses_on_the_chip_and_not_here(monkeypatch):
    """The five FM cells (default Adam, l2 = 0, 54,686,453 rows, 1,048,576
    slots; four chips gather rows) take the fused route on a TPU; the
    rehearsals on the CPU stay dense. No table is made: the route reads
    shapes."""
    from dmlc_tpu.models import fm as fm_mod

    from dmlc_tpu.parallel import RowRanges

    model = FMLearner.__new__(FMLearner)
    model.layout, model.l2, model.deal = "ell", 0.0, None
    model._adam = gs.AdamEpilogue(0.05)
    model.weight_dim, model.num_factors = 54_686_453, 8
    model.params = fm_mod.FMParams(*(jax.ShapeDtypeStruct((), jnp.float32),)
                                   * 3)
    assert model.table_update_route(65_536 * 16) == ("dense", "scatter_xla")
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    assert model.table_update_route(65_536 * 16) == ("fused", "adam")

    model.deal = RowRanges(54_686_453, 4)
    assert model.table_update_route(65_536 * 16) == ("fused", "adam")


@pytest.mark.parametrize("route", ["dense", "fused"])
def test_table_update_route_is_counted_once_a_traced_step(request, route):
    reason = "scatter_xla"
    if route == "fused":
        request.getfixturevalue("kernel_route")
        reason = "adam"
    before = telemetry.table_update_routes().get(route, 0)
    scatters = telemetry.grad_scatter_routes().get("kernel", 0)
    model = FMLearner(num_col=2999, num_factors=4, layout="ell")
    for s in range(3):                     # one trace, three steps
        model.step(_ell(3000, seed=s))
    assert telemetry.table_update_routes()[route] == before + 1
    model.step(_ell(3000, b=32))           # a new shape traces again
    assert telemetry.table_update_routes()[route] == before + 2
    model.predict(_ell(3000))              # a forward updates nothing
    assert telemetry.table_update_routes()[route] == before + 2
    assert (f'dmlc_tpu_table_update_route_total{{reason="{reason}",'
            f'route="{route}"}}' in telemetry.render_prometheus())
    assert telemetry.pod_snapshot()["table_update_routes"][route] >= 2
    # the fused update is a run of the scatter kernel, and counted as one
    assert telemetry.grad_scatter_routes().get("kernel", 0) == scatters + (
        2 if route == "fused" else 0)


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_route_is_counted_once_a_traced_backward(request, route):
    if route == "kernel":
        request.getfixturevalue("kernel_route")
    before = telemetry.grad_scatter_routes().get(route, 0)
    model = FMLearner(num_col=2999, num_factors=4, layout="ell")
    for s in range(3):                     # one trace, three steps
        model.step(_ell(3000, seed=s))
    assert telemetry.grad_scatter_routes()[route] == before + 1
    model.step(_ell(3000, b=32))           # a new shape traces again
    assert telemetry.grad_scatter_routes()[route] == before + 2
    assert (f'dmlc_tpu_grad_scatter_route_total{{collective="none",'
            f'route="{route}",width="5"}}' in telemetry.render_prometheus())
    assert telemetry.pod_snapshot()["grad_scatter_routes"][route] >= 2


def test_forward_only_calls_count_no_route():
    before = dict(telemetry.grad_scatter_routes())
    model = FMLearner(num_col=2999, num_factors=4, layout="ell")
    model.predict(_ell(3000))
    assert telemetry.grad_scatter_routes() == before


# ---------------- under a mesh ----------------
# (PR 54) The tables and both moments are laid by rows over the mesh in id
# order, a contiguous share a chip (``parallel.mesh.RowRanges``); the
# replicated tables and their two collectives went with their only caller.
# Every case of the tests that held those is a case of the row-laid step here.

MOMENTS = ("w", "v", "mu_w", "mu_v", "nu_w", "nu_v")
ROWS = 5000
# how a step goes on the laid tables: the learner's own Adam finished by the
# gradient kernel on every chip's shard, the forward by XLA's take on the
# shard (``kernel_route`` forces the update's route alone) or by the
# forward's kernel too (``kernels``); or a caller's Adam on the dense
# gradient, the one-device step partitioned by XLA
ROADS = ["fused", "fused_both_kernels", "dense"]


def _mesh_model(road=None, **kw):
    from dmlc_tpu.parallel import make_mesh

    mesh = make_mesh(devices=jax.devices()[:4])
    model = FMLearner(num_col=ROWS - 1, num_factors=8, layout="ell", seed=3,
                      mesh=mesh, **dict(dict(optimizer=_own_adam(
                          "kernel" if road == "dense" else "fused")), **kw))
    return model, model.batch_shardings()


def _state(model):
    adam = model.opt_state[0]
    return dict(zip(MOMENTS, (model.params.w, model.params.v, adam.mu.w,
                              adam.mu.v, adam.nu.w, adam.nu.v)))


def _shards(model):
    """Every leaf of the laid state as ``(first row, rows)`` a device."""
    return {k: sorted((sh.index[0].start or 0, np.asarray(sh.data))
                      for sh in x.addressable_shards)
            for k, x in _state(model).items()}


@functools.lru_cache(maxsize=None)
def _mesh_steps(road):
    """Three steps of FMLearner(layout='ell') on four devices, tables laid
    by rows and batch sharded, on ``road`` (the caller holds the fixture
    that forces it)."""
    model, batch_sh = _mesh_model(road)
    before = telemetry.table_update_routes()
    start = {k: np.asarray(x) for k, x in _state(model).items()}
    losses = [float(model.step(jax.device_put(_ell(ROWS, seed=s), batch_sh)))
              for s in range(3)]
    adam = model.opt_state[0]
    return {"loss": np.asarray(losses), "w": np.asarray(model.params.w),
            "v": np.asarray(model.params.v), "w0": np.asarray(model.params.w0),
            "mu_v": np.asarray(adam.mu.v), "nu_v": np.asarray(adam.nu.v),
            "count": int(adam.count), "shards": _shards(model),
            "start": start, "shard_slots": model.shard_slots(),
            "metrics": telemetry.render_prometheus(),
            "routes": dict(telemetry.grad_scatter_routes()),
            "shard_routes": telemetry.table_shard_routes(),
            "routed": _routed_since(before)}


def _on_the_mesh(request, road):
    request.getfixturevalue(
        "kernels" if road == "fused_both_kernels" else "kernel_route")
    return _mesh_steps(road)


@pytest.mark.parametrize("leaf", ["loss", "w", "v", "w0", "mu_v", "nu_v"])
@pytest.mark.parametrize("road", ROADS)
def test_kernel_route_under_a_mesh_matches_the_xla_route(request, road,
                                                         leaf):
    """Tables laid by rows, batch sharded: every chip sorts every slot,
    reads and updates the ones in its range (fused), or XLA partitions the
    one-device step (dense); leaf for leaf the one-device XLA step."""
    want = _three_steps("xla")["steps"][-1]
    want = dict(want, loss=np.asarray(
        [s["loss"] for s in _three_steps("xla")["steps"]]))
    got = _on_the_mesh(request, road)
    assert got["count"] == want["count"] == 3
    # (the first step's uncommitted state traces once more)
    assert set(got["routed"]) == {"dense" if road == "dense" else "fused"}
    have = got[leaf] if leaf in ("loss", "w0") else got[leaf][:ROWS]
    scale = np.abs(want[leaf]).max()
    assert np.abs(have - want[leaf]).max() <= 2e-6 * scale


@pytest.mark.parametrize("leaf", MOMENTS)
@pytest.mark.parametrize("road", ["fused", "fused_both_kernels"])
def test_every_chip_holds_the_rows_of_its_range(request, road, leaf):
    """(In the place of "replicas stay bit identical".) Each chip's shard
    holds exactly the rows of the one-device state in its range, the
    layout's padding behind them zero; and a row no batch touched is bit
    for bit the start on whichever chip holds it."""
    one = _three_steps("xla")
    want, touched = one["steps"][-1][leaf], one["touched"]
    got = _on_the_mesh(request, road)
    shards = got["shards"][leaf]
    local = -(-ROWS // 4)
    assert [first for first, _ in shards] == [c * local for c in range(4)]
    scale = np.abs(want).max()
    for first, rows in shards:
        assert len(rows) == local
        ids = np.arange(first, first + local)
        real = ids < ROWS
        assert not np.any(rows[~real])
        assert np.abs(rows[real] - want[ids[real]]).max() <= 2e-6 * scale
        rest = np.setdiff1d(ids[real], touched)
        assert rest.size > 200
        assert np.array_equal(rows[rest - first], got["start"][leaf][rest])
    assert sum(got["shard_slots"]) == 3 * 64 * 5    # the real slots stepped


@pytest.mark.parametrize("road", ROADS)
def test_collective_is_counted_and_shown(request, road):
    got = _on_the_mesh(request, road)
    collective = "xla" if road == "dense" else "all_slots"
    assert ('dmlc_tpu_table_shard_route_total{collective="%s",deal="ranges",'
            'learner="fm",shards="4"}' % collective in got["metrics"])
    assert got["shard_routes"][collective] >= 1
    if road == "dense":     # XLA's gather and scatter-add: no route of ours
        return
    assert ('dmlc_tpu_grad_scatter_route_total{collective="all_slots",'
            'route="kernel",width="9"}' in got["metrics"])
    assert got["routes"]["collective_all_slots"] >= 1
    assert got["routes"]["kernel"] >= got["routes"]["collective_all_slots"]
    assert telemetry.pod_snapshot()["grad_scatter_routes"][
        "collective_all_slots"] >= 1


def _collectives(hlo, op):
    """What every ``op`` instruction of a compiled module's text (or its
    async ``-start``) produces: the line up to the operation's name."""
    import re

    return " ".join(ln.split(f" {op}", 1)[0] for ln in hlo.splitlines()
                    if re.search(rf" {op}(-start)?\(", ln))


@pytest.mark.parametrize("road", ROADS)
def test_what_crosses_the_devices_in_the_compiled_step(request, road):
    """Counted from the compiled four-device step. On the fused road: the
    slots' all-gather, the rows' all-to-all, the cotangents' all-gather
    and scalars. On any road: no collective and no operand of the table's
    size."""
    import re

    request.getfixturevalue(
        "kernels" if road == "fused_both_kernels" else "kernel_route")
    model, batch_sh = _mesh_model(road)
    batch = jax.device_put(_ell(ROWS), batch_sh)
    hlo = model._step.lower(model.params, model.opt_state,
                            batch).compile().as_text()
    padded, (b, k) = model.deal.padded_rows, batch.indices.shape
    crossed = {op: _collectives(hlo, op) for op in (
        "all-gather", "all-to-all", "all-reduce", "reduce-scatter",
        "collective-permute")}
    for n in (ROWS, padded):
        assert not re.search(rf"[\[,]{n}[\],]", " ".join(crossed.values())), \
            crossed
    if road == "dense":
        return
    assert not re.search(rf"[\[,]({ROWS}|{padded})[\],]", hlo)
    assert f"s32[{k},4,{b // 4}]" in crossed["all-gather"], crossed
    assert f"f32[9,{k},4,{b // 4}]" in crossed["all-gather"], crossed
    # (the CPU's all-to-all is a tuple of the four chips' blocks)
    assert crossed["all-to-all"].count(f"f32[1,9,{k},1,{b // 4}]") == 4, \
        crossed
    assert not crossed["reduce-scatter"] and not crossed["collective-permute"]
    for ln in crossed["all-reduce"].split("%"):       # scalars and books
        assert not re.search(r"\[\d{3,}", ln), ln


@pytest.mark.parametrize("owner", ["one_chip_owns_every_slot",
                                   "the_last_chip_owns_every_slot"])
def test_the_skews_two_ends_still_give_the_one_device_state(kernels, owner):
    """A batch whose every slot one chip owns, and one no chip but the
    last owns: nothing overflows, there is no bucket to."""
    local = -(-ROWS // 4)
    lo, hi = (local, 2 * local) if owner.startswith("one") else (
        3 * local, ROWS - 1)
    rng = np.random.default_rng(5)
    idx = rng.integers(lo, hi, (64, 8)).astype(np.int32)
    val = rng.normal(size=(64, 8)).astype(np.float32)
    batch = EllBatch(jnp.asarray(idx), jnp.asarray(val),
                     jnp.asarray(rng.integers(0, 2, 64), jnp.float32),
                     jnp.ones(64, jnp.float32))
    one = FMLearner(num_col=ROWS - 1, num_factors=8, layout="ell", seed=3)
    four, batch_sh = _mesh_model("fused")
    for model, bt in ((one, batch), (four, jax.device_put(batch, batch_sh))):
        for _ in range(2):
            model.step(bt)
    assert four.shard_slots() == [
        2 * 512 * (c == (1 if owner.startswith("one") else 3))
        for c in range(4)]
    for (k, want), got in zip(_state(one).items(), _state(four).values()):
        want, got = np.asarray(want), np.asarray(got)[:ROWS]
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max(), k
    assert np.array_equal(np.asarray(one.params.w0), np.asarray(
        four.params.w0)) or abs(float(one.params.w0 - four.params.w0)) \
        <= 2e-6 * abs(float(one.params.w0))


# ---- an ELL batch's padding on the sentinel (PR 49) ----

from dmlc_tpu.ops import table_gather as tg  # noqa: E402
from tests.test_sorted_walk import PADDINGS, _padded_batch  # noqa: E402

# (the tables after the id axis, the epilogue): the FM's nine columns cross
# the permutes as lane-major columns, a field-aware FM's 20 as lines
PADDED_STEPS = {"fm_adam_columns": (((), (8,)), ADAM),
                "ffm_adagrad_lines": (((20,),), ADAGRAD)}


def _padded_step(name, learner, told):
    """One step of a learner's fused route on one chip, the slots K-major:
    ``table_rows`` and ``fused_table_update`` with the forward's sort, then
    the sink row set to zero; ``told``: the ops hear which slots are real.
    Eager on both sides, so that the interpreted kernels compile alike."""
    trailing, epilogue = PADDED_STEPS[learner]
    rows = 3 * 4096 + 11
    rng = np.random.default_rng(7)
    ids, real = (jnp.asarray(x) for x in _padded_batch(name, rows))
    state = []
    for tail in trailing:
        p = jnp.asarray(0.1 * rng.normal(size=(rows,) + tail), jnp.float32)
        p = p.at[-1].set(0.0)
        if epilogue is ADAM:
            m = jnp.asarray(0.01 * rng.normal(size=p.shape), jnp.float32)
            state.append((p, m.at[-1].set(0.0), jnp.square(m).at[-1].set(0.0)))
        else:
            state.append((p, 1.0 + jnp.square(p)))
    how = dict(real=real) if told else {}
    got, sorted_slots = tg.table_rows(tuple(t[0] for t in state), ids, **how)
    # a model's cotangent rows: the padding's value 0 makes theirs zero
    value = jnp.asarray(rng.normal(size=ids.shape), jnp.float32) * real
    cots = tuple(jnp.tanh(g) * (value if g.ndim == 2 else value[..., None])
                 for g in got)
    bias = ADAM.bias(jnp.int32(3)) if epilogue is ADAM else None
    out = gs.fused_table_update(ids, cots, tuple(state), bias, epilogue,
                                sorted_slots=sorted_slots, **how)
    return got, [tuple(x.at[-1].set(0.0) if i == 0 else x
                       for i, x in enumerate(table)) for table in out], state


@pytest.mark.parametrize("learner", list(PADDED_STEPS))
@pytest.mark.parametrize("name", PADDINGS)
def test_a_step_told_its_padding_is_the_parents_step_bit_for_bit(
        kernels, name, learner):
    """The one-chip step with the padding on the sentinel and both permutes
    run by run, against the same batch with the padding left on the sink id
    and nobody told: the rows, the updated tables, Adam's moments /
    AdaGrad's accumulators, bit for bit; the sink row zero."""
    before = telemetry.table_slot_groups()
    rows, tables, start = _padded_step(name, learner, told=True)
    counted = telemetry.table_slot_groups()
    parents_rows, parents, _ = _padded_step(name, learner, told=False)
    assert telemetry.table_slot_groups() == counted != before
    assert kernels == {"gather": 2, "scatter": 2}
    for got, want in zip(rows, parents_rows):
        assert np.array_equal(_bits(got), _bits(want))
    for table, parent in zip(tables, parents):
        for got, want in zip(table, parent):
            assert np.array_equal(_bits(got), _bits(want))
        assert not np.asarray(table[0])[-1].any()
    # (and the step moved the table)
    assert not np.array_equal(tables[0][0], start[0][0])
