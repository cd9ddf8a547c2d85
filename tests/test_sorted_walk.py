"""ops/sorted_walk.py: the walk that the backward (ops/grad_scatter.py), the
forward (ops/table_gather.py) and a ragged batch's row sums
(ops/slot_rows.py) stand on. The corners every kernel on it must survive
run here as cases of one test over the six ops, in Pallas' interpret mode
against XLA's routes; what each op adds is in its own file's tests. And the
rule that keeps the layout in one place: the walk's neighbours reach into
no private of ``grad_scatter.py``."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import slot_rows as sr
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops import table_gather as tg

T, C = 256, 128   # small tiles: the interpreter walks every block
ADAM, ADAGRAD = gs.AdamEpilogue(0.05), gs.AdaGradEpilogue(0.2)

CORNERS = ["a_chunk_spans_every_block", "blocks_no_chunk_touches",
           "chunks_of_sentinels_alone", "last_step_past_the_table",
           "table_shorter_than_a_block"]
# (op, blocks a grid step): with no epilogue the scatter takes one block a
# step, and slot_rows_take the forward's default (four here, one for the
# short table)
OPS = [("scatter", 1), ("scatter_adam", 1), ("scatter_adam", 3),
       ("scatter_adagrad", 1), ("scatter_adagrad", 3), ("gather", 1),
       ("gather", 3), ("slot_rows_sum", 1), ("slot_rows_take", None)]


def _corner(name):
    """``(num_rows, ids)``: 768 slots (but for the first) over four blocks
    of ``T`` ids (but for the last two)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    rows, n = 4 * T, 6 * C
    ids = rng.integers(0, rows, n)
    if name == "a_chunk_spans_every_block":     # and ends in sentinels
        ids = rng.integers(0, rows, C - 5)
    elif name == "blocks_no_chunk_touches":     # blocks 1 and 2 see no slot
        rows = 5 * T
        ids = np.where(ids % 2 == 0, ids % T, 3 * T + ids % (2 * T))
    elif name == "chunks_of_sentinels_alone":   # four of the six chunks
        ids[n // 3:] = rows + rng.integers(0, 50, n - n // 3)
    elif name == "last_step_past_the_table":
        # four blocks: at three a grid step the second step's last two lie
        # past the table's end and past the sentinel id
        rows = 3 * T + 77
        ids = rng.integers(0, rows, n)
        ids[:4] = rows - 1
    else:
        assert name == "table_shorter_than_a_block", name
        rows = T - 56
        ids = rng.integers(0, rows, n)
    return rows, ids.astype(np.int32)


def _draw(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _scatter(ids, rows, trailing, cots, *state, **how):
    """The backward's kernel on cotangents ``[N]`` / ``[N, F]``: one
    lane-major output a table (and leaf), as the op gives them."""
    bounds, ids_s, payload = sw.sorted_payload(
        ids, sw.cols_of_rows(cots, trailing), rows, T, C)
    return gs.grad_scatter_pallas(
        bounds, ids_s, payload, *state, num_rows=rows, trailing=trailing,
        block_ids=T, chunk_slots=C, interpret=True, **how)


def _close(got, want, scale=None):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    tol = 2e-6 * np.maximum(np.abs(want).max() if scale is None else scale,
                            1.0)
    assert np.all(np.abs(got - want) <= tol)


def _check_scatter(ids, rows, blocks_a_step, rng):
    trailing = ((), (8,))
    cots = (_draw(rng, ids.size), _draw(rng, ids.size, 8))
    dw, dv_t = _scatter(ids, rows, trailing, cots)
    want_w, want_v = gs.table_grad_xla(ids, cots, rows)
    # what a row's float32 sum may round by grows with what it sums
    scale = np.asarray(jnp.zeros(rows).at[ids].add(
        jnp.abs(cots[1]).max(axis=1)))
    _close(dw, want_w, scale)
    _close(dv_t.T, want_v, scale[:, None])
    untouched = np.setdiff1d(np.arange(rows), np.asarray(ids))
    assert not np.asarray(dw)[untouched].any()          # exact zeros
    assert not np.asarray(dv_t)[:, untouched].any()


def _check_scatter_adam(ids, rows, blocks_a_step, rng):
    trailing = ((), (8,))
    cots = (_draw(rng, ids.size), _draw(rng, ids.size, 8))
    state = tuple((0.01 * _draw(rng, *shape), 0.01 * _draw(rng, *shape),
                   jnp.square(0.01 * _draw(rng, *shape)))
                  for shape in ((rows,), (rows, 8)))
    bias = ADAM.bias(jnp.int32(3))
    out = _scatter(ids, rows, trailing, cots, bias,
                   *(x.T if x.ndim == 2 else x for t in state for x in t),
                   epilogue=ADAM, blocks_a_step=blocks_a_step)
    dense = gs.table_grad_xla(ids, cots, rows)
    for i, (g, leaves) in enumerate(zip(dense, state)):
        want = ADAM.apply(g, *leaves, bias[0], bias[1])
        for got, w in zip(out[3 * i:3 * i + 3], want):
            got, w = np.asarray(got.T if i else got), np.asarray(w)
            assert np.abs(got - w).max() <= 1e-5 * np.abs(w).max()


def _check_scatter_adagrad(ids, rows, blocks_a_step, rng):
    width = 20
    cot = _draw(rng, ids.size, width)
    w0 = 0.5 * jnp.abs(_draw(rng, rows, width))
    acc0 = 1.0 + jnp.square(_draw(rng, rows, width))
    out = _scatter(ids, rows, ((width,),), (cot,), w0.T, acc0.T,
                   epilogue=ADAGRAD, blocks_a_step=blocks_a_step)
    (dense,) = gs.table_grad_xla(ids, (cot,), rows)
    for got, want in zip(out, ADAGRAD.apply(dense, w0, acc0)):
        got, want = np.asarray(got.T), np.asarray(want)
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # a row no slot names keeps W and G bit for bit
    untouched = np.setdiff1d(np.arange(rows), np.asarray(ids))
    assert np.array_equal(np.asarray(out[0])[:, untouched],
                          np.asarray(w0.T)[:, untouched])
    assert np.array_equal(np.asarray(out[1])[:, untouched],
                          np.asarray(acc0.T)[:, untouched])


def _check_gather(ids, rows, blocks_a_step, rng):
    trailing = ((), (8,))
    tables = (_draw(rng, rows), _draw(rng, rows, 8))
    bounds, ids_s, perm = sw.sort_slots(ids, rows, T, C)
    rows_s = np.asarray(tg.table_gather_pallas(
        bounds, ids_s, tables[0], tables[1].T, num_rows=rows,
        trailing=trailing, block_ids=T, chunk_slots=C,
        blocks_a_step=blocks_a_step, interpret=True))
    back = np.asarray(sw.inverse_permutation(perm))[:ids.size]
    got = sw.rows_of_cols(rows_s[:, back], trailing)
    for g, x in zip(got, tables):           # value for value
        assert np.array_equal(g, np.asarray(jnp.take(
            x, ids, axis=0, mode="fill", fill_value=0)))
    # rows past the tables' columns and the padding's slots are zeros
    assert not rows_s[9:].any() and not rows_s[:, ids.size:].any()


def _check_slot_rows_sum(ids, rows, blocks_a_step, rng):
    row_ids = jnp.sort(ids)                 # ascending, the sentinels last
    slots = (_draw(rng, ids.size), _draw(rng, ids.size, 8))
    got = sr.rows_sum_kernel(slots, row_ids, rows, T, C)
    for g, x in zip(got, slots):
        want = jax.ops.segment_sum(x, row_ids, num_segments=rows)
        np.testing.assert_allclose(g, want, rtol=2e-6, atol=2e-5)


def _check_slot_rows_take(ids, rows, blocks_a_step, rng):
    row_ids = jnp.sort(ids)
    per_row = (_draw(rng, rows), _draw(rng, rows, 8))
    got = sr.rows_take_kernel(per_row, row_ids, T, C)
    for g, x in zip(got, per_row):
        assert np.array_equal(np.asarray(g), np.asarray(jnp.take(
            x, row_ids, axis=0, mode="fill", fill_value=0)))


CHECKS = {"scatter": _check_scatter, "scatter_adam": _check_scatter_adam,
          "scatter_adagrad": _check_scatter_adagrad, "gather": _check_gather,
          "slot_rows_sum": _check_slot_rows_sum,
          "slot_rows_take": _check_slot_rows_take}


@pytest.mark.parametrize("op,blocks_a_step", OPS, ids=[
    op + ("" if b is None else f"-{b}_a_step") for op, b in OPS])
@pytest.mark.parametrize("corner", CORNERS)
def test_every_op_on_the_walk_survives_its_corners(kernels, corner, op,
                                                   blocks_a_step):
    """``kernels`` interprets the two ``pallas_call``s that ``slot_rows``
    makes; the others are interpreted as they are called here."""
    rows, ids = _corner(corner)
    CHECKS[op](jnp.asarray(ids), rows, blocks_a_step,
               np.random.default_rng(1))
    if op.startswith("slot_rows"):
        assert kernels[{"slot_rows_sum": "scatter",
                        "slot_rows_take": "gather"}[op]] == 1


@pytest.mark.parametrize("rows,block,want", [
    (4 * T, T, 4), (T - 56, T, 1), (65_536, sr.ROW_BLOCK, 114)])
def test_slot_rows_take_walks_the_forwards_default_blocks_a_step(rows, block,
                                                                 want):
    assert tg._blocks_a_step(rows, 9, block) == want


def test_presorted_slots_are_sort_slots_of_ascending_ids():
    """The sentinel, the padding to whole chunks and the bounds are one
    function under both: ids ascending already give what the sort gives."""
    rows, ids = _corner("chunks_of_sentinels_alone")
    ids = jnp.sort(jnp.asarray(ids))[:-37]      # not a whole chunk
    bounds, ids_s, _ = sw.sort_slots(ids, rows, T, C)
    got_bounds, got_ids = sw.presorted_slots(ids, rows, T, C)
    assert np.array_equal(got_bounds, bounds)
    assert np.array_equal(got_ids, ids_s)
    assert got_ids.shape == (1, 6 * C) and bounds[1, -1] == 4 * T


# ---------------- who may know what ----------------

NEIGHBOURS = ["table_gather.py", "slot_rows.py", "table_exchange.py",
              "ffm_pairs.py"]


def _privates_of_grad_scatter(path):
    """Every underscore name of ``grad_scatter`` that the module at
    ``path`` imports or reads as an attribute, under whatever alias."""
    tree = ast.parse(path.read_text())
    aliases, found = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if node.module == "dmlc_tpu.ops" and a.name == "grad_scatter":
                    aliases.add(a.asname or a.name)
                elif node.module == "dmlc_tpu.ops.grad_scatter" \
                        and a.name.startswith("_"):
                    found.add(a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "dmlc_tpu.ops.grad_scatter" and a.asname:
                    aliases.add(a.asname)
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr.startswith("_"):
            base = node.value
            dotted = (isinstance(base, ast.Attribute)
                      and base.attr == "grad_scatter")
            if dotted or (isinstance(base, ast.Name) and base.id in aliases):
                found.add(node.attr)
    return found


@pytest.mark.parametrize("module", NEIGHBOURS)
def test_the_walks_neighbours_reach_into_no_private_of_grad_scatter(module):
    """The layout, the sort and the walk are ``sorted_walk``'s, under
    public names. ``_on_tpu_backend`` alone stays ``grad_scatter``'s: the
    one probe every route consults through that module's attribute, which
    the benchmark's ahead-of-time compiles assign to."""
    path = pathlib.Path(gs.__file__).with_name(module)
    assert _privates_of_grad_scatter(path) <= {"_on_tpu_backend"}


def test_the_walks_state_machine_is_written_once():
    """``_CUR`` / ``_FETCHED`` / ``_READY`` live in ``sorted_walk.py`` and
    in no other module of ``ops/``."""
    ops = pathlib.Path(gs.__file__).parent
    holders = sorted(p.name for p in ops.glob("*.py")
                     if "_FETCHED" in p.read_text())
    assert holders == ["sorted_walk.py"]
    assert sw.STATE_WORDS == 3


@pytest.mark.parametrize("first_id,last_id,want", [
    (1536 + 5, 1536 + 100, (0, 0)),          # inside the block's first tile
    (1536 + 127, 1536 + 128, (0, 1)),        # across a tile's edge
    (7, 1536 + 300, (0, 2)),                 # begun in an earlier block
    (1536 + 1400, 5000, (10, 11)),           # ends in a later block
    (0, 1 << 30, (0, 11)),                   # a middle block of many
    (3071, 3071, (11, 11))])                 # the block's last id, alone
def test_a_pairs_tile_window_is_cut_to_its_block(first_id, last_id, want):
    """``tile_window`` (PR 46: both kernels' three lines, in one place):
    the tiles of the block of ids [1536, 3072) between a chunk's first and
    last id, cut to the block."""
    bounds = jnp.asarray([[0, first_id], [0, last_id]], jnp.int32)
    first, last = sw.tile_window(bounds, 1, jnp.int32(1536), jnp.int32(3072))
    assert (int(first), int(last)) == want


@pytest.mark.parametrize("rungs", [(12,), (4, 12), sw.ladder(1536)])
def test_tile_counts_take_any_ladder(rungs):
    """Three chunks against blocks of twelve tiles: a chunk inside tile 5 of
    block 1, one over block 1's last nine tiles, one from block 2's last
    tile to block 3's third."""
    bounds = jnp.asarray([[1536 + 640, 1536 + 384, 4500, 6144],
                          [1536 + 700, 3071, 4608 + 300, 6144]], jnp.int32)
    need = [1, 9, 1, 3]                      # the four pairs' windows
    made, whole = sw.tile_counts(bounds, 6144, 1536, rungs)
    assert int(whole) == 4 * 12
    assert int(made) == sum(min(r for r in rungs if r >= n) for n in need)


# ---- the padding's way to the sentinel, and the permutes that skip it ----

# ELL batches handed K-major, ``(K, B)`` and which slots are real
PADDINGS = ["no_padding", "whole_padding_columns", "partly_filled_columns",
            "a_row_of_all_padding", "slots_not_a_multiple_of_the_group"]


def _padded_batch(name, rows, seed=0):
    """``(ids [K, B], real [K, B])``: an ELL batch's slots K-major, its
    padding on the sink id ``rows - 1`` behind every row's real slots."""
    rng = np.random.default_rng(seed + sum(map(ord, name)))
    k, b = (5, 37) if name == "slots_not_a_multiple_of_the_group" else (8, 64)
    nnz = {"no_padding": np.full(b, k),
           "whole_padding_columns": np.full(b, k - 3),
           "partly_filled_columns": rng.integers(1, k - 1, b),
           "a_row_of_all_padding": rng.integers(1, k + 1, b),
           "slots_not_a_multiple_of_the_group": rng.integers(0, k, b)}[name]
    if name == "a_row_of_all_padding":
        nnz[[0, b // 2]] = 0
    real = np.arange(k)[:, None] < nnz[None, :]
    ids = np.where(real, rng.integers(0, rows - 1, (k, b)), rows - 1)
    return ids.astype(np.int32), real


@pytest.mark.parametrize("layout", ["columns", "lines"])
@pytest.mark.parametrize("name", PADDINGS)
def test_permute_live_is_the_permute_with_zeros_behind_the_live_runs(
        name, layout):
    """Both of a step's permutes, as the forward and the update make them
    for this batch: ``payload[index]`` in every run of indices that starts
    before the last live slot, zeros in the runs behind it."""
    rows = 4 * T
    ids, real = _padded_batch(name, rows)
    flat, n = jnp.asarray(real.reshape(-1)), real.size
    bounds, _, perm = sw.sort_slots(jnp.asarray(ids.reshape(-1)), rows, T, C,
                                    real=flat)
    inverse = sw.inverse_permutation(perm)[:n]
    rng = np.random.default_rng(3)
    for index, live in ((inverse, sw.live_batch_slots(flat)),
                        (perm, sw.live_sorted_slots(bounds, C))):
        m = int(perm.shape[0])
        payload = _draw(rng, m, 128) if layout == "lines" else _draw(rng, 9, m)
        got = np.asarray(jax.jit(sw.permute_live, static_argnums=3)(
            payload, index, live, layout))
        at = np.asarray(index)
        want = np.array(payload[at] if layout == "lines" else payload[:, at])
        groups = sw.permute_groups(at.size)
        run = at.size // groups
        dead = -(-int(live) // run) * run
        (want if layout == "lines" else want.T)[dead:] = 0.0
        assert np.array_equal(got, want)
        # the runs that are gathered hold every real slot
        assert int(live) >= int(real.sum()) and (
            name != "no_padding" or dead == at.size)
    assert sw.permute_groups(n) == (1 if n % 16 else 16)
    # an ELL batch's padding lies behind its real slots in both orders
    assert int(sw.live_batch_slots(flat)) == 1 + np.flatnonzero(
        real.reshape(-1)).max()
    assert int(sw.live_sorted_slots(bounds, C)) == -(-int(real.sum()) // C) * C


@pytest.mark.parametrize("counts", [(0, 0, 0, 0), (128, 128, 128, 128),
                                    (1, 33, 64, 97), (128, 0, 32, 31),
                                    (0, 0, 0, 1)])
def test_permute_live_by_runs_gathers_the_runs_that_hold_a_real_slot(counts):
    """The line side told run by run (PR 51: an owner's received slots, a
    bucket of 128 a worker, real up to its count, so the dead runs are the
    tail of every bucket): ``payload[index]`` in every run of 32 that
    holds a real slot, zeros in the others, every real slot gathered."""
    cap, rng = 128, np.random.default_rng(5)
    real = (np.arange(cap)[None, :] < np.asarray(counts)[:, None]).reshape(-1)
    runs = sw.live_runs(jnp.asarray(real))
    assert runs.shape == (16,) and np.array_equal(np.asarray(runs), [
        32 * (g % 4) < counts[g // 4] for g in range(16)])
    payload = _draw(rng, real.size, 128)
    index = rng.permutation(real.size).astype(np.int32)
    got = np.asarray(jax.jit(sw.permute_live, static_argnums=3)(
        payload, jnp.asarray(index), runs, "lines"))
    want = np.array(payload[index])
    want[np.repeat(~np.asarray(runs), 32)] = 0.0
    assert np.array_equal(got, want)
    assert np.array_equal(got[real], np.asarray(payload)[index][real])
    # a scalar count of live slots is the same permute where the padding
    # is one tail
    if counts == (128, 128, 128, 128):
        assert np.array_equal(got, np.asarray(sw.permute_live(
            payload, jnp.asarray(index), jnp.int32(real.size), "lines")))


@pytest.mark.parametrize("name", PADDINGS)
def test_unreal_slots_sort_as_the_sentinel_behind_the_real_ones(name):
    """``sort_slots(real=)``: the real slots keep the order, the chunks and
    the bounds the parent gave them with the padding on the sink id (the
    table's last row sorts behind every real id too); the padding carries
    the sentinel, as an id outside the table does. (Slot for slot where the
    batch fills whole chunks, as the cells' do: the tail behind the real
    slots then holds one id on either side. Where it does not, the parent's
    tail holds two, the sink's and the chunk padding's sentinel, and the
    sort, which is not stable, may order equal real ids another way.)"""
    rows = 4 * T
    ids, real = _padded_batch(name, rows)
    ids, real = jnp.asarray(ids.reshape(-1)), real.reshape(-1)
    sentinel = sw.round_up(rows, T)
    bounds, ids_s, perm = sw.sort_slots(ids, rows, T, C, real=real)
    outside = sw.sort_slots(jnp.where(real, ids, rows + 7), rows, T, C)
    for got, want in zip((bounds, ids_s, perm), outside):
        assert np.array_equal(got, want)
    on_the_sink = sw.sort_slots(ids, rows, T, C)
    count = int(real.sum())
    assert np.array_equal(ids_s[0, :count], on_the_sink[1][0, :count])
    if ids.size % C == 0:
        assert np.array_equal(perm[:count], on_the_sink[2][:count])
    assert np.array_equal(np.sort(perm[:count]), np.flatnonzero(real))
    assert np.array_equal(ids[perm[:count]], ids_s[0, :count])
    assert np.all(np.asarray(ids_s)[0, count:] == sentinel)
    whole = count // C
    assert np.array_equal(bounds[:, :whole], on_the_sink[0][:, :whole])
