"""ops/table_gather.py: the rows of the ELL table gather read from sorted
slots by a one-hot kernel, against ``jnp.take``. On the CPU backend the
kernel runs in Pallas' interpret mode; the routing's hardware gate is
opened the way tests/test_grad_scatter.py opens the scatter's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.models import FFMLearner, FMLearner
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops import table_gather as tg
from dmlc_tpu.ops.sparse import EllBatch, ell_table_gather
from dmlc_tpu.utils import telemetry

T, C = 256, 128   # small tiles: the interpreter walks every block
# twelve tiles a block, so that the ladder has a gap (1 to 8, 10, 12 tiles)
# and a window can be pulled back
T_WIDE = 1536

# the tables after their id axis: an FM's (w, v), a field-aware FM's one
LAYOUTS = {"fm": ((), (8,)), "ffm": ((44,),)}


def _ids(name):
    """``(num_rows, ids)`` of one property the kernel must hold against
    ``jnp.take``, at blocks of ``_block_ids(name)`` ids."""
    rng = np.random.default_rng(sum(map(ord, name)))
    t = _block_ids(name)
    rows, n = 4 * t, 6 * C
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
    elif name == "negative_ids":
        ids[:6] = [-1, -rows, -3, 0, -rows + 1, -2]
    # the tile-limited contraction (PR 43): what a chunk's window may get
    # wrong
    elif name == "every_slot_on_one_id":
        ids[:] = t + 300
    elif name == "one_id_a_tile":         # every tile named, by one id
        ids = (np.arange(n) % (rows // 128)) * 128 + 77
    elif name == "chunk_skips_a_block":   # ids of blocks 0 and 2 in a chunk
        ids = np.concatenate([rng.integers(0, t, C // 2),
                              rng.integers(2 * t, 3 * t, C // 2),
                              rng.integers(3 * t, rows, C)])
    elif name == "chunk_ends_where_a_block_ends":
        # one chunk in block 0's last tile, the next in block 1's first
        ids = np.concatenate([rng.integers(t - 128, t, C),
                              rng.integers(t, t + 128, C)])
    elif name == "window_pulled_back":
        # tiles 3 to 11 of block 1: nine tiles, the rung of ten starts at 2
        ids = t + rng.integers(3 * 128, 12 * 128, C)
        ids[:2] = [t + 3 * 128, 2 * t - 1]
    elif name == "last_row_in_a_named_tile":
        rows = 3 * t + 77
        ids = rng.integers(3 * t, rows, n)
        ids[:3] = rows - 1
    elif name == "chunks_of_sentinels_alone":
        ids[n // 3:] = rows + rng.integers(0, 50, n - n // 3)
    elif name == "dense_ascending_row_ids":     # the slot_rows_take shape
        ids = np.arange(n) // 3
    elif name == "every_rung_of_the_cells_block":
        # the cells' blocks of 4,096 ids: a chunk a block, its window as
        # wide as a rung or one tile narrower, ending on the block's edge
        widths = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 19, 20,
                  23, 24, 27, 28, 31, 32]
        rows = len(widths) * t
        ids = np.concatenate([
            (b + 1) * t - 1 - np.append(rng.integers(0, w * 128, C - 2),
                                        [0, w * 128 - 1])
            for b, w in enumerate(widths)])
    else:
        assert name == "uniform", name
    return rows, ids.astype(np.int32)


CASES = ["uniform", "heavy_duplicates", "third_on_the_sink",
         "both_edges_of_a_block", "empty_blocks",
         "rows_not_a_multiple_of_the_block",
         "slots_not_a_multiple_of_the_chunk", "one_chunk_spans_every_block",
         "one_block_spans_many_chunks", "negative_ids",
         "every_slot_on_one_id", "one_id_a_tile", "chunk_skips_a_block",
         "chunk_ends_where_a_block_ends", "window_pulled_back",
         "last_row_in_a_named_tile", "chunks_of_sentinels_alone",
         "dense_ascending_row_ids", "every_rung_of_the_cells_block"]
BLOCK_OF = {**dict.fromkeys([
    "one_id_a_tile", "chunk_skips_a_block", "window_pulled_back",
    "last_row_in_a_named_tile", "dense_ascending_row_ids"], T_WIDE),
    "every_rung_of_the_cells_block": sw.BLOCK_IDS}


def _block_ids(name):
    return BLOCK_OF.get(name, T)


def _take(x, ids):
    """``jnp.take`` with an id outside the table reading 0, as the
    kernel's does (the default fills with NaN)."""
    return np.asarray(jnp.take(x, ids, axis=0, mode="fill", fill_value=0))


def _tables(rows, trailing, seed=1):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(rows,) + tail), jnp.float32)
                 for tail in trailing)


def _kernel_rows(ids, tables, t=T, c=C, blocks_a_step=2):
    """Steps 1 to 3 at the test's tile sizes: one array of rows a table,
    in the order of ``ids``."""
    rows = tables[0].shape[0]
    trailing = tuple(tuple(x.shape[1:]) for x in tables)
    bounds, ids_s, perm = sw.sort_slots(jnp.asarray(ids), rows, t, c)
    rows_s = tg.table_gather_pallas(
        bounds, ids_s, *(x.T if x.ndim == 2 else x for x in tables),
        num_rows=rows, trailing=trailing, block_ids=t, chunk_slots=c,
        blocks_a_step=blocks_a_step, interpret=True,
        layout=sw.slot_layout(sum(sw.widths(trailing))))
    if sw.slot_layout(sum(sw.widths(trailing))) == "lines":
        # (PR 47) a slot a row, its columns on the lanes: read as columns
        rows_s = rows_s.T
    back = np.empty(perm.shape[0], np.int64)
    back[np.asarray(perm)] = np.arange(perm.shape[0])
    cols = np.asarray(rows_s)[:, back[:len(ids)]]
    starts = sw.column_starts(trailing)
    return rows_s, tuple(
        cols[at:at + tail[0]].T if tail else cols[at]
        for tail, at in zip(trailing, starts))


LINE_CASES = ["uniform", "one_chunk_spans_every_block",
              "chunks_of_sentinels_alone",
              "slots_not_a_multiple_of_the_chunk", "third_on_the_sink",
              "negative_ids"]


@pytest.mark.parametrize("width", [17, 44, 130])
@pytest.mark.parametrize("name", LINE_CASES)
def test_the_line_side_reads_what_the_column_side_reads(name, width):
    """(PR 47) The kernel handing a wide table's rows out as lines, a
    chunk transposed in VMEM as the walk leaves it, against the same
    kernel handing them out lane-major, as every width did until then:
    the same bits, the lanes past the columns zeros. 130 columns take two
    tiles of lanes a line; ``one_chunk_spans_every_block`` is an ``Np`` of
    one chunk."""
    rows, ids = _ids(name)
    (table,) = _tables(rows, ((width,),))
    bounds, ids_s, _ = sw.sort_slots(jnp.asarray(ids), rows, T, C)
    lines, columns = (np.asarray(tg.table_gather_pallas(
        bounds, ids_s, table.T, num_rows=rows, trailing=((width,),),
        block_ids=T, chunk_slots=C, blocks_a_step=3, interpret=True,
        layout=layout)) for layout in ("lines", "columns"))
    padded, r = ids_s.shape[1], sw.round_up(width, sw.SPLIT_ROWS)
    assert columns.shape == (r, padded)
    assert lines.shape == (padded, sw.line_lanes(width))
    assert np.array_equal(lines.T[:r].view(np.uint32),
                          columns.view(np.uint32))
    assert not lines[:, width:].any()
    assert np.abs(columns).max() > 0


# the walk's own corners run for every op on it in tests/test_sorted_walk.py
WALK_CORNERS = {"empty_blocks", "rows_not_a_multiple_of_the_block",
                "one_chunk_spans_every_block", "chunks_of_sentinels_alone"}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("name", [n for n in CASES if n not in WALK_CORNERS])
def test_kernel_reads_what_take_reads(name, layout):
    """Two blocks a grid step at 9 columns; three at 44, where a step's
    last block may lie past the tables' end and past the sentinel."""
    rows, ids = _ids(name)
    tables = _tables(rows, LAYOUTS[layout])
    rows_s, got = _kernel_rows(ids, tables, t=_block_ids(name),
                               blocks_a_step={"fm": 2, "ffm": 3}[layout])
    for g, x in zip(got, tables):
        assert np.array_equal(g, _take(x, ids))
    # rows past the tables' columns and the padding's slots (sorted last)
    # are zeros
    width = sum(sw.widths(LAYOUTS[layout]))
    assert not np.asarray(rows_s)[width:].any()
    assert not np.asarray(rows_s)[:, len(ids):].any()


@pytest.mark.parametrize("blocks_a_step", [1, 4, 5, None])
def test_blocks_a_grid_step_change_no_value(blocks_a_step):
    """One block a step, the whole table in one step, a step larger than
    what is left, and the default (a mebibyte, capped by the table)."""
    rows, ids = _ids("rows_not_a_multiple_of_the_block")
    tables = _tables(rows, LAYOUTS["fm"])
    _, got = _kernel_rows(ids, tables, blocks_a_step=blocks_a_step)
    for g, x in zip(got, tables):
        assert np.array_equal(g, np.asarray(jnp.take(x, ids, axis=0)))


@pytest.mark.parametrize("rows,width,want", [
    (54_686_453, 9, 8), (13_671_614, 44, 2), (3 * 4096 + 5, 9, 3),
    (4096, 9, 1), (1 << 30, 300, 1)])
def test_a_grid_step_reads_about_a_mebibyte(rows, width, want):
    assert tg._blocks_a_step(rows, width, sw.BLOCK_IDS) == want


def test_an_id_outside_the_tables_reads_zero():
    """Documented: ``jnp.take`` fills with NaN, the kernel's slot reaches
    no block."""
    rows, ids = _ids("uniform")
    ids[:5] = [rows, rows + 5, 2 ** 30, -rows - 1, -2 ** 30]
    tables = _tables(rows, LAYOUTS["fm"])
    _, (w_g, v_g) = _kernel_rows(ids, tables)
    assert not w_g[:5].any() and not v_g[:5].any()
    assert np.array_equal(w_g[5:], np.asarray(tables[0])[ids[5:]])
    assert np.isnan(np.asarray(jnp.take(tables[0], ids[:5]))).all()


@pytest.mark.parametrize("value", [1.0, 1e-30, 3.0000002, -65504.125,
                                   1.1754944e-38, 3.4028235e38, 0.0])
def test_three_bfloat16_parts_bring_a_float32_back_exactly(value):
    ids = np.full(C, 5, np.int32)
    w = jnp.zeros((2 * T,), jnp.float32).at[5].set(value)
    v = jnp.stack([w, -w], axis=1)
    _, (w_g, v_g) = _kernel_rows(ids, (w, v))
    assert (w_g == np.float32(value)).all()
    assert (v_g[:, 0] == np.float32(value)).all()
    assert (v_g[:, 1] == -np.float32(value)).all()


@pytest.mark.parametrize("where,at,poisons_the_chunk", [
    ("named", 2 * T + 6, True),
    ("in_the_window_unnamed", 2 * T + 7, True),
    ("outside_the_window", 2 * T + 200, False)])
def test_a_non_finite_value_poisons_its_column_of_its_blocks_chunks_only(
        where, at, poisons_the_chunk):
    """The documented caveat, as a pair of bounds: 0 * inf in the
    contraction spreads a non-finite table value over its column in at
    most the slots of the chunks that reach its block and at least the
    slots that name it (``jnp.take`` would hand it to those alone), and
    over nothing else. Inside them it reaches the chunks whose window of
    tiles holds it: here every block's chunk names the even ids of its
    first tile, so a value in the second tile is not multiplied at all."""
    ids = (np.repeat(np.arange(4) * T, C) + np.tile(np.arange(C), 4)
           ) // 2 * 2
    w = jnp.ones((4 * T,), jnp.float32).at[at].set(jnp.inf)
    v = jnp.ones((4 * T, 2), jnp.float32)
    _, (w_g, v_g) = _kernel_rows(ids.astype(np.int32), (w, v))
    poisoned = ~np.isfinite(w_g)
    reach_its_block = np.zeros(4 * C, bool)
    reach_its_block[2 * C:3 * C] = True               # block 2's one chunk
    assert not poisoned[~reach_its_block].any()
    assert poisoned[ids == at].all() and np.isfinite(v_g).all()
    assert (ids == at).any() == (where == "named")
    assert poisoned[reach_its_block].all() == poisons_the_chunk
    assert poisoned.any() == poisons_the_chunk


def _plain_tile_counts(ids, rows, t, c, blocks_a_step):
    """The walk of ``_gather_kernel`` pair by pair in plain Python: every
    block the grid covers against every chunk that holds an id of it or
    of both sides of it."""
    ids = np.where(ids < 0, ids + rows, ids)
    sentinel = -(-rows // t) * t
    ids = np.sort(np.where((ids < 0) | (ids >= rows), sentinel, ids))
    ids = np.concatenate([ids, np.full(-len(ids) % c, sentinel)])
    ladder = sw.ladder(t)
    blocks = -(-rows // (t * blocks_a_step)) * blocks_a_step
    performed = pairs = 0
    for chunk in ids.reshape(-1, c):
        for base in range(0, blocks * t, t):
            if chunk[0] < base + t and chunk[-1] >= base:
                first = (max(chunk[0], base) - base) // 128
                last = (min(chunk[-1], base + t - 1) - base) // 128
                performed += min(r for r in ladder if r > last - first)
                pairs += 1
    return performed, pairs * (t // 128)


@pytest.mark.parametrize("blocks_a_step", [2, 3])
@pytest.mark.parametrize("name", CASES)
def test_tile_counts_are_the_walks(name, blocks_a_step):
    """``table_gather_tile_counts`` against the plain count, with the grid
    ending at the sentinel and reaching past it."""
    rows, ids = _ids(name)
    t = _block_ids(name)
    got = tg.table_gather_tile_counts(jnp.asarray(ids), rows, t, C,
                                      blocks_a_step)
    want = _plain_tile_counts(ids.astype(np.int64), rows, t, C,
                              blocks_a_step)
    assert tuple(map(int, got)) == want
    assert 0 < want[0] <= want[1]


@pytest.mark.parametrize("block_ids,want", [
    (4096, (1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16, 20, 24, 28, 32)),
    (1536, (1, 2, 3, 4, 5, 6, 7, 8, 10, 12)), (256, (1, 2)), (128, (1,))])
def test_the_ladder_ends_on_the_whole_block(block_ids, want):
    assert sw.ladder(block_ids) == want
    for need in range(1, want[-1] + 1):
        rung = want[int(sw.rung_index(jnp.int32(need), want))]
        assert rung == min(r for r in want if r >= need)


# pinned with the parent's own code (68779f0, jax 0.9.0), before PR 46
# edited anything: str(make_jaxpr) of ``table_gather_pallas`` at the three
# cells' shapes, sha256, first 16 digits. PR 46 gave the backward's kernel
# the forward's tile window and moved the window's arithmetic to
# ``sorted_walk.tile_window``, which both kernels now call: the forward's
# program is, character for character, the one it was
PARENT_JAXPRS = {
    (54_686_453, 1 << 20, ((), (8,))): "d12c9ee0915dc825",
    (13_671_614, 1 << 20, ((44,),)): "42cecc6e14c48fe8",
    (29_890_097, 1_966_080, ((), (8,))): "ad3c5dda1ba89262",
}


# pinned from PR 47's own tree (jax 0.9.0): the field-aware FM's 44
# columns leave the kernel as [Np, 128] lines (``sorted_walk.slot_layout``),
# one transposition a chunk in ``emit``; the two narrow shapes trace the
# parent's program still, and so does ``layout="columns"`` at 44
LINE_JAXPRS = {
    (13_671_614, 1 << 20, ((44,),)): "68ce61b7dd44540c",
}


@pytest.mark.parametrize("shape", list(PARENT_JAXPRS),
                         ids=["kdd12_fm", "kdd12_ffm", "kddb_fm"])
def test_the_forward_lowers_to_the_jaxpr_it_had_before_the_backwards_window(
        shape):
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    rows, n, trailing = shape
    padded = sw.round_up(n, C)
    sds = jax.ShapeDtypeStruct
    def digest(**how):
        text = str(jax.make_jaxpr(lambda *a: tg.table_gather_pallas(
            *a, num_rows=rows, trailing=trailing, **how))(
            sds((2, padded // C + 1), jnp.int32),
            sds((1, padded), jnp.int32),
            *(sds(tail + (rows,), jnp.float32) for tail in trailing)))
        assert "name=table_gather" in text
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    # the column side is the parent's program at every width; the FM's
    # payloads take it of themselves
    assert digest() == digest(layout="columns") == PARENT_JAXPRS[shape]
    if shape in LINE_JAXPRS:
        assert sw.slot_layout(sum(sw.widths(trailing))) == "lines"
        assert digest(layout="lines") == LINE_JAXPRS[shape]


# ---------------- the route ----------------

KDD12 = dict(num_rows=54_686_453, num_slots=65_536 * 16, widths=(1, 8))
FFM = dict(num_rows=13_671_614, num_slots=65_536 * 16, widths=(44,))


def _route(shape):
    shape = dict(shape)
    return tg.table_gather_route(
        shape.pop("num_rows"), shape.pop("num_slots"), shape.pop("widths"),
        shape.pop("dtype", jnp.float32), **shape)


@pytest.mark.parametrize("name,on_tpu,shape,want", [
    ("ffm_cell_on_the_chip", True, FFM, "kernel"),
    ("ffm_cell_on_the_cpu", False, FFM, "xla"),
    ("fm_cell_on_the_cpu", False, KDD12, "xla"),
    ("tiny_table", True, dict(FFM, num_rows=4096), "xla"),
    ("table_smaller_than_the_batch", True,
     dict(FFM, num_rows=(1 << 20) - 1), "xla"),
    ("table_as_large_as_the_batch", True, dict(FFM, num_rows=1 << 20),
     "kernel"),
    ("a_few_slots", True, dict(FFM, num_slots=64), "xla"),
    ("table_huge_against_the_batch", True, dict(FFM, num_slots=8192), "xla"),
    ("bfloat16_tables", True, dict(FFM, dtype=jnp.bfloat16), "xla"),
    # a chip of tables laid over four (PR 54): a quarter of the rows, the
    # slots of all
    ("one_shard_of_four", True, dict(KDD12, num_rows=13_671_614), "kernel"),
    # the replicated tables' quarter of the slots, which went with them
    ("a_quarter_of_the_slots", True, dict(KDD12, num_slots=16_384 * 16),
     "xla"),
])
def test_route_is_a_function_of_backend_dtype_shapes_and_shards(
        monkeypatch, name, on_tpu, shape, want):
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    assert _route(shape) == want, name


@pytest.mark.parametrize("cell,shape,want", [
    ("kdd12_fm_text", KDD12, "kernel"), ("kdd12_fm_snap", KDD12, "kernel"),
    ("kdd12_fm_bcache", KDD12, "kernel"),
    ("kdd12_fm_dp4_bcache", dict(KDD12, num_rows=13_671_614), "kernel"),
    ("kdd12_ffm_text", FFM, "kernel")])
def test_every_cell_is_routed_as_the_chip_measured(monkeypatch, cell, shape,
                                                   want):
    """Pinned: an edit of a constant cannot move a cell off the route its
    ledger lines were measured on."""
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    assert _route(shape) == want, cell


def test_route_crosses_over_once_as_the_table_grows(monkeypatch):
    """One algorithm chosen by shape: for the cell's batch the kernel is
    taken from a table as large as the batch up to some size, and XLA
    outside."""
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    for widths in ((1, 8), (44,)):
        routes = [tg.table_gather_route(1 << p, 1 << 20, widths,
                                        jnp.float32) for p in range(8, 34)]
        flips = sum(a != b for a, b in zip(routes, routes[1:]))
        assert routes[0] == "xla" and routes[-1] == "xla", routes
        assert "kernel" in routes and flips == 2, routes


@pytest.mark.parametrize("width,want", [(1, 15.3), (8, 17.0), (44, 62.0),
                                        (26, 39.5), (80, 107.0)])
def test_xla_gather_model_goes_through_its_readings(width, want):
    assert tg._xla_ns_per_index(width) == pytest.approx(want)


# ---------------- the op, the learners, the counter ----------------

CALLS = {"n": 0}     # traced forwards that took the kernel, all tests


@pytest.fixture
def forward_kernel(monkeypatch):
    """Every ELL forward takes the kernel, interpreted."""
    calls = {"n": 0}
    real = tg.table_gather_pallas

    def interpreted(*args, **kw):
        calls["n"] += 1
        CALLS["n"] += 1
        return real(*args, **dict(kw, interpret=True))

    monkeypatch.setattr(tg, "table_gather_pallas", interpreted)
    monkeypatch.setattr(tg, "table_gather_route", lambda *a: "kernel")
    return calls


@pytest.fixture
def backward_kernel(monkeypatch):
    """Every ELL backward takes the kernel, interpreted."""
    real = gs.grad_scatter_pallas
    monkeypatch.setattr(gs, "grad_scatter_pallas", lambda *a, **kw: real(
        *a, **dict(kw, interpret=True)))
    monkeypatch.setattr(gs, "grad_scatter_route", lambda *a: "kernel")


def _ell(rows, b=64, k=8, seed=0, sink_from=5, fields=None):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, rows - 1, (b, k)).astype(np.int32)
    val = rng.normal(size=(b, k)).astype(np.float32)
    idx[:, sink_from:], val[:, sink_from:] = rows - 1, 0.0   # padding slots
    plane = None
    if fields:
        plane = jnp.asarray(rng.integers(0, fields, (b, k)), jnp.uint8)
    return EllBatch(jnp.asarray(idx), jnp.asarray(val),
                    jnp.asarray(rng.integers(0, 2, b), jnp.float32),
                    jnp.ones(b, jnp.float32), plane)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("shape", [(64, 8), (8, 64), (37, 5), (700,)])
def test_op_reads_what_take_reads(forward_kernel, layout, shape):
    rows = 3000
    tables = _tables(rows, LAYOUTS[layout], seed=2)
    idx = jnp.asarray(np.random.default_rng(3).integers(0, rows, shape),
                      jnp.int32)
    got = jax.jit(lambda t, i: ell_table_gather(t, i))(tables, idx)
    assert forward_kernel["n"] == 1
    for g, x in zip(got, tables):
        want = jnp.take(x, idx, axis=0)
        assert g.shape == want.shape and g.dtype == want.dtype
        assert np.array_equal(np.asarray(g), np.asarray(want))


@pytest.mark.parametrize("route", ["kernel", "xla"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_caller_that_asks_takes_the_lines_where_they_come_as_lines(
        request, layout, route):
    """(PR 47) ``table_rows(lines=True)``: one wide table on the kernel
    route comes as ``[..., 128]`` lines, the columns first and zeros behind
    them, and the update takes their cotangent in that form, the same
    ``W`` and ``G`` bit for bit as from the rows; on XLA's route, and for
    the FM's narrow tables, the rows come as they always did."""
    if route == "kernel":
        request.getfixturevalue("forward_kernel")
        request.getfixturevalue("backward_kernel")
    tables = _tables(700, LAYOUTS[layout])
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 700, (8, 32)),
                      jnp.int32)
    rows, _ = tg.table_rows(tables, ids)
    got, sorted_slots = tg.table_rows(tables, ids, lines=True)
    if (layout, route) != ("ffm", "kernel"):
        assert all(np.array_equal(a, b) for a, b in zip(got, rows))
        return
    (lines,), (plain,) = got, rows
    assert lines.shape == ids.shape + (128,)
    assert np.array_equal(lines[..., :44], plain)
    assert np.array_equal(plain, _take(tables[0], ids))
    assert not np.asarray(lines[..., 44:]).any()
    state = ((tables[0], 1.0 + jnp.square(tables[0])),)
    cot = jnp.asarray(np.random.default_rng(3).normal(size=plain.shape),
                      jnp.float32)
    as_lines = jnp.pad(cot, ((0, 0), (0, 0), (0, 128 - 44)))
    (want,), (from_lines,) = (gs.fused_table_update(
        ids, (c,), state, None, gs.AdaGradEpilogue(0.2),
        sorted_slots=sorted_slots) for c in (cot, as_lines))
    for a, b in zip(from_lines, want):
        assert np.array_equal(np.asarray(a).view(np.uint32),
                              np.asarray(b).view(np.uint32))
    assert np.abs(np.asarray(want[0]) - np.asarray(tables[0])).max() > 0.01


def _sorts(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count(" sort[")


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_backward_takes_the_forwards_sort(request, layout):
    """Kernel forward and kernel backward: the ids are sorted once and the
    permutation inverted once; with XLA's forward the backward sorts
    itself. Same gradients either way, bit for bit: the forward's values
    are ``jnp.take``'s and the backward's arithmetic is one."""
    request.getfixturevalue("backward_kernel")
    rows = 3000
    tables = _tables(rows, LAYOUTS[layout], seed=4)
    idx = _ell(rows).indices

    def loss():          # a new function a trace: no cached jaxpr
        def of(tables):
            got = ell_table_gather(tables, idx)
            return sum(jnp.sum(jnp.sin(g) * (i + 1))
                       for i, g in enumerate(got))
        return of

    assert _sorts(jax.grad(loss()), tables) == 1
    want = jax.grad(loss())(tables)
    calls = request.getfixturevalue("forward_kernel")
    assert _sorts(jax.grad(loss()), tables) == 2
    assert _sorts(loss(), tables) == 2        # forward alone: sort, invert
    got = jax.grad(loss())(tables)
    assert calls["n"] == 3
    for g, x in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(x))


def _leaves(model):
    state = {"w": model.params.w}
    if hasattr(model.params, "v"):
        state["v"] = model.params.v
    for i, leaf in enumerate(jax.tree_util.tree_leaves(model.opt_state)):
        state[f"opt_{i}"] = leaf
    return {k: np.asarray(x) for k, x in state.items()}


@functools.lru_cache(maxsize=None)
def _three_steps(learner, forward, backward):
    """Final state of the learner (``layout='ell'``) after three steps on
    the named routes, with the losses."""
    rows = 5000
    if learner == "fm":
        model = FMLearner(num_col=rows - 1, num_factors=8, layout="ell",
                          seed=3)
        fields = None
    else:
        model = FFMLearner(rows - 1, 11, 4, seed=3)
        fields = 11
    before = CALLS["n"]
    losses = [float(model.step(_ell(rows, seed=s, fields=fields)))
              for s in range(3)]
    return dict(_leaves(model), loss=np.asarray(losses),
                kernel_forwards=CALLS["n"] - before)


FM_LEAVES = ["loss", "w", "v"] + [f"opt_{i}" for i in range(5)]
FFM_LEAVES = ["loss", "w", "opt_0"]


@pytest.mark.parametrize("learner,leaf", [("fm", x) for x in FM_LEAVES]
                         + [("ffm", x) for x in FFM_LEAVES])
def test_step_on_the_kernel_routes_matches_the_xla_routes(request, learner,
                                                          leaf):
    """Leaf by leaf after three steps: against XLA's gather and
    scatter-add to float32 rounding, and against XLA's gather with the
    kernel's backward bit for bit (the forward changes no value)."""
    want = _three_steps(learner, "xla", "xla")
    assert leaf in want, sorted(want)
    request.getfixturevalue("backward_kernel")
    same = _three_steps(learner, "xla", "kernel")
    request.getfixturevalue("forward_kernel")
    got = _three_steps(learner, "kernel", "kernel")
    assert (same["kernel_forwards"], got["kernel_forwards"]) == (0, 1)
    assert np.array_equal(got[leaf], same[leaf]), leaf
    scale = np.abs(want[leaf]).max()
    assert np.abs(got[leaf] - want[leaf]).max() <= 1e-6 * scale, leaf


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_route_is_counted_once_a_traced_forward(request, route):
    if route == "kernel":
        request.getfixturevalue("forward_kernel")
    before = telemetry.table_gather_routes().get(route, 0)
    model = FMLearner(num_col=2999, num_factors=4, layout="ell")
    for s in range(3):                     # one trace, three steps
        model.step(_ell(3000, seed=s))
    assert telemetry.table_gather_routes()[route] == before + 1
    model.step(_ell(3000, b=32))           # a new shape traces again
    assert telemetry.table_gather_routes()[route] == before + 2
    model.predict(_ell(3000))              # a forward alone is a forward
    assert telemetry.table_gather_routes()[route] == before + 3
    assert (f'dmlc_tpu_table_gather_route_total{{route="{route}",'
            f'width="5"}}' in telemetry.render_prometheus())
    assert telemetry.pod_snapshot()["table_gather_routes"][route] >= 3


def test_ffm_forward_is_counted_at_its_width(forward_kernel):
    model = FFMLearner(2999, 11, 4)
    model.step(_ell(3000, fields=11))
    assert ('dmlc_tpu_table_gather_route_total{route="kernel",width="44"}'
            in telemetry.render_prometheus())


def test_default_route_on_the_cpu_is_xla_and_the_same_program():
    """No gate opened: a CPU backend reads with ``jnp.take`` and the
    traced forward holds no sort and no kernel."""
    tables = _tables(3000, LAYOUTS["fm"])
    idx = _ell(3000).indices
    text = str(jax.make_jaxpr(lambda t: ell_table_gather(t, idx))(tables))
    assert " sort[" not in text and "pallas_call" not in text
    assert text.count("gather[") == 2


# ---------------- under a mesh ----------------
# (PR 54) the tables are laid by rows, a contiguous range a chip, and on the
# fused route every chip reads the slots of all that it owns from its shard

def _mesh_model():
    from dmlc_tpu.parallel import make_mesh

    mesh = make_mesh(devices=jax.devices()[:4])
    model = FMLearner(num_col=4999, num_factors=8, layout="ell", seed=3,
                      mesh=mesh)
    return model, model.batch_shardings()


@functools.lru_cache(maxsize=None)
def _mesh_steps(forward):
    model, batch_sh = _mesh_model()
    before = CALLS["n"]
    losses = [float(model.step(jax.device_put(_ell(5000, seed=s), batch_sh)))
              for s in range(3)]
    assert model.table_update_route(512) == ("fused", "adam")
    return dict(_leaves(model), loss=np.asarray(losses),
                kernel_forwards=CALLS["n"] - before)


@pytest.mark.parametrize("leaf", FM_LEAVES)
def test_kernel_forward_under_a_mesh_changes_no_value(request, leaf):
    """Tables laid by rows, batch sharded, the update fused on both sides:
    every chip reads the rows it owns with the kernel; three steps leave
    the same bits as XLA's ``take`` from the shard."""
    request.getfixturevalue("backward_kernel")
    want = _mesh_steps("xla")
    request.getfixturevalue("forward_kernel")
    got = _mesh_steps("kernel")
    assert want["kernel_forwards"] == 0 and got["kernel_forwards"] >= 1
    assert np.array_equal(got[leaf], want[leaf]), leaf


def test_each_chip_reads_the_slots_of_all_that_it_owns(forward_kernel):
    """Counted from the compiled four-device forward: the kernel runs on
    every chip's slots, sorted once, over a quarter of the tables; the ids
    cross as one all-gather and the rows come home as one all-to-all, and
    nothing of the tables' size crosses or is made."""
    import re

    from jax.sharding import PartitionSpec as P

    model, batch_sh = _mesh_model()
    batch = jax.device_put(_ell(5000), batch_sh)
    deal, lead = model.deal, P("data", None)
    hlo = jax.jit(jax.shard_map(
        lambda w, v, i, x: tg.table_rows((w, v), i.T, deal=deal,
                                         real=x.T != 0)[0],
        mesh=model.mesh, in_specs=(P("data"), lead, lead, lead),
        out_specs=(P(None, "data"), P(None, "data", None)),
        check_vma=False)).lower(
        model.params.w, model.params.v, batch.indices,
        batch.values).compile().as_text()
    for op in ("all-reduce", "reduce-scatter", "collective-permute"):
        assert f" {op}(" not in hlo and f" {op}-start(" not in hlo, op
    assert len(re.findall(r" all-gather(-start)?\(", hlo)) == 1
    assert len(re.findall(r" all-to-all(-start)?\(", hlo)) == 1
    slots = batch.indices.size
    assert f"s32[{slots}]" in hlo and f"[8,{deal.local_rows}]" in hlo
    assert not re.search(rf"[\[,]({deal.num_rows}|{deal.padded_rows})[\],]",
                         hlo)


# ---- an ELL batch's padding on the sentinel (PR 49) ----

from tests.test_sorted_walk import PADDINGS, _padded_batch  # noqa: E402


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("lines", [False, True], ids=["rows", "lines"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("name", PADDINGS)
def test_padding_named_unreal_reads_what_it_read_on_the_sink(
        forward_kernel, name, layout, lines):
    """``table_rows(real=)`` on one chip, the slots K-major: bit for bit
    the rows the parent read with the padding left on the sink id (a zero
    row) and nobody told: a real slot its row, the padding zeros, on both
    slot layouts; and the runs are counted."""
    rows = 3 * 4096 + 11
    trailing = LAYOUTS[layout]
    tables = tuple(t.at[-1].set(0.0) for t in _tables(rows, trailing, 4))
    ids, real = (jnp.asarray(x) for x in _padded_batch(name, rows))
    before = telemetry.table_slot_groups()
    got, sorted_slots = jax.jit(lambda t, i, r: tg.table_rows(
        t, i, real=r, lines=lines))(tables, ids, real)
    groups = sw.permute_groups(ids.size)
    after = telemetry.table_slot_groups()
    assert after.get(f"gather_{groups}", 0) - before.get(
        f"gather_{groups}", 0) == 1
    want, parents_sort = jax.jit(lambda t, i: tg.table_rows(
        t, i, lines=lines))(tables, ids)
    assert forward_kernel["n"] == 2
    for g, w, x in zip(got, want, tables):
        assert g.shape == w.shape and np.array_equal(_bits(g), _bits(w))
        cut = np.asarray(g)[..., :x.shape[1]] if x.ndim == 2 else g
        assert np.array_equal(cut, np.asarray(jnp.take(x, ids, axis=0)))
        assert not np.asarray(g)[~np.asarray(real)].any()
    # the sort is the parent's but for the tail, which holds the sentinel
    count = int(real.sum())
    assert np.array_equal(sorted_slots[1][0, :count],
                          parents_sort[1][0, :count])
    assert np.all(np.asarray(sorted_slots[1])[0, count:]
                  == sw.round_up(rows, sw.BLOCK_IDS))


def test_a_caller_that_names_no_padding_permutes_with_one_gather(
        forward_kernel):
    """``real=None`` (the ragged FM's flat slots, a dealt table's owner):
    nothing is counted and no ``cond`` stands outside the kernel's own;
    told which slots are real, the columns' permute is one ``switch``."""
    tables = _tables(3000, LAYOUTS["fm"], seed=2)
    idx = jnp.zeros((8, 64), jnp.int32)
    before = telemetry.table_slot_groups()
    text = str(jax.make_jaxpr(lambda t, i: tg.table_rows(t, i))(tables, idx))
    assert telemetry.table_slot_groups() == before
    told = str(jax.make_jaxpr(lambda t, i: tg.table_rows(
        t, i, real=i >= 0))(tables, idx))
    assert told.count("cond[") == text.count("cond[") + 1
