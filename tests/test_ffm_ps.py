"""A table dealt by rows over a mesh, parameter-server fashion (PR 32):
``parallel.mesh.RowDeal``, the ``deal=`` paths of ``ops/table_gather.py``
and ``ops/grad_scatter.py``, the exchange of the slots a chip owns between
them (PR 42: ``ops/table_exchange.py``, its capacity and the step that
does not fit), ``FFMLearner(mesh=)`` against the one-device
learner and the plain reference, the start drawn on the shards, the field
plane under a mesh, the jaxprs the undealt steps keep, and the new cells
of ``BENCHMARK.json`` at a tiny size. All on the CPU's virtual devices."""

import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from cellbench.reference import ffm_adagrad as reference
from cellbench.reference import ffm_start_blocks
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.models import FFMLearner, FMLearner
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops import table_exchange as tx
from dmlc_tpu.ops import table_gather as tg
from dmlc_tpu.ops.sparse import EllBatch, ell_table_gather
from dmlc_tpu.parallel import RowDeal, RowRanges, make_mesh
from dmlc_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, M, F, B, K = 401, 5, 4, 64, 8     # ids, fields, factors, rows, slots
SHARDS = 4


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(devices=jax.devices()[:SHARDS])


class RangeDeal(RowDeal):
    """The control: four contiguous ranges of ids, as ps-lite's servers own
    them before an application spreads its keys, sent through the
    exchange's buckets like the cyclic deal. (``RowRanges`` is the same
    rule with the road that builds none: PR 54.)"""

    def place(self, ids):
        return ids // self.local_rows, ids % self.local_rows


# ---------------- the deal ----------------

@pytest.mark.parametrize("deal_type", [RowDeal, RangeDeal, RowRanges])
@pytest.mark.parametrize("num_rows,shards", [
    (401, 4), (400, 4), (54_686_453, 4), (7, 8), (1, 4), (1000, 3)])
def test_the_deal_is_a_bijection_onto_chip_and_local_row(deal_type, num_rows,
                                                         shards):
    deal = deal_type(num_rows, shards)
    assert deal.local_rows == -(-num_rows // shards)
    assert deal.padded_rows == deal.local_rows * shards >= num_rows
    assert deal.padded_rows - num_rows < shards
    ids = np.arange(num_rows) if num_rows < 10_000 else np.unique(
        np.random.default_rng(0).integers(0, num_rows, 50_000).tolist()
        + [0, num_rows - 1])
    chip, row = deal.place(ids)
    assert chip.min() >= 0 and chip.max() < shards
    assert row.min() >= 0 and row.max() < deal.local_rows
    where = deal.physical_row(ids)
    assert np.array_equal(where, chip * deal.local_rows + row)
    assert len(np.unique(where)) == len(ids) and where.max() < deal.padded_rows
    if deal_type is RowDeal:       # the cyclic rule, and its even shares
        assert np.array_equal(chip, ids % shards)
        if num_rows < 10_000:
            counts = np.bincount(chip, minlength=shards)
            assert counts.max() - counts.min() <= 1
    if deal_type is RowRanges:     # the laid array is the table in id order
        assert np.array_equal(where, ids) and not deal.even
        assert deal.describe()["rule"] == "ranges"
    if deal_type is not RangeDeal:
        # what a checkpoint's file of a shard says of its rows: local row r
        # of chip c is id first + r * stride, for ``rows`` rows, and the
        # shards' rows are the table's
        owned = [deal.owned(c) for c in range(shards)]
        assert sum(rows for _, _, rows in owned) == num_rows
        for c, (first, stride, rows) in enumerate(owned):
            mine = first + stride * np.arange(min(rows, 1000))
            got_chip, got_row = deal.place(mine)
            assert np.all(got_chip == c)
            assert np.array_equal(got_row, np.arange(len(mine)))


def _generator_like_ids(rng, rows=4096, fields=11, num_features=54_686_452):
    """Ids as the benchmark's generator lays them out: every field its own
    contiguous range, half of the ids in field 0, a quarter in field 1."""
    sizes = [num_features >> (f + 1) for f in range(fields)]
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return (starts + rng.integers(0, sizes, (rows, fields))).astype(np.int32)


@pytest.mark.parametrize("deal_type,low,high", [(RowDeal, 1.0, 1.05),
                                                (RangeDeal, 2.0, 4.0)])
def test_slot_skew_reads_the_deal(mesh, deal_type, low, high):
    """Contiguous ranges put fields 2 to 10, nine of a row's eleven
    slots, on one chip; the cyclic deal gives every chip a quarter."""
    deal = deal_type(54_686_453, SHARDS)
    ids = _generator_like_ids(np.random.default_rng(3))
    counts = np.asarray(jax.jit(jax.shard_map(
        lambda i: deal.owned_slots(i, i >= 0), mesh=mesh,
        in_specs=P("data"), out_specs=P(), check_vma=False))(ids))
    assert counts.sum() == ids.size and counts.dtype == np.uint32
    chip, _ = deal.place(ids)
    assert np.array_equal(counts, np.bincount(chip.ravel(), minlength=4))
    assert low <= counts.max() * SHARDS / counts.sum() <= high


def _dealt(deal, mesh, table):
    """``table`` [num_rows, ...] as the deal lays it out."""
    out = np.zeros((deal.padded_rows,) + table.shape[1:], table.dtype)
    out[deal.physical_row(np.arange(len(table)))] = table
    return jax.device_put(out, deal.sharding(mesh, table.ndim))


@pytest.mark.parametrize("deal_type", [RowDeal, RangeDeal, RowRanges])
def test_take_reads_a_dealt_table_by_id(mesh, deal_type):
    deal = deal_type(N, SHARDS)
    table = np.random.default_rng(0).normal(size=(N, 6)).astype(np.float32)
    ids = np.random.default_rng(1).integers(0, N, 300)
    got = deal.take(mesh, _dealt(deal, mesh, table), jnp.asarray(ids))
    assert np.array_equal(np.asarray(got), table[ids])


def _slots(traffic: str, deal, rng):
    """``(idx [B, K], real [B, K] or None, whether the slots fit the
    exchange)`` at 256 slots a chip (a bucket holds 128). ``padded``: ids
    that repeat across chips, one hot on every chip's rows, the last two
    slots of a row the sink id and not real; ``hot``: one id in every row,
    so its owner's buckets overflow; ``even``: full rows, every chip the
    owner of a quarter of each chip's slots."""
    b, k, rows = 128, 8, deal.num_rows
    if traffic == "even":
        owner, _ = deal.place(np.arange(rows))
        owned = [np.flatnonzero(owner == d) for d in range(deal.shards)]
        return (np.array([owned[j % deal.shards][j // deal.shards]
                          for j in range(b * k)], np.int32).reshape(b, k),
                None, True)
    idx = rng.integers(0, rows, (b, k)).astype(np.int32)
    if traffic == "hot":
        idx[:, :5] = 17
        return idx, None, False
    idx[:, -2:] = rows - 1
    idx[::3, 0] = 17                      # a hot id on every chip's rows
    return idx, np.arange(k)[None, :] < np.full((b, 1), k - 2), True


@pytest.mark.parametrize("traffic", ["padded", "hot", "even"])
@pytest.mark.parametrize("route", ["xla", "kernel"])
@pytest.mark.parametrize("deal_type", [RowDeal, RangeDeal, RowRanges])
def test_dealt_gather_and_its_gradient_are_the_undivided_tables(
        request, mesh, deal_type, route, traffic):
    """Rows out and cotangent rows back through any deal, whether the
    slots fit the exchange's buckets or not: ``jnp.take`` of the whole
    table and the scatter-add of every chip's slots, with two tables in
    one id space. A slot that is not real reads zeros (the sink row of
    these tables does not hold them) and its cotangent goes nowhere."""
    if route == "kernel":
        request.getfixturevalue("kernels")
    rows = 9001
    deal = deal_type(rows, SHARDS)
    rng = np.random.default_rng(4)
    w = rng.normal(size=rows).astype(np.float32)
    v = rng.normal(size=(rows, 5)).astype(np.float32)
    idx, real, fits = _slots(traffic, deal, rng)
    c_w = rng.normal(size=idx.shape).astype(np.float32)
    c_v = rng.normal(size=idx.shape + (5,)).astype(np.float32)
    sent = np.ones(idx.shape, bool) if real is None else real

    def on_chip(w, v, idx, sent, c_w, c_v):
        def f(tables):
            g_w, g_v = ell_table_gather(tables, idx, deal,
                                        None if real is None else sent)
            return jnp.sum(g_w * c_w) + jnp.sum(g_v * c_v), (g_w, g_v)

        (_, got), grads = jax.value_and_grad(f, has_aux=True)((w, v))
        return got, grads, tx.overflows(deal, idx, sent)

    lead = P("data")
    got, grads, overflowed = jax.jit(jax.shard_map(
        on_chip, mesh=mesh, in_specs=(lead,) * 6,
        out_specs=((lead, lead), (lead, lead), P()), check_vma=False))(
        _dealt(deal, mesh, w), _dealt(deal, mesh, v), idx, sent, c_w, c_v)
    assert bool(overflowed) is not fits
    assert np.array_equal(np.asarray(got[0]), np.where(sent, w[idx], 0))
    assert np.array_equal(np.asarray(got[1]),
                          np.where(sent[..., None], v[idx], 0))
    assert w[rows - 1] != 0 and v[rows - 1].all()
    want_w = np.zeros_like(w)
    np.add.at(want_w, idx[sent], c_w[sent])
    want_v = np.zeros_like(v)
    np.add.at(want_v, idx[sent], c_v[sent])
    where = deal.physical_row(np.arange(rows))
    for got_g, want_g in ((grads[0], want_w), (grads[1], want_v)):
        assert np.abs(np.asarray(got_g)[where] - want_g).max() \
            <= 2e-6 * np.abs(want_g).max()
    inert = np.setdiff1d(np.arange(deal.padded_rows), where)
    assert not np.asarray(grads[1])[inert].any()


@pytest.mark.parametrize("num_slots,shards,want", [
    (262_144, 4, 81_920), (128, 4, 128), (256, 4, 128), (1_929_216, 4, 602_880),
    (1000, 3, 512)])
def test_a_bucket_holds_five_quarters_of_an_even_share(num_slots, shards,
                                                       want):
    """A constant of the shapes, in whole chunks of the kernels' slots: at
    the cell's 16,384 rows of 16 slots a chip, 81,920 (an owner's share of
    a chip's 11 real slots a row is about 45,056)."""
    assert tx.capacity(num_slots, shards) == want
    assert want % sw.CHUNK_SLOTS == 0 and want * 4 >= -(-num_slots // shards) * 5


@pytest.mark.parametrize("deal_type", [RowDeal, RangeDeal])
def test_padding_is_not_sent_and_the_buckets_hold_every_real_slot(mesh,
                                                                  deal_type):
    """The send buffers: every real slot's row at its owner, once, in its
    owner's bucket; none of the sink id, which the padding names; the row
    one past the shard elsewhere."""
    rows = 9001
    deal = deal_type(rows, SHARDS)
    idx, real, _ = _slots("padded", deal, np.random.default_rng(4))
    sink_chip, sink_row = (int(x) for x in deal.place(rows - 1))

    def on_chip(idx, real):
        buckets, send = tx.bucket_slots(deal, idx, real)
        return buckets.counts[None], send[None], buckets.overflow

    counts, send, overflowed = (np.asarray(x) for x in jax.jit(jax.shard_map(
        on_chip, mesh=mesh, in_specs=(P("data"),) * 2,
        out_specs=(P("data"), P("data"), P()), check_vma=False))(idx, real))
    assert not overflowed and send.shape == (SHARDS, SHARDS, 128)
    assert not (send[:, sink_chip] == sink_row).any()
    for chip, (ids, keep) in enumerate(zip(np.split(idx, SHARDS),
                                           np.split(real, SHARDS))):
        owner, row = deal.place(ids[keep])
        assert np.array_equal(counts[chip],
                              np.bincount(owner, minlength=SHARDS))
        for d in range(SHARDS):
            n = counts[chip, d]
            assert sorted(send[chip, d, :n]) == sorted(row[owner == d])
            assert np.all(send[chip, d, n:] == deal.local_rows)


# ---------------- the start ----------------

@pytest.mark.parametrize("num_col", [400, 401, 402, 403, 9000])
def test_the_start_drawn_on_the_shards_is_the_whole_draw(mesh, num_col):
    one = FFMLearner(num_col, M, F, seed=9)
    four = FFMLearner(num_col, M, F, seed=9, mesh=mesh)
    deal = four.deal
    assert four.params.w.shape == (deal.padded_rows, M * F)
    assert four.params.w.sharding == deal.sharding(mesh)
    dealt = np.asarray(four.params.w)
    where = deal.physical_row(np.arange(num_col + 1))
    assert np.array_equal(dealt[where], np.asarray(one.params.w))
    inert = np.setdiff1d(np.arange(deal.padded_rows), where)
    assert len(inert) == deal.padded_rows - num_col - 1
    assert not dealt[inert].any() and not dealt[where[-1]].any()
    assert np.all(np.asarray(four.accumulators) == 1.0)
    assert four.accumulators.sharding == deal.sharding(mesh)
    w, g = four.rows(np.arange(num_col + 1))
    assert np.array_equal(np.asarray(w), np.asarray(one.params.w))
    assert np.all(np.asarray(g) == 1.0)


@pytest.mark.parametrize("rows,block", [(5001, 1024), (401, 1 << 18)])
def test_the_blockwise_start_is_initial_rows_value_for_value(rows, block):
    rng = np.random.default_rng(1)
    lists = [rng.integers(0, rows, 3000), np.array([0, rows - 1, rows - 2])]
    want = reference.initial_rows(7, rows, 11, 4, *lists)
    got = ffm_start_blocks.initial_rows(7, rows, 11, 4, *lists,
                                        block_ids=block)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    assert not got[1][1].any() and got[1][2].any()


@pytest.mark.parametrize("width", [44, 65_535])
def test_the_blockwise_starts_flat_index_is_exact_past_32_bits(width):
    """libffm's whole table has 2,406,203,932 elements, past int32; a
    wider or longer table passes uint32 too: the two words from 16-bit
    limbs are numpy's 64-bit product."""
    ids = np.array([0, 3, 65_535, 65_536, 48_806_447, 54_686_452,
                    97_612_893, 97_612_894, 2 ** 32 - 1], np.uint32)
    hi, lo = ffm_start_blocks.flat_index_words(jnp.asarray(ids), width)
    want = ids[None, :].astype(np.uint64) * np.uint64(width) + np.arange(
        width, dtype=np.uint64)[:, None]
    assert want.max() > 2 ** 32
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).astype(np.uint64)
    assert np.array_equal(got, want)


# ---------------- the learner ----------------

def _rows(seed: int, short: bool = False, b: int = B):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, N, (b, K))
    fld = np.tile(np.arange(K) % M, (b, 1))
    val = rng.uniform(0.5, 2.0, (b, K)).astype(np.float32)
    if short:
        keep = rng.integers(1, K + 1, b)
        pad = np.arange(K)[None, :] >= keep[:, None]
        idx[pad], fld[pad], val[pad] = N, 0, 0.0
    return idx, fld, val, rng.integers(0, 2, b).astype(np.float32)


def _batch(idx, fld, val, lab, shardings=None) -> EllBatch:
    batch = EllBatch(np.asarray(idx, np.int32), val, lab,
                     np.ones(len(lab), np.float32), np.asarray(fld, np.uint8))
    if shardings is None:
        return EllBatch(*map(jnp.asarray, batch))
    return EllBatch(*(jax.device_put(a, sh)
                      for a, sh in zip(batch, shardings)))


def _libffm_adagrad(learning_rate=0.2):
    """The learner's own chain from outside: the same arithmetic from an
    optimizer the learner cannot see into (``reason="optimizer"``), so the
    dealt step keeps the shard's dense gradient and optax's sweep."""
    return optax.chain(
        optax.scale_by_rss(initial_accumulator_value=1.0, eps=0.0),
        optax.scale(-learning_rate))


@functools.lru_cache(maxsize=None)
def _three_steps(route: str):
    """One device, four devices, four devices on two passes and the plain
    reference after three steps (``route='kernel'``: under the ``kernels``
    fixture, where the dealt learner finishes AdaGrad inside the kernel
    on every shard and the two-pass one builds the shard's gradient)."""
    batches = [_rows(s, short=s == 1) for s in range(3)]
    mesh = make_mesh(devices=jax.devices()[:SHARDS])
    one = FFMLearner(N, M, F, seed=5)
    four = FFMLearner(N, M, F, seed=5, mesh=mesh)
    two_passes = FFMLearner(N, M, F, seed=5, mesh=mesh)
    two_passes.opt = _libffm_adagrad()
    (start,) = reference.initial_rows(5, N + 1, M, F, np.arange(N + 1))

    def steps(model):
        """The three losses and what ``table_update_route`` counted."""
        before = telemetry.table_update_routes()
        losses = [float(model.step(_batch(*b, model.batch_shardings())))
                  for b in batches]
        return losses, {k: v - before.get(k, 0)
                        for k, v in telemetry.table_update_routes().items()
                        if v != before.get(k, 0)}

    one_losses = [float(one.step(_batch(*b))) for b in batches]
    four_losses, routed_four = steps(four)
    two_losses, routed_two = steps(two_passes)
    ref = reference.train(start, batches, 0.2, 2e-5, M, F)
    w, g = four.rows(np.arange(N + 1))
    touched = np.unique(np.concatenate([b[0].ravel() for b in batches]))
    real = sum(int((b[2] != 0).sum()) for b in batches)
    return {"start": start, "real_slots": real,
            "routed": {"four": routed_four, "two_passes": routed_two},
            "loss": (np.asarray(list(zip(one_losses, four_losses))),
                     np.asarray([t[0] for t in ref])),
            "w": (np.asarray(w), np.asarray(one.params.w), ref[-1][1]),
            "g": (np.asarray(g), np.asarray(one.accumulators), ref[-1][2]),
            "untouched": np.setdiff1d(np.arange(N), touched),
            "dealt": (np.asarray(four.params.w),
                      np.asarray(four.accumulators), four.deal),
            "two_passes": (np.asarray(two_passes.params.w),
                           np.asarray(two_passes.accumulators),
                           np.asarray(two_losses)),
            "books": four.shard_slots(),
            "batches": batches}


@pytest.mark.parametrize("leaf", ["loss", "w", "g", "untouched", "inert",
                                  "books", "routed", "two_passes",
                                  "two_passes_exact"])
@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_dealt_learner_matches_one_device_and_the_plain_reference(
        request, route, leaf):
    if route == "kernel":
        request.getfixturevalue("kernels")
    run = _three_steps(route)
    if leaf == "loss":
        got, want = run["loss"]
        assert np.abs(got[:, 1] - got[:, 0]).max() <= 2e-6
        assert np.abs(got[:, 1] - want).max() <= 2e-6 * np.abs(want).max()
    elif leaf in ("w", "g"):
        four, one, ref = run[leaf]
        assert np.abs(four - one).max() <= 2e-6 * np.abs(one).max()
        assert np.abs(four - ref).max() <= 2e-6 * np.abs(ref).max()
    elif leaf == "untouched":     # rows no batch names: bit for bit
        rest = run["untouched"]
        assert rest.size > 10
        assert np.array_equal(run["w"][0][rest], run["start"][rest])
        assert np.all(run["g"][0][rest] == 1.0)
        assert not run["w"][0][N].any() and np.all(run["g"][0][N] == 1.0)
    elif leaf == "inert":         # the deal's padding rows, on their chips
        w, g, deal = run["dealt"]
        inert = np.setdiff1d(np.arange(deal.padded_rows),
                             deal.physical_row(np.arange(N + 1)))
        assert len(inert) == 2 and not w[inert].any()
        assert np.all(g[inert] == 1.0)
    elif leaf == "routed":
        # PR 40: a mesh is no reason for a dense gradient; where the
        # scatter takes the kernel the dealt step is the fused one
        assert run["routed"] == {
            "four": {"fused" if route == "kernel" else "dense": 1},
            "two_passes": {"dense": 1}}
    elif leaf == "two_passes":
        # the dealt arrays as they lie, W and G, against the shard's dense
        # gradient handed to the same chain; the losses step for step
        w, g, _ = run["dealt"]
        w2, g2, losses2 = run["two_passes"]
        assert np.abs(w - w2).max() <= 2e-6 * np.abs(w2).max()
        assert np.abs(g - g2).max() <= 2e-6 * np.abs(g2).max()
        assert np.abs(run["loss"][0][:, 1] - losses2).max() <= 2e-6
    elif leaf == "two_passes_exact":
        # rows no batch names, the deal's inert rows and the sink: the
        # same bits on whichever chip they live, whichever route
        w, g, deal = run["dealt"]
        w2, g2, _ = run["two_passes"]
        where = deal.physical_row(np.arange(N + 1))
        still = np.concatenate([
            where[run["untouched"]], where[N:],
            np.setdiff1d(np.arange(deal.padded_rows), where)])
        assert len(still) == len(run["untouched"]) + 3
        assert np.array_equal(w[still], w2[still])
        assert np.array_equal(g[still], g2[still])
        assert np.all(g[still] == 1.0) and not w[where[N]].any()
    else:
        assert sum(run["books"]) == run["real_slots"]
        chips = np.concatenate([
            b[0][b[2] != 0] % SHARDS for b in run["batches"]])
        assert run["books"] == np.bincount(chips, minlength=SHARDS).tolist()


@functools.lru_cache(maxsize=None)
def _one_big_step(route: str, traffic: str):
    """One device and four devices after one step on 1,024 rows of 4 slots
    (1,024 slots a chip; a bucket holds 384). ``hot``: one id in every row,
    so its owner's buckets overflow (256 + a quarter of 768); ``even``:
    full rows over all ids, which fit; ``short``: half of the slots
    padding, which fits only because the padding is not sent (the sink's
    owner would be handed 512 + 128)."""
    b, k = 1024, 4
    rng = np.random.default_rng(11)
    idx = (np.arange(b * k) % N).reshape(b, k) if traffic == "even" \
        else rng.integers(0, N, (b, k))
    fld = np.tile(np.arange(k) % M, (b, 1))
    val = rng.uniform(0.5, 2.0, (b, k)).astype(np.float32)
    if traffic == "hot":
        idx[:, 0] = 7
    elif traffic == "short":
        idx[:, 2:], fld[:, 2:], val[:, 2:] = N, 0, 0.0
    batch = EllBatch(idx.astype(np.int32), val,
                     rng.integers(0, 2, b).astype(np.float32),
                     np.ones(b, np.float32), fld.astype(np.uint8))
    one = FFMLearner(N, M, F, seed=5)
    four = FFMLearner(N, M, F, seed=5,
                      mesh=make_mesh(devices=jax.devices()[:SHARDS]))
    losses = (float(one.step(EllBatch(*map(jnp.asarray, batch)))),
              float(four.step(EllBatch(*(
                  jax.device_put(a, sh)
                  for a, sh in zip(batch, four.batch_shardings()))))))
    w, g = four.rows(np.arange(N + 1))
    return {"loss": losses, "w": (np.asarray(w), np.asarray(one.params.w)),
            "g": (np.asarray(g), np.asarray(one.accumulators)),
            "fallback": four.fallback_steps(), "books": four.shard_slots(),
            "real_slots": int((val != 0).sum())}


@pytest.mark.parametrize("leaf", ["loss", "w", "g", "fallback"])
@pytest.mark.parametrize("traffic", ["hot", "even", "short"])
@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_a_step_that_does_not_fit_the_exchange_is_the_same_step(
        request, route, traffic, leaf):
    """A hot id sends the whole step down the road with no capacity: the
    undivided table's step leaf for leaf, and one fallback step counted;
    a batch that fits counts none."""
    if route == "kernel":
        request.getfixturevalue("kernels")
    run = _one_big_step(route, traffic)
    if leaf == "loss":
        one, four = run["loss"]
        assert abs(four - one) <= 2e-6 * abs(one)
    elif leaf in ("w", "g"):
        four, one = run[leaf]
        assert np.abs(four - one).max() <= 2e-6 * np.abs(one).max()
        assert np.abs(one - (leaf == "g")).max() > 1e-3     # a step was taken
    else:
        assert run["fallback"] == (traffic == "hot")
        assert sum(run["books"]) == run["real_slots"]


def _skewed_rows(seed: int, traffic: str, b: int = 1024, k: int = 4):
    """``b`` rows of ``k`` slots, 1,024 slots a chip of four (a bucket holds
    384, four of an owner's sixteen runs of 96). ``skewed``: the last
    column is padding, and of every chip's 768 real slots the cyclic deal
    gives owner 0 350 (no run of its bucket dead), owner 1 100 (two of
    four), owner 2 none (all four) and owner 3 318 (none); ``full``: no
    padding, every id once a round, a quarter an owner (nothing to skip);
    ``hot``: ``skewed`` with one id in every row's first slot, whose
    owner's buckets overflow."""
    rng = np.random.default_rng(seed)
    fld = np.tile(np.arange(k) % M, (b, 1))
    val = rng.uniform(0.5, 2.0, (b, k)).astype(np.float32)
    if traffic == "full":
        idx = ((np.arange(b * k) + seed) % N).reshape(b, k)
    else:
        owner = np.repeat([0, 1, 3], [350, 100, 318])
        idx = np.concatenate([
            (rng.permutation(owner) + 4 * rng.integers(0, N // 4, 768)
             ).reshape(b // SHARDS, k - 1) for _ in range(SHARDS)])
        idx = np.concatenate([idx, np.full((b, 1), N)], axis=1)
        fld[:, -1], val[:, -1] = 0, 0.0
        if traffic == "hot":
            idx[:, 0] = 7
    return idx, fld, val, rng.integers(0, 2, b).astype(np.float32)


def test_the_skewed_rows_leave_none_some_and_all_of_a_buckets_runs_dead(mesh):
    """What the dealt road reads its four permutes' liveness from, on the
    rows the bit-for-bit test steps: an owner's received slots run by run
    (``sorted_walk.live_runs`` of the ids under the shard's rows) and a
    worker's count of slots sent, which lie first in both of its orders."""
    idx, _, val, _ = _skewed_rows(0, "skewed")
    deal = RowDeal(N + 1, SHARDS)

    def on_chip(idx, real):
        opened = tx.open_exchange(deal, idx.T, real.T)
        inverse = sw.inverse_permutation(opened.buckets.order)
        sent = jnp.sum(opened.buckets.counts)
        return (sw.live_runs(opened.received < deal.local_rows)[None],
                jnp.stack([sent, sw.live_batch_slots(inverse < sent),
                           sw.live_batch_slots(real.T.reshape(-1))])[None])

    runs, sent = jax.jit(jax.shard_map(
        on_chip, mesh=mesh, in_specs=(P("data"),) * 2,
        out_specs=(P("data"),) * 2, check_vma=False))(
        idx.astype(np.int32), val != 0)
    assert np.asarray(runs).tolist() == [
        [True] * 16, [True, True, False, False] * 4, [False] * 16, [True] * 16]
    assert np.asarray(sent).tolist() == [[768] * 3] * SHARDS


def _every_run_gathered(slots, index, live, layout):
    """``sorted_walk.permute_live`` as the dealt road had its four permutes
    until PR 51: one gather of every line, whatever is live."""
    assert layout == "lines"
    return sw.permute_lines(slots, index)


@functools.lru_cache(maxsize=None)
def _live_and_whole_steps(route: str, traffic: str):
    """Three steps of the dealt learner with its permutes run by run and
    with every run gathered (the parent's program), from the same start on
    the same batches: ``(W, G)`` of each as the deal lays them, the steps
    that overflowed and what ``table_slot_groups`` counted for the first."""
    from unittest import mock

    batches = [_skewed_rows(s, traffic) for s in range(3)]
    mesh = make_mesh(devices=jax.devices()[:SHARDS])

    def run():
        model = FFMLearner(N, M, F, seed=5, mesh=mesh)
        before = telemetry.table_slot_groups()
        for b in batches:
            model.step(_batch(*b, model.batch_shardings()))
        counted = {k: v - before.get(k, 0)
                   for k, v in telemetry.table_slot_groups().items()
                   if v != before.get(k, 0)}
        return (np.asarray(model.params.w), np.asarray(model.accumulators),
                model.fallback_steps(), counted)

    live = run()
    with mock.patch.object(sw, "permute_live", _every_run_gathered):
        whole = run()
    return live, whole


@pytest.mark.parametrize("leaf", ["w", "g", "counted"])
@pytest.mark.parametrize("traffic", ["skewed", "full", "hot"])
@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_the_dealt_step_by_live_runs_is_the_parents_step_bit_for_bit(
        request, route, traffic, leaf):
    """PR 51: the dealt road's four permutes gather only the runs that hold
    a slot somebody reads (a worker's rows home and cotangents out; on the
    kernel route the owner's un-permute, bucket by bucket, and its update
    permute), and a skipped run's zeros are what the whole gather left
    there: the table and the accumulators after three steps are the
    parent's bits, at buckets with none, some and all of their runs dead,
    with nothing to skip, and on the road of a step that overflows, which
    no permute of the exchange is on."""
    if route == "kernel":
        request.getfixturevalue("kernels")
    (w, g, fallbacks, counted), (w0, g0, fallbacks0, counted0) = \
        _live_and_whole_steps(route, traffic)
    assert fallbacks == fallbacks0 == (3 if traffic == "hot" else 0)
    if leaf == "counted":
        # once a trace each, under ops of their own; a chip's 1,024 slots
        # and the 1,536 it may receive both go in 16 runs
        want = {"rows_home_16": 1, "to_owners_16": 1}
        if route == "kernel":
            want.update(owner_gather_16=1, owner_update_16=1)
        assert counted == counted0 == want
        return
    got, want = (w, w0) if leaf == "w" else (g, g0)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    assert np.abs(got - (leaf == "g")).max() > 1e-3       # steps were taken


def test_dealt_learner_takes_both_kernels_once_a_road(kernels, mesh):
    model = FFMLearner(9000, M, F, seed=1, mesh=mesh)
    model.step(_batch(*_rows(0), model.batch_shardings()))
    # one trace of the step under shard_map: one forward and one backward a
    # road (the slots a chip owns; every chip's slots where they do not
    # fit), of which a step runs one
    assert kernels == {"gather": 2, "scatter": 2}
    assert model.fallback_steps() == 0


def test_dealt_learner_loop_surface(mesh):
    model = FFMLearner(N, M, F, seed=1, mesh=mesh)
    assert model.device_num_col() == N
    assert model.deal == RowDeal(N + 1, SHARDS, "data")
    sh = model.batch_shardings()
    assert isinstance(sh, EllBatch) and sh.fields.spec == P("data", None)
    batch = _batch(*_rows(0), sh)
    before = telemetry.table_shard_routes().get("owned_slots", 0)
    model.step(batch)
    assert telemetry.table_shard_routes()["owned_slots"] == before + 1
    assert ('dmlc_tpu_table_shard_route_total{collective="owned_slots",'
            'deal="cyclic",shards="4"}') in telemetry.render_prometheus()
    assert telemetry.pod_snapshot()["table_shard_routes"][
        "owned_slots"] >= 1
    # the steps that did not fit the exchange, as last read off the device
    assert model.fallback_steps() == 0
    assert telemetry.pod_snapshot()["table_shard_routes"][
        "fallback_steps"] == 0
    assert "dmlc_tpu_table_shard_fallback_steps 0" \
        in telemetry.render_prometheus()
    names = set(model.hlo_scopes().values())
    # (the buckets are made before the roads part; each road's own
    # exchange reads cond/branch_0_fun, the slots owned, or branch_1_fun)
    for scope in ("jvp(ffm_gather)/table_exchange",
                  "jvp(ffm_gather)/cond/branch_0_fun/table_exchange",
                  "transpose(jvp(ffm_gather))/cond/branch_0_fun/table_exchange",
                  "transpose(jvp(ffm_gather))/cond/branch_1_fun/table_exchange",
                  # (PR 51) a worker's two permutes, run by run
                  "jvp(ffm_gather)/cond/branch_0_fun/table_exchange/"
                  "exchange_permute/cond",
                  "transpose(jvp(ffm_gather))/cond/branch_0_fun/"
                  "table_exchange/exchange_permute/cond",
                  "ffm_interaction", "ffm_loss/psum", "ffm_optimizer",
                  "ffm_sink", "ffm_shard_books"):
        assert any(scope in n for n in names), scope
    assert model.predict(batch).shape == (B,)
    one = FFMLearner(N, M, F, seed=1)
    one.step(_batch(*_rows(0)))
    assert np.allclose(np.asarray(model.predict(batch)),
                       np.asarray(one.predict(_batch(*_rows(0)))), atol=1e-6)
    assert one.shard_slots() is None and one.deal is None
    assert one.fallback_steps() is None


def _compiled_dealt_step(mesh, rows=40_001, b=B) -> tuple:
    model = FFMLearner(rows - 1, M, F, seed=1, mesh=mesh)
    b = _batch(*_rows(0, b=b), model.batch_shardings())
    step_fn, options = model._step._jit_args
    return model, jax.jit(step_fn, **options).lower(
        model.params, model.opt_state, b).compile().as_text()


def test_the_compiled_dealt_step_holds_no_whole_table(mesh):
    """What crosses the devices: the rows' ids out and the rows back, the
    cotangent rows out (all-to-alls of the buckets; on the road of a step
    that does not fit, all-gathers of every slot); never a table, and no
    operand of the whole table's size on one device."""
    model, text = _compiled_dealt_step(mesh)
    width = M * F
    for n in (model.deal.num_rows, model.deal.padded_rows):
        assert f"f32[{n},{width}]" not in text
        assert f"f32[{width},{n}]" not in text
    assert f"f32[{model.deal.local_rows},{width}]" in text
    assert "all-to-all" in text and "all-gather" in text
    assert "all-reduce" in text or "reduce-scatter" in text


def test_the_compiled_owned_road_holds_no_operand_of_all_the_slots(kernels,
                                                                   mesh):
    """On the road a step takes when its slots fit, nothing has the size of
    every chip's slots together (``[shards * n, 128]``: the rows of 128
    lanes the permutes move): a chip sorts, reads and permutes the slots
    it received, ``[shards * cap, 128]``, and its own ``[n, 128]``. The
    road of a step that does not fit is today's and holds them."""
    import re

    b = 1024
    _, text = _compiled_dealt_step(mesh, b=b)
    n = b * K // SHARDS
    received, all_slots = SHARDS * tx.capacity(n, SHARDS), SHARDS * n
    assert n < received < all_slots
    roads = {"branch_0_fun": "", "branch_1_fun": ""}
    for line in text.splitlines():
        for road in roads:
            if road in line:
                roads[road] += line.split(", metadata=")[0] + "\n"

    def rows_of_128_lanes(road):
        return {int(d) for d in re.findall(r"f32\[(\d+),128\]", roads[road])}

    assert {n, received} <= rows_of_128_lanes("branch_0_fun")
    assert max(rows_of_128_lanes("branch_0_fun")) == received
    assert max(rows_of_128_lanes("branch_1_fun")) == all_slots


@pytest.mark.parametrize("which", ["gather", "scatter"])
def test_route_counters_carry_the_deals_labels(mesh, which):
    model = FFMLearner(N, M, F, seed=1, mesh=mesh)
    model.step(_batch(*_rows(0), model.batch_shardings()))
    text = telemetry.render_prometheus()
    if which == "gather":
        assert ('dmlc_tpu_table_gather_route_total{route="xla",shards="4",'
                'width="20"}') in text
    else:
        assert ('dmlc_tpu_grad_scatter_route_total{collective="owned_rows",'
                'route="xla",width="20"}') in text
        assert telemetry.grad_scatter_routes()["collective_owned_rows"] >= 1


@pytest.mark.parametrize("backend_is_tpu,want", [
    (True, ("kernel", "kernel")), (False, ("xla", "xla"))])
def test_a_chips_routes_are_those_of_its_shard_and_all_the_slots(
        monkeypatch, backend_is_tpu, want):
    """At the cell's shape a chip of four walks 13,671,614 rows and sorts
    1,048,576 slots: both kernels, as on the one chip of kdd12_ffm."""
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: backend_is_tpu)
    deal = RowDeal(54_686_453, 4)
    assert deal.local_rows == 13_671_614
    slots = 65_536 * 16
    assert (tg.table_gather_route(deal.local_rows, slots, (44,), jnp.float32),
            gs.grad_scatter_route(deal.local_rows, slots, 44, jnp.float32)
            ) == want


# pinned with the parent's own code (ab6a5e7, jax 0.9.0): str(make_jaxpr)
# of the learner's step function, sha256, first 16 digits. One chip and the
# replicated mesh do not see the deal. PR 34 (parent b7fb3af): the one-chip
# FFMLearner() on the kernel route finishes AdaGrad inside the kernel, by
# design another program (it read 42ff2873c307c7e4): re-pinned from PR
# 34's own tree; the dealt steps, which keep their two passes, are pinned
# from b7fb3af beside it. PR 36 (parent 5b31f0f): FFMLearner's pair terms
# are an op of their own (ops/ffm_pairs.py: on the kernel route two
# pallas_calls behind a custom_vjp, on the XLA route the same jax.numpy
# with the values under stop_gradient), by design another program in all
# four ("ffm", ...) cases (they read e15ccfc2788f52bc, 33ccadd2ac133032,
# 54cb65c8f593aa81, e6f1ed4844b335d9): re-pinned from PR 36's own tree.
# The five ("fm...", ...) digests are the parent's and were not touched:
# that they hold is the proof the FM cells run the program they ran.
# PR 40 (parent 863aae6): the dealt FFMLearner on the kernel route finishes
# AdaGrad inside the kernel on every chip's shard (fused_table_update(deal=)),
# by design another program (it read 413b80b18c8d2fdf): ("ffm", "kernel",
# True) re-pinned from PR 40's own tree. The other eight are the parent's
# and were not touched; ("ffm", "xla", True) among them: the dealt step on
# XLA's route keeps its two passes.
# PR 42 (parent 4c343ac): on a dealt table only the slots a chip owns reach
# it (ops/table_exchange.py: buckets by owner, two all-to-alls, and a
# lax.cond whose other road is the parent's all-gather of every slot), by
# design another program in both ("ffm", ..., True) cases (they read
# ac7821adb1a6e2ad and a43add4c6a4db3cd): re-pinned from PR 42's own tree.
# The other seven are the parent's and were not touched: no cell but
# kdd12_ffm_ps4_text runs another program than it ran.
# PR 43 (parent 7af0225): the forward's kernel contracts a (block, chunk)
# pair over the tiles the chunk can name (ops/table_gather.py: the body of
# the pallas_call ``table_gather`` holds a tree of ``cond``s over the
# ladder's rungs), by design another program in the five cases whose
# forward takes the kernel route (they read 3cdba6e711db78bc, 42e99ba81da433a6,
# e8175a70fce67a31, 2bd2390d1bdcedbb and 1c981a795d55a7b6): re-pinned from
# PR 43's own tree. The four "xla" digests are the parent's and were not
# touched, nor any of PARENT_FUSED_UPDATES: the backward and XLA's routes
# run the programs they ran.
# PR 46 (parent 68779f0): the backward's kernel takes the same window
# (ops/grad_scatter.py: the bodies of the pallas_calls ``grad_scatter`` and
# ``grad_scatter_adam`` hold the same tree of ``cond``s), by design another
# program in the five cases whose update takes the kernel route (they read
# c107eace0ef0c817, 0426cc6294259ccb, ebd29f3b3ccefbd4, cc044302e2dbc143 and
# beea57a1382a60f3) and in all four of PARENT_FUSED_UPDATES: re-pinned from
# PR 46's own tree. The four "xla" digests are the parent's and were not
# touched, and tests/test_table_gather.py pins the forward's kernel at the
# cells' shapes to the parent's program: the forward and XLA's routes run
# the programs they ran.
# PR 47 (parent ffdf49a): a payload of over 16 columns crosses the permutes
# as [Np, 128] float32 lines that the kernels write and read themselves
# (ops/sorted_walk.py: slot_layout; the toy's 5 fields x 4 factors are 20
# columns: lines), by design another program in the two ("ffm", "kernel", ...)
# cases (they read 0a9a2fe710becd2b and efa4ca97abee2b5c) and in the two
# "adagrad" cases of PARENT_FUSED_UPDATES, whose toy table is 20 wide:
# re-pinned from PR 47's own tree. The five "fm" cases, whose payload is 9
# columns, the four "xla" ones and the two "adam" ones are the parent's and
# were not touched; tests/test_table_gather.py and tests/test_grad_scatter.py
# hold the column side of both kernels to the parent's program at 44 columns
# too, and tests/test_ffm.py the step on lines to the step on columns, bit
# for bit.
# PR 49 (parent 46c5a06): the undealt learners tell the table ops which
# slots are their batch's padding (``real``: ``values != 0``) and the FM on
# one chip hands its slots K-major, as the field-aware FM always has; on
# the kernel routes the padding then takes the sort's sentinel and both
# permutes run by run (ops/sorted_walk.py: permute_live). By design another
# program in seven cases: both undealt "ffm" ones and every "fm" one (the
# flag is computed and handed over on XLA's routes too, which do not read
# it; under a mesh the FM keeps its slots batch-major, so ("fm", "xla",
# False) and ("fm", "xla", True) are two programs now). They read
# ea6fd2412e616681, 7bec2bc20f57aa2d, d84f5bc8115a7988 twice,
# 7468158b9d99aaea, e7dccc777e95e02d and 4de77dfbe982c2ed: re-pinned from
# PR 49's own tree. The two dealt cases, ("ffm", "xla", True) and ("ffm",
# "kernel", True), are the parent's and were not touched (a dealt step
# named its padding before, and its four permutes kept one gather each
# until PR 51), nor
# any of PARENT_FUSED_UPDATES (``fused_table_update`` with no ``real``
# traces to the program it traced to: the dealt owner's and the ragged
# route's), nor the ragged FM's step (pinned below, from the parent's code);
# tests/test_grad_scatter.py and tests/test_table_gather.py hold the told
# step to the untold one bit for bit on both slot layouts.
# PR 51 (parent 4c5f48f): the dealt road's four permutes go run by run too
# (ops/table_exchange.py: a worker's rows home and cotangents out, live up
# to the slots it sent; on the kernel route the owner's un-permute, live run
# by run of the slots it received, ``sorted_walk.live_runs``, and its update
# permute, live up to its sort's sentinel), by design another program in the
# two dealt cases (they read 90391b4dd35e3e58 and 16a18b9b42c9e25f; on XLA's
# route the worker's two permutes alone): re-pinned from PR 51's own tree.
# The seven undealt digests are the parent's and were not touched, nor any
# of PARENT_FUSED_UPDATES (none of the four is dealt), nor the ragged step's:
# ``permute_live`` with a scalar count traces to the program it traced to.
# test_the_dealt_step_by_live_runs_is_the_parents_step_bit_for_bit holds the
# dealt step to the one with every run gathered, bit for bit.
# PR 54 (parent c686086): under a mesh the FM's tables and moments are laid
# by rows in id order (parallel/mesh.py: RowRanges) and the replicated
# tables went: by design another program in the three ("fm...", ..., True)
# cases (they read 336f5c982ffc96c0, 03ea9f9b8ab314a6 and 057698d618f363b3):
# re-pinned from PR 54's own tree. On XLA's route and with a caller's Adam
# the step is the one-device step on the row-sharded operands (``jnp.take``,
# XLA's to partition), one program; on the kernel route every chip's
# ``_fused_step`` under ``shard_map``. The six undealt digests and the two
# dealt "ffm" ones are the parent's and were not touched: the ops lost their
# ``mesh=`` and kept every other program.
PARENT_STEPS = {
    ("ffm", "xla", False): "8aafdc21f53a49b2",
    ("ffm", "kernel", False): "6b3b6d7ba3f87dad",
    ("ffm", "xla", True): "5b175bf3e2d6b809",
    ("ffm", "kernel", True): "7e3e32bb693d4fda",
    ("fm", "xla", False): "6379e81c21f3ce06",
    ("fm", "xla", True): "073e55c2e6c636e8",
    ("fm", "kernel", False): "a83b357cc54d7ca7",
    ("fm", "kernel", True): "1f2f84cd8be91fab",
    ("fm_own_adam", "kernel", True): "073e55c2e6c636e8",
}


@pytest.mark.parametrize("case", list(PARENT_STEPS),
                         ids=["-".join(map(str, c)) for c in PARENT_STEPS])
def test_undealt_steps_trace_to_the_jaxprs_they_had(request, mesh, case):
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    learner, route, on_mesh = case
    if route == "kernel":
        request.getfixturevalue("kernels")
    how = dict(seed=1, mesh=mesh if on_mesh else None)
    if learner == "ffm":
        model = FFMLearner(9001, 5, 4, **how)
    else:
        model = FMLearner(9001, 8, layout="ell", optimizer=(
            optax.adam(0.05) if learner == "fm_own_adam" else None), **how)
    sds = jax.ShapeDtypeStruct
    batch = EllBatch(sds((64, 8), jnp.int32), sds((64, 8), jnp.float32),
                     sds((64,), jnp.float32), sds((64,), jnp.float32),
                     sds((64, 8), jnp.uint8) if learner == "ffm" else None)
    step_fn, _ = model._step._jit_args
    text = str(jax.make_jaxpr(step_fn)(model.params, model.opt_state, batch))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_STEPS[case]


def test_the_ragged_step_traces_to_the_jaxpr_it_had(kernels):
    """``FMLearner(layout="bcoo")`` names no padding to the table ops (its
    pad slots are the tail of a bucket, 2% in the cell) and its permutes
    keep one gather: the fused step on flat slots is the program PR 49's
    parent (46c5a06) traced, pinned with the parent's own code."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digest was taken under jax 0.9.0")
    from jax.experimental import sparse as jsparse

    model = FMLearner(9001, 8, layout="bcoo", seed=1)
    mat = jsparse.BCOO((jnp.ones(4096, jnp.float32),
                        jnp.zeros((4096, 2), jnp.int32)), shape=(64, 9001))
    step_fn, _ = model._step._jit_args
    text = str(jax.make_jaxpr(step_fn)(model.params, model.opt_state, (
        mat, jnp.zeros(64, jnp.float32), jnp.ones(64, jnp.float32))))
    assert "grad_scatter_adam" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "2917303f011024d5"


# pinned with the parent's own code (863aae6, jax 0.9.0), as PARENT_STEPS:
# str(make_jaxpr) of ``fused_table_update`` under the ``kernels`` fixture.
# PR 40 gave it and ``table_update_kernel`` a ``deal=``; without one, on
# one chip and on a mesh that replicates the tables (collective "rows"),
# they trace to the programs they traced to.
# PR 46 (parent 68779f0): the kernel's tile window, by design another
# program in all four (they read d34a52b110e1fa3d, 2d248ab346eeb2aa,
# 0d6b3a0a65a3be8a and afd6c5b3253ad8bf): re-pinned from PR 46's own tree;
# what ``deal=None`` must not change is held as before.
# PR 47 (parent ffdf49a): the two "adagrad" cases (20 columns: the line
# side, as PARENT_STEPS says; they read 43658afac3ca9071 and
# cb4e112f82c57791) re-pinned from PR 47's own tree; the two "adam" cases (9
# columns) are the parent's.
# PR 54 (parent c686086): ``fused_table_update(mesh=)``, the replicated
# tables' all-gathered rows, went with its only caller; the two (..., True)
# cases (they read d8d99ebe2492e2de and 62de82f8ec39c1c3) are now the call
# on every chip's shard of tables laid in ranges (``deal=RowRanges``: the
# road with no buckets), pinned from PR 54's own tree. The two one-chip
# cases are the parent's and were not touched
PARENT_FUSED_UPDATES = {
    ("adagrad", False): "c6639e9264ecf789",
    ("adagrad", True): "712b0cd065ff4b4e",
    ("adam", False): "ef1462fc6437b788",
    ("adam", True): "3db8c216715a487c",
}


def _fused_update_jaxpr(epilogue: str, mesh, **how) -> str:
    """``fused_table_update`` on one chip, or (``mesh``) inside
    ``shard_map`` on every chip's shard of tables laid in ranges."""
    from dmlc_tpu.parallel import RowRanges

    sds = jax.ShapeDtypeStruct
    rows, b, k = 9001, 64, 8
    # (laid tables take their slots K-major, the batch along axis 1)
    lead = (b, k) if mesh is None else (k, b)

    def traced(update, *args):
        if mesh is None:
            return str(jax.make_jaxpr(functools.partial(update, **how))(
                *args))
        deal = RowRanges(rows, SHARDS)
        laid = lambda x: P(*(("data",) + (None,) * (len(x.shape) - 1)  # noqa: E731
                             if x.shape and x.shape[0] == rows else ()))
        slots = lambda x: P(*((None, "data") + (None,) * (  # noqa: E731
            len(x.shape) - 2)))
        specs = (jax.tree_util.tree_map(laid, args[0]),) + tuple(
            laid(x) if x.shape == (2,) else slots(x) for x in args[1:])
        args = (jax.tree_util.tree_map(lambda x: sds(
            (deal.padded_rows,) + x.shape[1:], x.dtype), args[0]),) + args[1:]
        return str(jax.make_jaxpr(jax.shard_map(
            functools.partial(update, deal=deal), mesh=mesh, in_specs=specs,
            out_specs=specs[0], check_vma=False))(*args))

    if epilogue == "adagrad":
        table = sds((rows, 20), jnp.float32)
        return traced(
            lambda state, i, g, **how: gs.fused_table_update(
                i, (g,), state, None, gs.AdaGradEpilogue(0.2), **how),
            ((table, table),), sds(lead, jnp.int32),
            sds(lead + (20,), jnp.float32))
    w, v = sds((rows,), jnp.float32), sds((rows, 8), jnp.float32)
    return traced(
        lambda state, bias, i, g_w, g_v, **how: gs.fused_table_update(
            i, (g_w, g_v), state, bias, gs.AdamEpilogue(0.05), **how),
        ((w,) * 3, (v,) * 3), sds((2,), jnp.float32), sds(lead, jnp.int32),
        sds(lead, jnp.float32), sds(lead + (8,), jnp.float32))


@pytest.mark.parametrize("case", list(PARENT_FUSED_UPDATES),
                         ids=["-".join(map(str, c))
                              for c in PARENT_FUSED_UPDATES])
def test_fused_update_without_a_deal_traces_to_the_jaxpr_it_had(
        kernels, mesh, case):
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    epilogue, on_mesh = case
    text = _fused_update_jaxpr(epilogue, mesh if on_mesh else None)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_FUSED_UPDATES[case]
    if not on_mesh:     # ``deal=None`` said aloud is the same call
        assert text == _fused_update_jaxpr(epilogue, None, deal=None)


@pytest.mark.parametrize("leaf", ["w", "g", "elsewhere", "counted"])
@pytest.mark.parametrize("traffic", ["repeats", "padded", "hot"])
@pytest.mark.parametrize("deal_type", [RowDeal, RangeDeal])
def test_fused_update_of_a_dealt_table_is_the_undivided_tables(
        kernels, mesh, deal_type, traffic, leaf):
    """``fused_table_update(deal=)`` inside ``shard_map``, with no learner
    around it (the buckets are made in the call): AdaGrad on every chip's
    shard from the cotangent rows of the slots it owns, against optax on
    the whole table's scatter-added gradient; a row no slot names keeps its
    bits on whichever chip it lives. ``hot``: three slots of every row name
    one id, the step does not fit the exchange and all-gathers its slots."""
    rows, width = 9001, 20
    deal = deal_type(rows, SHARDS)
    rng = np.random.default_rng(7)
    w = rng.normal(size=(rows, width)).astype(np.float32)
    # (the accumulators start at 1 and only grow, the deal's inert rows
    # too: AdaGradEpilogue divides by no zero)
    grown = rng.uniform(size=(rows, width)).astype(np.float32)
    acc = np.float32(1.0) + grown
    b = 512 if traffic == "hot" else B
    idx = rng.integers(0, rows // 2, (K, b)).astype(np.int32)
    idx[0, ::3] = 17                      # a hot id from every chip's rows
    real = np.ones((K, b), bool)
    if traffic == "hot":
        idx[:3] = 17
    elif traffic == "padded":
        idx[-2:], real[-2:] = rows - 1, False
    c = rng.normal(size=(K, b, width)).astype(np.float32) * real[..., None]
    before = telemetry.grad_scatter_routes().get("collective_owned_rows", 0)

    def on_chip(w, acc, idx, real, c):
        ((w, acc),) = gs.fused_table_update(
            idx, (c,), ((w, acc),), None, gs.AdaGradEpilogue(0.2), deal=deal,
            real=real)
        return w, acc, tx.overflows(deal, idx, real)

    table, slots = P("data", None), P(None, "data")
    got_w, got_acc, overflowed = jax.jit(jax.shard_map(
        on_chip, mesh=mesh,
        in_specs=(table, table, slots, slots, P(None, "data", None)),
        out_specs=(table, table, P()), check_vma=False))(
        _dealt(deal, mesh, w), 1.0 + _dealt(deal, mesh, grown), idx, real, c)
    assert bool(overflowed) is (traffic == "hot")
    where = deal.physical_row(np.arange(rows))
    grad = np.zeros_like(w)
    np.add.at(grad, idx, c)
    want_acc = acc + grad * grad
    want_w = w - 0.2 * grad / np.sqrt(want_acc)
    if leaf == "w":
        assert np.abs(np.asarray(got_w)[where] - want_w).max() \
            <= 2e-6 * np.abs(want_w).max()
    elif leaf == "g":
        assert np.abs(np.asarray(got_acc)[where] - want_acc).max() \
            <= 2e-6 * np.abs(want_acc).max()
    elif leaf == "elsewhere":
        rest = np.setdiff1d(np.arange(rows), idx[real])
        # (the sink row among them, which only slots that are not real name)
        assert rest.size > rows // 2 and rows - 1 in rest
        assert np.array_equal(np.asarray(got_w)[where[rest]], w[rest])
        assert np.array_equal(np.asarray(got_acc)[where[rest]], acc[rest])
        inert = np.setdiff1d(np.arange(deal.padded_rows), where)
        assert not np.asarray(got_w)[inert].any()
        assert np.all(np.asarray(got_acc)[inert] == 1.0)
    else:
        # one backward's worth of books a traced update, the deal's label;
        # the kernel is traced once a road (the slots owned, the slots of
        # all) and a step runs one of them
        assert telemetry.grad_scatter_routes()[
            "collective_owned_rows"] == before + 1
        assert kernels == {"gather": 0, "scatter": 2}


# ---------------- the field plane under a mesh ----------------

def _libfm(path, rows=300, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(rows):
            n = int(rng.integers(1, 7))
            f.write(str(int(rng.integers(0, 2))) + " " + " ".join(
                f"{int(rng.integers(0, M))}:{int(rng.integers(0, N))}:1"
                for _ in range(n)) + "\n")
    return str(path) + "?format=libfm"


@pytest.mark.parametrize("given", ["shardings", "mesh_only"])
def test_device_iter_shards_the_field_plane_with_the_batch(tmp_path, mesh,
                                                           given):
    uri = _libfm(tmp_path / "c.libfm")
    how = dict(num_col=N, batch_size=64, layout="ell", max_nnz=6,
               fields=True)
    one = DeviceIter(create_parser(uri), **how)
    want = [jax.tree_util.tree_map(np.asarray, b) for b in one]
    one.close()
    model = FFMLearner(N, M, F, mesh=mesh)
    four = DeviceIter(create_parser(uri), mesh=mesh, shardings=(
        model.batch_shardings() if given == "shardings" else None), **how)
    got = list(four)
    stats = four.stats()
    four.close()
    assert len(got) == len(want) == 5
    for a, b in zip(got, want):
        assert a.fields.sharding.spec == P("data", None)
        assert len(a.fields.addressable_shards) == SHARDS
        assert a.fields.addressable_shards[1].data.shape == (16, 6)
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), y)
    assert stats["field_plane_bytes"] == 5 * 64 * 6
    model.step(got[0])                       # the learner takes them as put


# ---------------- the benchmark's new entries, at a tiny size ----------------

NEW_CELLS = ["kdd12_ffm_ps4_text", "kdd12_ffm_bcache"]
NEW_METRICS = ["ffm_exchange_device_ms", "table_shard_slot_skew",
               "ffm_ps_adagrad_step_roofline",
               "ffm_ps_grad_scatter_kernel_roofline"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_entries_are_appended_and_lawful(bench):
    # (later PRs append after them: PR 37's ninth cell, on one chip)
    assert [w["name"] for w in bench["workloads"]][6:8] == NEW_CELLS
    assert len(bench["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2 == 8 // 4
    config = bench["configs"][4]
    assert config["name"] == "kdd12_ffm_ps4" and len(bench["configs"]) >= 5
    assert config["reduced"] == ["rows"] and len(config["source"]) <= 200
    ps4, bcache = bench["workloads"][6:8]
    assert (ps4["config"], ps4["traffic"], ps4["chips"]) == (
        "kdd12_ffm_ps4", "text_epochs", 4)
    assert (bcache["config"], bcache["traffic"], bcache["chips"]) == (
        "kdd12_ffm", "block_cache_epochs", 1)
    assert all(1 <= len(w["why"]) <= 200 for w in (ps4, bcache))
    # appended when they came (PR 32); later PRs append after them
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_METRICS[0])
    metrics = bench["per_layer"][at:at + len(NEW_METRICS)]
    assert [m["name"] for m in metrics] == NEW_METRICS
    for m in metrics:
        assert m["workloads"] == ["kdd12_ffm_ps4_text"]
        assert ("roofline" in m["name"]) == (m["unit"] == "%")
        assert os.path.exists(os.path.join(
            ROOT, "cellbench", "metrics", m["name"] + ".json"))
    by = {m["name"]: m["workloads"] for m in bench["per_layer"]}
    # the undivided table's counts would read over 100% on a shard
    for name in ("ffm_adagrad_step_roofline",
                 "ffm_grad_scatter_kernel_roofline"):
        assert "kdd12_ffm_ps4_text" not in by[name]
        assert "kdd12_ffm_bcache" in by[name]
    assert "kdd12_ffm_bcache" in by["cache_read_busy_s_per_mrow"]
    assert "kdd12_ffm_bcache" not in by["parse_busy_s_per_mrow"]
    for cell in NEW_CELLS:
        for name in ("step_device_ms", "ffm_gather_device_ms",
                     "field_plane_bytes_per_row", "jit_compile_s"):
            assert cell in by[name]


def test_the_exchanges_permutes_have_a_metric_on_their_scope(bench):
    """PR 51: ``exchange_permute_device_ms`` is two data entries, the
    benchmark's last per-layer metric and a file on the reader the walk's
    five use, reading the scope ``table_exchange`` gives a worker's two
    permutes; its layer is the mesh's as the exchange's other metrics'."""
    # (PR 55 appended its three behind the two)
    last = bench["per_layer"][-5]
    mesh_layer = {m["name"]: m["layer"] for m in bench["per_layer"]}[
        "ffm_exchange_device_ms"]
    # PR 54's own data entry behind it: the laid FM's books on the reader
    # the dealt table's skew has
    assert bench["per_layer"][-4] == {
        "name": "fm_shard_slot_skew", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": mesh_layer,
        "moves": "rows_per_s", "workloads": ["kdd12_fm_dp4_bcache"]}
    for name in ("fm_shard_slot_skew", "table_shard_slot_skew"):
        with open(os.path.join(ROOT, "cellbench", "metrics",
                               name + ".json")) as f:
            assert json.load(f) == {"reader": "shard_slot_skew",
                                    "a_count": True}
    assert last == {
        "name": "exchange_permute_device_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": mesh_layer, "moves": "rows_per_s",
        "workloads": ["kdd12_ffm_ps4_text"]}
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           last["name"] + ".json")) as f:
        assert json.load(f) == {"reader": "scope_device_ms",
                                "include": [tx.PERMUTE_SCOPE]}
    assert tx.PERMUTE_SCOPE not in sw.WALK_SCOPES + (tx.EXCHANGE_SCOPE,)


def test_kdd12_ffm_ps4_states_the_whole_table_and_its_deal():
    with open(os.path.join(ROOT, "cellbench/configs/kdd12_ffm_ps4.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "cellbench/configs/kdd12_ffm.json")) as f:
        quarter = json.load(f)
    assert config["num_features"] == 54_686_452 == \
        config["source_num_features"] == config["generator"]["num_features"]
    for key in ("num_fields", "num_factors", "max_nnz", "batch_size",
                "learning_rate", "l2", "optimizer", "normalize", "layout",
                "fields", "dtype", "rows", "format"):
        assert config[key] == quarter[key], key
    assert set(config["reduced"]) == {"rows"}
    deal = RowDeal(config["num_features"] + 1, config["shards"])
    assert (deal.local_rows, deal.padded_rows) == (
        config["shard_rows"], config["padded_rows"])
    assert config["shard_num_features"] + 1 == config["shard_rows"]
    assert config["inert_rows"] == deal.padded_rows - deal.num_rows == 3
    assert config["chip_batch_size"] * config["shards"] == \
        config["batch_size"]
    assert config["mesh"] == {"data": 4} and config["chips"] == 4
    assert set(config["limits"]) == set(quarter["limits"])
    assert len(config["guarantees"]) == len(quarter["guarantees"]) + 2
    # a chip's shards at rest: over the 4 GiB floor
    assert 2 * config["shard_rows"] * 44 * 4 >= 4 << 30


def test_per_chip_costs_count_a_chips_share():
    from cellbench import costs_ffm, costs_ffm_ps

    chip = costs_ffm_ps.ffm_ps_chip_step_min_bytes(11, 4, 65_536, 16, 4)
    assert chip == costs_ffm.ffm_adagrad_step_min_bytes(11, 4, 16_384, 16)
    assert chip == 6 * 16_384 * 16 * 44 * 4 + 16_384 * 16 * 9 + 16_384 * 8
    # the kernel's count with the shard's sizes is a quarter table's
    kernel = costs_ffm.ffm_grad_scatter_kernel_bytes(
        13_671_613, 11, 4, 16_384, 16)
    whole = costs_ffm.ffm_grad_scatter_kernel_bytes(
        54_686_452, 11, 4, 65_536, 16)
    assert 3.9 < whole / kernel < 4.0


def _mirrored(R):
    """``BENCHMARK.json`` with every ``kdd12_`` name read as ``tiny_``, in
    memory (``tests/test_ffm.py``)."""
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kdd12_", "tiny_"))
        return real(*parts)

    return load_json


# (one run of the block-cache cell a process: the artifact store keeps a
# tier directory's lock file open, and a second run empties the directory)
@pytest.mark.parametrize("cell,trace", [("tiny_ffm_ps4_text", 0),
                                        ("tiny_ffm_ps4_text", 1),
                                        ("tiny_ffm_bcache", 1)])
def test_new_cells_rehearse_correct_on_the_cpu(monkeypatch, capsys, cell,
                                               trace):
    from cellbench import run as R
    from cellbench.readers import _program as P

    monkeypatch.setattr(R, "load_json", _mirrored(R))
    P._cache.clear()
    assert R.main(["--workload", cell, "--seed", "2147483999", "--seconds",
                   "1", "--trace", str(trace), "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True, [ln for ln in out.splitlines()
                                     if ln.endswith("NOT OK")]
    assert line["failed"] == 0 and line["rehearsal"] is True
    assert line["device"]["count"] >= (4 if "ps4" in cell else 1)
    values = {k: v["value"] for k, v in line["metrics"].items()}
    if trace:
        plane = values.pop("field_plane_bytes_per_row")
        assert 16.0 <= plane < 20.0
        assert values.pop("put_bytes_per_row") == pytest.approx(9.5 * plane)
        if "ps4" in cell:
            # a count: the largest chip's owned slots over the mean
            assert 1.0 <= values.pop("table_shard_slot_skew") < 1.6
        else:
            assert "table_shard_slot_skew" not in values
    assert all(v is None for v in values.values()), values


# ---------------- the new readers ----------------

def _ctx(learner=None):
    import types

    return types.SimpleNamespace(
        trace={}, adapter=types.SimpleNamespace(
            learner=learner, config={"step_module": "^jit_step$"}))


def test_exchange_metric_counts_what_crosses_the_chips(monkeypatch):
    """``all-gather.9`` by XLA's name and ``all_to_all.3`` by jax's, inside
    the counted executions of the step (not the first and the last whole
    one), overlaps once, and no other operation."""
    from cellbench import run as R
    from cellbench.readers import _program as P

    ms = 1e6
    step = [("jit_step(7)", i * 100 * ms, (i * 100 + 80) * ms)
            for i in range(4)]
    ops = []
    for _, a, _b in step:
        ops += [("fusion.1 f32[8] fusion kLoop", a, a + 80 * ms),
                ("all-gather.9 s32[1048576] all-gather", a + 10 * ms,
                 a + 12 * ms),
                ("all_to_all.3 f32[4,44,262144] all-to-all", a + 11 * ms,
                 a + 15 * ms),
                ("all_to_all_like.2 f32[4] fusion", a + 30 * ms, a + 40 * ms)]
    trace = {"devices": {0: {"ops": ops, "modules": step},
                         1: {"ops": ops, "modules": step}}}
    monkeypatch.setattr(P, "find_trace", lambda ctx=None: "x")
    monkeypatch.setattr(P, "loaded", lambda path: trace)
    spec = R.load_json(R.HERE, "metrics", "ffm_exchange_device_ms.json")
    reader = R.plugin("readers", spec["reader"])
    assert reader.read(_ctx(), spec) == pytest.approx(5.0)
    assert reader.read(_ctx(), {"reader": "collective_ms"}) \
        == pytest.approx(2.0)
    trace["devices"] = {0: {"ops": ops[:1], "modules": step}}
    assert reader.read(_ctx(), spec) is None
    monkeypatch.setattr(P, "find_trace", lambda ctx=None: None)
    assert reader.read(_ctx(), spec) is None


def test_skew_metric_reads_the_learners_books(mesh):
    import types

    from cellbench import run as R

    spec = R.load_json(R.HERE, "metrics", "table_shard_slot_skew.json")
    reader = R.plugin("readers", spec["reader"])
    books = types.SimpleNamespace(shard_slots=lambda: [30, 10, 10, 10])
    assert reader.read(_ctx(books), spec) == pytest.approx(2.0)
    # a learner with no such books (a parent commit, one chip): no metric
    assert reader.read(_ctx(types.SimpleNamespace()), spec) is None
    assert reader.read(_ctx(FFMLearner(N, M, F)), spec) is None
    dealt = FFMLearner(N, M, F, mesh=mesh)
    assert reader.read(_ctx(dealt), spec) is None      # no step yet
    dealt.step(_batch(*_rows(0), dealt.batch_shardings()))
    assert 1.0 <= reader.read(_ctx(dealt), spec) <= 4.0
