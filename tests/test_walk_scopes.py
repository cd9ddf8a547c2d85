"""The sorted walk's pieces by name, and its work by count (PR 50).

Every route that takes the tables' kernels names the walk's five pieces
with the scopes of ``ops/sorted_walk.py:WALK_SCOPES``, nested in the
learner's own; the scopes are metadata only. ``learner.walk_books()``
counts, outside any step, what the update's kernel walked for the batch
the last step took, and ``learner.step_memory()`` keeps what the compile
behind ``hlo_scopes()`` says of the step's memory."""

import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental import sparse as jsparse

from dmlc_tpu.models import FFMLearner, FMLearner
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import slot_rows as sr
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops.sparse import EllBatch
from dmlc_tpu.ops.table_exchange import EXCHANGE_SCOPE, capacity
from dmlc_tpu.parallel.mesh import make_mesh
from dmlc_tpu.utils import telemetry
from tests.test_tracing import _strip_metadata

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARDS = 4
NUM_COL = 40_000          # ten blocks of 4,096 ids
B, K = 64, 16             # eight chunks of 128 slots


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(devices=jax.devices()[:SHARDS])


@pytest.fixture
def all_kernels(monkeypatch, kernels):
    """``kernels`` and the ragged rows' sums on theirs, interpreted."""
    monkeypatch.setattr(sr, "slot_rows_route", lambda *a: "kernel")
    return kernels


def _ell(seed=0, fields=False, real_share=0.7):
    """An ELL batch whose padding (value 0, the sink id) is scattered."""
    rng = np.random.default_rng(seed)
    values = (rng.random((B, K)) < real_share).astype(np.float32)
    # a Zipf-like head: chunks of one id, and ids spread over the blocks
    ids = np.minimum(rng.zipf(1.3, (B, K)), NUM_COL) - 1
    ids = np.where(values != 0, ids, NUM_COL).astype(np.int32)
    return EllBatch(
        jnp.asarray(ids), jnp.asarray(values),
        jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
        jnp.ones(B, jnp.float32),
        jnp.asarray(rng.integers(0, 5, (B, K)).astype(np.uint8))
        if fields else None)


def _ragged(seed=0, rows=256, slots=4096):
    """A ragged batch: flat slots row after row, the bucket's tail padded
    with the coordinates one past both ends."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 24, rows)
    nnz = int(lens.sum())
    coords = np.full((slots, 2), (rows, NUM_COL), np.int32)
    coords[:nnz, 0] = np.repeat(np.arange(rows), lens)
    coords[:nnz, 1] = np.minimum(rng.zipf(1.3, nnz), NUM_COL) - 1
    data = np.zeros(slots, np.float32)
    data[:nnz] = rng.normal(size=nnz)
    mat = jsparse.BCOO((jnp.asarray(data), jnp.asarray(coords)),
                       shape=(rows, NUM_COL))
    return (mat, jnp.asarray(rng.integers(0, 2, rows).astype(np.float32)),
            jnp.ones(rows, jnp.float32))


# route -> (the learner, its batch, the learner's scope of the forward and
# of the update)
def _route(name, mesh):
    if name == "fm":
        return FMLearner(NUM_COL, 8, layout="ell", seed=1), _ell()
    if name == "fm_dense_gradient":       # the caller's optimizer: the
        return FMLearner(NUM_COL, 8, layout="ell", seed=1,   # kernel's
                         optimizer=optax.adam(0.05)), _ell()  # gradient
    if name == "fm_dp4":
        model = FMLearner(NUM_COL, 8, layout="ell", seed=1, mesh=mesh)
        return model, jax.device_put(_ell(), model.batch_shardings())
    if name == "ragged":
        return FMLearner(NUM_COL, 8, layout="bcoo", seed=1), _ragged()
    if name == "ffm":
        return FFMLearner(NUM_COL, 5, 4, seed=1), _ell(fields=True)
    assert name == "ffm_dealt"
    model = FFMLearner(NUM_COL, 5, 4, seed=1, mesh=mesh)
    return model, jax.device_put(_ell(fields=True), model.batch_shardings())


ROUTES = ["fm", "fm_dense_gradient", "fm_dp4", "ragged", "ffm", "ffm_dealt"]
# where the update's two pieces stand: the fused routes' in the optimizer's
# scope, the dense gradient's in the gather's transpose
UPDATE_UNDER = {"fm_dense_gradient": "transpose(jvp(fm_gather))"}


def _operations(jaxpr, under=""):
    """``(scope path, primitive)`` of every equation of ``jaxpr`` and of
    the jaxprs inside it (a kernel's body is the kernel's)."""
    for eqn in jaxpr.eqns:
        path = "/".join(p for p in (under, str(eqn.source_info.name_stack))
                        if p)
        inner = [] if eqn.primitive.name == "pallas_call" else \
            list(jax.core.jaxprs_in_params(eqn.params))
        if not inner:
            yield path, eqn.primitive.name
        for sub in inner:
            yield from _operations(sub, path)


def _step_operations(model, batch):
    step_fn, _ = model._step._jit_args
    return list(_operations(jax.make_jaxpr(step_fn)(
        model.params, model.opt_state, batch).jaxpr))


def _walk_scopes_of(path):
    return [s for s in sw.WALK_SCOPES if s in path.split("/")]


@pytest.mark.parametrize("route", ROUTES)
def test_every_walk_scope_is_under_the_learners(all_kernels, mesh, route):
    model, batch = _route(route, mesh)
    paths = {path for path, _ in _step_operations(model, batch)}
    gather = "ffm_gather" if route.startswith("ffm") else "fm_gather"
    update = UPDATE_UNDER.get(route, gather.replace("gather", "optimizer"))
    want = {sw.SORT_SCOPE: gather, sw.GATHER_KERNEL_SCOPE: gather,
            sw.GATHER_PERMUTE_SCOPE: gather,
            sw.UPDATE_PERMUTE_SCOPE: update, sw.UPDATE_KERNEL_SCOPE: update}
    for scope, learners in want.items():
        inside = [p for p in paths if scope in p.split("/")]
        assert inside, (scope, route)
        assert any(p.index(learners) < p.index(scope) for p in inside
                   if learners in p), (scope, learners, inside)
    if route == "fm_dp4":
        # every chip's slots are sorted once, in the forward; the update
        # takes that sort (as on one chip)
        assert not any(sw.SORT_SCOPE in p.split("/") and update in p
                       for p in paths)
    if route == "ragged":
        # the row sums' bounds are the walk's, their two kernels stay
        # under the learner's fm_rowsum alone
        ops = _step_operations(model, batch)
        assert any("fm_rowsum" in p and sw.SORT_SCOPE in p for p, _ in ops)
        rowsum_kernels = [p for p, prim in ops if prim == "pallas_call"
                          and "fm_rowsum" in p]
        assert len(rowsum_kernels) >= 2
        assert not any(_walk_scopes_of(p) for p in rowsum_kernels)


# what moves, sorts or walks slots: such an operation between the learner's
# gather and its sink is in exactly one walk scope, or it is the exchange's
HEAVY = {"sort", "gather", "pallas_call", "scatter", "scatter-add",
         "dynamic_update_slice", "dynamic_slice", "pad",
         "optimization_barrier", "empty", "cumsum", "reduce_max",
         "reduce_sum"}
# what stands in no walk scope by intent: the learner's own view of its
# batch and its tables (the slots K-major, `values != 0`, a table's
# lane-major `.T`, the flat `reshape`s around a kernel-route op), the fused
# optimizer's scalar parameter and bias (Adam's `w0`, `bias(count)`), what
# crosses the chips (`table_exchange`; the all-gather of the rows)
LIGHT = {"reshape", "transpose", "ne", "convert_element_type", "squeeze",
         "slice", "concatenate", "broadcast_in_dim", "add", "sub", "mul",
         "div", "pow", "sqrt", "lt", "select_n", "integer_pow", "all_gather",
         "neg", "copy", "copy_p", "iota", "eq", "and", "max", "min"}


@pytest.mark.parametrize("route", ROUTES)
def test_operations_between_gather_and_sink_are_in_one_walk_scope_or_listed(
        all_kernels, mesh, route):
    model, batch = _route(route, mesh)
    between = [(p, prim) for p, prim in _step_operations(model, batch)
               if re.search(r"f?fm_(gather|optimizer)", p)]
    assert between
    outside = set()
    for path, prim in between:
        scopes = _walk_scopes_of(path)
        assert len(scopes) <= 1, (path, prim)
        if scopes or EXCHANGE_SCOPE in path.split("/"):
            continue
        outside.add(prim)
        assert prim not in HEAVY, (path, prim)
    assert outside <= LIGHT, sorted(outside - LIGHT)
    # and the five hold what their names say
    by_scope = {}
    for path, prim in between:
        for scope in _walk_scopes_of(path):
            by_scope.setdefault(scope, set()).add(prim)
    assert "sort" in by_scope[sw.SORT_SCOPE]
    assert by_scope[sw.GATHER_KERNEL_SCOPE] == {"pallas_call"}
    assert "pallas_call" in by_scope[sw.UPDATE_KERNEL_SCOPE]
    assert not {"sort", "pallas_call"} & by_scope[sw.GATHER_PERMUTE_SCOPE]
    assert "pallas_call" not in by_scope[sw.UPDATE_PERMUTE_SCOPE]
    assert "gather" in (by_scope[sw.GATHER_PERMUTE_SCOPE]
                        | by_scope[sw.UPDATE_PERMUTE_SCOPE])


@pytest.mark.parametrize("route", ["fm", "ragged", "ffm", "ffm_dealt"])
def test_walk_scopes_change_metadata_only(monkeypatch, all_kernels, mesh,
                                          route):
    """The compiled step, metadata stripped, is the same program with the
    scopes and without."""
    def build():
        model, batch = _route(route, mesh)
        return model._step.lower(model.params, model.opt_state,
                                 batch).compile().as_text()

    scoped = build()
    assert all(scope in scoped for scope in sw.WALK_SCOPES)
    named = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name in sw.WALK_SCOPES else named(name))
    plain = build()
    assert not any(scope in plain for scope in sw.WALK_SCOPES)
    assert _strip_metadata(scoped) == _strip_metadata(plain)


# ---------------- the books ----------------

def _plain_books(ids, real, num_rows, block=sw.BLOCK_IDS,
                 chunk=sw.CHUNK_SLOTS):
    """The count by hand: sort, cut into chunks, count the (block, chunk)
    pairs the walk meets and the ladder's rung each takes."""
    ids = np.asarray(ids).reshape(-1).astype(np.int64)
    keep = (ids >= 0) & (ids < num_rows)
    if real is not None:
        keep &= np.asarray(real).reshape(-1)
    sentinel = -(-num_rows // block) * block
    walked = np.sort(np.concatenate([
        np.where(keep, ids, sentinel),
        np.full(-ids.size % chunk, sentinel, np.int64)]))
    rungs = sw.ladder(block)
    chunks = pairs = made = 0
    for c in walked.reshape(-1, chunk):
        if c[0] >= sentinel:
            continue
        chunks += 1
        last = min(c[-1], sentinel - 1)
        for blk in range(c[0] // block, last // block + 1):
            lo, hi = max(c[0], blk * block), min(last, (blk + 1) * block - 1)
            need = (hi - blk * block) // sw.TILE_IDS \
                - (lo - blk * block) // sw.TILE_IDS + 1
            pairs += 1
            made += next(r for r in rungs if r >= need)
    return {"slots": ids.size, "real_slots": int(keep.sum()),
            "chunks": chunks, "pairs": pairs, "tile_products": made,
            "whole_block_tile_products": pairs * rungs[-1],
            "blocks_touched": len(set(walked[walked < sentinel] // block))}


def _batch_slots(route, batch):
    """``(ids, real)`` of what the update's walk sorts, by hand."""
    if route == "ragged":
        return np.asarray(batch[0].indices[:, 1]), None
    return np.asarray(batch.indices), np.asarray(batch.values) != 0


@pytest.mark.parametrize("route", ["fm", "fm_dense_gradient", "fm_dp4",
                                   "ragged", "ffm"])
def test_walk_books_are_the_plain_count(all_kernels, mesh, route):
    """An ELL batch with its padding named, a ragged batch (whose padding
    is the sink row's: walked) and a batch all-gathered over four chips,
    each of which walks the slots in its range of the laid tables."""
    model, batch = _route(route, mesh)
    assert model.walk_books() == {}              # before any step
    model.step(batch)
    books = model.walk_books()
    ids, real = _batch_slots(route, batch)
    if route == "fm_dp4":
        _a_chip_walks_the_slots_in_its_range(model, books, ids, real)
        return
    assert books == _plain_books(ids, real, NUM_COL + 1)
    assert books["pairs"] > books["chunks"] > 1  # the batch spans blocks
    assert books["slots"] > books["real_slots"] or route == "ragged"
    # one function under both counts
    made, whole = gs.grad_scatter_tile_counts(
        jnp.where(real, ids, NUM_COL + 1) if real is not None else ids,
        NUM_COL + 1)
    assert (books["tile_products"], books["whole_block_tile_products"]) \
        == (int(made), int(whole))
    assert telemetry.walk_books() == books
    assert telemetry.pod_snapshot()["walk_books"] == books
    text = telemetry.render_prometheus()
    assert f'dmlc_tpu_walk_books{{what="pairs"}} {books["pairs"]:.0f}\n' in text


def _a_chip_walks_the_slots_in_its_range(model, books, ids, real):
    """On tables laid in ranges the books are a count a chip, of every
    chip's slots with the ones it does not own at the sentinel: mean and
    largest."""
    deal = model.deal
    chips = []
    for chip in range(SHARDS):
        mine = real & (ids // deal.local_rows == chip)
        chips.append(_plain_books(ids - chip * deal.local_rows, mine,
                                  deal.local_rows))
    for what in sw.walk_books(jnp.zeros(1, jnp.int32), 1):
        per_chip = [c[what] for c in chips]
        assert books[what] == pytest.approx(np.mean(per_chip)), what
        assert books[what + "_largest_chip"] == max(per_chip), what
    assert books["slots"] == ids.size
    assert sum(c["real_slots"] for c in chips) == int(real.sum())
    assert model.shard_slots() == [c["real_slots"] for c in chips]
    assert telemetry.walk_books() == books


def test_an_owner_walks_what_it_received(all_kernels, mesh):
    """On a table dealt by rows the books are a count a chip, of the slots
    the exchange's own bucketing handed it: mean and largest."""
    model, batch = _route("ffm_dealt", mesh)
    model.step(batch)
    books = model.walk_books()
    ids, real = _batch_slots("ffm_dealt", batch)
    deal = model.deal
    cap = capacity(ids.size // SHARDS, SHARDS)
    chips = []
    for chip in range(SHARDS):
        mine = real & (ids % SHARDS == chip)
        got = np.full(SHARDS * cap, deal.local_rows, np.int64)
        got[:mine.sum()] = ids[mine] // SHARDS
        chips.append(_plain_books(got, None, deal.local_rows))
    for what in sw.walk_books(jnp.zeros(1, jnp.int32), 1):
        per_chip = [c[what] for c in chips]
        assert books[what] == pytest.approx(np.mean(per_chip)), what
        assert books[what + "_largest_chip"] == max(per_chip), what
    assert books["slots"] == SHARDS * cap
    assert sum(c["real_slots"] for c in chips) == int(real.sum())


def test_a_step_that_takes_no_kernel_keeps_no_books():
    """XLA's routes (the CPU, a small table) walk nothing."""
    model = FMLearner(NUM_COL, 8, layout="ell", seed=1)
    model.step(_ell())
    assert model.walk_books() == {}
    dense = FMLearner(7, layout="dense")
    dense.step((np.ones((4, 8), np.float32), np.ones(4, np.float32),
                np.ones(4, np.float32)))
    assert dense.walk_books() == {}


def test_books_of_one_reading_do_not_outlive_it(all_kernels, mesh):
    dealt, batch = _route("ffm_dealt", mesh)
    dealt.step(batch)
    assert "pairs_largest_chip" in dealt.walk_books()
    one, batch = _route("ffm", mesh)
    one.step(batch)
    books = one.walk_books()
    assert telemetry.walk_books() == books
    assert "pairs_largest_chip" not in telemetry.walk_books()


@pytest.mark.parametrize("route", ["fm", "ffm_dealt"])
def test_step_memory_is_kept_from_the_scopes_compile(all_kernels, mesh,
                                                     route):
    model, batch = _route(route, mesh)
    assert model.step_memory() == {}             # before any step
    model.step(batch)
    compiles = telemetry.compile_counters()["jit_compilations"]
    sizes = model.step_memory()
    assert set(sizes) == {"temp", "argument", "output", "alias"}
    assert all(isinstance(v, int) and v >= 0 for v in sizes.values())
    # the donated state is counted in the arguments and in the alias
    state = sum(x.nbytes // (SHARDS if route == "ffm_dealt" else 1)
                for x in jax.tree_util.tree_leaves(
                    (model.params, model.opt_state)) if x.ndim == 2)
    assert sizes["argument"] >= sizes["alias"] >= state
    assert telemetry.step_memory() == sizes
    assert telemetry.pod_snapshot()["step_memory_bytes"] == sizes
    # one compile serves the names and the sizes
    model.hlo_scopes(), model.step_memory()
    assert telemetry.compile_counters()["jit_compilations"] == compiles + 1


# ---------------- the benchmark's five metric files ----------------

WALK_METRICS = {
    "walk_sort_device_ms": sw.SORT_SCOPE,
    "walk_gather_kernel_device_ms": sw.GATHER_KERNEL_SCOPE,
    "walk_gather_permute_device_ms": sw.GATHER_PERMUTE_SCOPE,
    "walk_update_permute_device_ms": sw.UPDATE_PERMUTE_SCOPE,
    "walk_update_kernel_device_ms": sw.UPDATE_KERNEL_SCOPE,
}


@pytest.mark.parametrize("route", ["fm", "ragged", "ffm", "ffm_dealt"])
def test_the_metric_files_name_scopes_the_compiled_step_holds(
        all_kernels, mesh, route):
    model, batch = _route(route, mesh)
    model.step(batch)
    op_names = set(model.hlo_scopes().values())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, scope in WALK_METRICS.items():
        with open(os.path.join(ROOT, "cellbench", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec == {"reader": "scope_device_ms", "include": [scope]}
        assert any(scope in op for op in op_names), (name, route)
        entry = listed[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            "ms", "lower", "device_trace", "model step", "rows_per_s")


# ---------------- the benchmark's two new readers ----------------

class _Adapter:
    def __init__(self, learner):
        self.learner = learner


class _Observed:
    trace = None            # an untraced run: no kernel time to divide

    def __init__(self, learner):
        self.adapter = _Adapter(learner)


def _metric(name):
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_readers_read_the_learner_and_nothing_of_a_parent(
        all_kernels, mesh, capsys):
    from cellbench.readers import _program as P
    from cellbench.readers import step_memory, walk_books

    model, batch = _route("fm", mesh)
    model.step(batch)
    P._cache.clear()
    ctx = _Observed(model)
    books = model.walk_books()
    assert walk_books.read(ctx, _metric("walk_pairs_per_step")) \
        == books["pairs"]
    assert walk_books.read(ctx, _metric("walk_tile_products_per_step")) \
        == books["tile_products"]
    assert step_memory.read(ctx, _metric("step_temp_gb")) \
        == model.step_memory()["temp"] * 1e-9
    out = capsys.readouterr().out
    assert "walk books of the last batch stepped" in out
    assert "NOT THE SAME" not in out and '"pairs"' in out
    assert "step memory a chip" in out
    # a parent commit's learner has neither method: no value, nothing
    # raised
    P._cache.clear()
    for learner in (object(), None):
        old = _Observed(learner)
        assert walk_books.read(old, _metric("walk_pairs_per_step")) is None
        assert step_memory.read(old, _metric("step_temp_gb")) is None
        P._cache.clear()
