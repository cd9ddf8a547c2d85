"""Ragged rows end to end (PR 37): ``ops/slot_rows.py`` against XLA,
``FMLearner(layout="bcoo")`` against the plain reference and against the
ELL path on the same rows, ``DeviceIter(layout="bcoo", batch_size=)`` over
libsvm text (one shape an epoch set, both wires, the block cache), the two
libsvm engines on the benchmark generator's text, the permute of columns
past XLA's gather cliff, the new cell's books, and the cell itself at a
tiny size through the harness. All on the CPU; the kernels interpreted."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import sparse as jsparse

from cellbench.generators import ragged_zipf_libsvm as gen
from cellbench.reference import fm_adam_ragged as reference
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.models import FMLearner
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import slot_rows as sr
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops.sparse import EllBatch, block_to_bcoo_host, \
    parts_to_csr_host
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError

GEN = {"num_features": 3000, "zipf_s": 1.1, "label_noise": 1.0,
       "len_mu": 2.0, "len_sigma": 0.6, "len_min": 1, "len_max": 40}


@pytest.fixture
def row_kernels(monkeypatch, kernels):
    """``kernels`` (the tables' two, interpreted) and the row sums on them
    too, as a chip takes them at the cell's shape."""
    monkeypatch.setattr(sr, "slot_rows_route", lambda *a: "kernel")
    return kernels


def _ragged(rows, longest, seed, num_col=3000, empty=True):
    """Seeded rows of 0 (or 1) to ``longest`` non-zeros with decimal
    values: ``(lens, ids, vals, labels)``, the ids distinct in a row."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0 if empty else 1, longest + 1, rows)
    ids = np.concatenate([np.sort(rng.choice(num_col, n, replace=False))
                          for n in lens]).astype(np.int64)
    vals = np.round(rng.normal(size=len(ids)), 6).astype(np.float32)
    return lens, ids, vals, rng.integers(0, 2, rows).astype(np.float32)


def _bcoo(lens, ids, vals, labels, num_col, slots=None, pad_value=0.0):
    rows, nnz = len(lens), len(ids)
    slots = slots or -(-max(nnz, 1) // 256) * 256
    coords = np.full((slots, 2), (rows, num_col), np.int32)
    coords[:nnz, 0] = np.repeat(np.arange(rows), lens)
    coords[:nnz, 1] = ids
    data = np.full(slots, pad_value, np.float32)
    data[:nnz] = vals
    mat = jsparse.BCOO((jnp.asarray(data), jnp.asarray(coords)),
                       shape=(rows, num_col))
    return mat, jnp.asarray(labels), jnp.ones(rows, jnp.float32)


def _ell(lens, ids, vals, labels, num_col):
    rows, k = len(lens), max(int(lens.max()), 1)
    idx = np.full((rows, k), num_col, np.int32)
    val = np.zeros((rows, k), np.float32)
    real = np.arange(k) < lens[:, None]
    idx[real], val[real] = ids, vals
    return EllBatch(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(labels),
                    jnp.ones(rows, jnp.float32))


# ---------------- ops/slot_rows.py ----------------

@pytest.mark.parametrize("rows,longest,block,chunk", [
    (64, 12, 256, 1024), (700, 40, 256, 1024), (1500, 300, 512, 128),
    (300, 3, 128, 256)])
def test_slot_rows_kernels_match_segment_sum_and_take(row_kernels, rows,
                                                      longest, block, chunk):
    rng = np.random.default_rng(rows)
    lens = rng.integers(0, longest + 1, rows)
    nnz = int(lens.sum())
    slots = nnz + 37                      # a tail of slots of no row
    rid = jnp.asarray(np.concatenate([np.repeat(np.arange(rows), lens),
                                      np.full(slots - nnz, rows)]), jnp.int32)
    live = (np.arange(slots) < nnz)
    q = jnp.asarray(rng.normal(size=slots) * live, jnp.float32)
    a = jnp.asarray(rng.normal(size=(slots, 8)) * live[:, None], jnp.float32)
    want = tuple(jax.ops.segment_sum(x, rid, num_segments=rows)
                 for x in (q, a))
    got = sr.rows_sum_kernel((q, a), rid, rows, block, chunk)
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=2e-6, atol=2e-5)
    tq = jnp.asarray(rng.normal(size=rows), jnp.float32)
    ta = jnp.asarray(rng.normal(size=(rows, 8)), jnp.float32)
    got = sr.rows_take_kernel((tq, ta), rid, block, chunk)
    for t, g in zip((tq, ta), got):
        want = np.where(live.reshape((-1,) + (1,) * (t.ndim - 1)),
                        np.asarray(t)[np.minimum(np.asarray(rid), rows - 1)],
                        0.0)
        np.testing.assert_array_equal(np.asarray(g), want)
    assert row_kernels["scatter"] == 1 and row_kernels["gather"] == 1


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_slot_rows_sum_and_take_are_each_others_vjp(request, route):
    if route == "kernel":
        request.getfixturevalue("row_kernels")
    rows = 90
    lens, _, _, _ = _ragged(rows, 9, 3)
    rid = jnp.asarray(np.repeat(np.arange(rows), lens), jnp.int32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(len(rid), 4)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(rows, 4)), jnp.float32)
    before = telemetry.slot_rows_routes().get(route, 0)

    def through(summed):
        return jax.grad(lambda x: jnp.sum(jnp.sin(summed(x)) * t))(x)

    got = through(lambda x: sr.slot_rows_sum((x,), rid, rows)[0])
    want = through(lambda x: jax.ops.segment_sum(x, rid, num_segments=rows))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda t: jnp.sum(
        jnp.sin(sr.slot_rows_take((t,), rid)[0]) * x))(t)
    want = jax.grad(lambda t: jnp.sum(jnp.sin(t[rid]) * x))(t)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # sum, its take back, take, its sum back
    assert telemetry.slot_rows_routes()[route] == before + 4


@pytest.mark.parametrize("on_tpu,rows,slots,dtype,want", [
    (True, 65_536, 1_929_216, jnp.float32, "kernel"),
    (False, 65_536, 1_929_216, jnp.float32, "xla"),
    (True, 65_536, 1_929_216, jnp.bfloat16, "xla"),
    (True, 64, 1_929_216, jnp.float32, "xla"),
    (True, 65_536, 512, jnp.float32, "xla")])
def test_slot_rows_route_is_a_function_of_what_it_observes(
        monkeypatch, on_tpu, rows, slots, dtype, want):
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    assert sr.slot_rows_route(rows, slots, dtype) == want


# ---------------- the permute past XLA's gather cliff ----------------

@pytest.mark.parametrize("width,n,want", [
    (9, 1_048_576, False), (9, 1_572_864, False), (9, 1_929_216, True),
    (16, 2_097_152, True), (8, 2_097_152, False), (44, 2_097_152, False)])
def test_only_an_operand_past_the_cliff_is_permuted_in_groups(width, n, want):
    """The ELL cells' ``[9, 1048576]`` stays on the one gather it had (their
    jaxprs are pinned in tests/test_ffm_ps.py); 65,536 ragged rows do not."""
    assert sw.permutes_in_groups(width, n) is want


@pytest.mark.parametrize("width", [9, 12, 16])
def test_permute_wide_columns_is_permute_columns(monkeypatch, width):
    rng = np.random.default_rng(width)
    n = 4000
    cols = jnp.asarray(rng.normal(size=(width, n)), jnp.float32)
    perm = jnp.asarray(rng.permutation(n), jnp.int32)
    inverse = sw.inverse_permutation(perm)
    np.testing.assert_array_equal(np.asarray(inverse)[np.asarray(perm)],
                                  np.arange(n))
    want = sw.permute_columns(cols, perm)
    np.testing.assert_array_equal(
        sw.permute_wide_columns(cols, perm, inverse), want)
    np.testing.assert_array_equal(
        sw.scatter_columns_by_sort(cols, inverse), want)
    # and with every group past the cliff: a sort a column
    monkeypatch.setattr(sw, "GATHER_OPERAND_BYTES", 0)
    np.testing.assert_array_equal(
        sw.permute_wide_columns(cols, perm, inverse), want)


def test_both_table_ops_take_the_grouped_permute_past_the_cliff(
        monkeypatch, kernels):
    """The forward's un-permute and the backward's permute, forced over
    the cliff at a small size, give the rows and the gradient they gave."""
    from dmlc_tpu.ops.sparse import ell_table_gather

    rows, f = 5000, 8
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=rows), jnp.float32)
    v = jnp.asarray(rng.normal(size=(rows, f)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, rows, 3000), jnp.int32)

    def run():
        def loss(w, v):
            a, b = ell_table_gather((w, v), ids)
            return jnp.sum(jnp.sin(a)) + jnp.sum(b * b * a[:, None])
        return jax.value_and_grad(loss, argnums=(0, 1))(w, v)

    want = run()
    monkeypatch.setattr(sw, "GATHER_OPERAND_BYTES", 1 << 16)
    assert sw.permutes_in_groups(9, 3072)
    got = run()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------- FMLearner(layout="bcoo") ----------------

def _reference_steps(lens, ids, vals, labels, num_col, seed, steps=3):
    v0, = reference.initial_rows(seed, num_col + 1, 4, 0.01,
                                 np.arange(num_col + 1))
    batch = (ids, vals, np.repeat(np.arange(len(lens)), lens), labels)
    return reference.train(v0, [batch] * steps, 0.05)


@pytest.mark.parametrize("route", ["xla", "kernels"])
@pytest.mark.parametrize("elided_ones_at_the_pad", [False, True])
def test_bcoo_step_follows_the_plain_reference(request, route,
                                               elided_ones_at_the_pad):
    """Three Adam steps on rows of 0 to 40 non-zeros with decimal values:
    losses, the first gradient (from the first moment), and the
    parameters and both moments after the third step."""
    if route == "kernels":
        request.getfixturevalue("row_kernels")
    num_col, seed = 3000, 11
    rows = _ragged(96, 40, seed, num_col)
    trace = _reference_steps(*rows, num_col, seed)
    model = FMLearner(num_col, 4, layout="bcoo", seed=seed)
    batch = _bcoo(*rows, num_col,
                  pad_value=1.0 if elided_ones_at_the_pad else 0.0)
    before = telemetry.table_update_routes()
    losses = [float(model.step(batch)) for _ in range(3)]
    np.testing.assert_allclose(losses, [t[0] for t in trace], rtol=2e-6)
    fused = telemetry.table_update_routes().get("fused", 0) \
        - before.get("fused", 0)
    assert fused == (1 if route == "kernels" else 0)
    adam = model.opt_state[0]
    _, p, m, n = trace[-1]
    # (Adam divides a gradient by its own root: where one is all but zero,
    # a sum in another order moves the parameter by a part of lr = 0.05)
    for got, want, tol in ((model.params.w, p[1], 2e-4),
                           (model.params.v, p[2], 2e-4),
                           (adam.mu.w, m[1], 1e-7), (adam.mu.v, m[2], 1e-7),
                           (adam.nu.w, n[1], 1e-9), (adam.nu.v, n[2], 1e-9)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=tol)
    assert float(model.params.w[-1]) == 0.0 and not np.any(
        np.asarray(model.params.v[-1]))


def test_bcoo_first_gradient_is_the_references():
    num_col, seed = 3000, 4
    rows = _ragged(64, 30, seed, num_col)
    trace = _reference_steps(*rows, num_col, seed, steps=1)
    model = FMLearner(num_col, 4, layout="bcoo", seed=seed)
    model.step(_bcoo(*rows, num_col))
    for got, want in zip(model.opt_state[0].mu, trace[0][2]):
        np.testing.assert_allclose(np.asarray(got) / 0.1, want / 0.1,
                                   rtol=1e-4, atol=1e-8)


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_the_same_rows_through_ell_and_bcoo_train_the_same_model(request,
                                                                 route):
    """Float tolerance: Adam divides a gradient by its own root, so sums in
    another order move a parameter by a few units in 1e-5."""
    if route == "kernels":
        request.getfixturevalue("row_kernels")
    num_col, seed = 3000, 7
    rows = _ragged(128, 40, seed, num_col)
    ell = FMLearner(num_col, 4, layout="ell", seed=seed)
    bcoo = FMLearner(num_col, 4, layout="bcoo", seed=seed)
    for _ in range(3):
        a = ell.step(_ell(*rows, num_col))
        b = bcoo.step(_bcoo(*rows, num_col))
        np.testing.assert_allclose(float(a), float(b), rtol=2e-6)
    np.testing.assert_allclose(bcoo.params.w, ell.params.w, atol=2e-4)
    np.testing.assert_allclose(bcoo.params.v, ell.params.v, atol=2e-4)
    np.testing.assert_allclose(
        bcoo.predict(_bcoo(*rows, num_col)),
        ell.predict(_ell(*rows, num_col)), atol=1e-4)


def test_bcoo_step_on_the_chips_routes_holds_no_scatter_and_no_bcoo_dot(
        row_kernels):
    num_col = 3000
    model = FMLearner(num_col, 4, layout="bcoo", seed=1)
    batch = _bcoo(*_ragged(64, 20, 1, num_col), num_col)
    step_fn, _ = model._step._jit_args
    text = str(jax.make_jaxpr(step_fn)(model.params, model.opt_state, batch))
    assert "bcoo_dot_general" not in text and "scatter-add" not in text
    assert "scatter_add" not in text
    for name in ("table_gather", "grad_scatter_adam", sr.SUM_KERNEL,
                 sr.TAKE_KERNEL):
        assert f"name={name}" in text, name
    model.step(batch)
    scopes = set(model.hlo_scopes().values())
    for scope in ("fm_gather", "fm_interaction", "fm_rowsum", "fm_loss",
                  "fm_optimizer", "fm_sink"):
        assert any(scope in s for s in scopes), scope
    assert any("transpose(jvp(fm_rowsum))" in s for s in scopes)


def test_bcoo_learner_feeds_from_its_device_num_col_and_refuses_a_mesh():
    from dmlc_tpu.parallel import make_mesh

    model = FMLearner(50, 4, layout="bcoo")
    assert model.device_num_col() == 50 and model.weight_dim == 51
    with pytest.raises(DMLCError, match="bcoo.*no mesh.*flat list"):
        FMLearner(50, 4, layout="bcoo",
                  mesh=make_mesh(devices=jax.devices()[:2]))


# ---------------- DeviceIter(layout="bcoo", batch_size=) ----------------

def _corpus(tmp_path, rows=1000, seed=5):
    path = str(tmp_path / "c.libsvm")
    sums = gen.generate(GEN, seed, rows, path)
    return path, sums


def _epoch(it):
    out = [(np.asarray(m.data), np.asarray(m.indices), m.shape,
            np.asarray(y), np.asarray(w)) for m, y, w in it]
    it.reset()
    return out


def _same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x[2] == y[2]
        for u, v in zip(x[:2] + x[3:], y[:2] + y[3:]):
            np.testing.assert_array_equal(u, v)


@pytest.mark.parametrize("csr_wire", [True, False])
def test_ragged_libsvm_batches_hold_every_row_once_and_one_shape(tmp_path,
                                                                 csr_wire):
    path, sums = _corpus(tmp_path)
    lens, ids, vals, labels = reference.parse_libsvm_rows(path, 1000)
    it = DeviceIter(create_parser(path + "?format=libsvm"),
                    num_col=GEN["num_features"] + 1, batch_size=128,
                    layout="bcoo", nnz_bucket=64, csr_wire=csr_wire)
    first, second = _epoch(it), []
    for m, y, w in it:
        second.append((np.asarray(m.data), np.asarray(m.indices)))
        if len(second) == len(first):
            # the books at the last hand-out: the epoch's end plans (and
            # counts) the next one's first batches for a consumer that loops
            stats = it.stats()
    it.close()
    assert len(first) == 8
    got_ids, got_vals, got_lens, got_labels = [], [], [], []
    for data, coords, shape, y, w in first:
        assert shape == (128, GEN["num_features"] + 1)
        real = coords[:, 0] < 128
        assert np.all(coords[~real] == [128, GEN["num_features"] + 1])
        assert np.all(np.diff(coords[real, 0]) >= 0)       # rows ascending
        live = int(w.sum())
        got_lens.append(np.bincount(coords[real, 0], minlength=128)[:live])
        got_ids.append(coords[real, 1])
        got_vals.append(data[real])
        got_labels.append(y[:live])
    # every row once, in file order, no non-zero cut and none doubled
    np.testing.assert_array_equal(np.concatenate(got_lens), lens)
    np.testing.assert_array_equal(np.concatenate(got_ids), ids)
    np.testing.assert_array_equal(np.concatenate(got_vals), vals)
    np.testing.assert_array_equal(np.concatenate(got_labels), labels)
    assert int(np.concatenate(got_ids).astype(np.uint64).sum()
               % (1 << 32)) == sums["index_sum"]
    # the slot counts only grow, a bucket multiple each; from the second
    # epoch on there is one
    shapes = [len(b[0]) for b in first]
    assert shapes == sorted(shapes) and all(s % 64 == 0 for s in shapes)
    assert {len(b[0]) for b in second} == {max(shapes)}
    assert stats["bcoo"]["shapes"] == sorted(set(shapes))
    assert stats["bcoo"]["nnz"] == 2 * sums["nnz"]
    assert stats["bcoo"]["slots"] == sum(shapes) + 8 * max(shapes)
    # 4 B of value and 4 of id a slot, and the rows: 4 B a slot as pairs,
    # a pointer a row on the CSR wire
    per_slot = stats["bytes_to_device"] - 16 * 128 * 8 \
        - (4 * 129 * 16 if csr_wire else 0)
    assert per_slot == (8 if csr_wire else 12) * stats["bcoo"]["slots"]


def test_both_wires_and_the_block_cache_serve_the_same_batches(tmp_path):
    path, _ = _corpus(tmp_path)
    how = dict(num_col=GEN["num_features"] + 1, batch_size=128,
               layout="bcoo", nnz_bucket=64)
    cold = DeviceIter(create_parser(path + "?format=libsvm"), **how)
    want = [_epoch(cold), _epoch(cold)]
    cold.close()
    pairs = DeviceIter(create_parser(path + "?format=libsvm"),
                       csr_wire=False, **how)
    _same(_epoch(pairs), want[0])
    pairs.close()
    cached = DeviceIter(create_parser(
        path + "?format=libsvm", block_cache=str(tmp_path / "t.blockcache")),
        **how)
    got = [_epoch(cached), _epoch(cached)]
    assert cached.stats()["cache_state"] == "warm"
    cached.close()
    _same(got[0], want[0])      # the cold pass that writes the cache
    _same(got[1], want[1])      # parsed blocks read back, byte for byte


def test_csr_host_wire_is_the_pair_wire_less_the_rows():
    from dmlc_tpu.data.device import _csr_coords
    from dmlc_tpu.data.row_block import RowBlock

    lens, ids, vals, labels = _ragged(50, 9, 2)
    offset = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    block = RowBlock(offset=offset, label=labels, index=ids.astype(np.uint32),
                     value=vals)
    coords, v1, y1, w1, shape1 = block_to_bcoo_host(
        block, 3000, pad_rows_to=64, pad_nnz_to=512)
    cols, ptr, v2, y2, w2, shape2 = parts_to_csr_host(
        [block], 3000, pad_rows_to=64, pad_nnz_to=512)
    assert shape1 == shape2 == (64, 3000) and ptr.shape == (65,)
    np.testing.assert_array_equal(cols, coords[:, 1])
    np.testing.assert_array_equal(
        np.asarray(_csr_coords(jnp.asarray(cols), jnp.asarray(ptr))), coords)
    for a, b in ((v1, v2), (y1, y2), (w1, w2)):
        np.testing.assert_array_equal(a, b)


def _blocks(sizes, seed, values=True):
    """Seeded RowBlocks of the given row counts, rows of 0-9 non-zeros."""
    from dmlc_tpu.data.row_block import RowBlock

    out = []
    for i, n in enumerate(sizes):
        lens, ids, vals, labels = _ragged(n, 9, seed + i)
        out.append(RowBlock(
            offset=np.concatenate([[0], np.cumsum(lens)]).astype(np.int64),
            label=labels, index=ids.astype(np.uint64),
            value=vals if values else None,
            weight=None if i % 2 else np.full(n, 0.5, np.float32)))
    return out


@pytest.mark.parametrize("sizes,batch,drop", [
    ([5, 7, 3, 20, 1, 9], 8, False), ([5, 7, 3, 20, 1, 9], 8, True),
    ([64], 16, False), ([3, 1, 3, 2], 4, False), ([2, 2], 16, False)])
def test_rebatch_parts_groups_the_rows_rebatch_blocks_merges(sizes, batch,
                                                             drop):
    from dmlc_tpu.data.device import rebatch_blocks, rebatch_parts

    blocks = _blocks(sizes, 11)
    merged = list(rebatch_blocks(iter(blocks), batch, drop))
    grouped = list(rebatch_parts(iter(blocks), batch, drop))
    assert [sum(len(p) for p in g) for g in grouped] == \
        [len(m) for m in merged]
    for parts, block in zip(grouped, merged):
        want = parts_to_csr_host([block], 3000, pad_rows_to=batch,
                                 pad_nnz_to=256)
        got = parts_to_csr_host(parts, 3000, pad_rows_to=batch,
                                pad_nnz_to=256)
        assert got[5] == want[5]
        for a, b in zip(got[:5], want[:5]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("values,unit,elided", [
    (True, False, False), (True, True, False), (False, True, True),
    (False, False, False)])
def test_csr_parts_fill_a_used_slot_as_they_fill_a_fresh_one(values, unit,
                                                              elided):
    parts = _blocks([6, 1, 9], 3, values=values)
    fresh = parts_to_csr_host(parts, 3000, pad_rows_to=32, pad_nnz_to=128,
                              unit_values_as_none=unit)
    used = {"cols": np.full(128, -7, np.int32),
            "row_ptr": np.full(33, -7, np.int32),
            "vals": np.full(128, np.nan, np.float32),
            "label": np.full(32, np.nan, np.float32),
            "weight": np.full(32, np.nan, np.float32)}
    again = parts_to_csr_host(parts, 3000, pad_rows_to=32, pad_nnz_to=128,
                              unit_values_as_none=unit, out=used)
    assert (fresh[2] is None) == elided and (again[2] is None) == elided
    assert again[0] is used["cols"] and again[4] is used["weight"]
    for a, b in zip(fresh[:5], again[:5]):
        if a is not None:
            np.testing.assert_array_equal(a, b)
    nnz = sum(len(p.index) for p in parts)
    assert np.all(fresh[0][nnz:] == 3000) and np.all(fresh[1][17:] == nnz)
    assert np.all(fresh[4][16:] == 0) and fresh[4][0] == 0.5 \
        and fresh[4][6] == 1.0
    if not elided:
        assert np.all(fresh[2][nnz:] == 0)
        if not values:
            assert np.all(fresh[2][:nnz] == 1)


def test_a_ring_slot_whose_arrays_all_died_is_free_at_the_next_scan():
    """Four arrays a slot, as the CSR wire attaches: one scan has to take
    them all off (``enumerate`` used to keep the one in hand alive, so a
    slot of n arrays took n scans, and an epoch's first batch missed)."""
    from dmlc_tpu.data.device import _StagingRing

    ring = _StagingRing(lambda: {"a": np.zeros(4, np.float32)}, depth=1)
    bufs = ring.acquire()
    ring.attach(bufs, [jnp.ones(3) * k for k in range(4)])
    assert ring.acquire() is bufs
    assert ring.stats() == {"depth": 1, "hits": 1, "misses": 0}
    held = [jnp.ones(3) * k for k in range(4)]
    ring.attach(bufs, held)
    assert ring.acquire() is not bufs            # pinned: a miss
    del held[2:]
    assert ring.acquire() is not bufs            # two of four still live
    del held
    assert ring.acquire() is bufs
    assert ring.stats() == {"depth": 1, "hits": 2, "misses": 2}


def test_the_csr_ring_outlives_reset_and_follows_the_slot_count(tmp_path):
    path, _ = _corpus(tmp_path)
    it = DeviceIter(create_parser(path + "?format=libsvm"),
                    num_col=GEN["num_features"] + 1, batch_size=128,
                    layout="bcoo", nnz_bucket=64)
    first = _epoch(it)
    ring = it._ring
    assert ring is not None and ring.key == max(len(b[0]) for b in first)
    del first
    before = it.stats()["staging_ring"]
    digests = []
    for _ in range(2):
        digests.append([(float(m.data.sum()), int(m.indices.sum()),
                         float((y * w).sum())) for m, y, w in it])
        it.reset()
    after = it.stats()["staging_ring"]
    assert it._ring is ring                   # the same slots, two resets on
    # the batches were dropped as they came: every one found a slot free
    # (the two epochs' 16, and what the head start of the epoch after them
    # has converted by now: DeviceIter._prestart_next_epoch)
    assert 16 <= after["hits"] - before["hits"] <= 24 \
        and after["misses"] == before["misses"]
    assert digests[0] == digests[1] and len(digests[0]) == 8
    it.close()
    pairs = DeviceIter(create_parser(path + "?format=libsvm"),
                       num_col=GEN["num_features"] + 1, batch_size=128,
                       layout="bcoo", nnz_bucket=64, csr_wire=False)
    _epoch(pairs)
    assert pairs._ring is None                # the pair wire allocates
    pairs.close()


@pytest.mark.parametrize("what,message", [
    ("snapshot", "snapshot= cannot store layout='bcoo'.*slot count differs"),
    ("mesh", "layout='bcoo' takes no mesh=.*flat list of slots")])
def test_bcoo_refusals_name_the_kind_and_the_reason(tmp_path, what, message):
    from dmlc_tpu.parallel import make_mesh

    path, _ = _corpus(tmp_path, rows=10)
    how = (dict(snapshot=str(tmp_path / "s.snap")) if what == "snapshot"
           else dict(mesh=make_mesh(devices=jax.devices()[:2])))
    with pytest.raises(DMLCError, match=message):
        DeviceIter(create_parser(path + "?format=libsvm"), num_col=3001,
                   batch_size=8, layout="bcoo", **how)


@pytest.mark.parametrize("indexing_mode", [0, 1])
def test_both_libsvm_engines_agree_on_the_generators_text(tmp_path,
                                                          indexing_mode):
    """1-based ids and six-digit decimals, as the plain reader has them."""
    from dmlc_tpu import native

    if not native.available():
        pytest.skip("no native engine")
    path, _ = _corpus(tmp_path, rows=400)
    lens, ids, vals, labels = reference.parse_libsvm_rows(path, 400)
    assert ids.min() >= 1 and len({len(b"%.6g" % v) for v in vals}) > 1
    blocks = {}
    for engine in ("native", "python"):
        parser = create_parser(path + f"?format=libsvm&engine={engine}"
                               f"&indexing_mode={indexing_mode}")
        got = list(parser)
        blocks[engine] = (
            np.concatenate([np.diff(b.offset) for b in got]),
            np.concatenate([b.index for b in got]).astype(np.int64),
            np.concatenate([b.value for b in got]),
            np.concatenate([b.label for b in got]))
        parser.close() if hasattr(parser, "close") else None
    for engine, got in blocks.items():
        for g, w in zip(got, (lens, ids - indexing_mode, vals, labels)):
            np.testing.assert_array_equal(g, w, err_msg=engine)


# ---------------- the generator, the costs, the readers ----------------

def test_generator_is_seeded_ragged_distinct_and_one_based(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    sums = gen.generate(GEN, 2_700_000_401, 3000, a, threads=1)
    assert gen.generate(GEN, 2_700_000_401, 3000, b, threads=4) == sums
    assert open(a, "rb").read() == open(b, "rb").read()
    lens, ids, vals, labels = reference.parse_libsvm_rows(a, 3000)
    assert sums["rows"] == 3000 and sums["nnz"] == len(ids) == lens.sum()
    assert lens.min() >= 1 and lens.max() <= 40 and len(set(lens)) > 20
    assert 1 <= ids.min() and ids.max() <= GEN["num_features"]
    ends = np.cumsum(lens)
    for lo, hi, n in zip(ends - lens, ends, lens):
        assert np.all(np.diff(ids[lo:hi]) > 0)
        np.testing.assert_allclose(vals[lo:hi], 1 / np.sqrt(n), rtol=1e-5)
    u = ids.astype(np.uint64)
    assert int(u.sum() % (1 << 32)) == sums["index_sum"]
    assert int(((u * u) % (1 << 32)).sum() % (1 << 32)) \
        == sums["index_sq_sum"]
    assert int(labels.sum()) == sums["label_sum"]


def test_ragged_costs_count_the_real_slots():
    from cellbench import costs, costs_fm_ragged as ragged

    # at B * K slots the step's bytes are the ELL count plus the row ids
    ell = costs.fm_adam_step_min_bytes(54_686_452, 8, 65_536, 16)
    assert ragged.fm_ragged_adam_step_min_bytes(
        54_686_453, 8, 65_536, 65_536 * 16) == ell + 4 * 65_536 * 16
    table = 29_890_097 * 9 * 4
    assert ragged.table_gather_kernel_bytes(29_890_097, 8, 65_536, 1e6) \
        == table + 4e6 + 64e6
    assert ragged.grad_scatter_adam_kernel_bytes(
        29_890_097, 8, 65_536, 1e6) == 6 * table + 96e6 + 4e6


def test_ragged_readers_read_the_books_and_keep_silent_without_them():
    from types import SimpleNamespace

    from cellbench.readers import _ragged, ragged_hbm_roofline_share, \
        ragged_pad_share

    config = {"num_features": 1000, "first_id": 1, "num_factors": 8,
              "batch_size": 64}
    ctx = SimpleNamespace(
        stats_start={"batches": 2, "bcoo": {"nnz": 100, "slots": 128}},
        stats_end={"batches": 4, "bcoo": {"nnz": 1100, "slots": 1408}},
        steps_dispatched=10, adapter=SimpleNamespace(config=config), peaks={"hbm_bytes_per_s": 1e9},
        trace={"step": {"device_s_per_execution": 1e-3}})
    assert ragged_pad_share.read(ctx, {}) == pytest.approx(100 * 280 / 1280)
    assert _ragged.sizes(ctx) == (1002, 8, 64, 100.0)
    assert 0 < ragged_hbm_roofline_share.read(ctx, {}) < 100
    ctx.stats_start = ctx.stats_end = {"batches": 3}      # a parent commit
    assert ragged_pad_share.read(ctx, {}) is None
    assert ragged_hbm_roofline_share.read(ctx, {}) is None


# ---------------- the cell, tiny, through the harness ----------------

def _mirrored(R):
    """``BENCHMARK.json`` with ``kddb_fm`` read as ``tiny_kddb_fm``, in
    memory: ``cellbench/rehearsal.json`` is the benchmark's own file."""
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kddb_fm", "tiny_kddb_fm"))
        return real(*parts)

    return load_json


def test_new_cell_rehearses_correct_on_the_cpu(monkeypatch, capsys):
    from cellbench import run as R
    from cellbench.readers import _program as P

    monkeypatch.setattr(R, "load_json", _mirrored(R))
    P._cache.clear()
    assert R.main(["--workload", "tiny_kddb_fm_bcache", "--seed",
                   "2147483999", "--seconds", "1", "--trace", "1",
                   "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True, [ln for ln in out.splitlines()
                                     if ln.endswith("NOT OK")]
    assert line["failed"] == 0 and line["rehearsal"] is True
    values = {k: v["value"] for k, v in line["metrics"].items()}
    # a CPU run reports what was counted, never a time or a share of one
    assert 0 < values.pop("nnz_pad_share") < 50
    assert values.pop("put_bytes_per_row") > 8 * 7
    assert values and all(v is None for v in values.values()), values
    assert "compilations inside the window: 0 (limit == 0) ok" in out


def test_kddb_fm_keeps_the_published_shapes_and_cuts_rows_alone():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"] if c["name"] == "kddb_fm"]
    assert entry["reduced"] == ["rows"] and len(entry["source"]) <= 200
    cell, = [w for w in bench["workloads"] if w["config"] == "kddb_fm"]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "kddb_fm_bcache", "block_cache_epochs", 1)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    assert (config["num_features"], config["num_factors"], config["dtype"],
            config["format"], config["first_id"], config["layout"]) == (
        29_890_095, 8, "float32", "libsvm", 1, "bcoo")
    assert list(config["reduced"]) == ["rows"] and "max_nnz" not in config
    mine = [m["name"] for m in bench["per_layer"]
            if "kddb_fm_bcache" in m.get("workloads", [])]
    # (22 until PR 50's five walk scopes, two books and step_temp_gb)
    assert len(mine) == 30 and all(
        os.path.exists(os.path.join(root, "cellbench", "metrics",
                                    name + ".json")) for name in mine)
    at_rest = 3 * 4 * 9 * (config["num_features"] + config["first_id"] + 1)
    assert at_rest >= 3 << 30
