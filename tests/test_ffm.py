"""The field-aware factorization machine and the libfm field plane that
feeds it (PR 26): ``FFMLearner`` against the plain reference
(``cellbench/reference/ffm_adagrad.py``, which imports nothing of the
program), ``block_to_ell`` / ``DeviceIter(fields=True)`` against the
parser's field column through every tier, the checked refusals, the
one-table gather's gradient at the learners' widths, and the new cells of
``BENCHMARK.json`` held to ``cellbench/tests/test_contract.py``'s rules at
a tiny size. All on the CPU backend."""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.reference import ffm_adagrad as reference
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.data.row_block import RowBlock
from dmlc_tpu.models.ffm import FFMLearner
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops.sparse import (
    EllBatch, block_to_ell, ell_table_gather, ell_truncated_slots,
)
from dmlc_tpu.utils.check import DMLCError

N, M, F, B, K = 400, 5, 4, 48, 8     # ids, fields, factors, rows, slots


# ---------------- the learner against the plain reference ----------------

def _rows(case: str, seed: int):
    """``(indices, fields, values, labels)`` of one batch; padding slots
    hold id N, field 0, value 0."""
    rng = np.random.default_rng(seed + sum(map(ord, case)))
    idx = rng.integers(0, N, (B, K))
    fld = np.tile(np.arange(K) % M, (B, 1))
    val = rng.uniform(0.5, 2.0, (B, K)).astype(np.float32)
    if case == "a_field_missing":         # no slot of field 2 in any row
        fld = np.where(fld == 2, 3, fld)
    elif case == "two_ids_in_one_field":  # and a row with one field only
        fld[:, 1] = fld[:, 0]
        fld[0] = 4
    elif case == "padding_slots":         # short rows, a row of one slot
        keep = rng.integers(1, K + 1, B)
        keep[0] = 1
        pad = np.arange(K)[None, :] >= keep[:, None]
        idx[pad], fld[pad], val[pad] = N, 0, 0.0
    else:
        assert case == "every_field_once", case
    return idx, fld, val, rng.integers(0, 2, B).astype(np.float32)


def _batch(idx, fld, val, lab) -> EllBatch:
    return EllBatch(jnp.asarray(idx, jnp.int32), jnp.asarray(val),
                    jnp.asarray(lab), jnp.ones(B, jnp.float32),
                    jnp.asarray(fld, jnp.uint8))


CASES = ["every_field_once", "a_field_missing", "two_ids_in_one_field",
         "padding_slots"]


def _libffm_adagrad(learning_rate=0.2):
    """The learner's own chain, as a caller would hand it in: the same
    arithmetic from an optimizer the learner cannot see into."""
    import optax

    return optax.chain(
        optax.scale_by_rss(initial_accumulator_value=1.0, eps=0.0),
        optax.scale(-learning_rate))


def _routed_since(before, counter: str = "table_update_routes"):
    from dmlc_tpu.utils import telemetry

    return {k: v - before.get(k, 0)
            for k, v in getattr(telemetry, counter)().items()
            if v != before.get(k, 0)}


@functools.lru_cache(maxsize=None)
def _three_steps(case: str, zero_fields: bool = False, route: str = "xla"):
    """The program's and the reference's state after three steps.
    ``route``: ``xla`` (this backend's own); under the ``kernels`` fixture
    ``fused`` (the default learner) / ``kernel`` (two passes: the dense
    gradient from the kernel, then the caller's optimizer), both with the
    pair terms on their kernels; under ``pair_kernels`` alone ``pairs``
    (XLA's gather and scatter around the pair terms' kernels)."""
    from dmlc_tpu.utils import telemetry

    batches = [_rows(case, s) for s in range(3)]
    model = FFMLearner(N, M, F, seed=5)
    if route == "kernel":
        model.opt = _libffm_adagrad()
    before = telemetry.table_update_routes()
    pairs_before = telemetry.ffm_interaction_routes()
    (start,) = reference.initial_rows(5, N + 1, M, F, np.arange(N + 1))
    got_start = np.asarray(model.params.w)
    losses = [float(model.step(_batch(
        i, np.zeros_like(f) if zero_fields else f, v, y)))
        for i, f, v, y in batches]
    ref = reference.train(start, batches, 0.2, 2e-5, M, F)
    touched = np.unique(np.concatenate([b[0].ravel() for b in batches]))
    return {"routed": _routed_since(before),
            "pairs_routed": _routed_since(pairs_before,
                                          "ffm_interaction_routes"),
            "structure": jax.tree_util.tree_structure(model.opt_state),
            "start": (got_start, start),
            "loss": (np.asarray(losses), np.asarray([t[0] for t in ref])),
            "w": (np.asarray(model.params.w), ref[-1][1]),
            "g": (np.asarray(model.accumulators), ref[-1][2]),
            "untouched": np.setdiff1d(np.arange(N), touched)}


@pytest.mark.parametrize("leaf", ["start", "loss", "w", "g", "untouched",
                                  "unused_coordinates"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("route", ["xla", "fused", "pairs"])
def test_ffm_three_steps_match_the_plain_reference(request, route, case,
                                                   leaf):
    if route != "xla":
        request.getfixturevalue(
            "kernels" if route == "fused" else "pair_kernels")
    run = _three_steps(case, route=route)
    assert run["routed"] == {"fused" if route == "fused" else "dense": 1}
    # the interaction's route, counted once a traced step
    assert run["pairs_routed"] == {"xla" if route == "xla" else "kernel": 1}
    if leaf == "untouched":       # rows no batch names: bit for bit
        rest = run["untouched"]
        assert rest.size > 10
        assert np.array_equal(run["w"][0][rest], run["start"][1][rest])
        assert np.all(run["g"][0][rest] == 1.0)
        assert not run["w"][0][N].any() and np.all(run["g"][0][N] == 1.0)
        return
    if leaf == "unused_coordinates":
        # a coordinate no pair of any row uses (id i in a slot, field f in
        # another slot of that row) keeps its start and its accumulator's
        # 1, bit for bit, even in a row that other fields touched
        used = np.zeros((N + 1, M), bool)
        for idx, fld, val, _ in (_rows(case, s) for s in range(3)):
            for i, f, x in zip(idx, fld, val):
                for s_ in range(K):
                    for t_ in range(K):
                        if s_ != t_ and x[s_] * x[t_] != 0:
                            used[i[s_], f[t_]] = True
        still = np.repeat(~used, F, axis=1)
        assert 0.05 < still[:N].mean() < 0.99
        assert np.all(run["g"][0][still] == 1.0)
        assert np.array_equal(run["w"][0][still], run["start"][1][still])
        return
    got, want = run[leaf]
    if leaf == "start":
        assert np.array_equal(got, want)
    else:
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max(), leaf


@pytest.mark.parametrize("case", CASES)
def test_ffm_with_every_field_zeroed_is_another_model(case):
    """The control: a learner that drops the plane does not pass."""
    sound, flat = _three_steps(case), _three_steps(case, zero_fields=True)
    assert np.abs(flat["loss"][0] - sound["loss"][1]).max() > 1e-3
    assert np.abs(flat["w"][0] - sound["w"][1]).max() > 1e-2


def test_ffm_takes_a_mesh_and_refuses_a_batch_without_the_plane():
    from dmlc_tpu.parallel import make_mesh

    # PR 32: a mesh deals the table by rows (tests/test_ffm_ps.py)
    dealt = FFMLearner(N, M, F, mesh=make_mesh(devices=jax.devices()[:2]))
    assert dealt.deal.shards == 2 and dealt.params.w.shape[0] == N + 2
    model = FFMLearner(N, M, F)
    with pytest.raises(DMLCError, match="fields=True"):
        model.step(_batch(*_rows("every_field_once", 0))._replace(
            fields=None))


def test_ffm_scopes_and_loop_surface():
    model = FFMLearner(N, M, F, seed=1)
    assert model.device_num_col() == N and model.batch_shardings() is None
    batch = _batch(*_rows("every_field_once", 0))
    model.step(batch)
    names = set(model.hlo_scopes().values())
    for scope in ("ffm_gather", "transpose(jvp(ffm_gather))",
                  "ffm_interaction", "ffm_loss", "ffm_optimizer",
                  "ffm_sink"):
        assert any(scope in n for n in names), scope
    assert model.predict(batch).shape == (B,)


# ---------------- AdaGrad finished inside the kernel (PR 34) ----------------

@pytest.mark.parametrize("leaf", ["loss", "w", "g", "untouched"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("passes", ["kernel", "xla"])
def test_ffm_fused_step_matches_its_two_passes(request, passes, case, leaf):
    """Step for step: the dense gradient handed to the same AdaGrad chain
    against the kernel finishing AdaGrad on ``W`` and ``G`` itself. The
    two passes on the same kernels (``kernel``: the caller's optimizer;
    the pair terms on their kernels on both sides), and with no kernel at
    all (``xla``: the pair terms in plain ``jax.numpy`` too)."""
    want = _three_steps(case) if passes == "xla" else None
    request.getfixturevalue("kernels")
    got = _three_steps(case, route="fused")
    want = want or _three_steps(case, route="kernel")
    assert want["routed"] == {"dense": 1} and got["routed"] == {"fused": 1}
    assert got["pairs_routed"] == {"kernel": 1}
    assert want["pairs_routed"] == {passes: 1}
    if leaf == "untouched":
        rest = want["untouched"]
        for key in ("w", "g"):
            assert np.array_equal(got[key][0][rest], want[key][0][rest])
        return
    scale = np.abs(want[leaf][0]).max()
    assert np.abs(got[leaf][0] - want[leaf][0]).max() <= 2e-6 * scale


def test_ffm_fused_step_keeps_optaxs_state_as_it_is(kernels):
    """The benchmark's adapter and users read ``learner.params.w`` and ``learner.accumulators``: the pytree is
    ``self.opt.init(params)``'s after fused steps too, type for type,
    shape for shape."""
    import optax

    run = _three_steps("padding_slots", route="fused")
    model = FFMLearner(N, M, F, seed=5)
    init = model.opt.init(model.params)
    assert run["structure"] == jax.tree_util.tree_structure(init)
    assert isinstance(init[0], optax.ScaleByRssState)
    model.step(_batch(*_rows("padding_slots", 0)))
    assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(
        model.opt_state)] == [(x.shape, x.dtype)
                              for x in jax.tree_util.tree_leaves(init)]
    assert model.accumulators.shape == model.params.w.shape == (N + 1, M * F)
    assert not np.asarray(model.params.w)[N].any()          # the sink row
    w, g = model.rows(np.arange(3))
    assert np.array_equal(np.asarray(g), np.asarray(model.accumulators)[:3])


def _routed_learner(monkeypatch, on_tpu=True, **kw):
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    return FFMLearner(**dict(dict(num_col=8191, num_fields=M, num_factors=F),
                             **kw))


@pytest.mark.parametrize("name,on_tpu,slots,want", [
    ("the_learners_own_adagrad_on_the_chip", True, 1024,
     ("fused", "adagrad")),
    ("the_cpu", False, 1024, ("dense", "scatter_xla")),
    ("a_table_smaller_than_the_batch", True, 16384,
     ("dense", "scatter_xla")),
    ("a_few_slots", True, 64, ("dense", "scatter_xla")),
])
def test_table_update_route_is_a_function_of_what_the_learner_observes(
        monkeypatch, name, on_tpu, slots, want):
    model = _routed_learner(monkeypatch, on_tpu)
    assert model.table_update_route(slots) == want, name


def test_table_update_route_with_another_optimizer_or_a_mesh(monkeypatch):
    """An optimizer the learner did not build is opaque, whatever its
    arithmetic; a mesh is no reason of its own (PR 40): a dealt table takes
    the route of one chip with a shard's rows and the whole batch's slots."""
    import optax

    from dmlc_tpu.parallel import make_mesh

    model = _routed_learner(monkeypatch)
    assert model.table_update_route(1024) == ("fused", "adagrad")
    model.opt = _libffm_adagrad()
    assert model.table_update_route(1024) == ("dense", "optimizer")
    model.opt = optax.sgd(0.1)
    assert model.table_update_route(1024) == ("dense", "optimizer")
    mesh = make_mesh(devices=jax.devices()[:2])
    dealt = _routed_learner(monkeypatch, mesh=mesh)
    assert dealt.deal.local_rows == 4096
    assert dealt.table_update_route(1024) == ("fused", "adagrad")
    # the dealt array's own rows (the step passes params.w.shape[0])
    assert dealt.table_update_route(1024, dealt.params.w.shape[0]) \
        == ("fused", "adagrad")
    # a shard of 4,096 rows is small against 8,192 gathered slots, where
    # the whole table's 8,192 rows on one chip are not
    assert dealt.table_update_route(8192) == ("dense", "scatter_xla")
    assert _routed_learner(monkeypatch).table_update_route(8192) \
        == ("fused", "adagrad")
    dealt.opt = _libffm_adagrad()
    assert dealt.table_update_route(1024) == ("dense", "optimizer")
    on_cpu = _routed_learner(monkeypatch, on_tpu=False, mesh=mesh)
    assert on_cpu.table_update_route(1024) == ("dense", "scatter_xla")


@pytest.mark.parametrize("on_tpu,want", [
    (True, ("fused", "adagrad")), (False, ("dense", "scatter_xla"))])
def test_a_dealt_table_routes_by_its_shard_at_the_cells_shape(monkeypatch,
                                                              on_tpu, want):
    """kdd12_ffm_ps4 (54,686,453 rows dealt over four chips, 1,048,576
    gathered slots): a chip's 13,671,614 rows route as kdd12_ffm's one
    chip does; the rehearsals on the CPU keep the two passes."""
    from dmlc_tpu.parallel import make_mesh

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    model = FFMLearner(7, 11, 4, mesh=make_mesh(devices=jax.devices()[:4]))
    assert model.table_update_route(65_536 * 16, 54_686_456) == want
    # the toy's own two rows a chip are no table for the kernel
    assert model.table_update_route(65_536 * 16) == ("dense", "scatter_xla")


def test_the_cells_shape_fuses_on_the_chip_and_not_here(monkeypatch):
    """kdd12_ffm (13,671,614 rows of 44 columns, 1,048,576 slots) takes
    the fused route on a TPU; the rehearsals on the CPU stay dense. The
    step routes by the traced table's rows, so a learner built small
    compiles the cell's step (``cellbench/tools/aot_compile_ffm.py``)."""
    from dmlc_tpu.ops.ffm_pairs import ffm_interaction_route

    model = FFMLearner(7, 11, 4)
    assert model.table_update_route(65_536 * 16, 13_671_614) \
        == ("dense", "scatter_xla")
    assert ffm_interaction_route(65_536, jnp.float32) == ("xla", "backend")
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    assert model.table_update_route(65_536 * 16, 13_671_614) \
        == ("fused", "adagrad")
    # the pair terms of the cell's 65,536 rows, and of the 16,384 a chip of
    # kdd12_ffm_ps4 has; the rehearsals' 64 rows fill no block
    assert ffm_interaction_route(65_536, jnp.float32) == ("kernel", "none")
    assert ffm_interaction_route(16_384, jnp.float32) == ("kernel", "none")
    assert ffm_interaction_route(64, jnp.float32) == ("xla", "rows")
    assert model.table_update_route(65_536 * 16) == ("dense", "scatter_xla")


# ---------------- the two layouts of the slot side (PR 47) ----------------

def _tiny_cell_steps(monkeypatch, side: str):
    """``(losses, W, G)`` after three steps of ``FFMLearner`` at the tiny
    cell's shape (cellbench/configs/tiny_ffm.json: 44 columns, 16 slots,
    1,024 rows of its own generator) under the ``kernels`` fixture, the
    kernels' slot side as the payload's width picks it or, ``side ==
    "columns"``, lane-major at every width, which is the parent's program
    (tests/test_table_gather.py and tests/test_grad_scatter.py pin the
    column side's kernels to the parent's jaxprs at 44 columns)."""
    from cellbench.generators import fields_zipf_libfm as gen

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "cellbench", "configs",
                           "tiny_ffm.json")) as f:
        config = json.load(f)
    if side == "columns":
        monkeypatch.setattr(sw, "slot_layout", lambda width: "columns")
    w1, slots = config["num_features"] + 1, config["max_nnz"]
    rows, fields = config["batch_size"], config["num_fields"]
    model = FFMLearner(config["num_features"], fields,
                       config["num_factors"], seed=3)
    losses = []
    for step in range(3):
        ids, labels = gen.draw_rows(config["generator"],
                                    np.random.SeedSequence(step), rows)
        idx = np.full((rows, slots), w1 - 1, np.int32)
        idx[:, :fields] = ids
        idx[step::7, 5:] = w1 - 1               # short rows
        real = idx != w1 - 1
        losses.append(np.asarray(model.step(EllBatch(
            jnp.asarray(idx), jnp.asarray(real.astype(np.float32)),
            jnp.asarray(labels.astype(np.float32)),
            jnp.ones(rows, jnp.float32),
            jnp.asarray(np.where(real, np.arange(slots) % fields,
                                 0).astype(np.uint8))))))
    return (np.asarray(losses), np.asarray(model.params.w),
            np.asarray(model.accumulators))


_TINY_STEPS: dict = {}


@pytest.mark.parametrize("leaf", ["loss", "w", "g"])
def test_the_step_on_lines_is_the_parents_step_bit_for_bit(
        request, monkeypatch, leaf):
    """The whole step of the tiny cell with the wide payload's kernels on
    the line side (the forward's rows leave as lines, the update's
    cotangent rows arrive as lines) against the same step with both on
    the column side between XLA's pads and transposes, as the parent ran
    it: a transposition moves bits, so every loss and every element of
    ``W`` and ``G`` is the same float32."""
    from dmlc_tpu.utils import telemetry

    cache = _TINY_STEPS          # the six steps run once for the three leaves
    if not cache:
        calls = request.getfixturevalue("kernels")
        before = telemetry.table_slot_layouts()
        cache["lines"] = _tiny_cell_steps(monkeypatch, "lines")
        cache["counted"] = _routed_since(before, "table_slot_layouts")
        assert calls["gather"] == calls["scatter"] == 1
        cache["columns"] = _tiny_cell_steps(monkeypatch, "columns")
        assert calls["gather"] == calls["scatter"] == 2
    at = ["loss", "w", "g"].index(leaf)
    got, want = cache["lines"][at], cache["columns"][at]
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    if leaf == "loss":
        assert 0.3 < got[-1] < got[0] < 1.0
        # the width picked the line side for both kernels, once a trace
        assert cache["counted"] == {"gather_lines": 1, "scatter_lines": 1,
                                    "pair_grads_lines": 1}
    else:
        start = 1.0 if leaf == "g" else 0.0
        assert (got != start).sum() > 1000


def test_slot_layout_is_counted_by_op_where_a_step_is_traced(kernels):
    """``table_slot_layout{op=, layout=}``: an ``FFMLearner`` step (44
    columns here as in the cells: over ``PERMUTE_BY_COLUMNS``) counts its
    gather, its scatter and the cotangent rows its pair terms hand back on
    lines, an ``FMLearner(layout="ell")`` step (9 columns) on columns;
    once a traced step, shown by name."""
    from dmlc_tpu.models import FMLearner
    from dmlc_tpu.utils import telemetry

    before = telemetry.table_slot_layouts()
    ffm = FFMLearner(N, 11, F)
    idx, _, val, lab = _rows("every_field_once", 0)
    fld = np.tile(np.arange(K) % 11, (B, 1))
    for _ in range(2):                          # one trace, two steps
        ffm.step(_batch(idx, fld, val, lab))
    assert _routed_since(before, "table_slot_layouts") == {
        "gather_lines": 1, "scatter_lines": 1, "pair_grads_lines": 1}
    before = telemetry.table_slot_layouts()
    fm = FMLearner(N, 8, layout="ell")
    for _ in range(2):
        fm.step(_batch(idx, fld, val, lab)._replace(fields=None))
    assert _routed_since(before, "table_slot_layouts") == {
        "gather_columns": 1, "scatter_columns": 1}
    text = telemetry.render_prometheus()
    for op, layout in (("gather", "lines"), ("scatter", "lines"),
                       ("gather", "columns"), ("scatter", "columns")):
        assert (f'dmlc_tpu_table_slot_layout_total{{layout="{layout}",'
                f'op="{op}"}}' in text)
    assert telemetry.pod_snapshot()["table_slot_layouts"][
        "gather_lines"] >= 1


def test_slot_groups_are_counted_where_a_step_names_its_padding(kernels):
    """``table_slot_groups{op=, groups=}`` (PR 49): both learners' one-chip
    steps tell the table ops which slots are padding, so the forward's
    un-permute and the update's permute run by run, 16 runs where the
    slots divide: counted once a traced step beside ``table_slot_layout``,
    shown by name."""
    from dmlc_tpu.models import FMLearner
    from dmlc_tpu.utils import telemetry

    idx, _, val, lab = _rows("every_field_once", 0)
    fld = np.tile(np.arange(K) % 11, (B, 1))
    groups = np.gcd(B * K, 16)
    for model, batch in ((FFMLearner(N, 11, F), _batch(idx, fld, val, lab)),
                         (FMLearner(N, 8, layout="ell"),
                          _batch(idx, fld, val, lab)._replace(fields=None))):
        before = telemetry.table_slot_groups()
        for _ in range(2):                      # one trace, two steps
            model.step(batch)
        assert _routed_since(before, "table_slot_groups") == {
            f"gather_{groups}": 1, f"update_{groups}": 1}
    text = telemetry.render_prometheus()
    for op in ("gather", "update"):
        assert (f'dmlc_tpu_table_slot_groups_total{{groups="{groups}",'
                f'op="{op}"}}' in text)
    assert telemetry.pod_snapshot()["table_slot_groups"][
        f"gather_{groups}"] >= 2


@pytest.mark.parametrize("route,reason,on_mesh", [
    ("dense", "scatter_xla", False), ("dense", "optimizer", False),
    ("fused", "adagrad", False), ("fused", "adagrad", True),
    ("dense", "scatter_xla", True)],
    ids=["dense-scatter_xla", "dense-optimizer", "fused-adagrad",
         "mesh-fused-adagrad", "mesh-dense-scatter_xla"])
def test_table_update_route_is_counted_once_a_traced_step(request, route,
                                                          reason, on_mesh):
    from dmlc_tpu.parallel import make_mesh
    from dmlc_tpu.utils import telemetry

    if reason != "scatter_xla":
        request.getfixturevalue("kernels")
    mesh = make_mesh(devices=jax.devices()[:2]) if on_mesh else None
    before = telemetry.table_update_routes().get(route, 0)
    scatters = telemetry.grad_scatter_routes().get("kernel", 0)
    owned = telemetry.grad_scatter_routes().get("collective_owned_rows", 0)
    shards = telemetry.table_shard_routes().get("owned_slots", 0)
    pairs_route = "xla" if reason == "scatter_xla" else "kernel"
    pairs = telemetry.ffm_interaction_routes().get(pairs_route, 0)
    model = FFMLearner(N, M, F, mesh=mesh)
    if reason == "optimizer":
        model.opt = _libffm_adagrad()
    for s in range(2):                     # one trace, two steps
        model.step(_batch(*_rows("every_field_once", s)))
    assert telemetry.table_update_routes()[route] == before + 1
    # the pair terms' route beside it: once a traced step, and once more
    # for a traced forward (predict), which updates no table
    assert telemetry.ffm_interaction_routes()[pairs_route] == pairs + 1
    model.predict(_batch(*_rows("every_field_once", 0)))
    assert telemetry.table_update_routes()[route] == before + 1
    assert telemetry.ffm_interaction_routes()[pairs_route] == pairs + 2
    assert (f'dmlc_tpu_table_update_route_total{{reason="{reason}",'
            f'route="{route}"}}' in telemetry.render_prometheus())
    # the fused update is a run of the scatter kernel, counted as one
    assert telemetry.grad_scatter_routes().get("kernel", 0) == scatters + (
        0 if reason == "scatter_xla" else 1)
    # under a mesh the deal's own counters beside it, fused or dense
    assert telemetry.grad_scatter_routes().get(
        "collective_owned_rows", 0) == owned + on_mesh
    assert telemetry.table_shard_routes().get(
        "owned_slots", 0) == shards + on_mesh


def _pallas_call_names(jaxpr) -> list:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _pallas_call_names(sub)
    return names


def _step_kernel_names(model, fields: bool) -> list:
    """The ``pallas_call`` names of ``model``'s step on 64 rows of 8
    slots, in their order (call under ``kernels``)."""
    sds = jax.ShapeDtypeStruct
    batch = EllBatch(sds((64, 8), jnp.int32), sds((64, 8), jnp.float32),
                     sds((64,), jnp.float32), sds((64,), jnp.float32),
                     sds((64, 8), jnp.uint8) if fields else None)
    step_fn, _ = model._step._jit_args
    return _pallas_call_names(jax.make_jaxpr(step_fn)(
        model.params, model.opt_state, batch).jaxpr)


def test_the_fused_kernel_keeps_the_name_the_benchmark_reads(kernels):
    """PR 33 was refused for this and nothing else: the benchmark's
    ``ffm_grad_scatter_kernel_roofline`` finds its operation in a trace by
    the pattern in its metric file, a Pallas kernel's HLO instruction
    carries its ``pallas_call``'s name, the reader gives no value for an
    absent operation, ``cellbench/run.py`` drops a metric with no value
    and the harness refuses a traced line that lacks one. A ``perf_opt``
    PR may not edit the metric file, so the kernel that updates
    kdd12_ffm's table has to answer to the pattern that is there; the
    Adam kernel, which no kernel roofline reads, keeps its own name."""
    import optax

    from dmlc_tpu.models import FMLearner

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           "ffm_grad_scatter_kernel_roofline.json")) as f:
        op = re.compile(json.load(f)["op"])
    fused = FFMLearner(9001, 5, 4)
    assert fused.table_update_route(64 * 8) == ("fused", "adagrad")
    # (the pair terms' two kernels between them: PR 36,
    # tests/test_ffm_pairs.py holds their names to no pattern)
    pairs = ["ffm_pair_terms", "ffm_pair_grads"]
    assert _step_kernel_names(fused, True) \
        == ["table_gather"] + pairs + ["grad_scatter"]
    # as XLA names the instruction in a trace: the name, or name.N
    assert op.search("grad_scatter") and op.search("grad_scatter.1")
    two_passes = FFMLearner(9001, 5, 4)
    two_passes.opt = _libffm_adagrad()
    assert _step_kernel_names(two_passes, True) \
        == ["table_gather"] + pairs + ["grad_scatter"]
    adam = _step_kernel_names(FMLearner(9001, 8, layout="ell"), False)
    assert adam == ["table_gather", "grad_scatter_adam"]
    assert not op.search("grad_scatter_adam.1")
    assert FMLearner(9001, 8, layout="ell",
                     optimizer=optax.adam(0.05)).table_update_route(512) \
        == ("dense", "optimizer")


def test_the_new_kernels_answer_to_no_pattern_of_the_benchmark(kernels):
    """ROADMAP D18 (PR 33 was refused unmeasured for a name): a metric
    file finds its operation in a trace by a pattern over the
    ``pallas_call``'s name. The two kernels this PR adds matched none, and
    the two that were there keep the names those files read. Since PR 55 one
    file reads the pair kernels, both by one pattern, and no other does."""
    import glob

    patterns = {}
    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "cellbench", "metrics", "*.json"))):
        with open(path) as f:
            op = json.load(f).get("op")
        if op:
            patterns[os.path.basename(path)] = re.compile(op)
    assert set(patterns) >= {"ffm_grad_scatter_kernel_roofline.json",
                             "ffm_ps_grad_scatter_kernel_roofline.json"}
    pairs = patterns.pop("ffm_pair_kernels_roofline.json")
    assert _step_kernel_names(FFMLearner(9001, 5, 4), True) == [
        "table_gather", "ffm_pair_terms", "ffm_pair_grads", "grad_scatter"]
    for name in ("ffm_pair_terms", "ffm_pair_grads"):
        # as XLA names the instruction in a trace: the name, or name.N
        for traced in (name, name + ".1"):
            assert not any(p.search(traced) for p in patterns.values()), name
            assert pairs.search(traced), traced
    for name, pattern in patterns.items():
        if name.startswith("ffm"):
            assert pattern.search("grad_scatter.1")
            assert not pattern.search("table_gather.1")
    # PR 37's two read the FM's kernels on a ragged step and not the row
    # sums' kernels, which share their code and not their names
    gather = patterns["fm_ragged_table_gather_kernel_roofline.json"]
    adam = patterns["fm_ragged_grad_scatter_adam_kernel_roofline.json"]
    assert gather.search("table_gather.2") and adam.search(
        "grad_scatter_adam.1")
    for traced in ("slot_rows_sum.1", "slot_rows_take.3", "grad_scatter.1"):
        assert not gather.search(traced) and not adam.search(traced)


# ---------------- the field plane, host side ----------------

def _libfm(path, rows=300, max_len=9, seed=0):
    rng = np.random.default_rng(seed)
    want = []
    with open(path, "w") as f:
        for r in range(rows):
            n = int(rng.integers(1, max_len + 1))
            toks = [(int(rng.integers(0, 11)), int(rng.integers(0, N)),
                     int(rng.integers(1, 4))) for _ in range(n)]
            want.append(toks)
            f.write(f"{r % 2} " + " ".join(
                f"{a}:{b}:{c}" for a, b, c in toks) + "\n")
    return str(path), want


def _epoch(it):
    out = [jax.tree_util.tree_map(np.asarray, b) for b in it]
    it.reset()
    return out


def _same(a, b):
    return len(a) == len(b) and all(
        x.dtype == y.dtype and np.array_equal(x, y)
        for p, q in zip(a, b) for x, y in zip(p, q))


def test_block_to_ell_fields_slot_for_slot():
    block = RowBlock(offset=np.array([0, 2, 2, 5]), label=np.zeros(3),
                     index=np.array([7, 3, 1, 2, 9]),
                     field=np.array([4, 0, 10, 3, 3]))
    ell = block_to_ell(block, num_col=20, max_nnz=2, pad_rows_to=4,
                       fields=True)
    assert ell.fields.dtype == np.uint8 and ell.fields.shape == (4, 2)
    assert ell.fields.tolist() == [[4, 0], [0, 0], [10, 3], [0, 0]]
    assert ell.indices.tolist() == [[7, 3], [20, 20], [1, 2], [20, 20]]
    assert ell_truncated_slots(block, 2) == 1
    assert ell_truncated_slots(block, None) == 0
    plain = block_to_ell(block, num_col=20, max_nnz=2, pad_rows_to=4)
    assert plain.fields is None and len(plain) == 5
    for a, b in zip(plain[:4], ell[:4]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    wide = RowBlock(offset=np.array([0, 1]), label=np.zeros(1),
                    index=np.array([1]), field=np.array([300]))
    assert block_to_ell(wide, 20, fields=True).fields.dtype == np.uint16
    with pytest.raises(DMLCError, match="no field"):
        block_to_ell(RowBlock(offset=np.array([0, 1]), label=np.zeros(1),
                              index=np.array([1])), 20, fields=True)


@pytest.mark.parametrize("engine", ["python", "native"])
def test_device_iter_carries_the_parsers_fields(tmp_path, engine):
    if engine == "native":
        from dmlc_tpu import native

        if not native.available():
            pytest.skip("no native engine here")
    path, want = _libfm(tmp_path / "c.libfm")
    it = DeviceIter(create_parser(path + "?format=libfm", engine=engine),
                    num_col=N, batch_size=64, layout="ell", max_nnz=6,
                    fields=True)
    batches = _epoch(it)
    stats = it.stats()
    it.close()
    cut = sum(max(0, len(t) - 6) for t in want)
    assert stats["ell_truncated_slots"] == cut > 0
    assert stats["field_plane_bytes"] == len(batches) * 64 * 6
    assert stats["bytes_to_device"] == len(batches) * 64 * (6 * 9 + 8)
    fields = np.concatenate([b.fields for b in batches])
    ids = np.concatenate([b.indices for b in batches])
    vals = np.concatenate([b.values for b in batches])
    assert fields.dtype == np.uint8
    for r, toks in enumerate(want):
        toks = toks[:6]
        assert fields[r, :len(toks)].tolist() == [t[0] for t in toks]
        assert ids[r, :len(toks)].tolist() == [t[1] for t in toks]
        assert vals[r, :len(toks)].tolist() == [t[2] for t in toks]
        assert not fields[r, len(toks):].any()
    assert not fields[len(want):].any()      # the tail's padding rows


def test_fields_off_changes_no_byte_of_a_batch(tmp_path):
    path, want = _libfm(tmp_path / "c.libfm")
    kw = dict(num_col=N, batch_size=64, layout="ell", max_nnz=6)
    off = DeviceIter(create_parser(path + "?format=libfm"), **kw)
    on = DeviceIter(create_parser(path + "?format=libfm"), fields=True, **kw)
    a, b = _epoch(off), _epoch(on)
    assert all(x.fields is None for x in a)
    assert _same([x[:4] for x in a], [y[:4] for y in b])
    # the parent's put: int32 index and float32 value a slot, label and
    # weight a row (136.0 B/row at the cells' K = 16)
    assert off.stats()["bytes_to_device"] == len(a) * 64 * (6 * 8 + 8)
    assert off.stats()["field_plane_bytes"] == 0
    assert off.stats()["ell_truncated_slots"] == \
        on.stats()["ell_truncated_slots"] > 0
    assert "fields" not in off._snapshot_geometry()
    assert on._snapshot_geometry()["fields"] is True
    off.close(), on.close()


@pytest.mark.parametrize("tier", ["block_cache", "snapshot"])
def test_a_warm_tier_serves_the_plane_byte_identical(tmp_path, tier):
    path, _ = _libfm(tmp_path / "c.libfm")
    kw = dict(num_col=N, batch_size=64, layout="ell", max_nnz=6, fields=True)
    cold_it = DeviceIter(create_parser(path + "?format=libfm"), **kw)
    cold = _epoch(cold_it)
    cold_it.close()
    it = DeviceIter(create_parser(path + "?format=libfm",
                                  **{tier: str(tmp_path / "tier")}), **kw)
    first = _epoch(it)
    state = []
    warm = []
    for batch in it:
        state.append(it.stats()[
            "cache_state" if tier == "block_cache" else "snapshot_state"])
        warm.append(jax.tree_util.tree_map(np.asarray, batch))
    it.reset()
    assert set(state) == {"warm"}
    assert _same(cold, first) and _same(cold, warm)
    assert warm[0].fields.dtype == np.uint8
    assert it.stats()["field_plane_bytes"] == 2 * len(cold) * 64 * 6
    it.close()
    if tier == "snapshot":
        # the plane is part of the snapshot's geometry: a pipeline without
        # it does not serve this file, it writes its own
        plain = DeviceIter(create_parser(
            path + "?format=libfm", snapshot=str(tmp_path / "tier")),
            **dict(kw, fields=False))
        got = []
        for batch in plain:
            assert plain.stats()["snapshot_state"] == "cold"
            got.append(batch)
        assert got[0].fields is None
        plain.close()


class _ServiceLike:
    """What DeviceIter knows a service client by."""

    def resize_pipeline_depth(self, depth):
        return True


@pytest.mark.parametrize("what", ["no_field_column", "dense", "bcoo",
                                  "service_wire", "four_shardings"])
def test_fields_where_they_cannot_go_is_a_checked_error(tmp_path, what):
    path, _ = _libfm(tmp_path / "c.libfm", rows=20)
    kw = dict(num_col=N, batch_size=8, layout="ell", max_nnz=4, fields=True)
    if what == "no_field_column":
        svm = tmp_path / "c.libsvm"
        svm.write_text("1 3:1 4:2\n0 1:1\n")
        it = DeviceIter(create_parser(str(svm) + "?format=libsvm"), **kw)
        with pytest.raises(DMLCError, match="no field column"):
            _epoch(it)
        it.close()
        return
    source = create_parser(path + "?format=libfm")
    if what in ("dense", "bcoo"):
        kw["layout"] = what
    elif what == "service_wire":
        source = _ServiceLike()
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dmlc_tpu.parallel import make_mesh

        mesh = make_mesh(devices=jax.devices()[:2])
        kw.update(mesh=mesh,
                  shardings=[NamedSharding(mesh, P("data"))] * 4)
    with pytest.raises(DMLCError, match="fields=True"):
        DeviceIter(source, **kw)


# ---------------- one op, any width ----------------

@pytest.mark.parametrize("width", [1, 9, 44])
@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_dense_table_grad_of_one_table_matches_scatter_add(
        monkeypatch, route, width):
    real = gs.grad_scatter_pallas
    monkeypatch.setattr(gs, "grad_scatter_pallas", lambda *a, **kw: real(
        *a, **dict(kw, interpret=True)))
    monkeypatch.setattr(gs, "grad_scatter_route", lambda *a: route)
    rows = 1000
    rng = np.random.default_rng(width)
    idx = jnp.asarray(rng.integers(0, rows, (40, 6)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(40, 6, width)), jnp.float32)
    want = np.zeros((rows, width))
    np.add.at(want, np.asarray(idx), np.asarray(g, np.float64))
    (got,) = gs.dense_table_grad(idx, (g,), rows)
    assert got.shape == (rows, width)
    assert np.abs(np.asarray(got) - want).max() <= 2e-6 * np.abs(want).max()
    untouched = np.setdiff1d(np.arange(rows), np.asarray(idx))
    assert not np.asarray(got)[untouched].any()
    # and through the op: one table's gradient, the table's shape
    table = jnp.asarray(rng.normal(size=(rows, width)), jnp.float32)
    (dt,) = jax.grad(lambda t: jnp.sum(
        ell_table_gather(t, idx)[0] * g))((table,))
    assert np.abs(np.asarray(dt) - want).max() <= 2e-6 * np.abs(want).max()


@pytest.mark.parametrize("trailing,starts", [
    (((), (8,)), (8, 0)),          # the FM's (w, v): v in rows 0..7, w in 8
    (((44,),), (0,)),              # the field-aware FM's one table
    (((), (1,)), (0, 1)),          # a tie keeps the tables' own order
    (((2,), (), (16,)), (16, 18, 0))])
def test_payload_columns_lie_widest_table_first(trailing, starts):
    assert sw.column_starts(trailing) == starts


def test_route_counter_carries_the_payloads_width():
    from dmlc_tpu.utils import telemetry

    model = FFMLearner(N, M, F)
    before = telemetry.grad_scatter_routes().get("xla", 0)
    model.step(_batch(*_rows("every_field_once", 0)))
    assert telemetry.grad_scatter_routes()["xla"] == before + 1
    assert (f'dmlc_tpu_grad_scatter_route_total{{collective="none",'
            f'route="xla",width="{M * F}"}}') in telemetry.render_prometheus()


def test_kernel_route_is_taken_at_the_ffm_cells_shape(monkeypatch):
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    assert gs.grad_scatter_route(13_671_614, 65_536 * 16, 44,
                                 jnp.float32) == "kernel"


# ---------------- the new cells, by the contract's rules ----------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
NEW_CELLS = ["kdd12_ffm_text", "kdd12_fm_bcache"]
NEW_METRICS = ["ffm_gather_device_ms", "ffm_grad_scatter_device_ms",
               "ffm_optimizer_device_ms", "ffm_adagrad_step_roofline",
               "ffm_grad_scatter_kernel_roofline",
               "field_plane_bytes_per_row"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_new_cell_resolves_to_files_by_the_contracts_rules(bench, cell):
    from cellbench import run as R

    entry = {w["name"]: w for w in bench["workloads"]}[cell]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] == 1 and 1 <= len(entry["why"]) <= 200
    _, config, traffic, layer, end = R.find_cell(cell, False)
    assert config["chips"] == 1
    R.plugin("feeds", traffic["feed"])
    learner = R.plugin("learners", config["learner"])
    for name in ("Adapter", "reference_digest", "compare",
                 "control_numbers"):
        assert hasattr(learner, name)
    R.plugin("generators", config["generator"]["name"])
    assert {"loss_gap", "grad_norm_gap", "update_norm_gap",
            "untouched_gap"} <= set(config["limits"])
    assert config["limits"]["untouched_gap"] == 0.0
    declared = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    assert set(declared["reduced"]) == set(config["reduced"])
    assert len(end) == 3 and len(layer) >= 9
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for m in layer:
        spec = R.load_json(R.HERE, "metrics", m["name"] + ".json")
        assert hasattr(R.plugin("readers", spec["reader"]), "read")
        assert m["layer"] in perf
        assert m["moves"] in ("rows_per_s", "setup_s")


def test_new_entries_are_appended_and_lawful(bench):
    # PR 26's entries stand where that PR appended them: after the three
    # cells, two configurations and seventeen metrics it found (later PRs
    # append after them in turn)
    assert [w["name"] for w in bench["workloads"]][3:5] == NEW_CELLS
    ffm = bench["configs"][2]
    assert ffm["name"] == "kdd12_ffm"
    metrics = bench["per_layer"][17:17 + len(NEW_METRICS)]
    assert [m["name"] for m in metrics] == NEW_METRICS
    assert set(ffm) == {"name", "source", "file", "reduced", "why"}
    assert sum(w["chips"] == 4 for w in bench["workloads"][:6]) == 1
    for m in metrics:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # (PR 32 appended its two cells of the same learner to some lists,
        # PR 41 its one, PR 45 its one, PR 48 its one, PR 55 its one)
        assert NAME.match(m["name"]) and m["workloads"][0] == "kdd12_ffm_text"
        assert set(m["workloads"][1:]) <= {"kdd12_ffm_ps4_text",
                                           "kdd12_ffm_bcache",
                                           "kdd12_ffm_ckpt_bcache",
                                           "kdd12_ffm_rand_bcache",
                                           "kdd12_ffm_csv_text",
                                           "criteo_ffm_csv_text"}
        assert ("roofline" in m["name"]) == (m["unit"] == "%")
    for text in [ffm["source"], ffm["why"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_kdd12_ffm_states_its_widths_and_its_cuts():
    with open(os.path.join(ROOT, "cellbench/configs/kdd12_ffm.json")) as f:
        config = json.load(f)
    assert (config["num_fields"], config["num_factors"], config["max_nnz"],
            config["batch_size"]) == (11, 4, 16, 65_536)
    assert config["optimizer"] == "adagrad" and config["fields"] is True
    assert (config["learning_rate"], config["l2"]) == (0.2, 2e-5)
    assert config["source_num_features"] == 54_686_452
    assert config["num_features"] == 54_686_452 // 4 == \
        config["generator"]["num_features"]
    assert set(config["reduced"]) == {"num_features", "rows"}
    for key in ("source", "deployment", "assumed", "guarantees"):
        assert config[key]
    # at rest: the table and its accumulators, over the 4 GiB floor
    at_rest = 2 * (config["num_features"] + 1) * 44 * 4
    assert at_rest >= 4 << 30


def _mirrored(R):
    """``BENCHMARK.json`` with every ``kdd12_`` name read as ``tiny_``, in
    memory: the rehearsal's file is the benchmark's own and is left as it
    is."""
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kdd12_", "tiny_"))
        return real(*parts)

    return load_json


@pytest.mark.parametrize("cell,trace", [("tiny_ffm_text", 0),
                                        ("tiny_ffm_text", 1),
                                        ("tiny_fm_bcache", 0)])
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
    values = {k: v["value"] for k, v in line["metrics"].items()}
    if trace:
        # a CPU run reports what was counted, never a time or a share: one
        # byte of field beside 8.5 of the rest a slot (the puts run a few
        # batches ahead of the steps when the window closes inside an epoch)
        plane = values.pop("field_plane_bytes_per_row")
        assert 16.0 <= plane < 20.0
        assert values.pop("put_bytes_per_row") == pytest.approx(9.5 * plane)
    assert all(v is None for v in values.values()), values


@pytest.mark.parametrize("control", ["bfloat16", "zero_fields"])
@pytest.mark.parametrize("seed", [2_147_483_999, 5])
def test_each_control_fails_a_limit_and_the_reference_passes(
        tmp_path, control, seed):
    from cellbench import run as R
    from cellbench.generators import fields_zipf_libfm as gen
    from cellbench.learners import ffm

    config = R.load_json(R.HERE, "configs", "tiny_ffm.json")
    corpus = str(tmp_path / "c.libfm")
    gen.generate(config["generator"], seed, 3 * config["batch_size"], corpus)
    ref = ffm.reference_digest(config, seed, corpus)
    numbers = ffm.control_numbers(config, seed, corpus, ref)
    prefix = "" if control == "bfloat16" else "zero_fields."
    over = {k: numbers[prefix + k] for k, lim in config["limits"].items()
            if numbers[prefix + k] > lim}
    assert over, numbers
    if control == "zero_fields":
        assert numbers["zero_fields.untouched_gap"] == 0.0
    same = ffm.compare(ref, ref["losses"], ref["grad_norms"],
                       ref["update_norms"], ref["touched"],
                       {"w": ref["untouched_w"],
                        "g": np.ones_like(ref["untouched_w"])})
    assert all(same[k] <= lim for k, lim in config["limits"].items()), same


def test_ffm_costs_count_what_the_docstrings_say():
    from cellbench import costs_ffm

    step = costs_ffm.ffm_adagrad_step_min_bytes(11, 4, 65_536, 16)
    assert step == 6 * 65_536 * 16 * 44 * 4 + 65_536 * 16 * 9 + 65_536 * 8
    kernel = costs_ffm.ffm_grad_scatter_kernel_bytes(
        13_671_613, 11, 4, 65_536, 16)
    assert kernel == (13_671_614 * 44 * 4 + 3 * 48 * 65_536 * 16 * 2
                      + 65_536 * 16 * 4)
