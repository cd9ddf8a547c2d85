"""The main path's kernel compiled at the benchmark's real widths for a
described TPU v5e, with no chip: what Mosaic refuses (tile alignment,
VMEM, SMEM) shows here at no chip time. Nothing runs. The topology is
described inside a fixture, in the test's own process, and every such test
lives in this one file (only one process may hold the TPU's library)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.ops import sorted_walk as sw
from dmlc_tpu.ops import table_exchange as tx
from dmlc_tpu.ops import table_gather as tg

# W + 1 rows and the tables after the id axis: kdd12_fm's linear column and
# libFM's 8 factors; kdd12_ffm's one table of 11 fields x 4 factors
SHAPES = {"fm": (54_686_453, ((), (8,))), "ffm": (13_671_614, ((44,),)),
          # kddb_fm's tables, read by a ragged batch's 1,966,080 flat slots
          "fm_ragged": (29_890_097, ((), (8,)))}


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("learner,slots", [
    ("fm", 65_536 * 16), ("fm", 16_384 * 16), ("ffm", 65_536 * 16)],
    ids=["one_chip_batch", "one_shard_of_four", "ffm_one_chip_batch"])
def test_grad_scatter_kernel_compiles_at_the_cells_shape(one_chip, learner,
                                                         slots):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    num_rows, trailing = SHAPES[learner]
    width = sum(t[0] if t else 1 for t in trailing)
    rows = 3 * (-(-width // 16) * 16)
    # the payload as the backward lays one of this width (PR 47): three
    # bfloat16 parts of lane-major columns, or float32 lines
    payload = (sds((slots, sw.line_lanes(width)), jnp.float32)
               if sw.slot_layout(width) == "lines" else
               sds((rows, slots), jnp.bfloat16))
    assert (learner == "ffm") == (payload.dtype == jnp.float32)
    compiled = jax.jit(lambda b, i, p: gs.grad_scatter_pallas(
        b, i, p, num_rows=num_rows, trailing=trailing)).lower(
        sds((2, slots // sw.CHUNK_SLOTS + 1), jnp.int32),
        sds((1, slots), jnp.int32), payload,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # lane-major outputs and nothing else of the table's size: no zero
    # fill, no re-layout
    for tail in trailing:
        assert f"f32[{','.join(map(str, tail + (num_rows,)))}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("learner,slots", [
    ("fm", 65_536 * 16), ("fm", 16_384 * 16), ("ffm", 65_536 * 16),
    ("fm_ragged", 65_536 * 30)],
    ids=["one_chip_batch", "one_shard_of_four", "ffm_one_chip_batch",
         "ragged_batch"])
def test_table_gather_kernel_compiles_at_the_cells_shape(one_chip, learner,
                                                         slots):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    num_rows, trailing = SHAPES[learner]
    width = sum(t[0] if t else 1 for t in trailing)
    layout = sw.slot_layout(width)
    compiled = jax.jit(lambda b, i, *t: tg.table_gather_pallas(
        b, i, *t, num_rows=num_rows, trailing=trailing,
        layout=layout)).lower(
        sds((2, slots // sw.CHUNK_SLOTS + 1), jnp.int32),
        sds((1, slots), jnp.int32),
        *(sds(tail + (num_rows,), jnp.float32) for tail in trailing),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # (PR 47) the field-aware FM's 44 columns leave as lines
    assert (learner == "ffm") == (layout == "lines")
    assert (f"f32[{slots},{sw.line_lanes(width)}]" if layout == "lines"
            else f"f32[{-(-width // 16) * 16},{slots}]") in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("learner", ["fm", "ffm"])
def test_kernel_forward_reads_the_tables_in_place(one_chip, learner,
                                                  monkeypatch):
    """The whole forward on the kernel route at the cell's shape, routed
    by the module's own cost model: the tables reach the kernel lane-major
    through a bitcast, nothing of a table's size is copied or transposed,
    and the slots are sorted twice (ids, then the positions back)."""
    import re

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    num_rows, trailing = SHAPES[learner]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda i, *t: tg.table_rows(t, i)[0]).lower(
        sds((65_536, 16), jnp.int32),
        *(sds((num_rows,) + tail, jnp.float32) for tail in trailing),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    made = [ln.split(" = ", 1)[1] for ln in text.splitlines()
            if " = " in ln and str(num_rows) in ln.split(" = ", 1)[1]
            .split("(")[0]]
    assert made and all(re.match(r"f32\[[\d,]+\]\S* (parameter|bitcast)\(",
                                 m) for m in made), made
    assert len(re.findall(r" sort\(", text)) == 2


def test_four_chip_backward_gathers_rows_at_the_cells_shape(topo,
                                                            monkeypatch):
    """The dense gradient of kdd12_fm_dp4's tables laid by rows over the
    described 2x2 mesh (PR 54: the step of a caller's optimizer would make
    it, were its scatter the kernel's): every chip all-gathers the slots'
    ids and cotangent rows, K-major over the whole batch, and builds the
    gradient of its shard from the ones it owns; nothing of the tables'
    size is made or crosses."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dmlc_tpu.parallel import RowRanges

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    num_rows, _ = SHAPES["fm"]
    deal = RowRanges(num_rows, 4)
    b, k, f = 65_536, 16, 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(
            mesh, P(None, "data", *(None,) * (len(shape) - 2))))

    slots = P(None, "data")
    text = jax.jit(jax.shard_map(
        lambda i, g_w, g_v: gs.dense_table_grad(
            i, (g_w, g_v), deal.local_rows, deal=deal),
        mesh=mesh, in_specs=(slots, slots, P(None, "data", None)),
        out_specs=(P("data"), P("data", None)), check_vma=False)).lower(
        sds((k, b), jnp.int32), sds((k, b), jnp.float32),
        sds((k, b, f), jnp.float32)).compile().as_text()
    made = {op: " ".join(
        ln.split(f" {op}", 1)[0] for ln in text.splitlines()
        if re.search(rf" {op}(-start)?\(", ln))
        for op in ("all-gather", "all-reduce", "all-to-all")}
    assert f"s32[{k},{b}]" in made["all-gather"], made
    assert f"f32[{f + 1},{k},4,{b // 4}]" in made["all-gather"], made
    assert not made["all-reduce"] and not made["all-to-all"], made
    for n in (num_rows, deal.padded_rows):
        assert str(n) not in text
    assert "tpu_custom_call" in text
    assert f"f32[{f},{deal.local_rows}]" in text


# ---------------- the Adam epilogue (PR 31) ----------------

ADAM = gs.AdamEpilogue(0.05)


def _made_at_table_size(text, num_rows):
    """The operation of every instruction of a compiled module that makes
    an array (or a tuple holding one) with ``num_rows`` in its shape."""
    import re

    ops = []
    for ln in text.splitlines():
        made = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (?P<type>.*?[\]})]) "
                        r"(?P<op>[a-z][\w\-]*)\(", ln)
        if made and str(num_rows) in made["type"]:
            ops.append(made["op"])
    return ops


# what may make an array of a table's size where the update runs in place
IN_PLACE = {"parameter", "bitcast", "custom-call", "get-tuple-element",
            "tuple"}


def _fm_state(sds, num_rows, f):
    """``((w, m, n), (v, m, n))`` as the learner holds them."""
    return tuple(tuple(sds(shape, jnp.float32) for _ in range(3))
                 for shape in ((num_rows,), (num_rows, f)))


def test_fused_update_runs_in_place_at_the_cells_shape(one_chip,
                                                       monkeypatch):
    """kdd12_fm's update on one chip, routed by the module's own cost
    model, with the state donated as the train loop donates it: the
    kernel with the epilogue is there, all six tables reach it and leave
    it through bitcasts (no ``copy``, no ``transpose``, no dense gradient:
    nothing else of a table's size is made), every one aliased to its
    operand, and the temporaries are the sorted slots' only."""
    import re

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    num_rows, _ = SHAPES["fm"]
    b, k, f = 65_536, 16, 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda state, bias, i, g_w, g_v: gs.fused_table_update(
            i, (g_w, g_v), state, bias, ADAM),
        donate_argnums=0).lower(
        _fm_state(sds, num_rows, f), sds((2,), jnp.float32),
        sds((b, k), jnp.int32), sds((b, k), jnp.float32),
        sds((b, k, f), jnp.float32)).compile()
    text = compiled.as_text()
    assert "grad_scatter_adam" in text and "tpu_custom_call" in text
    made = _made_at_table_size(text, num_rows)
    assert "custom-call" in made and set(made) <= IN_PLACE, made
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 3 * 4 * num_rows * (f + 1)
    assert memory.temp_size_in_bytes < (512 << 20)


ADAGRAD = gs.AdaGradEpilogue(0.2)


def test_ffm_fused_update_runs_in_place_at_the_cells_shape(one_chip,
                                                           monkeypatch):
    """kdd12_ffm's update on one chip (PR 34), routed by the module's own
    cost model, the state donated: the kernel with the AdaGrad epilogue
    is there under the name the benchmark's kernel roofline reads, ``W``
    and ``G`` reach it and leave it through bitcasts and are aliased to
    their operands, and no 2.41 GB gradient is among the temporaries."""
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    num_rows, ((width,),) = SHAPES["ffm"]
    b, k = 65_536, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table = sds((num_rows, width), jnp.float32)
    compiled = jax.jit(
        lambda state, i, g: gs.fused_table_update(
            i, (g,), state, None, ADAGRAD),
        donate_argnums=0).lower(
        ((table, table),), sds((k, b), jnp.int32),
        sds((k, b, width), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grad_scatter_adam" not in text
    assert any(" custom-call(" in ln and "grad_scatter" in ln
               for ln in text.splitlines())
    made = _made_at_table_size(text, num_rows)
    assert "custom-call" in made and set(made) <= IN_PLACE, made
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * 4 * num_rows * width
    # the permute's rows padded to 128 lanes, before and after (2 x 537
    # MB), and the sorted slots
    assert memory.temp_size_in_bytes < 4 * num_rows * width // 2


def _laid_fm_step_compiled(topo):
    """``kdd12_fm_dp4``'s whole step compiled for the four described chips:
    the learner at a toy size on four of the CPU's devices for its
    functions, then the described mesh and the cell's rows in the toy's
    place."""
    from dmlc_tpu.models import FMLearner
    from dmlc_tpu.ops.sparse import EllBatch
    from dmlc_tpu.parallel.mesh import make_mesh

    b, k, f = 65_536, 16, 8
    model = FMLearner(num_col=63, num_factors=f, layout="ell",
                      mesh=make_mesh(devices=jax.devices()[:4]))
    toy = model.deal.padded_rows
    model.num_col, model.weight_dim = 54_686_452, 54_686_453
    model._lay_over(make_mesh(devices=topo.devices[:4]))
    params_sh, opt_sh, batch_sh, _ = model._shardings
    step_fn, options = model._build_step()._jit_args
    sds = jax.ShapeDtypeStruct

    def at_size(x, sh):
        shape = (model.deal.padded_rows,) + x.shape[1:] \
            if x.ndim and x.shape[0] == toy else x.shape
        return sds(shape, x.dtype, sharding=sh)

    batch = EllBatch(
        sds((b, k), jnp.int32, sharding=batch_sh.indices),
        sds((b, k), jnp.float32, sharding=batch_sh.values),
        sds((b,), jnp.float32, sharding=batch_sh.label),
        sds((b,), jnp.float32, sharding=batch_sh.weight))
    return model, jax.jit(step_fn, **options).lower(
        jax.tree_util.tree_map(at_size, model.params, params_sh),
        jax.tree_util.tree_map(at_size, model.opt_state, opt_sh),
        batch).compile()


def test_four_chip_fused_update_gathers_rows_at_the_cells_shape(
        topo, monkeypatch):
    """kdd12_fm_dp4_bcache's whole step on the described 2x2 mesh (PR 54:
    the tables and moments laid by rows, 13,671,614 a chip): both kernels
    run on a chip's shard, ``grad_scatter_adam`` on six operands of the
    shard's rows, every one aliased to its operand; the slots' ids, the
    rows home and the cotangent rows cross, K-major over the whole batch;
    no buffer of the whole tables' rows is on a chip, none of a shard's
    size crosses, and every slot is sorted once (and the sort inverted
    once) a step."""
    import re

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    model, compiled = _laid_fm_step_compiled(topo)
    text, deal = compiled.as_text(), model.deal
    b, k, f = 65_536, 16, 8
    assert deal.local_rows == 13_671_614 and deal.padded_rows == 54_686_456
    # (in no shape: the padded count is the id a slot that is not real
    # crosses the chips as)
    assert not re.search(
        rf"[\[,]({deal.num_rows}|{deal.padded_rows})[\],]", text)
    (adam,) = [ln for ln in text.splitlines()
               if " custom-call(" in ln and "grad_scatter_adam" in ln]
    assert adam.split(" custom-call(")[0].count(
        f"[{deal.local_rows}]") == 3 and adam.split(" custom-call(")[0].count(
        f"[{f},{deal.local_rows}]") == 3
    assert any(" custom-call(" in ln and "table_gather" in ln
               and f"f32[16,{b * k}]" in ln for ln in text.splitlines())
    made = _made_at_table_size(text, deal.local_rows)
    assert set(made) <= IN_PLACE | {"fusion", "dynamic-update-slice"}, made
    crossed = {op: " ".join(
        ln.split(f" {op}", 1)[0] for ln in text.splitlines()
        if re.search(rf" {op}(-start)?\(", ln))
        for op in ("all-gather", "all-to-all", "all-reduce",
                   "reduce-scatter", "collective-permute")}
    assert f"s32[{k},{b}]" in crossed["all-gather"], crossed
    assert f"f32[{f + 1},{k},4,{b // 4}]" in crossed["all-gather"], crossed
    assert f",{f + 1},{k},4,{b // 4}]" in crossed["all-to-all"], crossed
    assert not crossed["reduce-scatter"] \
        and not crossed["collective-permute"], crossed
    assert str(deal.local_rows) not in " ".join(crossed.values()), crossed
    assert not re.search(r"\[\d{3,}", crossed["all-reduce"]), crossed
    sorts = [ln for ln in text.splitlines() if re.search(r" sort\(", ln)]
    assert len(sorts) == 2 and all("walk_sort" in ln for ln in sorts)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 3 * 4 * deal.local_rows * (f + 1)
    assert memory.temp_size_in_bytes < (512 << 20)


# ---------------- a table dealt by rows (PR 32) ----------------

def test_four_chip_dealt_gather_and_scatter_at_the_cells_shape(topo,
                                                               monkeypatch):
    """kdd12_ffm_ps4_text's forward and backward on the described 2x2
    mesh: libffm's whole table dealt by rows, 13,671,614 a chip. On the
    road of a step whose slots fit (PR 42) the rows' ids go to their owners
    in buckets of 81,920, both kernels run on a chip's shard over the
    327,680 slots it received, and the rows and the cotangent rows cross as
    all-to-alls of lane-major ``[4, 44, 81920]`` blocks; on the other road
    every chip's slots are all-gathered as they were; nothing of the whole
    table's size is on a chip, and nothing of a shard's size crosses the
    chips."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dmlc_tpu.ops.sparse import ell_table_gather
    from dmlc_tpu.ops.table_exchange import capacity
    from dmlc_tpu.parallel.mesh import RowDeal

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    deal = RowDeal(54_686_453, 4)
    k, b, width = 16, 65_536, 44
    table = jax.ShapeDtypeStruct((deal.padded_rows, width), jnp.float32,
                                 sharding=deal.sharding(mesh))
    batch = NamedSharding(mesh, P(None, "data"))

    def on_chip(w, idx, c):
        def f(w):
            (rows,) = ell_table_gather((w,), idx, deal)
            return jnp.sum(rows * c), rows

        (_, rows), grad = jax.value_and_grad(f, has_aux=True)(w)
        return grad, rows

    compiled = jax.jit(jax.shard_map(
        on_chip, mesh=mesh,
        in_specs=(P("data", None), P(None, "data"), P(None, "data", None)),
        out_specs=(P("data", None), P(None, "data", None)),
        check_vma=False)).lower(
        table, jax.ShapeDtypeStruct((k, b), jnp.int32, sharding=batch),
        jax.ShapeDtypeStruct((k, b, width), jnp.float32,
                             sharding=NamedSharding(
                                 mesh, P(None, "data", None)))).compile()
    text = compiled.as_text()
    # table_gather, grad_scatter: a road each
    assert text.count("tpu_custom_call") >= 4
    assert f"f32[{width},{deal.local_rows}]" in text
    for n in (deal.num_rows, deal.padded_rows):
        assert str(n) not in text
    crossed = {op: " ".join(
        ln.split(f" {op}", 1)[0] for ln in text.splitlines()
        if re.search(rf" {op}(-start)?\(", ln))
        for op in ("all-gather", "all-to-all", "all-reduce")}
    slots, cap = k * b, capacity(k * b // 4, 4)
    assert cap == 81_920
    assert f"s32[4,1,{cap}]" in crossed["all-to-all"], crossed
    assert crossed["all-to-all"].count(f"f32[4,{width},{cap}]") == 2, crossed
    assert f"s32[{slots}]" in crossed["all-gather"], crossed
    block = f"f32[4,{width},{slots // 4}]"
    assert block in crossed["all-gather"], crossed
    assert block in crossed["all-to-all"], crossed
    assert str(deal.local_rows) not in " ".join(crossed.values()), crossed
    # the buckets, the slots received and the two ways back to batch order;
    # the backward sorts nothing. (The other road: the forward's two, and
    # its backward sorts again)
    sorts = [ln for ln in text.splitlines() if re.search(r" sort\(", ln)]
    assert len(sorts) == 7 and sum("branch_1_fun" in ln for ln in sorts) == 3
    assert not any("transpose(" in ln and "branch_0_fun" in ln
                   for ln in sorts)
    assert f"f32[{slots},128]" in text and f"f32[{4 * cap},128]" in text
    assert not any(f"[{slots}," in ln.split(", metadata=")[0]
                   for ln in text.splitlines() if "branch_0_fun" in ln)
    # a shard's gradient and the gathered slots' rows: well inside a chip
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes + stats.output_size_in_bytes < 8 << 30


def _dealt_step_compiled(topo, two_passes: bool):
    """``kdd12_ffm_ps4``'s whole step compiled for the four described chips
    (what ``cellbench/tools/aot_compile_ffm_ps4.py`` does): the learner at
    a toy size on four of the CPU's devices for its functions, then the
    described mesh and the cell's rows in the toy's place. ``two_passes``:
    the learner's own chain handed in from outside, so the step keeps the
    shard's dense gradient and optax's sweep, as the dealt step did until
    PR 40."""
    import optax

    from dmlc_tpu.models import FFMLearner
    from dmlc_tpu.ops.sparse import EllBatch
    from dmlc_tpu.parallel.mesh import make_mesh

    m, f, b, k = 11, 4, 65_536, 16
    model = FFMLearner(num_col=63, num_fields=m, num_factors=f,
                       mesh=make_mesh(devices=jax.devices()[:4]))
    if two_passes:
        model.opt = optax.chain(
            optax.scale_by_rss(initial_accumulator_value=1.0, eps=0.0),
            optax.scale(-0.2))
    model.num_col, model.weight_dim = 54_686_452, 54_686_453
    model._deal_over(make_mesh(devices=topo.devices[:4]))
    params_sh, opt_sh, batch_sh, _ = model._shardings
    step_fn, options = model._build_step()._jit_args
    sds = jax.ShapeDtypeStruct
    table = sds((model.deal.padded_rows, m * f), jnp.float32,
                sharding=params_sh.w)
    opt_state = jax.tree_util.tree_map(
        lambda x, sh: sds(table.shape if x.ndim == 2 and x.shape[1] == m * f
                          else x.shape, x.dtype, sharding=sh),
        model.opt_state, opt_sh)
    shapes = dict(indices=((b, k), jnp.int32), values=((b, k), jnp.float32),
                  label=((b,), jnp.float32), weight=((b,), jnp.float32),
                  fields=((b, k), jnp.uint8))
    batch = EllBatch(**{name: sds(*shapes[name],
                                  sharding=getattr(batch_sh, name))
                        for name in shapes})
    return jax.jit(step_fn, **options).lower(
        type(model.params)(w=table), opt_state, batch).compile(), model.deal


def _crossed(text):
    """``[(operation, result type)]`` of every collective of a module."""
    import re

    return sorted(
        (c["op"], c["type"]) for c in (re.match(
            r"\s*(?:ROOT )?%?[\w.\-]+ = (?P<type>.*?[\]})]) (?P<op>all-gather|"
            r"all-reduce|reduce-scatter|all-to-all|collective-permute)"
            r"(-start)?\(", ln) for ln in text.splitlines()) if c)


def test_four_chip_dealt_step_updates_its_shard_in_place_at_the_cells_shape(
        topo, monkeypatch):
    """kdd12_ffm_ps4_text's whole step on the described 2x2 mesh (PR 40),
    every route the chip's: the four kernels, the two of the table once a
    road (PR 42: the slots a chip owns; every chip's slots), one of them
    ``grad_scatter`` under the name the cell's kernel roofline reads;
    nothing of a shard's size is made but the donated ``W`` and ``G`` on
    their way through the ``lax.cond`` and the kernel and the sink's row
    written in place (no gradient, no sweep, no copy of either); the
    collectives are the two-pass step's and no other; and the compiler's
    temporaries a chip lie under that step's by most of the shard's
    gradient."""
    import re

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    fused, deal = _dealt_step_compiled(topo, two_passes=False)
    text = fused.as_text()
    calls = [ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert sorted(calls) == ["ffm_pair_grads", "ffm_pair_terms",
                             "grad_scatter", "grad_scatter",
                             "table_gather", "table_gather"]
    made = _made_at_table_size(text, deal.local_rows)
    assert made.count("custom-call") == 2, made
    assert set(made) <= IN_PLACE | {"dynamic-update-slice",
                                    "conditional"}, made
    for n in (deal.num_rows, deal.padded_rows):
        assert str(n) not in text
    # (the update sorts nothing on the road of the owned slots)
    assert len(re.findall(r" sort\(", text)) == 7
    dense, _ = _dealt_step_compiled(topo, two_passes=True)
    dense_text = dense.as_text()
    assert "fusion" in _made_at_table_size(dense_text, deal.local_rows)
    slots, width = 65_536 * 16, 44
    block = f"f32[4,{width},{slots // 4}]"
    crossed = _crossed(text)
    assert [op for op, _ in crossed] == (
        ["all-gather"] * 3 + ["all-reduce"] * 3 + ["all-to-all"] * 4)
    assert sum(f"s32[{slots}]" in t for _, t in crossed[:3]) == 2
    assert any(block in t for _, t in crossed[:3]) and block in crossed[6][1]
    assert [t.split("{")[0] for _, t in crossed[7:]] == [
        "f32[4,44,81920]", "f32[4,44,81920]", "s32[4,1,81920]"]
    assert [(op, t.split("{")[0]) for op, t in crossed] \
        == [(op, t.split("{")[0]) for op, t in _crossed(dense_text)]
    stats, before = fused.memory_analysis(), dense.memory_analysis()
    # W and G updated in place, as the two passes had them
    assert stats.alias_size_in_bytes == before.alias_size_in_bytes \
        >= 2 * 4 * deal.local_rows * width
    # 2,928 -> 1,090 MB: the gradient's 2.41 GB had shared 0.57 GB of its
    # room with the permute's rows
    gradient = 4 * deal.local_rows * width
    assert before.temp_size_in_bytes - stats.temp_size_in_bytes \
        > 0.7 * gradient
    assert stats.temp_size_in_bytes < gradient // 2


def test_four_chip_dealt_step_gathers_its_permutes_run_by_run(topo,
                                                              monkeypatch):
    """kdd12_ffm_ps4_text's step on the described 2x2 mesh (PR 51): each of
    the owned road's four permutes is sixteen conditionals over a buffer
    nobody fills, a run's gather in each (an owner's 327,680 received slots
    in runs of 20,480, a worker's 262,144 in runs of 16,384), and no
    branch moves anything of the whole permute's size: the cotangent
    columns an owner received are lines row-major *before* the runs
    (``sorted_walk.row_major_lines``; without it XLA lays them column-major
    and every live run's branch transposes all of them, 0.7 ms a run for
    0.19 on the chip)."""
    import re

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    compiled, deal = _dealt_step_compiled(topo, two_passes=False)
    text = compiled.as_text()
    n = 65_536 * 16 // 4
    received = 4 * tx.capacity(n, 4)
    assert (n, received) == (262_144, 327_680)
    for slots in (n, received):
        assert len(re.findall(
            rf" = f32\[{slots // 16},128\]\S* gather\(", text)) == 32
    assert text.count('custom_call_target="AllocateBuffer"') == 4
    # a computation that holds a run's gather holds nothing of all the
    # slots' lines but the carried result it writes into
    for body in text.split("\n\n"):
        if re.search(r" = f32\[(16384|20480),128\]\S* fusion\(.*kCustom",
                     body) and body.lstrip().startswith("%region"):
            assert not re.search(
                rf" = f32\[({n}|{received}),128\]\S* (copy|pad|transpose|"
                rf"concatenate|broadcast)\(", body), body[:400]
    assert not re.search(rf" = f32\[{received},128\]\S* copy\(", text)


# ---------------- the field-aware FM's pair terms (PR 36) ----------------

@pytest.mark.parametrize("rows", [65_536, 16_384],
                         ids=["kdd12_ffms_batch", "a_chip_of_kdd12_ffm_ps4"])
def test_pair_terms_compile_with_no_pair_tensor_in_hbm(one_chip, rows,
                                                       monkeypatch):
    """Value and gradient of kdd12_ffm's pair terms from the gathered rows
    on the kernel route, routed by the op itself: both kernels are there
    (their blocks, the pair tensor's scratch and the compiler's spills fit
    the VMEM they ask for), no operand holds the ``[k, K, K, B]`` pair
    tensor and nothing is selected or reduced over it outside them."""
    from dmlc_tpu.ops import ffm_pairs as fp

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    m, f, k = 11, 4, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(got, fields, values, weight):
        phi, reg = fp.ffm_pair_terms(got, fields, values, m)
        return jnp.sum((phi + reg) * weight)

    text = jax.jit(jax.value_and_grad(loss)).lower(
        sds((k, rows, m * f), jnp.float32), sds((k, rows), jnp.uint8),
        sds((k, rows), jnp.float32), sds((rows,), jnp.float32),
    ).compile().as_text()
    calls = [ln for ln in text.splitlines() if " custom-call(" in ln]
    assert [name for name in ("ffm_pair_terms", "ffm_pair_grads")
            if any(name in ln for ln in calls)] \
        == ["ffm_pair_terms", "ffm_pair_grads"]
    assert f"f32[{f},{k},{k}," not in text
    assert "select_reduce" not in text


def test_the_ffm_step_moves_the_gathered_rows_once_each_way(one_chip,
                                                            monkeypatch):
    """kdd12_ffm's whole step on one chip, every route the chip's: the four
    kernels in their order, no ``[k, K, K, B]`` pair tensor and no
    ``select_reduce`` fusion over one, and between the un-permute and the
    pair terms' kernels one copy of the rows in lines of 128 (through a
    ``[m * k, K, B]`` layout there were two; until PR 47 one more on the
    way back to the permute)."""
    import re

    from dmlc_tpu.models import FFMLearner
    from dmlc_tpu.ops.sparse import EllBatch

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    num_rows, ((width,),) = SHAPES["ffm"]
    b, k, m = 65_536, 16, 11

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the learner at a toy size, for its step function: it routes by the
    # traced table's rows
    model = FFMLearner(num_col=7, num_fields=m, num_factors=width // m)
    step_fn, options = model._step._jit_args
    table = sds((num_rows, width), jnp.float32)
    opt_state = jax.tree_util.tree_map(
        lambda x: table if x.ndim == 2 else sds(x.shape, x.dtype),
        model.opt_state)
    text = jax.jit(step_fn, **options).lower(
        type(model.params)(w=table), opt_state,
        EllBatch(sds((b, k), jnp.int32), sds((b, k), jnp.float32),
                 sds((b,), jnp.float32), sds((b,), jnp.float32),
                 sds((b, k), jnp.uint8))).compile().as_text()
    calls = [ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert calls == ["table_gather", "ffm_pair_terms", "ffm_pair_grads",
                     "grad_scatter"]
    assert f"f32[{width // m},{k},{k}," not in text
    assert "select_reduce" not in text
    lines = b // 128
    moved = [ln.split(" = ", 1)[1].split("(")[0] for ln in text.splitlines()
             if re.search(r" (copy|transpose|reshape)\(", ln)
             and re.search(rf"f32\[({k},{lines},128,{width}|"
                           rf"{width},{k},({lines},128|{b})|"
                           rf"{k},{b},{width})\]", ln)]
    # (PR 47) one: the forward's lines cut to 44 lanes and laid as the
    # pair terms' operand; the cotangent leaves ``ffm_pair_grads`` as lines
    assert len(moved) == 1, moved
    # and the wide payload's kernels move their slots as the permutes'
    # lines themselves: what makes an array of the slots' lines is the two
    # kernels that write them and XLA's two permutes, with no pad, copy,
    # transpose or split of XLA's around either. A permute (PR 49) is a
    # buffer nobody fills and sixteen conditionals that carry it, each
    # writing its run into it in place (XLA moves the un-permute's last
    # write out of its branches)
    slots = b * k
    made = [(m["op"], m["type"].split("{")[0]) for m in (re.match(
        r"\s*(?:ROOT )?%?[\w.\-]+ = (?P<type>\S+) (?P<op>[a-z][\w\-]*)\(",
        ln) for ln in text[text.index("ENTRY"):].splitlines())
        if m and m["op"] not in ("bitcast", "tuple", "get-tuple-element",
                                 "conditional")
        and re.search(
            rf"(f32|bf16)\[({slots},\d+|\d+,{slots}|{k},{lines},128,128)\]",
            m["type"])]
    assert made == [
        ("custom-call", f"f32[{slots},128]"),               # table_gather
        ("custom-call", f"f32[{slots},128]"),               # lax.empty
        ("fusion", f"f32[{slots},128]"),                    # the last write
        ("custom-call", f"f32[{k},{lines},128,128]"),       # ffm_pair_grads
        ("custom-call", f"f32[{slots},128]"),               # lax.empty
    ], made
    assert text.count('custom_call_target="AllocateBuffer"') == 2
    assert text[text.index("ENTRY"):].count(" conditional(") == 32
    # inside the branches: a run's gather, and its write or its zeros into
    # the result; nothing of the whole result's size is made there
    inside = text[:text.index("ENTRY")]
    assert not re.search(
        rf" = f32\[{slots},128\]\S* (copy|pad|transpose|concatenate|"
        rf"broadcast)\(", inside)
    assert len(re.findall(
        rf" = f32\[{slots // 16},128\]\S* gather\(", inside)) == 32


def test_the_ffm_step_on_id_columns_runs_eleven_slots_a_row(one_chip,
                                                            monkeypatch):
    """kdd12_ffm_csv's whole step on one chip (PR 48): the dense int32
    plane of 11 id columns goes through the ELL step's four kernels at
    K = 11, 720,896 slots a batch, with nothing padded to 16 on the way:
    no kernel needs a multiple of 8."""
    import numpy as np

    from dmlc_tpu.models import FFMLearner

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    num_rows, ((width,),) = SHAPES["ffm"]
    b, m = 65_536, 11

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = FFMLearner(num_col=64, num_fields=m, num_factors=width // m,
                       layout="dense", column_offsets=np.arange(m))
    # the step reads the sink's id off the learner: the cell's table
    model.weight_dim = num_rows
    step_fn, options = model._step._jit_args
    table = sds((num_rows, width), jnp.float32)
    opt_state = jax.tree_util.tree_map(
        lambda x: table if x.ndim == 2 else sds(x.shape, x.dtype),
        model.opt_state)
    text = jax.jit(step_fn, **options).lower(
        type(model.params)(w=table), opt_state,
        (sds((b, m), jnp.int32), sds((b,), jnp.float32),
         sds((b,), jnp.float32))).compile().as_text()
    calls = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]:
             ln.split(" = ", 1)[1].split(" custom-call(")[0]
             for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln}
    assert list(calls) == ["table_gather", "ffm_pair_terms",
                           "ffm_pair_grads", "grad_scatter"]
    slots = b * m
    assert slots == 720_896 == 5_632 * 128
    assert calls["table_gather"].startswith(f"f32[{slots},128]")
    assert calls["ffm_pair_grads"].startswith(f"f32[{m},{b // 128},128,128]")
    # no plane of 16 slots a row anywhere: the host padded nothing and the
    # device pads nothing
    assert f"[{b * 16}" not in text and f",{b * 16}]" not in text
    assert f"[16,{b // 128},128," not in text
    assert "ffm_columns" in text


def test_the_criteo_step_fits_a_v5e_with_positional_pair_blocks_of_eight_lines(
        one_chip, monkeypatch):
    """criteo_ffm's whole step on one chip (PR 55): 39 hashed columns of
    one id space, a [1,000,001, 156] table, 638,976 slots a batch, beside
    which the table is 1.6 times as long: still the kernels' route. A row
    crosses as a line of 256 lanes. Since PR 56 the dense learner hands
    the pair terms no field plane and they take their positional kernels:
    a grid of (16 blocks of eight lines, 39 slots), no pair tensor in VMEM
    (the general backward's ``[4, 39, 39, lines, 128]`` scratch cut its
    blocks to four lines, ``f32[39,32,4,128,256]``), a slot's ``d wg`` in
    a ``[156, 8, 128]`` scratch and out as a block of eight lines."""
    import numpy as np
    from jax.experimental import pallas as pl

    from dmlc_tpu.models import FFMLearner

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    num_rows, b, m, f = 1_000_001, 16_384, 39, 4
    real, seen = pl.pallas_call, {}

    def spy(kernel, *args, **kw):
        if kw["name"].startswith("ffm_pair_"):
            seen[kw["name"]] = (
                kw["grid"], [tuple(x.shape) for x in kw["scratch_shapes"]],
                [tuple(x.block_shape) for x in kw["out_specs"]])
        return real(kernel, *args, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = FFMLearner(num_col=64, num_fields=m, num_factors=f,
                       layout="dense", column_offsets=np.zeros(m, np.int32))
    model.weight_dim = num_rows
    step_fn, options = model._step._jit_args
    table = sds((num_rows, m * f), jnp.float32)
    opt_state = jax.tree_util.tree_map(
        lambda x: table if x.ndim == 2 else sds(x.shape, x.dtype),
        model.opt_state)
    compiled = jax.jit(step_fn, **options).lower(
        type(model.params)(w=table), opt_state,
        (sds((b, m), jnp.int32), sds((b,), jnp.float32),
         sds((b,), jnp.float32))).compile()
    text = compiled.as_text()
    calls = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]:
             ln.split(" = ", 1)[1].split(" custom-call(")[0]
             for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln}
    assert list(calls) == ["table_gather", "ffm_pair_terms",
                           "ffm_pair_grads", "grad_scatter"]
    slots = b * m
    assert slots == 638_976 < num_rows
    assert calls["table_gather"].startswith(f"f32[{slots},256]")
    # d wg leaves as the slots' lines, whole: no block is cut
    assert calls["ffm_pair_grads"].startswith(f"f32[{m},{b // 128},128,256]")
    assert seen["ffm_pair_terms"] == (
        (b // 1024, m), [], [(8, 128), (8, 128)])
    assert seen["ffm_pair_grads"] == (
        (b // 1024, m), [(m * f, 8, 128)], [(None, 8, 128, 256)])
    # no pair tensor: not in a kernel's VMEM, not in HBM, no field plane
    # to select on
    assert f"[{f},{m},{m}," not in text and "select_reduce" not in text
    assert f"s32[{m},{b // 128},128]" not in text
    # W and G at rest and the step's temporaries: under a quarter of a chip
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 1.3e9
    assert memory.temp_size_in_bytes < 2.5e9


@pytest.mark.parametrize("learner", ["fm", "ffm"])
def test_the_walks_scopes_are_metadata_to_the_chips_compiler(one_chip,
                                                             monkeypatch,
                                                             learner):
    """PR 50: kdd12_fm's and kdd12_ffm's whole steps compiled for the chip
    with the sorted walk's five scopes and without are the same HLO but
    for metadata: no operation, fusion, layout or buffer differs, and the
    names are there to be read."""
    import contextlib

    from dmlc_tpu.models import FFMLearner, FMLearner
    from dmlc_tpu.ops.sparse import EllBatch
    from tests.test_tracing import _strip_metadata as plain

    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: True)
    num_rows, _ = SHAPES[learner]
    b, k = 65_536, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def at_size(x):     # a table's leaf at the cell's rows, a scalar as is
        return sds((num_rows,) + x.shape[1:] if x.ndim else (), x.dtype)

    def compiled():
        if learner == "ffm":
            model = FFMLearner(num_col=7, num_fields=11, num_factors=4)
        else:
            model = FMLearner(num_col=7, num_factors=8, layout="ell")
            model.weight_dim = num_rows        # the route reads it
        step_fn, options = model._step._jit_args
        return jax.jit(step_fn, **options).lower(
            jax.tree_util.tree_map(at_size, model.params),
            jax.tree_util.tree_map(at_size, model.opt_state),
            EllBatch(sds((b, k), jnp.int32), sds((b, k), jnp.float32),
                     sds((b,), jnp.float32), sds((b,), jnp.float32),
                     sds((b, k), jnp.uint8) if learner == "ffm" else None)
        ).compile().as_text()

    scoped = compiled()
    assert all(scope in scoped for scope in sw.WALK_SCOPES)
    assert "grad_scatter" in scoped and "table_gather" in scoped
    named = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name in sw.WALK_SCOPES else named(name))
    unscoped = compiled()
    assert not any(scope in unscoped for scope in sw.WALK_SCOPES)
    assert plain(scoped) == plain(unscoped)


@pytest.mark.parametrize("op", ["sum", "take"])
def test_slot_rows_kernels_compile_at_the_ragged_cells_shape(one_chip, op):
    """kddb_fm (PR 37): 65,536 rows of 1,929,216 flat slots, the FM's nine
    columns as one table of the batch's rows, blocks of ``ROW_BLOCK`` rows
    (a 1-D operand's tile of 1,024 lanes would refuse them)."""
    from dmlc_tpu.ops import slot_rows as sr

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, slots = 65_536, 1_929_216
    lead = slots if op == "sum" else rows
    fn = (lambda q, a, r: sr.rows_sum_kernel((q, a), r, rows)) if op == "sum" \
        else (lambda q, a, r: sr.rows_take_kernel((q, a), r))
    compiled = jax.jit(fn).lower(
        sds((lead,), jnp.float32), sds((lead, 8), jnp.float32),
        sds((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert (sr.SUM_KERNEL if op == "sum" else sr.TAKE_KERNEL) in text
