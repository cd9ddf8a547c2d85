"""The field-aware FM's pair terms as an op of their own (PR 36,
``ops/ffm_pairs.py``): the two kernels, interpreted on the CPU, against the
plain ``jax.numpy`` form they replace on the chip, row kind by row kind;
the identity the kernels rest on; the route and its counter. (Their
``pallas_call`` names are held to no pattern of the benchmark beside the
other kernels', in ``tests/test_ffm.py``.) Since PR 56 also the positional
pair (no field plane: slot ``t`` is field ``t``), against the plain form fed
``fields = arange`` and against the general kernels."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.ops import ffm_pairs as fp
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.utils import telemetry

F = 4
KINDS = ["every_field_once", "padded_slots", "an_empty_row",
         "two_slots_in_one_field", "a_field_no_slot_has"]
# (fields, slots, rows): a batch of under 1,024 rows is one block of as
# many lines of 128 as it has, above that blocks of 8 lines
SHAPES = {"m5_k8_one_block": (5, 8, 50), "m11_k16_one_block": (11, 16, 300),
          "m5_k16_three_blocks": (5, 16, 2_100),
          "m11_k8_two_blocks": (11, 8, 1_030)}


def _operands(shape: str):
    """``(rows [K, B, m * F], fields, values [K, B], kind of every row)``:
    row ``b`` is of kind ``b % 5``."""
    m, slots, batch = SHAPES[shape]
    rng = np.random.default_rng(sum(map(ord, shape)))
    rows = rng.normal(size=(slots, batch, m * F)).astype(np.float32)
    fields = np.tile((np.arange(slots) % m)[:, None], (1, batch))
    values = rng.uniform(0.5, 2.0, (slots, batch)).astype(np.float32)
    kind = np.arange(batch) % len(KINDS)
    keep = rng.integers(1, slots, batch)
    short = (np.arange(slots)[:, None] >= keep[None, :]) & (kind == 1)
    values[short], fields[short] = 0.0, 0
    values[:, kind == 2] = 0.0
    fields[1, kind == 3] = fields[0, kind == 3]
    fields[:, kind == 4] = np.where(fields[:, kind == 4] == 2, 3,
                                    fields[:, kind == 4])
    if slots == 16:                  # the cells' padding: no row fills it
        values[-2:] = 0.0
    if batch > 2_048:                # and a slot one block alone leaves empty
        values[0, 1_024:2_048] = 0.0
    return rows, fields.astype(np.uint8), values, kind


@functools.lru_cache(maxsize=None)
def _both_routes(shape: str):
    """``{route: (phi, reg, d rows)}`` of a loss that weighs every row's
    ``phi`` and ``reg`` differently; call under ``pair_kernels``."""
    m = SHAPES[shape][0]
    rows, fields, values, _ = _operands(shape)
    rng = np.random.default_rng(7)
    w_phi, w_reg = (jnp.asarray(rng.normal(size=rows.shape[1]).astype(
        np.float32)) for _ in range(2))
    out = {}
    for route, fn in (("kernel", fp.ffm_pair_terms_kernel),
                      ("xla", fp.ffm_pair_terms_xla)):
        def loss(r, fn=fn):
            phi, reg = fn(r, jnp.asarray(fields, jnp.int32),
                          jnp.asarray(values), m)
            return jnp.sum(phi * w_phi) + jnp.sum(reg * w_reg), (phi, reg)

        (_, (phi, reg)), grad = jax.value_and_grad(loss, has_aux=True)(
            jnp.asarray(rows))
        out[route] = tuple(np.asarray(x) for x in (phi, reg, grad))
    return out


@pytest.mark.parametrize("leaf", ["phi", "reg", "d_rows"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernels_match_the_plain_form(pair_kernels, shape, kind, leaf):
    both = _both_routes(shape)
    if pair_kernels["terms"]:            # the first test of a shape ran them
        assert pair_kernels == {"terms": 1, "grads": 1}
    at = ["phi", "reg", "d_rows"].index(leaf)
    got, want = both["kernel"][at], both["xla"][at]
    assert got.shape == want.shape and np.isfinite(got).all()
    mine = _operands(shape)[3] == KINDS.index(kind)
    assert mine.sum() >= 10
    got, want = (x[:, mine] if leaf == "d_rows" else x[mine]
                 for x in (got, want))
    if kind == "an_empty_row":
        assert not got.any() and not want.any()
        return
    assert np.abs(want).max() > 0.1
    # the selects and products are exact; the sums run in another order
    assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()


def _d_wg_on_both_sides(m, rows, fields, values):
    """``(d wg as columns, d wg as lines, lines of 128 rows)`` from the
    backward kernel called as the op's backward calls it (``fields`` None:
    the positional kernel), with cotangents of its own."""
    batch = rows.shape[1]
    lines = -(-batch // 128)
    lines = -(-lines // fp._block_lines(lines)) * fp._block_lines(lines)
    rng = np.random.default_rng(11)

    def blocked(x, axis):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, lines * 128 - batch)
        x = np.pad(x, pad)
        return jnp.asarray(x.reshape(
            x.shape[:axis] + (lines, 128) + x.shape[axis + 1:]))

    operands = (
        jnp.transpose(blocked(rows, 1), (3, 0, 1, 2)),
        None if fields is None else blocked(fields.astype(np.int32), 1),
        blocked(values, 1),
        blocked(np.asarray(fp._inverse_norm(jnp.asarray(values))), 0),
        *(blocked(rng.normal(size=batch).astype(np.float32), 0)
          for _ in range(2)))
    columns, as_lines = (np.asarray(fp.pair_grads_pallas(
        *operands, num_fields=m, interpret=True, lines=side))
        for side in (False, True))
    return columns, as_lines, lines


def _assert_the_lines_hold_the_columns(columns, as_lines, lines, slots, width):
    lanes = fp._sw.line_lanes(width)
    assert columns.shape == (width, slots, lines, 128)
    assert as_lines.shape == (slots * lines * 128, lanes)
    assert np.array_equal(
        as_lines[:, :width].view(np.uint32),
        np.transpose(columns, (1, 2, 3, 0)).reshape(-1, width).view(
            np.uint32))
    assert not as_lines[:, width:].any()
    assert np.abs(columns).max() > 0.1


@pytest.mark.parametrize("shape", list(SHAPES))
def test_d_wg_as_lines_is_d_wg_bit_for_bit(shape):
    """(PR 47) The backward kernel handing ``d wg`` out as the slots'
    lines, a block transposed in VMEM, against the same kernel handing it
    out lane-major: row ``(s * L + l) * 128 + b`` holds ``d wg[:, s, l,
    b]``, the same bits, and zeros on the lanes past the ``m * F``
    columns. One block of fewer than eight lines, and blocks of eight."""
    m, slots, _ = SHAPES[shape]
    rows, fields, values, _ = _operands(shape)
    _assert_the_lines_hold_the_columns(
        *_d_wg_on_both_sides(m, rows, fields, values), slots, m * F)
    assert fp._sw.line_lanes(m * F) == 128


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_slot_no_pair_uses_has_no_cotangent(pair_kernels, shape):
    """A slot of value 0 takes part in no pair: its row's cotangent is an
    exact zero on both routes, whole blocks of them skipped or not."""
    _, _, values, _ = _operands(shape)
    for route in ("kernel", "xla"):
        grad = _both_routes(shape)[route][2]
        assert (values == 0).sum() > 20
        assert not grad[values == 0].any(), route


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_partner_tensor_is_the_pair_tensor_transposed(shape):
    """What the kernels rest on: ``c[d, s, t] = wg[f_s, d, t]`` is ``a[d,
    t, s]``, bit for bit, so ``c`` and its backward compute nothing new."""
    m, slots, batch = SHAPES[shape]
    rows, fields, _, _ = _operands(shape)
    wg = jnp.moveaxis(jnp.asarray(rows), -1, 0).reshape(m, F, slots, batch)
    a, c = fp.pair_tensors(wg, jnp.asarray(fields, jnp.int32))
    assert a.shape == (F, slots, slots, batch)
    assert np.asarray(a).any()
    assert np.array_equal(np.asarray(c), np.asarray(jnp.swapaxes(a, 1, 2)))


@pytest.mark.parametrize("name,on_tpu,rows,dtype,want", [
    ("kdd12_ffms_batch_on_the_chip", True, 65_536, jnp.float32,
     ("kernel", "none")),
    ("a_chips_share_of_kdd12_ffm_ps4s", True, 16_384, jnp.float32,
     ("kernel", "none")),
    ("the_cpu", False, 65_536, jnp.float32, ("xla", "backend")),
    ("another_dtype", True, 65_536, jnp.bfloat16, ("xla", "dtype")),
    ("rows_that_fill_no_block", True, 65_536 + 128, jnp.float32,
     ("xla", "rows")),
    ("a_test_sized_batch", True, 64, jnp.float32, ("xla", "rows")),
])
def test_ffm_interaction_route_is_a_function_of_what_the_op_observes(
        monkeypatch, name, on_tpu, rows, dtype, want):
    monkeypatch.setattr(gs, "_on_tpu_backend", lambda: on_tpu)
    assert fp.ffm_interaction_route(rows, dtype) == want, name


@pytest.mark.parametrize("route", ["kernel", "xla"])
def test_ffm_interaction_route_is_counted_where_the_op_is_traced(request,
                                                                 route):
    if route == "kernel":
        request.getfixturevalue("pair_kernels")
    m, slots, batch = SHAPES["m5_k8_one_block"]
    rows, fields, values, _ = _operands("m5_k8_one_block")
    before = telemetry.ffm_interaction_routes().get(route, 0)
    fn = jax.jit(lambda r: fp.ffm_pair_terms(r, fields, values, m)[0])
    for _ in range(2):                          # one trace, two calls
        phi = fn(rows)
    assert phi.shape == (batch,)
    assert telemetry.ffm_interaction_routes()[route] == before + 1
    reason = "none" if route == "kernel" else "backend"
    assert (f'dmlc_tpu_ffm_interaction_route_total{{fields="plane",'
            f'reason="{reason}",route="{route}"}}'
            in telemetry.render_prometheus())


@pytest.mark.parametrize("route", ["kernel", "xla"])
def test_values_get_no_cotangent_on_either_route(request, route):
    if route == "kernel":
        request.getfixturevalue("pair_kernels")
    m = SHAPES["m5_k8_one_block"][0]
    rows, fields, values, _ = _operands("m5_k8_one_block")
    grad = jax.grad(lambda x: jnp.sum(fp.ffm_pair_terms(
        jnp.asarray(rows), fields, x, m)[0]))(jnp.asarray(values))
    assert not np.asarray(grad).any()


# ---------------- no field plane: a slot's field is its position ----------

# (fields = slots, rows): one block of under eight lines, at the csv cells'
# 11 and 39 columns, and blocks of eight lines
POSITIONAL = {"m5_one_block": (5, 50), "m11_one_block": (11, 300),
              "m39_one_block": (39, 200), "m5_three_blocks": (5, 2_100)}
ROW_KINDS = ["every_column_valued", "a_padded_row", "an_empty_cell"]


def _columns(shape: str):
    """``(rows [m, B, m * F], values [m, B], kind of every row)``: row
    ``b`` is of kind ``b % 3``; a padded row (weight 0) has no value at
    all, an empty cell takes one slot's."""
    m, batch = POSITIONAL[shape]
    rng = np.random.default_rng(sum(map(ord, shape)))
    rows = rng.normal(size=(m, batch, m * F)).astype(np.float32)
    values = np.ones((m, batch), np.float32)
    kind = np.arange(batch) % len(ROW_KINDS)
    values[:, kind == 1] = 0.0
    values[rng.integers(0, m, batch), np.arange(batch)] *= kind != 2
    return rows, values, kind


def _arange_plane(m: int, batch: int):
    return jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[:, None],
                            (m, batch))


@functools.lru_cache(maxsize=None)
def _positional_routes(shape: str):
    """``{route: (phi, reg, d rows)}``: the positional kernels on columns
    and on lines, the general kernels and the plain form fed ``fields =
    arange``; call under ``pair_kernels``."""
    m, batch = POSITIONAL[shape]
    rows, values, _ = _columns(shape)
    rng = np.random.default_rng(7)
    w_phi, w_reg = (jnp.asarray(rng.normal(size=batch).astype(np.float32))
                    for _ in range(2))
    plane, width = _arange_plane(m, batch), m * F
    lanes = fp._sw.line_lanes(width)
    lined = np.pad(rows, ((0, 0), (0, 0), (0, lanes - width)))
    out = {}
    for route, fn, operand in (
            ("columns", lambda r: fp.ffm_pair_terms_kernel(
                r, None, jnp.asarray(values), m, width), rows),
            ("lines", lambda r: fp.ffm_pair_terms_kernel(
                r, None, jnp.asarray(values), m, width), lined),
            ("general", lambda r: fp.ffm_pair_terms_kernel(
                r, plane, jnp.asarray(values), m, width), rows),
            ("xla", lambda r: fp.ffm_pair_terms_xla(
                r, plane, jnp.asarray(values), m), rows)):
        def loss(r, fn=fn):
            phi, reg = fn(r)
            return jnp.sum(phi * w_phi) + jnp.sum(reg * w_reg), (phi, reg)

        (_, (phi, reg)), grad = jax.value_and_grad(loss, has_aux=True)(
            jnp.asarray(operand))
        out[route] = tuple(np.asarray(x) for x in (phi, reg, grad))
    return out


@pytest.mark.parametrize("leaf", ["phi", "reg", "d_rows"])
@pytest.mark.parametrize("side", ["columns", "lines"])
@pytest.mark.parametrize("shape", list(POSITIONAL))
def test_the_positional_kernels_match_the_plain_form_fed_arange(
        pair_kernels, shape, side, leaf):
    m = POSITIONAL[shape][0]
    routes = _positional_routes(shape)
    at = ["phi", "reg", "d_rows"].index(leaf)
    got, want = routes[side][at], routes["xla"][at]
    if leaf == "d_rows" and side == "lines":
        # the cotangent of lines is lines: zeros past the m * F columns
        assert got.shape[-1] == fp._sw.line_lanes(m * F) > m * F
        assert not got[..., m * F:].any()
        got = got[..., :m * F]
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()
    # the lines hold the columns' values bit for bit
    other = routes["columns"][at]
    assert np.array_equal(got.view(np.uint32), other.view(np.uint32))


@pytest.mark.parametrize("leaf", ["phi", "reg", "d_rows"])
@pytest.mark.parametrize("shape", list(POSITIONAL))
def test_the_positional_and_the_general_kernels_agree(pair_kernels, shape,
                                                      leaf):
    """One input, ``fields = arange`` as data and as nothing: the same
    products, ``phi`` and ``reg`` summed in another order (to the ulps
    ``test_the_kernels_match_the_plain_form`` allows), ``d wg`` one
    product pair a value on both sides and so the same number."""
    routes = _positional_routes(shape)
    at = ["phi", "reg", "d_rows"].index(leaf)
    got, want = routes["columns"][at], routes["general"][at]
    assert np.abs(want).max() > 0.1
    if leaf == "d_rows":
        assert np.array_equal(got, want)
    else:
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()


@pytest.mark.parametrize("side", ["columns", "lines"])
@pytest.mark.parametrize("shape", list(POSITIONAL))
def test_a_row_without_values_has_no_terms_and_no_cotangent(pair_kernels,
                                                            shape, side):
    """A padded row (weight 0: every value 0) reads ``phi = reg = 0`` and
    an exact-zero cotangent on every slot; an empty cell's slot, value 0
    among valued ones, takes part in no pair either."""
    _, values, kind = _columns(shape)
    phi, reg, grad = _positional_routes(shape)[side]
    padded = kind == ROW_KINDS.index("a_padded_row")
    assert padded.sum() >= 10
    assert not phi[padded].any() and not reg[padded].any()
    assert not grad[:, padded].any()
    empty = (values == 0) & ~padded[None, :]
    assert empty.sum() >= 10 and not grad[empty].any()
    assert np.abs(grad).max(axis=-1)[values != 0].min() > 0


@pytest.mark.parametrize("shape", list(POSITIONAL))
def test_positional_d_wg_as_lines_is_d_wg_bit_for_bit(shape):
    """``test_d_wg_as_lines_is_d_wg_bit_for_bit`` for the positional
    backward kernel (lines of 256 lanes at 39 fields)."""
    m = POSITIONAL[shape][0]
    rows, values, _ = _columns(shape)
    _assert_the_lines_hold_the_columns(
        *_d_wg_on_both_sides(m, rows, None, values), m, m * F)


def test_no_plane_needs_a_slot_a_field():
    from dmlc_tpu.utils.check import DMLCError

    rows, values, _ = _columns("m5_one_block")
    with pytest.raises(DMLCError, match="4 slots are not 5 fields"):
        fp.ffm_pair_terms(rows[:4], None, values[:4], 5)


@pytest.mark.parametrize("route", ["kernel", "xla"])
@pytest.mark.parametrize("layout,fields", [("dense", "position"),
                                           ("ell", "plane")])
def test_a_learners_step_says_where_its_fields_came_from(request, route,
                                                         layout, fields):
    """``FFMLearner(layout="dense")`` hands the op no plane and its step
    is counted under ``fields="position"``, once a traced step; an ELL
    batch's plane under ``fields="plane"``."""
    from dmlc_tpu.models import FFMLearner
    from dmlc_tpu.ops.sparse import EllBatch

    if route == "kernel":
        request.getfixturevalue("pair_kernels")
    m, batch = 5, 64
    rng = np.random.default_rng(56)
    x = rng.integers(0, 10, (batch, m)).astype(np.int32)
    label = (rng.random(batch) < 0.5).astype(np.float32)
    weight = np.ones(batch, np.float32)
    if layout == "dense":
        model = FFMLearner(10 * m, m, F, layout="dense",
                           column_offsets=10 * np.arange(m))
        data = (x, label, weight)
    else:
        model = FFMLearner(10 * m, m, F)
        data = EllBatch(x + 10 * np.arange(m), np.ones((batch, m), np.float32),
                        label, weight,
                        np.tile(np.arange(m, dtype=np.uint8), (batch, 1)))

    def count():
        return int(telemetry.REGISTRY.sum_by(
            telemetry.FFM_INTERACTION_ROUTE_METRIC, "fields",
            route=route).get(fields, 0))

    before, total = count(), telemetry.ffm_interaction_routes().get(route, 0)
    for _ in range(2):                          # one trace, two steps
        assert np.isfinite(float(model.step(data)))
    assert count() == before + 1
    assert telemetry.ffm_interaction_routes()[route] == total + 1
