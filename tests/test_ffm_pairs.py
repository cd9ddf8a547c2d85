"""The field-aware FM's pair terms as an op of their own (PR 36,
``ops/ffm_pairs.py``): the two kernels, interpreted on the CPU, against the
plain ``jax.numpy`` form they replace on the chip, row kind by row kind;
the identity the kernels rest on; the route and its counter. (Their
``pallas_call`` names are held to no pattern of the benchmark beside the
other kernels', in ``tests/test_ffm.py``.)"""

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


@pytest.mark.parametrize("shape", list(SHAPES))
def test_d_wg_as_lines_is_d_wg_bit_for_bit(shape):
    """(PR 47) The backward kernel handing ``d wg`` out as the slots'
    lines, a block transposed in VMEM, against the same kernel handing it
    out lane-major: row ``(s * L + l) * 128 + b`` holds ``d wg[:, s, l,
    b]``, the same bits, and zeros on the lanes past the ``m * F``
    columns. One block of fewer than eight lines, and blocks of eight."""
    m, slots, batch = SHAPES[shape]
    rows, fields, values, _ = _operands(shape)
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
        blocked(fields.astype(np.int32), 1), blocked(values, 1),
        blocked(np.asarray(fp._inverse_norm(jnp.asarray(values))), 0),
        *(blocked(rng.normal(size=batch).astype(np.float32), 0)
          for _ in range(2)))
    columns, as_lines = (np.asarray(fp.pair_grads_pallas(
        *operands, num_fields=m, interpret=True, lines=side))
        for side in (False, True))
    assert columns.shape == (m * F, slots, lines, 128)
    assert as_lines.shape == (slots * lines * 128, 128)
    assert np.array_equal(
        as_lines[:, :m * F].view(np.uint32),
        np.transpose(columns, (1, 2, 3, 0)).reshape(-1, m * F).view(
            np.uint32))
    assert not as_lines[:, m * F:].any()
    assert np.abs(columns).max() > 0.1


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
    assert (f'dmlc_tpu_ffm_interaction_route_total{{reason="{reason}",'
            f'route="{route}"}}' in telemetry.render_prometheus())


@pytest.mark.parametrize("route", ["kernel", "xla"])
def test_values_get_no_cotangent_on_either_route(request, route):
    if route == "kernel":
        request.getfixturevalue("pair_kernels")
    m = SHAPES["m5_k8_one_block"][0]
    rows, fields, values, _ = _operands("m5_k8_one_block")
    grad = jax.grad(lambda x: jnp.sum(fp.ffm_pair_terms(
        jnp.asarray(rows), fields, x, m)[0]))(jnp.asarray(values))
    assert not np.asarray(grad).any()
