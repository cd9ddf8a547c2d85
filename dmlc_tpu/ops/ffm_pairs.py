"""The field-aware FM's pair terms, forward and backward in one pass each
over the batch, with the pair tensor never in HBM.

A row's slots ``s = 1..K`` hold a feature's gathered table row ``wg[f, d,
s]`` (its factor ``d`` for field ``f``), a field ``f_s`` and a value
``x_s``. The model's two per-row sums (``models/ffm.py``) are taken over
the *pair tensor* ``a[d, s, t] = wg[f_t, d, s]``, slot ``s``'s vector for
slot ``t``'s field:

    phi = r * sum_{s<t} sum_d a[d, s, t] * a[d, t, s] * x_s x_t
    reg =     sum_{s != t, x_s x_t != 0} sum_d a[d, s, t]^2

with ``r = 1 / sum_s x_s^2`` (0 for an empty row). ``a`` is a *select* of
``wg`` over the ``m`` fields, exact in float32 (a one-hot contraction would
round the table to the MXU's bfloat16), and ``K * K * k`` values a row: at
the benchmark's 16 slots, 4 factors and 65,536 rows, 268 MB.

:func:`ffm_pair_terms_xla` is the plain ``jax.numpy`` form: it builds ``a``
and its partner ``c[d, s, t] = wg[f_s, d, t]`` by ``m`` ``where``s each,
and autodiff transposes every ``where`` on its own, one pass over a 268 MB
cotangent a field and a tensor. It is the route of the CPU and the oracle
the kernels are tested against.

The kernels rest on ``c[d, s, t] == a[d, t, s]`` (the same table value,
named from the other slot): ``c`` and its whole backward compute nothing
new, so ``a`` is selected once.

- **Forward** (:func:`pair_terms_pallas`): a grid over blocks of 1,024
  rows. The batch fills sublanes *and* lanes (the rows are read as ``[m *
  k, K, B / 128, 128]``), so every ``(field, d, s)`` of a block is one
  ``[8, 128]`` vector register's worth of rows, ``a[d, s, t]`` and ``a[d,
  t, s]`` are two addresses (no transposition in a register) and a
  field's mask is a plane, never a broadcast along sublanes. A block
  builds ``a`` in a VMEM scratch by ``m`` selects, off the diagonal only,
  then walks the pairs ``s < t``.
- **Backward** (:func:`pair_grads_pallas`): the residuals are ``wg``,
  ``fields``, ``values`` and ``r`` (no pair tensor is saved). A block
  rebuilds ``a``, forms ``da[d, s, t] = dphi r x_s x_t a[d, t, s] + 2 dreg
  [x_s x_t != 0] a[d, s, t]`` (both triangles of the pair sum land on
  ``a``) and adds it into ``d wg[f_t, d, s]`` for all ``m`` fields in the
  same pass; ``d wg`` leaves once.

A block takes 2.9 MB of ``wg`` (twice: the pipeline's two buffers), 4.2 MB
of ``a`` and, backward, 2.9 MB of ``d wg`` (twice) at 11 fields, 4 factors
and 16 slots: 10.1 and 15.9 MB of VMEM. At 39 fields and 39 slots a block
of eight lines is 24.9 MB of ``wg`` and as much of ``a``: 83 MB forward,
and backward, cut to four lines, 99 MB.

**Where a slot's field is its position** (``fields`` None: the columns of
a table, ``FFMLearner(layout="dense")``; ``K == m``), ``a`` is no select
and lies in ``wg`` itself:

    a[d, s, t] = wg[t * k + d, s]
    d wg[t * k + d, s] = dphi r x_s x_t wg[s * k + d, t]
                         + 2 dreg [x_s x_t != 0] wg[t * k + d, s]   (t != s)

one multiply-add a value where the general kernels spend ``m`` selects and
``m`` masked adds, and no pair tensor to hold. The positional kernels
(below the general ones, which stay as they are for ELL input: same
operands, same layouts, same two names) run a grid of (blocks of eight
lines, slots): a step is one slot ``s`` and sees ``wg[:, s]`` and the ``k``
rows ``wg[s * k:, :]``, 0.64 MB each at 39 fields (0.18 at 11), the
forward adds the slot's share into ``(phi, reg)``, the backward writes
``d wg[:, s]``, as columns or transposed to a 1 MB block of lines (0.5 MB
at 11 fields): 2.9 MB of VMEM forward and 5.6 MB backward with the
pipeline's second buffers at 39 fields, 0.8 and 2.0 MB at 11, and no block
of the pair tensor's size at any number of fields. ``wg`` is read twice a
pass (2.25 GB a step with the lines at 39 fields and 16,384 rows).

**Values.** The selects are exact and so is every product; only the order
of the float32 sums over ``d`` and ``(s, t)`` differs from the plain form
(``reg`` and ``d wg`` to a few ulps; the positional ``d wg`` is the general
kernels' bit for bit, ``phi`` and ``reg`` theirs in another order). A pair
of slots whose ``x_s x_t`` is 0 in all rows of a block (the padding of
short rows) is skipped by the general kernels: for finite ``wg`` its terms
are exact zeros, which the positional kernels compute.

:func:`ffm_pair_terms` is the entry point: it picks the route from what it
can observe (:func:`ffm_interaction_route`; the kernels from whether it was
given a field plane) and counts it in the telemetry counter
``ffm_interaction_route``. Both routes give ``values`` no cotangent (the
learners differentiate with respect to the table's rows alone).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dmlc_tpu.ops import grad_scatter as _gs
from dmlc_tpu.ops import sorted_walk as _sw
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import check as _check

_LANES = 128
_SUBLANES = 8
# the rows of one grid step: one float32 vector register a (field, d, s)
BLOCK_ROWS = _SUBLANES * _LANES


def ffm_interaction_route(num_rows: int, dtype) -> Tuple[str, str]:
    """``(route, reason)`` for the pair terms of ``num_rows`` rows of
    ``dtype``. ``"kernel"`` on a TPU backend, for float32, for whole blocks
    of ``BLOCK_ROWS`` rows (reason ``"none"``); the plain ``"xla"`` form
    everywhere else, because of the ``backend``, the ``dtype`` or the
    ``rows``."""
    if not _gs._on_tpu_backend():
        return "xla", "backend"
    if jnp.dtype(dtype) != jnp.float32:
        return "xla", "dtype"
    if num_rows % BLOCK_ROWS:
        return "xla", "rows"
    return "kernel", "none"


def _inverse_norm(values: jax.Array) -> jax.Array:
    norm = jnp.sum(values * values, axis=0)
    return jnp.where(norm > 0, 1.0 / norm, 0.0)     # an empty row: phi = 0


def pair_tensors(wg: jax.Array, fields: jax.Array):
    """``(a, c)`` [k, K, K, B] of the plain form: ``a[d, s, t] = wg[f_t, d,
    s]`` and ``c[d, s, t] = wg[f_s, d, t]``, selects over the fields."""
    m, k, slots, rows = wg.shape
    a = c = jnp.zeros((k, slots, slots, rows), wg.dtype)
    for field in range(m):
        here = fields == field
        a = a + jnp.where(here[None, None, :, :],
                          wg[field][:, :, None, :], 0.0)
        c = c + jnp.where(here[None, :, None, :],
                          wg[field][:, None, :, :], 0.0)
    return a, c


def ffm_pair_terms_xla(rows: jax.Array, fields: jax.Array,
                       values: jax.Array, num_fields: int):
    """:func:`ffm_pair_terms` in plain ``jax.numpy``, differentiated by
    autodiff: the pair tensor is a ``[k, K, K, B]`` array, slot-major and
    batch-minor so that every elementwise operation fills the TPU's
    lanes."""
    slots, batch, width = rows.shape
    wg = jnp.moveaxis(rows, -1, 0).reshape(
        num_fields, width // num_fields, slots, batch)
    x = values
    a, c = pair_tensors(wg, fields)
    s_id = jax.lax.broadcasted_iota(jnp.int32, (slots, slots, 1), 0)
    t_id = jax.lax.broadcasted_iota(jnp.int32, (slots, slots, 1), 1)
    xx = x[:, None, :] * x[None, :, :]                    # [K, K, B]
    pairs = jnp.sum(a * c, axis=0) * xx
    phi = _inverse_norm(x) * jnp.sum(
        jnp.where(s_id < t_id, pairs, 0.0), axis=(0, 1))
    used = (xx != 0) & (s_id != t_id)
    reg = jnp.sum(jnp.where(used, jnp.sum(a * a, axis=0), 0.0), axis=(0, 1))
    return phi, reg


# ---------------- the kernels ----------------

def _over_live_pairs(live_ref, slots: int, of_t) -> None:
    """``of_t(t)(s)`` for every ordered pair of live slots ``s != t`` of
    the block (:func:`_mark_live`), ``t`` outermost."""
    from jax.experimental import pallas as pl

    def outer(t, _):
        of_s = of_t(t)

        def inner(s, _):
            pl.when(live_ref[s] != 0)(lambda: of_s(s))

        @pl.when(live_ref[t] != 0)
        def _slot():
            jax.lax.fori_loop(0, t, inner, None)
            jax.lax.fori_loop(t + 1, slots, inner, None)

    jax.lax.fori_loop(0, slots, outer, None)


def _fill_pair_tensor(wg_ref, f_ref, live_ref, a_ref) -> None:
    """``a_ref[d, s, t] = wg_ref[f_t * k + d, s]`` for every live pair of
    slots ``s != t`` of the block."""
    k, slots = a_ref.shape[:2]
    m = wg_ref.shape[0] // k

    def of_t(t):
        ft = f_ref[t]
        here = [ft == field for field in range(m)]

        def of_s(s):
            for d in range(k):
                v = jnp.zeros(ft.shape, a_ref.dtype)
                for field in range(m):
                    v = jnp.where(here[field], wg_ref[field * k + d, s], v)
                a_ref[d, s, t] = v

        return of_s

    _over_live_pairs(live_ref, slots, of_t)


def _mark_live(x_ref, live_ref) -> None:
    """``live_ref[s]``: 1 where slot ``s`` has a value other than 0 in some
    row of the block. A pair with a slot that is not live has ``x_s x_t ==
    0`` in every row: it adds nothing to ``phi``, ``reg`` or ``d wg``."""
    def of_s(s, _):
        live_ref[s] = (jnp.max(jnp.abs(x_ref[s])) > 0).astype(jnp.int32)

    jax.lax.fori_loop(0, x_ref.shape[0], of_s, None)


def _terms_kernel(wg_ref, f_ref, x_ref, r_ref, phi_ref, reg_ref, a_ref,
                  live_ref):
    k, slots = a_ref.shape[:2]
    _mark_live(x_ref, live_ref)
    _fill_pair_tensor(wg_ref, f_ref, live_ref, a_ref)
    zero = jnp.zeros(phi_ref.shape, phi_ref.dtype)

    def of_s(s, carry):
        xs = x_ref[s]

        def of_t(t, carry):
            def pair(carry):
                phi, reg = carry
                xx = xs * x_ref[t]
                dot = sq = zero
                for d in range(k):
                    a_st, a_ts = a_ref[d, s, t], a_ref[d, t, s]
                    dot = dot + a_st * a_ts
                    sq = sq + (a_st * a_st + a_ts * a_ts)
                return phi + dot * xx, reg + jnp.where(xx != 0, sq, 0.0)

            return jax.lax.cond((live_ref[s] != 0) & (live_ref[t] != 0),
                                pair, lambda carry: carry, carry)

        return jax.lax.fori_loop(s + 1, slots, of_t, carry)

    phi, reg = jax.lax.fori_loop(0, slots, of_s, (zero, zero))
    phi_ref[...] = r_ref[...] * phi
    reg_ref[...] = reg


def _grads_kernel(wg_ref, f_ref, x_ref, r_ref, dphi_ref, dreg_ref, out_ref,
                  a_ref, live_ref, dwg_ref=None):
    # on the line side ``d wg`` is built in a scratch and leaves as lines
    lines_ref, dwg_ref = (None, out_ref) if dwg_ref is None else (
        out_ref, dwg_ref)
    k, slots = a_ref.shape[:2]
    m = wg_ref.shape[0] // k
    _mark_live(x_ref, live_ref)
    _fill_pair_tensor(wg_ref, f_ref, live_ref, a_ref)
    dwg_ref[...] = jnp.zeros(dwg_ref.shape, dwg_ref.dtype)
    g_phi = dphi_ref[...] * r_ref[...]
    g_reg = 2.0 * dreg_ref[...]

    def of_t(t):
        ft, xt = f_ref[t], x_ref[t]
        here = [ft == field for field in range(m)]

        def of_s(s):
            xx = x_ref[s] * xt
            of_phi = g_phi * xx
            of_reg = jnp.where(xx != 0, g_reg, 0.0)
            for d in range(k):
                da = of_phi * a_ref[d, t, s] + of_reg * a_ref[d, s, t]
                for field in range(m):
                    dwg_ref[field * k + d, s] += jnp.where(
                        here[field], da, 0.0)

        return of_s

    _over_live_pairs(live_ref, slots, of_t)
    if lines_ref is None:
        return
    # d wg[:, s, l, :] is [m * k, 128 rows of the batch]: transposed, the
    # rows' lines, the lanes past the m * k columns zeros
    width, lanes = dwg_ref.shape[0], lines_ref.shape[-1]
    for s in range(slots):
        for line in range(dwg_ref.shape[2]):
            lines_ref[s, line] = jnp.concatenate([
                dwg_ref[:, s, line, :],
                jnp.zeros((lanes - width, _LANES), dwg_ref.dtype)]).T


# what one grid step of the general kernels may ask of a core's VMEM (a
# v5e core has 128 MiB): a block of 8 lines is 15.9 MB backward at 11
# fields and 16 slots; 39 ELL slots of 39 fields would ask 190 MB and are
# cut to 4 lines
VMEM_BUDGET = 100 << 20


def _block_lines(lines: int) -> int:
    """The most lines of 128 rows a grid step takes: a vector register's 8
    sublanes, or all of a batch that has fewer."""
    return min(_SUBLANES, lines)


def _call(kernel, name: str, num_fields: int, operands, outs,
          interpret: bool, scratch=lambda step: (), out_lines_axis: int = -2):
    """``kernel`` over a grid of blocks of lines. ``outs(lines)`` /
    ``scratch(step)`` give the results' and the extra scratches' shapes. A
    grid step takes :func:`_block_lines` lines, halved until its blocks
    (twice: the pipeline's buffers), the pair tensor and the scratches fit
    ``VMEM_BUDGET``; blocks of under 8 lines are a leading axis of the
    operands, so that a block is whole in its last two axes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    wg = operands[0]
    width, slots, lines = wg.shape[:3]
    k = width // num_fields

    def vmem_bytes(step):
        # every block twice, the pair tensor, the scratches, and room for
        # what the compiler spills
        return 4 * (2 * step * sum(x.size // lines
                                   for x in (*operands, *outs))
                    + k * slots * slots * step * _LANES
                    + sum(math.prod(x.shape) for x in scratch(step))
                    ) + (8 << 20)

    step = _block_lines(lines)
    while step > 1 and vmem_bytes(step) > VMEM_BUDGET:
        step //= 2
    assert lines % step == 0, (lines, step)
    cut = step < _block_lines(lines)

    def spec(x, at=-2):
        # a grid step's lines of axis ``at``: blocked operands end [..., L,
        # 128]; d wg as lines is [K, L, 128 rows, lanes]
        at %= x.ndim
        lead, rest = x.shape[:at], x.shape[at + 1:]
        if cut:     # [..., L / step, step, ...]: block i of the new axis
            return pl.BlockSpec(lead + (None, step) + rest, lambda i: (
                0,) * len(lead) + (i, 0) + (0,) * len(rest))
        return pl.BlockSpec(lead + (step,) + rest, lambda i: (
            0,) * len(lead) + (i,) + (0,) * len(rest))

    def in_blocks(x, at=-2):
        at %= len(x.shape)
        return x.shape[:at] + (lines // step, step) + x.shape[at + 1:]

    in_specs = [spec(x) for x in operands]
    out_shape = outs
    if cut:
        operands = [x.reshape(in_blocks(x)) for x in operands]
        out_shape = [jax.ShapeDtypeStruct(in_blocks(x, out_lines_axis),
                                          x.dtype) for x in outs]
    got = pl.pallas_call(
        kernel,
        grid=(lines // step,),
        in_specs=in_specs,
        out_specs=[spec(x, out_lines_axis) for x in outs],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((k, slots, slots, step, _LANES), wg.dtype),
                        pltpu.SMEM((slots,), jnp.int32), *scratch(step)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes(step)),
        name=name,
        interpret=interpret,
    )(*operands)
    return [y.reshape(x.shape) for y, x in zip(got, outs)] if cut else got


@functools.partial(jax.jit, static_argnames=("num_fields", "interpret"))
def pair_terms_pallas(wg: jax.Array, fields: jax.Array, values: jax.Array,
                      r: jax.Array, num_fields: int,
                      interpret: bool = False):
    """The forward kernel on blocked operands: ``(phi, reg)`` [L, 128] from
    ``wg`` [m * k, K, L, 128] (row ``f * k + d`` is factor ``d`` for field
    ``f``), ``fields`` (int32) and ``values`` [K, L, 128] and ``r``
    [L, 128]; L lines of 128 rows, whole blocks of them."""
    vector = jax.ShapeDtypeStruct(r.shape, wg.dtype)
    if fields is None:
        return _positional_call(
            _positional_terms_kernel, "ffm_pair_terms", num_fields, wg,
            (values, r), [vector, vector], interpret)
    return _call(_terms_kernel, "ffm_pair_terms", num_fields,
                 (wg, fields, values, r), [vector, vector], interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_fields", "interpret", "lines"))
def pair_grads_pallas(wg: jax.Array, fields: jax.Array, values: jax.Array,
                      r: jax.Array, dphi: jax.Array, dreg: jax.Array,
                      num_fields: int, interpret: bool = False,
                      lines: bool = False) -> jax.Array:
    """The backward kernel: ``d wg`` [m * k, K, L, 128] from the forward's
    operands and the cotangents ``dphi``, ``dreg`` [L, 128]. With
    ``lines`` the same values leave as the slots' lines, ``[K * L * 128,
    lanes]`` with row ``(s * L + l) * 128 + b`` holding ``d wg[:, s, l,
    b]`` on its first ``m * k`` lanes and zeros behind them
    (``sorted_walk.slot_layout``): the block is transposed in VMEM, and the
    update's permute takes the lines as they are."""
    from jax.experimental.pallas import tpu as pltpu

    if fields is None:
        return _positional_grads(wg, (values, r, dphi, dreg), num_fields,
                                 interpret, lines)
    if not lines:
        (dwg,) = _call(_grads_kernel, "ffm_pair_grads", num_fields,
                       (wg, fields, values, r, dphi, dreg),
                       [jax.ShapeDtypeStruct(wg.shape, wg.dtype)], interpret)
        return dwg
    width, slots, count = wg.shape[:3]
    lanes = _sw.line_lanes(width)
    (out,) = _call(
        _grads_kernel, "ffm_pair_grads", num_fields,
        (wg, fields, values, r, dphi, dreg),
        [jax.ShapeDtypeStruct((slots, count, _LANES, lanes), wg.dtype)],
        interpret, scratch=lambda step: (pltpu.VMEM(
            (width, slots, step, _LANES), wg.dtype),),
        out_lines_axis=1)
    return out.reshape(-1, lanes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blocked_terms(num_fields, wg, fields, values, r):
    return pair_terms_pallas(wg, fields, values, r, num_fields=num_fields)


def _blocked_fwd(num_fields, wg, fields, values, r):
    return (_blocked_terms(num_fields, wg, fields, values, r),
            (wg, fields, values, r))


def _blocked_bwd(num_fields, saved, cotangents):
    return pair_grads_pallas(*saved, *cotangents,
                             num_fields=num_fields), None, None, None


_blocked_terms.defvjp(_blocked_fwd, _blocked_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lined_terms(num_fields, width, lined, fields, values, r):
    """:func:`_blocked_terms` from the gathered rows as they come on the
    line side, ``lined`` [K, L, 128, lanes] with the ``width`` columns on
    a line's first lanes: the cotangent goes back as such lines, written
    by the backward kernel itself (``pair_grads_pallas(lines=True)``)."""
    return _lined_fwd(num_fields, width, lined, fields, values, r)[0]


def _lined_fwd(num_fields, width, lined, fields, values, r):
    wg = jnp.transpose(lined[..., :width], (3, 0, 1, 2))
    return (pair_terms_pallas(wg, fields, values, r, num_fields=num_fields),
            (wg, fields, values, r))


def _lined_bwd(num_fields, width, saved, cotangents):
    slots, lines = saved[0].shape[1:3]
    return pair_grads_pallas(
        *saved, *cotangents, num_fields=num_fields, lines=True).reshape(
        slots, lines, _LANES, -1), None, None, None


_lined_terms.defvjp(_lined_fwd, _lined_bwd)


# ---------------- the positional kernels ----------------
# ``fields is None``: slot ``t``'s field is ``t`` and ``K == m``, so ``a[d,
# s, t]`` is ``wg[t * k + d, s]`` (module docstring). A grid step is one
# slot ``s`` of one block of lines and sees ``wg`` twice: ``col_ref`` [m *
# k, lines, 128] is ``wg[:, s]`` (``col_ref[t * k + d] == a[d, s, t]``) and
# ``row_ref`` [k, K, lines, 128] the ``k`` rows ``wg[s * k:, :]``
# (``row_ref[d, t] == a[d, t, s]``).

def _positional_terms_kernel(col_ref, row_ref, x_ref, r_ref, phi_ref,
                             reg_ref):
    from jax.experimental import pallas as pl

    s = pl.program_id(1)
    k, slots = row_ref.shape[:2]
    xs = x_ref[s]
    zero = jnp.zeros(phi_ref.shape, phi_ref.dtype)

    def squares(t, carry):
        phi, reg = carry
        sq = zero
        for d in range(k):
            sq = sq + col_ref[t * k + d] * col_ref[t * k + d]
        return phi, reg + jnp.where(xs * x_ref[t] != 0, sq, 0.0)

    def pair(t, carry):         # s < t: the pair's product, counted once
        phi, reg = squares(t, carry)
        dot = zero
        for d in range(k):
            dot = dot + col_ref[t * k + d] * row_ref[d, t]
        return phi + dot * (xs * x_ref[t]), reg

    phi, reg = jax.lax.fori_loop(
        s + 1, slots, pair, jax.lax.fori_loop(0, s, squares, (zero, zero)))

    @pl.when(s == 0)
    def _first():
        phi_ref[...] = zero
        reg_ref[...] = zero

    phi_ref[...] += phi
    reg_ref[...] += reg

    @pl.when(s == slots - 1)
    def _last():
        phi_ref[...] *= r_ref[...]


def _positional_grads_kernel(col_ref, row_ref, x_ref, r_ref, dphi_ref,
                             dreg_ref, out_ref, dwg_ref=None):
    from jax.experimental import pallas as pl

    # on the line side ``d wg[:, s]`` is built in a scratch and leaves as
    # lines, as in ``_grads_kernel``
    lines_ref, dwg_ref = (None, out_ref) if dwg_ref is None else (
        out_ref, dwg_ref)
    s = pl.program_id(1)
    k, slots = row_ref.shape[:2]
    g_phi = dphi_ref[...] * r_ref[...]
    g_reg = 2.0 * dreg_ref[...]
    xs = x_ref[s]

    def of_t(t, _):
        xx = xs * x_ref[t]
        of_phi = g_phi * xx
        of_reg = jnp.where(xx != 0, g_reg, 0.0)
        for d in range(k):
            dwg_ref[t * k + d] = (of_phi * row_ref[d, t]
                                  + of_reg * col_ref[t * k + d])

    jax.lax.fori_loop(0, s, of_t, None)
    jax.lax.fori_loop(s + 1, slots, of_t, None)
    for d in range(k):              # no pair (s, s)
        dwg_ref[s * k + d] = jnp.zeros(dwg_ref.shape[1:], dwg_ref.dtype)
    if lines_ref is None:
        return
    width, lanes = dwg_ref.shape[0], lines_ref.shape[-1]
    for line in range(dwg_ref.shape[1]):
        lines_ref[line] = jnp.concatenate([
            dwg_ref[:, line, :],
            jnp.zeros((lanes - width, _LANES), dwg_ref.dtype)]).T


def _positional_call(kernel, name: str, num_fields: int, wg, rest, outs,
                     interpret: bool, out_specs=None, scratch=()):
    """``kernel`` over a grid of (blocks of :func:`_block_lines` lines,
    slots), the slot innermost: ``rest`` (``values`` [K, L, 128] and
    vectors [L, 128]) and, where no ``out_specs`` say otherwise, the
    results keep their block over a block's slots. A step holds 1.3 MB of
    ``wg`` at 39 fields and 4 factors (0.4 MB at 11) and, on the line
    side, a 1 MB block of lines (0.5 MB): no cut, no VMEM to ask for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    width, slots, lines = wg.shape[:3]
    assert slots == num_fields, (slots, num_fields)
    step = _block_lines(lines)
    assert lines % step == 0, (lines, step)

    def kept(x):            # [..., L, 128]: the block's lines, every slot
        lead = x.shape[:-2]
        return pl.BlockSpec(lead + (step, _LANES), lambda i, s: (
            0,) * len(lead) + (i, 0))

    return pl.pallas_call(
        kernel,
        grid=(lines // step, slots),
        in_specs=[
            pl.BlockSpec((width, None, step, _LANES),
                         lambda i, s: (0, s, i, 0)),
            pl.BlockSpec((width // num_fields, slots, step, _LANES),
                         lambda i, s: (s, 0, i, 0)),
            *(kept(x) for x in rest)],
        out_specs=out_specs or [kept(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM(shape, wg.dtype) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(wg, wg, *rest)


def _positional_grads(wg, rest, num_fields: int, interpret: bool,
                      lines: bool):
    """``pair_grads_pallas`` with no field plane: a grid step writes ``d
    wg[:, s]`` of its block, as columns or as the slot's lines."""
    from jax.experimental import pallas as pl

    width, slots, count = wg.shape[:3]
    step, lanes = _block_lines(count), _sw.line_lanes(width)
    if lines:
        shape, scratch = (slots, count, _LANES, lanes), [(width, step, _LANES)]
        spec = pl.BlockSpec((None, step, _LANES, lanes),
                            lambda i, s: (s, i, 0, 0))
    else:
        shape, scratch = wg.shape, []
        spec = pl.BlockSpec((width, None, step, _LANES),
                            lambda i, s: (0, s, i, 0))
    (out,) = _positional_call(
        _positional_grads_kernel, "ffm_pair_grads", num_fields, wg, rest,
        [jax.ShapeDtypeStruct(shape, wg.dtype)], interpret, [spec], scratch)
    return out.reshape(-1, lanes) if lines else out


def ffm_pair_terms_kernel(rows: jax.Array, fields: Optional[jax.Array],
                          values: jax.Array, num_fields: int,
                          width: Optional[int] = None):
    """:func:`ffm_pair_terms` on the kernels, with their own backward: the
    positional pair where ``fields`` is None, the general one for a plane.
    The batch is padded with empty rows to whole blocks and cut into lines
    of 128 (a batch of under ``BLOCK_ROWS`` rows is one block of as many
    lines as it has), and ``rows`` reaches the kernels' ``[m * k, K, L,
    128]`` by one transpose: through a ``[m, k, K, B]`` array it would be
    two passes, whose tiles hold 8 slots of 128 rows where the kernels'
    hold 8 lines. ``rows`` wider than ``width`` are lines
    (:func:`ffm_pair_terms`)."""
    batch = rows.shape[1]
    lines = -(-batch // _LANES)
    lines = -(-lines // _block_lines(lines)) * _block_lines(lines)

    def blocked(x, axis):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, lines * _LANES - batch)
        x = jnp.pad(x, pad)
        return x.reshape(x.shape[:axis] + (lines, _LANES)
                         + x.shape[axis + 1:])

    rest = (None if fields is None else blocked(fields, 1),
            blocked(values, 1), blocked(_inverse_norm(values), 0))
    if width is not None and rows.shape[-1] > width:
        phi, reg = _lined_terms(num_fields, width, blocked(rows, 1), *rest)
    else:
        phi, reg = _blocked_terms(
            num_fields, jnp.transpose(blocked(rows, 1), (3, 0, 1, 2)), *rest)
    return phi.reshape(-1)[:batch], reg.reshape(-1)[:batch]


def ffm_pair_terms(rows: jax.Array, fields: Optional[jax.Array],
                   values: jax.Array, num_fields: int,
                   num_factors: Optional[int] = None):
    """``(phi [B], reg [B])`` of the module docstring from the gathered
    table rows ``rows`` [K, B, m * k] (column ``f * k + d`` is factor ``d``
    for field ``f``), the slots' field ids ``fields`` [K, B] (integers) and
    ``values`` [K, B], differentiable with respect to ``rows``. ``fields``
    None says that slot ``t``'s field is ``t`` in every row (``K == m``:
    the columns of a table). Called while a step is traced: picks the route
    (:func:`ffm_interaction_route`) and counts it in
    ``ffm_interaction_route{route=, reason=, fields=}``, ``fields`` saying
    whether the op read a slot's field off its ``position`` or a ``plane``.

    With ``num_factors`` said, ``rows`` may come as the gather's lines,
    ``[K, B, lanes]`` with the ``m * k`` columns on a line's first lanes
    (``table_rows(lines=True)``): their cotangent is then lines too, which
    the backward kernel writes itself and the update's permute takes as
    they are; counted in ``table_slot_layout{op="pair_grads"}``."""
    slots = rows.shape[0]
    _check(fields is not None or slots == num_fields,
           f"ffm_pair_terms: with no field plane slot t is field t, and "
           f"{slots} slots are not {num_fields} fields")
    route, reason = ffm_interaction_route(rows.shape[1], rows.dtype)
    _telemetry.REGISTRY.counter(
        _telemetry.FFM_INTERACTION_ROUTE_METRIC, route=route, reason=reason,
        fields="position" if fields is None else "plane").inc(1)
    width = rows.shape[-1] if num_factors is None else (
        num_fields * num_factors)
    if fields is not None:
        fields = fields.astype(jnp.int32)
    values = jax.lax.stop_gradient(values)
    if route != "kernel":
        if fields is None:      # the plain form selects on a plane
            fields = jnp.broadcast_to(
                jnp.arange(slots, dtype=jnp.int32)[:, None], values.shape)
        return ffm_pair_terms_xla(rows[..., :width], fields, values,
                                  num_fields)
    _telemetry.REGISTRY.counter(
        _telemetry.TABLE_SLOT_LAYOUT_METRIC, op="pair_grads",
        layout="lines" if rows.shape[-1] > width else "columns").inc(1)
    return ffm_pair_terms_kernel(rows, fields, values, num_fields, width)
