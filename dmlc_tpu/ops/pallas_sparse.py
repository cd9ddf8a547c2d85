"""Pallas TPU kernel for the ELL sparse matvec.

XLA lowers ``jnp.take`` (ops/sparse.ell_matvec) to an HBM-bound dynamic
gather per batch element. This kernel instead keeps the weight vector
resident in VMEM across the whole batch grid and turns the gather into
one-hot contractions — compare + multiply + reduce, all VPU/MXU-friendly
primitives with static shapes, no HBM gather traffic.

out[b] = sum_k w[idx[b, k]] * val[b, k]

Form: inputs are fed K-MAJOR (``[K8, B]``, K padded to a multiple of 8
with zero-valued slots) so the K loop lives in the GRID with ``(8, bb)``
blocks — both block dims satisfy the (8, 128) tiling rule, every index is
static, and the kernel body unrolls exactly 8 compare+accumulate steps
regardless of K. A VMEM scratch holds the one-hot slab ``[D, bb]`` across
the sequential k steps (TPU grids iterate the last dimension innermost);
the final k step contracts ``w[1, D] @ slab`` on the MXU. Compiled by
Mosaic under libtpu 0.0.34 on a TPU v5e at D=4096/K=64, D=2048/K=64 and
D=1024/K=48 (``chip_smoke.py`` phase 2). Earlier forms — a statically
unrolled K loop, dynamic lane-dimension slices, K as a ``(bb, 1)``-blocked
grid dimension — were rejected by an earlier compiler and have not been
retried on this one.

Why there is NO pallas kernel for high D (the KDD/1M regime), by
construction rather than by un-tuned accident:
- the one-hot algorithm is O(B*K*D) compare-multiply work — at D = 2^20
  it is arithmetically disqualified regardless of lowering;
- an in-kernel VMEM table gather (O(B*K) work) is not expressible:
  Mosaic's dynamic-gather primitive requires input/indices/output of THE
  SAME 2D shape (per-lane shuffles), i.e. it cannot index a [D] table
  with [B, K] indices ("Only 2D gather is supported" / "Shape mismatch
  in input, indices and output");
- a scalar-core loop over B*K VMEM loads costs ~B*K cycles (~140 us at
  8192x16), ~6x worse than XLA's measured 24 us gather at kdd_like.
So beyond the VMEM slab budget the right lowering IS XLA's native
gather, and :func:`ell_matvec_auto` routes there; the A/B on record is
SPARSE_TPU_r03.json (from an earlier installation, not re-measured).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dmlc_tpu.ops.sparse import EllBatch, ell_matvec as _xla_ell_matvec

_KTILE = 8  # sublane tile: K is padded to a multiple of this


def _ell_kernel(idx_ref, val_ref, w_ref, out_ref, slab_ref):
    import jax.experimental.pallas as pl

    k = pl.program_id(1)
    num_k = pl.num_programs(1)
    num_d = w_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (num_d, 1), 0)

    @pl.when(k == 0)
    def _init():
        slab_ref[...] = jnp.zeros_like(slab_ref)

    # 8 static compare+accumulate steps per grid step: padded slots carry
    # value 0, so they add nothing regardless of their index
    slab = slab_ref[...]
    for j in range(_KTILE):
        idx_j = idx_ref[j:j + 1, :]                       # [1, bb], static
        val_j = val_ref[j:j + 1, :]
        slab += val_j * (idx_j == iota).astype(jnp.float32)  # [D, bb]
    slab_ref[...] = slab

    @pl.when(k == num_k - 1)
    def _contract():
        # full-f32 dot: the MXU's default bf16 operands lose ~1e-2 here
        out_ref[...] = jnp.dot(w_ref[...], slab_ref[...],
                               precision=jax.lax.Precision.HIGHEST)  # [1, bb]


# Mosaic's default scoped-VMEM limit on a TPU v5e (libtpu 0.0.34): a
# kernel whose blocks, scratch and temporaries exceed it fails to compile
# ("Scoped allocation ... exceeded scoped vmem limit"). Both tile pickers
# (here and ops/device_decode.py) budget against this one number.
SCOPED_VMEM_BYTES = 16 << 20
# the footprint model below read up to 0.7 MiB under the compiler's figure
# (D=4480, bb=256: 16.34 MiB against 15.63), so this kernel keeps 1 MiB back
_ELL_VMEM_BUDGET = SCOPED_VMEM_BYTES - (1 << 20)


def _kernel_vmem_bytes(num_d: int, bb: int) -> int:
    """Model of the kernel's scoped-VMEM footprint at lane tile
    ``bb``, term by term as Mosaic allocates it (calibrated against the
    compiler's own "Scoped allocation with size" figures at D=4480..16384:
    within 5% either way — hence ``_ELL_VMEM_BUDGET``'s headroom)."""
    slab = num_d * bb * 4
    return (3 * slab                # scratch slab + its loaded value + one
                                    # compare/product temporary
            + num_d * 128 * 4       # the [D, 1] iota column, lane-padded
            + 2 * 8 * num_d * 4     # (1, D) weight block: 8 sublanes, x2 buffers
            + 2 * 2 * _KTILE * bb * 4   # idx + val blocks, double-buffered
            + 2 * 8 * bb * 4)       # (1, bb) output block, x2 buffers


def _valid_block_b(num_b: int, num_d: int, bb: int,
                   vmem_budget: int = _ELL_VMEM_BUDGET) -> bool:
    """Would the hardware kernel accept this lane tile? The single source
    of truth for the tile constraints — Mosaic lane alignment (bb in
    {128, 256}), B divisibility, and the kernel's whole VMEM footprint
    (:func:`_kernel_vmem_bytes`, not just the slab) within the scoped
    limit — shared with the bench grid sweep so its tile list can never
    diverge from what the kernel enforces."""
    return (bb in (256, 128) and num_b % bb == 0
            and _kernel_vmem_bytes(max(num_d, 1), bb) <= vmem_budget)


def _pick_block_b(num_b: int, num_d: int,
                  vmem_budget: int = _ELL_VMEM_BUDGET) -> int:
    """Largest lane-aligned tile (128 or 256) dividing B whose footprint
    fits the VMEM budget; 0 when none exists.

    bb sits in the LANE dimension of the kernel's (8, bb)/(1, bb) blocks,
    and Mosaic requires lane tiles to be multiples of 128 — a smaller bb
    lowers in interpret mode but fails on hardware, so rather than rely on
    caller guards this returns 0 and the entry point refuses loudly."""
    for bb in (256, 128):
        if _valid_block_b(num_b, num_d, bb, vmem_budget):
            return bb
    return 0


def _pick_block_b_interpret(num_b: int, num_d: int,
                            slab_budget: int = 4 << 20) -> int:
    """Interpret-mode tile pick: any power-of-2 (Mosaic constraints do not
    apply off-hardware), so small-shape correctness tests stay cheap."""
    limit = max(8, slab_budget // max(num_d * 4, 1))
    bb = 1
    while bb * 2 <= min(num_b, 256, limit) and num_b % (bb * 2) == 0:
        bb *= 2
    return bb


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def ell_matvec_pallas(
    weights: jax.Array,
    indices: jax.Array,
    values: jax.Array,
    *,
    block_b: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Pallas ELL matvec (one-hot slab). block_b=0 picks a VMEM-sized tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if weights.ndim != 1:
        raise ValueError(
            f"ell_matvec_pallas: weights must be a [D] table, got shape "
            f"{weights.shape} — multinomial [D, C] tables route through the "
            f"XLA gather (ell_matvec)")
    num_b, num_k = indices.shape
    num_d = weights.shape[0]
    if block_b == 0:
        block_b = (_pick_block_b_interpret(num_b, num_d) if interpret
                   else _pick_block_b(num_b, num_d))
        if block_b == 0:
            raise ValueError(
                f"ell_matvec_pallas: no Mosaic-lane-aligned tile for "
                f"B={num_b}, D={num_d} (need B % 128 == 0 and the kernel's "
                f"footprint at a 128-lane tile within VMEM) — use "
                f"ell_matvec_auto / the XLA gather")
    assert num_b % block_b == 0, (num_b, block_b)
    k8 = -(-num_k // _KTILE) * _KTILE
    # K-major layout, K padded to the sublane tile with zero-valued slots
    idx_t = jnp.zeros((k8, num_b), jnp.int32).at[:num_k].set(
        indices.astype(jnp.int32).T)
    val_t = jnp.zeros((k8, num_b), jnp.float32).at[:num_k].set(values.T)
    grid = (num_b // block_b, k8 // _KTILE)
    out = pl.pallas_call(
        _ell_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_KTILE, block_b), lambda i, k: (k, i)),
            pl.BlockSpec((_KTILE, block_b), lambda i, k: (k, i)),
            pl.BlockSpec((1, num_d), lambda i, k: (0, 0)),  # resident w
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, num_b), jnp.float32),
        scratch_shapes=[pltpu.VMEM((num_d, block_b), jnp.float32)],
        interpret=interpret,
    )(idx_t, val_t, weights[None, :])
    return out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ell_matvec_pallas_ad(weights, indices, values, interpret=False):
    """Differentiable wrapper: pallas forward, XLA backward.

    ``pallas_call`` has a JVP rule but NO transpose rule in current JAX, so
    reverse-mode AD through the raw kernel fails at trace time. The VJP of
    ``out[b] = sum_k w[idx[b,k]] * val[b,k]`` is closed-form: a scatter-add
    for dw and a gather for dval — both standard XLA lowerings, so training
    steps (value_and_grad) can route through the kernel's fast forward.
    """
    return ell_matvec_pallas(weights, indices, values, interpret=interpret)


def _ell_ad_fwd(weights, indices, values, interpret=False):
    return (_ell_matvec_pallas_ad(weights, indices, values, interpret),
            (weights, indices, values))


def _ell_ad_bwd(interpret, res, g):
    weights, indices, values = res
    dw = jnp.zeros_like(weights).at[indices].add(values * g[:, None])
    dval = jnp.take(weights, indices, axis=0) * g[:, None]
    return dw, None, dval


_ell_matvec_pallas_ad.defvjp(_ell_ad_fwd, _ell_ad_bwd)


# the measured pallas win band, inclusive (SPARSE_TPU_r05.json): see
# pallas_band() and the ell_matvec_auto docstring for the evidence
_BAND_D_LO = 512
_BAND_D_HI = 4096


def _on_tpu_backend() -> bool:
    """The auto-route's hardware gate (separate so tests can monkeypatch
    it and exercise the routing wire off-chip in interpret mode)."""
    return jax.default_backend() == "tpu"


def pallas_band(num_b: int, num_d: int, weights_ndim: int = 1) -> bool:
    """True iff (B, D) sits in the pallas kernel's measured win band.

    The band (SPARSE_TPU_r05.json, TPU v5 lite): lane-aligned
    D in [512, 4096] — D a multiple of 128 so the [1, D] weight block and
    the [D, bb] slab tile cleanly — with B lane-aligned and the slab
    within the VMEM budget (``_pick_block_b`` != 0), and a 1-D weight
    table (the kernel is a [D]-table matvec only; multinomial [D, C]
    tables stay on the XLA gather). Everything outside routes to the
    gather: D=28 dense-in-sparse loses (23.7 vs 16.2 us) and high D is
    disqualified by construction (module docstring).
    """
    return (weights_ndim == 1
            and _BAND_D_LO <= num_d <= _BAND_D_HI
            and num_d % 128 == 0
            and _pick_block_b(num_b, num_d) != 0)


def ell_matvec_auto(weights: jax.Array, batch: EllBatch,
                    use_pallas: Optional[bool] = None) -> jax.Array:
    """ELL matvec: routes to the pallas kernel in its measured win band
    on TPU, the XLA gather everywhere else.

    Routing data (SPARSE_TPU_r05.json, TPU v5 lite, taken on an earlier
    installation and not re-measured on this one): the grid-K kernel won
    at D=512/K=32 (16.1 vs 17.5 us), D=2048/K=64 (16.1 vs 33.2 us) and
    D=4096/K=64 (22.3 vs 24.9 us); it lost at D=28/K=28 (23.7 vs 16.2 us
    — dense-in-sparse belongs on the gather or a dense matmul) and once
    in-band at D=1024/K=48 (52.1 vs 17.5 us, same block_b=256 as the
    winning shapes — ROADMAP S5 re-runs the grid,
    bench_sparse_tpu.py with DMLC_SPARSE_GRID=1). For high D the XLA
    gather is the right lowering by construction — see the module
    docstring. The default (``use_pallas=None``) therefore routes to the
    kernel exactly for lane-aligned D in [512, 4096]
    (:func:`pallas_band`) on a TPU backend. ``use_pallas=True``/``False``
    force either path (a forced True off-band still enforces the
    kernel's shape requirements and raises loudly).
    """
    if use_pallas is None:
        use_pallas = (
            pallas_band(batch.indices.shape[0], weights.shape[0],
                        weights.ndim)
            and _on_tpu_backend())
    if not use_pallas:
        return _xla_ell_matvec(weights, batch)
    return _ell_matvec_pallas_ad(
        weights, jnp.asarray(batch.indices), jnp.asarray(batch.values))
