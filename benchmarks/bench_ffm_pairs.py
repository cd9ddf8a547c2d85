"""Time the field-aware FM's pair terms (ops/ffm_pairs.py) on the chip,
alone, at kdd12_ffm's shape: the two kernels (the backward's also with
``d wg`` leaving as lines, beside the XLA passes that replaces: PR 47), the
transposes that bring the gathered rows to them and back, and value and
gradient on each route, which must agree (PERF.md §6, PR 36):

    chiprun -- python3 benchmarks/bench_ffm_pairs.py [--rows N]

``--rows`` takes another batch (16,384: a chip's share of
kdd12_ffm_ps4's). Rows hold 11 real slots of 16, as the cells' do, a
twentieth of them fewer. One JSON line per timing (median ms of five warm
calls); needs a TPU.

    chiprun -- python3 benchmarks/bench_ffm_pairs.py --columns 39 --rows 16384

(PR 56) times the pair kernels on a table's id columns instead, ``--columns``
slots of as many fields, every value 1 (criteo_ffm's 39 at 16,384 rows;
kdd12_ffm_csv's 11 at 65,536): the positional kernels (no field plane)
beside the general ones fed ``fields = arange``, eight calls in flight a
timing so that the chip sets the pace; ``d wg`` must be equal bit for bit.
"""

from __future__ import annotations

import json
import sys
import time

from _common import timed_stats       # first: the path, the compile cache

import jax
import jax.numpy as jnp
import numpy as np

from dmlc_tpu.ops import ffm_pairs as fp

M, F, K = 11, 4, 16
B = int(sys.argv[sys.argv.index("--rows") + 1]) if "--rows" in sys.argv \
    else 65_536


def timed(name: str, fn, *args, **note):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    _, median, _ = timed_stats(lambda: jax.block_until_ready(fn(*args)), 5)
    print(json.dumps(dict(note, name=name, rows=B,
                          first_call_s=round(first, 2),
                          ms=round(median * 1e3, 3))), flush=True)
    return out


def columns(m: int) -> None:
    """The csv cells' pair terms: ``m`` id columns, positional and general."""
    lines = B // 128
    rng = np.random.default_rng(56)
    wg = jnp.asarray(rng.uniform(0, 0.5, (m * F, m, lines, 128)).astype(
        np.float32))
    values = jnp.ones((m, lines, 128), jnp.float32)
    plane = jnp.broadcast_to(
        jnp.arange(m, dtype=jnp.int32)[:, None, None], values.shape)
    r = jnp.full((lines, 128), 1.0 / m, jnp.float32)
    cots = [jnp.asarray(rng.normal(size=(lines, 128)).astype(np.float32))
            for _ in range(2)]

    def paced(name, fn, *args, calls=8):
        out = jax.block_until_ready(fn(*args))
        _, median, _ = timed_stats(lambda: jax.block_until_ready(
            [fn(*args) for _ in range(calls)]), 5)
        print(json.dumps(dict(name=name, columns=m, rows=B, ms=round(
            median / calls * 1e3, 3))), flush=True)
        return out

    got = {}
    for side, fields in (("positional", None), ("general", plane)):
        got[side] = (
            paced(f"pair_terms_kernel_{side}", lambda: fp.pair_terms_pallas(
                wg, fields, values, r, num_fields=m)),
            paced(f"pair_grads_kernel_{side}", lambda: fp.pair_grads_pallas(
                wg, fields, values, r, *cots, num_fields=m)),
            paced(f"pair_grads_kernel_lines_{side}", lambda:
                  fp.pair_grads_pallas(wg, fields, values, r, *cots,
                                       num_fields=m, lines=True)))
    (terms, dwg, as_lines), (terms_g, dwg_g, as_lines_g) = (
        got["positional"], got["general"])
    gaps = {name: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for name, a, b in zip(("phi", "reg"), terms, terms_g)}
    print(json.dumps({"name": "positional_against_general", "d_wg_equal": bool(
        jnp.array_equal(dwg, dwg_g) and jnp.array_equal(as_lines, as_lines_g)),
        "max_gap_over_max": gaps}), flush=True)
    assert max(gaps.values()) < 1e-5, gaps


def main() -> None:
    device = jax.devices()[0]
    assert device.platform == "tpu", f"needs a TPU, found {device.platform}"
    print(json.dumps({"device": device.device_kind}), flush=True)
    if "--columns" in sys.argv:
        return columns(int(sys.argv[sys.argv.index("--columns") + 1]))
    rng = np.random.default_rng(36)
    rows = jnp.asarray(rng.uniform(0, 0.5, (K, B, M * F)).astype(np.float32))
    fields = np.tile((np.arange(K) % M)[:, None], (1, B)).astype(np.int32)
    values = rng.uniform(0.5, 2.0, (K, B)).astype(np.float32)
    real = np.where(rng.random(B) < 0.05, rng.integers(1, M, B), M)
    values[np.arange(K)[:, None] >= real[None, :]] = 0.0
    fields, values = jnp.asarray(fields), jnp.asarray(values)
    w_phi, w_reg = (jnp.asarray(rng.normal(size=B).astype(np.float32))
                    for _ in range(2))

    def loss(route):
        terms = getattr(fp, f"ffm_pair_terms_{route}")

        def of_rows(rows):
            phi, reg = terms(rows, fields, values, M)
            return jnp.sum(phi * w_phi) + 1e-3 * jnp.sum(reg * w_reg), (
                phi, reg)

        return jax.jit(jax.value_and_grad(of_rows, has_aux=True))

    lines = B // 128
    wg = jnp.transpose(rows.reshape(K, lines, 128, M * F), (3, 0, 1, 2))
    blocked = [fields.reshape(K, lines, 128), values.reshape(K, lines, 128),
               fp._inverse_norm(values).reshape(lines, 128)]
    timed("transpose_to_the_kernel", jax.jit(lambda x: jnp.transpose(
        x.reshape(K, lines, 128, M * F), (3, 0, 1, 2))), rows)
    timed("transpose_back", jax.jit(lambda x: jnp.transpose(
        x, (1, 2, 3, 0)).reshape(K, B, M * F)), wg)
    timed("pair_terms_kernel", lambda *a: fp.pair_terms_pallas(
        *a, num_fields=M), wg, *blocked)
    cots = (w_phi.reshape(lines, 128), w_reg.reshape(lines, 128))
    dwg = timed("pair_grads_kernel", lambda *a: fp.pair_grads_pallas(
        *a, num_fields=M), wg, *blocked, *cots)
    # PR 47: the same cotangent leaving as the slots' [K * B, 128] lines,
    # transposed block by block in VMEM, beside the XLA passes that take
    # the lane-major ``d wg`` there (the backward's copy and pad before its
    # permute)
    as_lines = timed("pair_grads_kernel_lines", lambda *a:
                     fp.pair_grads_pallas(*a, num_fields=M, lines=True),
                     wg, *blocked, *cots)
    by_xla = timed("xla_lines_of_d_wg", jax.jit(lambda x: jnp.pad(
        jnp.transpose(x, (1, 2, 3, 0)).reshape(K * B, M * F),
        ((0, 0), (0, as_lines.shape[1] - M * F)))), dwg)
    print(json.dumps({"name": "pair_grads_lines_bits", "equal": bool(
        jnp.array_equal(as_lines, by_xla))}), flush=True)
    del dwg, as_lines, by_xla
    got = {route: timed(f"value_and_grad_{route}", loss(route), rows)
           for route in ("kernel", "xla")}
    (_, (phi, reg)), grad = got["kernel"]
    (_, (phi_x, reg_x)), grad_x = got["xla"]
    gaps = {name: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for name, a, b in (("phi", phi, phi_x), ("reg", reg, reg_x),
                               ("grad", grad, grad_x))}
    print(json.dumps({"name": "kernel_against_xla", "max_gap_over_max": gaps}))
    assert max(gaps.values()) < 1e-5, gaps


if __name__ == "__main__":
    main()
