"""Raw host->HBM transfer floor for BASELINE config #1's bytes.

Times repeated-shape ``jax.device_put`` of config #1's dense batches
([8192, 28] f32 and bf16) with NO parsing attached. Purpose: if
raw transfer alone is at or below the
host-only parse rate, config #1's f32 ratio is a link-bandwidth floor on
this host, not a pipeline defect — the pipeline's job is to hide parse
behind transfer, and it cannot ship bytes faster than the link. Conversely
a floor well above the pipeline's rate would indict the pipeline.

One JSON line; vs_baseline is 0.0 (the comparison target is a host-only
parse rate, which this script does not take).
"""

import numpy as np

from _common import TARGET_MB, emit, log, timed_stats


import jax  # noqa: E402

BATCH, NUM_COL = 8192, 28  # HIGGS-like dense batch (BASELINE config #1)


def run() -> None:
    rng = np.random.default_rng(0)
    x32 = rng.standard_normal((BATCH, NUM_COL)).astype(np.float32)
    batch_mb = x32.nbytes / 2**20
    n = max(8, int(min(TARGET_MB, 256) / batch_mb))

    def leg(arr):
        def f():
            handles = [jax.device_put(arr) for _ in range(n)]
            jax.block_until_ready(handles)
        return f

    dev = jax.devices()[0]
    log(f"transfer floor: device {dev}, {n} x {batch_mb:.2f} MB batches")
    jax.block_until_ready(jax.device_put(x32))  # transfer-plan warmup
    mb = n * batch_mb
    best, med, times = timed_stats(leg(x32), reps=5)
    log(f"f32 device_put: {mb / best:.1f} MB/s best, {mb / med:.1f} median")

    from dmlc_tpu.native import bf16_dtype

    x16 = x32.astype(bf16_dtype())
    jax.block_until_ready(jax.device_put(x16))
    mb16 = n * x16.nbytes / 2**20
    b16, m16, _ = timed_stats(leg(x16), reps=5)
    log(f"bf16 device_put: {mb16 / b16:.1f} MB/s best, {mb16 / m16:.1f} median")

    # per-ARRAY overhead probe: the pipeline ships each batch as ONE
    # device_put call of [x, y, w] (1.8 MB + 64 KB + 64 KB). If the link
    # charges per array rather than per call, the two small aux arrays tax
    # every batch and packing label/weight into x's trailing columns
    # (native repack) would pay; if the delta is noise, packing is
    # pointless ABI churn. This leg decides with data.
    y = rng.standard_normal(BATCH).astype(np.float32)
    w = np.ones(BATCH, np.float32)

    def leg3():
        handles = [jax.device_put([x32, y, w]) for _ in range(n)]
        jax.block_until_ready(handles)

    jax.block_until_ready(jax.device_put([x32, y, w]))
    mb3 = n * (x32.nbytes + y.nbytes + w.nbytes) / 2**20
    b3, m3, _ = timed_stats(leg3, reps=5)
    log(f"f32 [x,y,w] device_put: {mb3 / b3:.1f} MB/s best, "
        f"{mb3 / m3:.1f} median (aux-array overhead vs x-only: "
        f"{(mb / med) / (mb3 / m3):.3f}x)")

    emit("device_put_floor_mb_per_sec", mb / best, "MB/s", 0.0,
         median=mb / med,
         spread=[round(mb / max(times), 2), round(mb / min(times), 2)],
         reps=5,
         bf16_mb_per_sec=round(mb16 / b16, 2),
         bf16_median=round(mb16 / m16, 2),
         # corpus-equivalent rates: config #1's text rows are ~110 B and
         # ship as 112 B (f32) / 56 B (bf16) of x — the bf16 wire rate
         # DOUBLES the corpus MB/s the same link can sustain
         bf16_corpus_equiv=round(2 * mb16 / b16, 2),
         xyw_mb_per_sec=round(mb3 / b3, 2),
         xyw_median=round(mb3 / m3, 2),
         aux_overhead_median=round((mb / med) / (mb3 / m3), 3))


if __name__ == "__main__":
    run()
