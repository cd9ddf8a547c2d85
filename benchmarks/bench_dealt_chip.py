"""One chip's work in the dealt field-aware FM's step (kdd12_ffm_ps4), on
one chip, by operation (PERF.md §6, PR 42):

    chiprun -- python3 benchmarks/bench_dealt_chip.py [--hot-every N]

``FFMLearner(mesh=)`` over a mesh of one device is a deal of one shard: the
chip owns every id, so its buckets hold all of its real slots and the
exchange's capacity is that of the four-chip cell's chip (a shard of
13,671,614 rows, 16,384 rows of 16 slots with 11 real: 262,144 slots out,
327,680 received). Every operation of a four-chip step but the
collectives, which are copies here, runs at the shapes it has there; four
chips are needed only for what crosses them. ``--hot-every N``: every Nth
batch names one id in 12 of its 16 slots, so that step takes the road with
no capacity (on one shard: the chip's own 262,144 slots, a quarter of what
a chip of four gathers there).

Steps are traced by the profiler; one JSON line per operation of the step
that takes 0.1 ms or more (ms a step, mean over the traced steps), largest
first, then the sums under the walk's five scopes and ``exchange_permute``
with what ``table_slot_groups`` counted, then the step's device time and
``fallback_steps``. Needs a TPU. One shard is one bucket of 327,680 slots
with 180,224 real: the owner's un-permute has 9 of its 16 runs live here
and 12 on four chips (three of every bucket's four); the other three
permutes are the cell's (PERF.md §6, PR 51).
"""

from __future__ import annotations

import json
import sys

import _common  # first: the path, the compile cache

import jax
import numpy as np

from dmlc_tpu.models import FFMLearner
from dmlc_tpu.ops import sorted_walk, table_exchange
from dmlc_tpu.ops.sparse import EllBatch
from dmlc_tpu.parallel import make_mesh
from dmlc_tpu.utils import telemetry

ROWS, M, F, B, K, REAL = 13_671_613, 11, 4, 16_384, 16, 11
STEPS = 24


def batches(n: int, hot_every: int, shardings):
    rng = np.random.default_rng(42)
    out = []
    for i in range(n):
        idx = rng.integers(0, ROWS, (B, K)).astype(np.int32)
        val = np.ones((B, K), np.float32)
        if hot_every and i % hot_every == 0:
            idx[:, :12], val[:, 12:] = 17, 0.0
            idx[:, 12:] = ROWS
        else:
            idx[:, REAL:], val[:, REAL:] = ROWS, 0.0
        batch = EllBatch(idx, val, rng.integers(0, 2, B).astype(np.float32),
                         np.ones(B, np.float32),
                         np.tile(np.arange(K) % M, (B, 1)).astype(np.uint8))
        out.append(EllBatch(*(jax.device_put(a, sh)
                              for a, sh in zip(batch, shardings))))
    return out


def main() -> int:
    device = jax.devices()[0]
    assert device.platform == "tpu", f"needs a TPU, found {device.platform}"
    hot_every = int(sys.argv[sys.argv.index("--hot-every") + 1]) \
        if "--hot-every" in sys.argv else 0
    model = FFMLearner(ROWS, M, F, mesh=make_mesh(devices=[device]))
    feed = batches(8, hot_every, model.batch_shardings())
    for b in feed[:2]:
        jax.block_until_ready(model.step(b))
    found, by_scope = _common.traced_steps(
        model, feed, STEPS,
        sorted_walk.WALK_SCOPES + (table_exchange.PERMUTE_SCOPE,))
    print(json.dumps({
        "ms_a_step_by_scope": by_scope,
        "table_slot_groups": telemetry.table_slot_groups()}), flush=True)
    print(json.dumps({
        "device": device.device_kind, "steps": STEPS, "hot_every": hot_every,
        "step_device_ms": round(
            found["step"]["device_s_per_execution"] * 1e3, 3),
        "busy_s": found["busy_s"], "window_s": found["window_s"],
        "fallback_steps": model.fallback_steps(),
        "shard_slots": model.shard_slots()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
