"""One chip's work in the row-laid FM's step (kdd12_fm_dp4), on one chip,
by operation (PERF.md §6, PR 54):

    chiprun -- python3 benchmarks/bench_laid_chip.py [--chips 3,0]

``FMLearner(mesh=)`` over a mesh of one device lays the tables as one
range. Handed a shard's rows (13,671,614 of the cell's 54,686,456) and the
cell's whole batch (65,536 rows of 16 slots, 11 real, drawn by the cell's
own generator) with the ids of chip ``c``'s range brought to ``[0, L)`` and
every other id sent past the table, the one chip does what chip ``c`` of
four does: it sorts 1,048,576 slots of which it owns chip ``c``'s share
(chips 0 and 1 half of field 0 each, chip 2 field 1, chip 3 fields 2 to 10:
589,824 slots), reads and updates those, and skips the runs of the batch's
columns it owns nothing of. Every operation of a four-chip step but the
collectives, which are copies here, runs at the shapes it has there, except
the margin and the loss, which take the whole batch's 65,536 rows where a
chip of four takes 16,384. Four chips are needed only for what crosses
them.

Steps are traced by the profiler; for every chip asked for, one JSON line
per operation of the step that takes 0.1 ms or more (ms a step, mean over
the traced steps), largest first, then the sums under the walk's five
scopes with what ``table_slot_groups`` counted, the step's device time, the
walk's books and ``shard_slots``. Needs a TPU.
"""

from __future__ import annotations

import json
import sys

import _common  # first: the path, the compile cache

import jax
import numpy as np

from cellbench.generators import fields_zipf_libfm
from dmlc_tpu.models import FMLearner
from dmlc_tpu.ops import sorted_walk
from dmlc_tpu.ops.sparse import EllBatch
from dmlc_tpu.parallel import make_mesh
from dmlc_tpu.utils import telemetry

NUM_FEATURES, SHARDS, F, B, K, FIELDS = 54_686_452, 4, 8, 65_536, 16, 11
LOCAL = -(-(NUM_FEATURES + 1) // SHARDS)
STEPS = 24


def batches(n: int, chip: int, shardings):
    params = dict(num_features=NUM_FEATURES, fields=FIELDS, zipf_s=1.1,
                  label_noise=1.0)
    out = []
    for i in range(n):
        ids, labels = fields_zipf_libfm.draw_rows(
            params, np.random.SeedSequence([42, i]), B)
        # chip ``chip``'s range at [0, LOCAL), the others' ids past it
        # (the one-shard layout owns nothing there)
        mine = ids // LOCAL == chip
        idx = np.full((B, K), LOCAL - 1, np.int64)          # the sink
        idx[:, :FIELDS] = np.where(mine, ids - chip * LOCAL,
                                   LOCAL + ids % LOCAL)
        val = np.zeros((B, K), np.float32)
        val[:, :FIELDS] = 1.0
        batch = EllBatch(idx.astype(np.int32), val,
                         labels.astype(np.float32), np.ones(B, np.float32))
        out.append(EllBatch(*(jax.device_put(a, sh) for a, sh in zip(
            batch, shardings[:4]))))
    return out


def one_chip(device, chip: int) -> None:
    model = FMLearner(LOCAL - 1, F, layout="ell",
                      mesh=make_mesh(devices=[device]))
    assert model.deal.padded_rows == LOCAL
    feed = batches(8, chip, model.batch_shardings())
    for b in feed[:2]:
        jax.block_until_ready(model.step(b))
    found, by_scope = _common.traced_steps(
        model, feed, STEPS, sorted_walk.WALK_SCOPES + (
            "table_exchange", "fm_gather", "fm_optimizer"), chip=chip)
    print(json.dumps({
        "chip": chip, "ms_a_step_by_scope": by_scope,
        "table_slot_groups": telemetry.table_slot_groups()}), flush=True)
    print(json.dumps({
        "chip": chip, "device": device.device_kind, "steps": STEPS,
        "step_device_ms": round(
            found["step"]["device_s_per_execution"] * 1e3, 3),
        "busy_s": found["busy_s"], "window_s": found["window_s"],
        "walk_books": model.walk_books(),
        "shard_slots": model.shard_slots(),
        "step_memory": model.step_memory()}), flush=True)


def main() -> int:
    device = jax.devices()[0]
    assert device.platform == "tpu", f"needs a TPU, found {device.platform}"
    chips = [int(c) for c in sys.argv[sys.argv.index("--chips") + 1].split(
        ",")] if "--chips" in sys.argv else [3, 0]
    for chip in chips:
        one_chip(device, chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
