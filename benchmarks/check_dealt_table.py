"""Is a table dealt by rows over four chips, after some hundreds of steps,
the table one chip trains?

    chiprun --chips 4 -- python3 benchmarks/check_dealt_table.py [--steps 320]

At a size one device holds (``kdd12_ffm``'s quarter: 13,671,613 ids x 44
columns, 5.3 GB with its accumulators), two ``FFMLearner``s start from one
seed, one on the first chip and one dealt over the mesh
(``FFMLearner(mesh=)``, ``parallel.mesh.RowDeal``), and take the same
``--steps`` batches of the seed's corpus through ``DeviceIter(fields=True)``
each. The two steps sum a hot id's gradient rows in different orders (a
chip's kernel sees the slots it owns, in the order their chips sent them),
so they agree to float32 rounding and not bit for bit; a row that no batch
touched has to be the same bits. With ``--hot-every N`` every Nth step's
batch names one id in 12 of its 16 slots, so that step does not fit the
exchange's buckets and all-gathers its slots (ops/table_exchange.py): the
count of such steps is checked against ``fallback_steps``.
One JSON line last: the loss of every 32nd step on both, the root-mean-
square gap of ``W`` and of ``G - 1`` over all rows against their root mean
squares, the widest gap of one row (against the larger of that row's norm
and the median row's), the touched rows that differ by more than
``--row-limit`` of that, the untouched rows that differ at all, the
route labels and the steps that took the fallback. Exit code 1 if any row
does, or the fallback count is not the hot steps'.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHUNK = 1 << 20     # ids compared at a time


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=320)
    ap.add_argument("--seed", type=int, default=3_200_000_401)
    ap.add_argument("--row-limit", type=float, default=1e-3)
    ap.add_argument("--hot-every", type=int, default=0,
                    help="every Nth step names one id in 12 slots of a row")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny_ffm's size: a rehearsal on CPU devices")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cellbench.generators import fields_zipf_libfm as gen
    from cellbench.run import HERE, load_json
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import FFMLearner
    from dmlc_tpu.parallel import make_mesh
    from dmlc_tpu.utils import telemetry

    cfg = load_json(HERE, "configs",
                    ("tiny_ffm" if args.tiny else "kdd12_ffm") + ".json")
    mesh = make_mesh(devices=jax.devices()[:4])
    work = tempfile.mkdtemp(prefix="dealt_", dir=os.environ.get("TMPDIR"))
    corpus = os.path.join(work, "corpus.libfm")
    gen.generate(cfg["generator"], args.seed, cfg["rows"], corpus)
    how = dict(num_col=cfg["num_features"], num_fields=cfg["num_fields"],
               num_factors=cfg["num_factors"],
               learning_rate=cfg["learning_rate"], l2=cfg["l2"],
               seed=args.seed % (2 ** 31 - 1))
    one, four = FFMLearner(**how), FFMLearner(mesh=mesh, **how)
    feed = dict(num_col=cfg["num_features"], batch_size=cfg["batch_size"],
                layout="ell", max_nnz=cfg["max_nnz"], fields=True)
    uri = corpus + "?format=libfm"
    it1 = DeviceIter(create_parser(uri), **feed)
    it4 = DeviceIter(create_parser(uri), mesh=mesh,
                     shardings=four.batch_shardings(), **feed)
    touched = jnp.zeros(cfg["num_features"] + 1, bool)
    mark = jax.jit(lambda seen, idx: seen.at[idx.reshape(-1)].set(True))

    def hot(batch, sharding=None):
        idx = batch.indices.at[:, :12].set(17)
        vals = batch.values.at[:, :12].set(1.0)
        if sharding is not None:
            idx, vals = (jax.device_put(idx, sharding.indices),
                         jax.device_put(vals, sharding.values))
        return batch._replace(indices=idx, values=vals)

    losses, n, hot_steps, t0 = [], 0, 0, time.time()
    try:
        while n < args.steps:
            for b1, b4 in zip(it1, it4):
                if args.hot_every and n % args.hot_every == 0:
                    b1, b4 = hot(b1), hot(b4, four.batch_shardings())
                    hot_steps += 1
                touched = mark(touched, b1.indices)
                l1, l4 = one.step(b1), four.step(b4)
                if n % 32 == 0 or n == args.steps - 1:
                    losses.append((n, float(l1), float(l4)))
                n += 1
                if n >= args.steps:
                    break
            it1.reset()
            it4.reset()
    finally:
        it1.close()
        it4.close()
        shutil.rmtree(work, ignore_errors=True)
    seconds = time.time() - t0

    @jax.jit
    def gaps(w1, g1, w4, g4, seen):
        dw, dg = w4 - w1, g4 - g1
        row = lambda x: jnp.sqrt(jnp.sum(jnp.square(x), axis=1))  # noqa: E731
        same = jnp.all((dw == 0) & (dg == 0), axis=1)
        return (jnp.sum(jnp.square(dw)), jnp.sum(jnp.square(w1)),
                jnp.sum(jnp.square(dg)), jnp.sum(jnp.square(g1 - 1.0)),
                row(dw), row(w1), jnp.sum(~same & ~seen),
                jnp.sum(~same & seen))

    sums = np.zeros(4, np.float64)
    row_gap, row_norm, untouched_differ, touched_differ = [], [], 0, 0
    rows = cfg["num_features"] + 1
    for at in range(0, rows, CHUNK):
        ids = jnp.arange(at, min(at + CHUNK, rows))
        w4, g4 = four.rows(ids)
        w1, g1 = one.rows(ids)
        out = gaps(w1, g1, w4, g4, touched[at:at + CHUNK])
        sums += [float(x) for x in out[:4]]
        row_gap.append(np.asarray(out[4]))
        row_norm.append(np.asarray(out[5]))
        untouched_differ += int(out[6])
        touched_differ += int(out[7])
    row_gap, row_norm = np.concatenate(row_gap), np.concatenate(row_norm)
    scale = np.maximum(row_norm, np.median(row_norm))
    over = int(np.sum(row_gap > args.row_limit * scale))
    line = {
        "steps": n, "seconds_for_both": round(seconds, 1), "rows": rows,
        "losses_one_and_four": losses,
        "table_rms_gap": float(np.sqrt(sums[0] / sums[1])),
        "accumulator_rms_gap": float(np.sqrt(sums[2] / max(sums[3], 1e-30))),
        "widest_row_gap": float(np.max(row_gap / scale)),
        "rows_touched": int(jnp.sum(touched)),
        "touched_rows_not_bit_identical": touched_differ,
        f"touched_rows_over_{args.row_limit:g}": over,
        "untouched_rows_that_differ": untouched_differ,
        "shard_slots": four.shard_slots(),
        "hot_steps": hot_steps,
        "fallback_steps": four.fallback_steps(),
        "table_shard_routes": telemetry.table_shard_routes(),
        "devices": jax.device_count(),
        "routes": [ln for ln in telemetry.render_prometheus().splitlines()
                   if "_route_total" in ln],
    }
    print(json.dumps(line), flush=True)
    return 1 if (over or untouched_differ
                 or line["fallback_steps"] != hot_steps) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
