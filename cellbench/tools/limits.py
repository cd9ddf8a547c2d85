"""Read, at a cell's own size and in one process, the two numbers every
limit of the comparison is set from: what sound runs of the program give
over many seeds, and what the control gives (the plain reference in
bfloat16, put in the program's place).

    python3 -m cellbench.tools.limits --workload kdd12_fm_text --seeds 12 [--first-seed N]

Training's readings need no measured window: per seed it makes the first
three batches of the seed's corpus, runs the reference and the control,
builds the learner and the cell's own feed, and drives the three steps
through the window's own call. One JSON line per seed goes to
``chiprun_out/limits_<workload>.jsonl``; a summary closes the output.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

from cellbench import run as R


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_100_000_000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell, config, traffic, _, _ = R.find_cell(args.workload, args.rehearse)
    sys.path.insert(0, R.ROOT)
    devices, _ = R.claim_devices(cell, args.rehearse)
    learners = R.plugin("learners", config["learner"])
    gen = R.plugin("generators", config["generator"]["name"])
    feed = R.plugin("feeds", traffic["feed"])
    mesh = R.cell_mesh(cell, devices)
    out_dir = os.path.join(R.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"limits_{args.workload}.jsonl")
    sound, control = {}, {}
    with open(out_path, "a") as out:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t = time.time()
            # a directory per seed: the program's artifact store keeps the
            # lock file of a tier's directory open across feeds
            work = os.path.join(R.CACHE, "work", "limits_" + cell["name"],
                                str(seed))
            shutil.rmtree(work, ignore_errors=True)
            os.makedirs(work)
            corpus = os.path.join(work, "corpus." + config["format"])
            gen.generate(config["generator"], seed,
                         R.FIRST_STEPS * config["batch_size"], corpus)
            ref = learners.reference_digest(config, seed, corpus)
            ctl = learners.control_numbers(config, seed, corpus, ref)
            adapter = learners.Adapter(config, seed, mesh=mesh)
            it = feed.open_feed(f"{corpus}?format={config['format']}", work,
                                adapter.device_iter_kwargs(), traffic)
            try:
                losses, readings = R.first_steps(adapter, iter(it), ref)
            finally:
                it.close()
                shutil.rmtree(work, ignore_errors=True)
            got = learners.compare(ref, losses, *readings)
            del adapter, readings
            gc.collect()
            line = {"seed": seed, "sound": got, "control": ctl,
                    "losses": losses, "ref_losses": ref["losses"],
                    "seconds": round(time.time() - t, 2)}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            for k in got:
                sound.setdefault(k, []).append(got[k])
                control.setdefault(k, []).append(ctl[k])
    print("number: largest sound / median sound / smallest control / ratio of the first and last")
    for k in sound:
        hi, lo = max(sound[k]), min(control[k])
        med = sorted(sound[k])[len(sound[k]) // 2]
        print(f"  {k}: {hi:.6g} / {med:.6g} / {lo:.6g} / "
              f"{(lo / hi if hi else float('inf')):.3g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
