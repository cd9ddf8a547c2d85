"""A worker that survives its own death: the ``DMLC_NUM_ATTEMPT`` contract
closed (docs/checkpoint.md).

``dmlc-submit --cluster local --local-num-attempt 2`` restarts a worker
that exits non-zero and exports ``DMLC_NUM_ATTEMPT`` (reference
tracker/dmlc_tracker/local.py:26-49). This worker takes the recovery path
the reference leaves to the application (rabit: ``LoadCheckPoint`` at
start, ``CheckPoint`` after each iteration): it trains a field-aware FM
over libfm text, saves the model **and** the iterator's position every
``--save-every`` steps through ``learner.save_async``, and when it comes
up with ``DMLC_NUM_ATTEMPT > 0`` it resumes both from the newest published
checkpoint and rejoins the tracker under its old rank. ``--die-at K``
kills the first attempt after step K is dispatched (``os._exit``: no
clean-up runs), so the two runs below end with the same tables:

    python -m dmlc_tpu.tracker.submit --cluster local --num-workers 1 \\
        --local-num-attempt 2 --host-ip 127.0.0.1 -- \\
        python examples/train_ffm_resume.py --work /tmp/job --die-at 11
    python examples/train_ffm_resume.py --work /tmp/ref

Each writes ``<work>/result.json``: the steps run by the last attempt, the
attempt, every loss it saw, and a digest of ``W`` and ``G``.
"""

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from dmlc_tpu.data import create_parser  # noqa: E402
from dmlc_tpu.data.device import DeviceIter  # noqa: E402
from dmlc_tpu.models import FFMLearner  # noqa: E402

NUM_COL, FIELDS, BATCH, K = 600, 5, 32, 8


def make_corpus(path: str, rows: int = 320, seed: int = 7) -> None:
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for r in range(rows):
            n = int(rng.integers(2, K + 1))
            toks = " ".join(
                f"{int(rng.integers(0, FIELDS))}:"
                f"{int(rng.integers(0, NUM_COL))}:1" for _ in range(n))
            f.write(f"{r % 2} {toks}\n")


def tracker_client(attempt: int):
    """Join the tracker when launched under one (``dmlc-submit``): a
    first attempt starts, a restarted one recovers its old rank."""
    uri = os.environ.get("DMLC_TRACKER_URI")
    if not uri:
        return None
    from dmlc_tpu.tracker.client import WorkerClient

    client = WorkerClient(uri, int(os.environ["DMLC_TRACKER_PORT"]))
    if attempt:
        client.recover(int(os.environ.get("DMLC_TASK_ID", "0")))
    else:
        client.start()
    return client


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--work", required=True, help="the job's directory")
    ap.add_argument("--steps", type=int, default=25)
    ap.add_argument("--save-every", type=int, default=4)
    ap.add_argument("--die-at", type=int, default=0,
                    help="first attempt only: die after this step")
    args = ap.parse_args(argv)
    attempt = int(os.environ.get("DMLC_NUM_ATTEMPT", "0") or 0)
    client = tracker_client(attempt)
    os.makedirs(args.work, exist_ok=True)
    corpus = os.path.join(args.work, "train.libfm")
    if not os.path.exists(corpus):
        make_corpus(corpus)
    ckpt = os.path.join(args.work, "ckpt")

    learner = FFMLearner(num_col=NUM_COL, num_fields=FIELDS, seed=1)
    it = DeviceIter(create_parser(corpus + "?format=libfm"),
                    num_col=learner.device_num_col(), batch_size=BATCH,
                    layout="ell", max_nnz=K, fields=True)
    step = 0
    if attempt and learner.latest(ckpt) is not None:
        step = learner.restore(ckpt, device_iter=it)["step"]
        print(f"attempt {attempt}: resumed after step {step}", flush=True)
    losses, handle = [], None
    while step < args.steps:
        batch = next(it, None)
        if batch is None:           # the end of a pass over the data
            it.reset()
            continue
        losses.append(learner.step(batch))
        step += 1
        if step % args.save_every == 0:
            handle = learner.save_async(ckpt, step=step, device_iter=it)
        if attempt == 0 and step == args.die_at:
            if handle is not None:
                handle.wait()       # what was acknowledged is on disk
            os._exit(1)             # a preemption: nothing is cleaned up
    if handle is not None:
        handle.wait()
    it.close()
    w, g = learner.rows(np.arange(NUM_COL + 1))
    digest = hashlib.sha256(np.asarray(w).tobytes()
                            + np.asarray(g).tobytes()).hexdigest()
    with open(os.path.join(args.work, "result.json"), "w") as f:
        json.dump({"attempt": attempt, "steps": step, "digest": digest,
                   "losses": [float(x) for x in losses]}, f)
    if client is not None:
        client.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
