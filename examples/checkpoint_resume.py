"""Checkpoint/resume of the data pipeline — a capability the reference lacks
(SURVEY.md §5.4 flags iterator-state checkpointing as the natural addition).

Simulates a preempted ingest job: consume a few batches, snapshot the
iterator state to JSON, 'restart the process' (fresh parser + DeviceIter),
restore, and continue — the resumed stream picks up exactly where the first
left off.

Run: python examples/checkpoint_resume.py
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.utils.compile_cache import enable_compile_cache

NUM_COL, BATCH = 8, 128


def make_corpus(path: str, rows: int = 2000) -> None:
    with open(path, "w") as f:
        for i in range(rows):
            feats = " ".join(f"{j}:{(i * 13 + j) % 7}.5" for j in range(NUM_COL))
            f.write(f"{i % 2} {feats}\n")


def open_pipeline(path: str) -> DeviceIter:
    parser = create_parser(path, 0, 1, "libsvm", threaded=True, chunk_bytes=8192)
    return DeviceIter(parser, num_col=NUM_COL, batch_size=BATCH, layout="dense")


def main() -> None:
    enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        _run(os.path.join(tmp, "train.libsvm"))


def _run(path: str) -> None:
    make_corpus(path)

    it = open_pipeline(path)
    consumed = [np.asarray(next(it)[0]) for _ in range(3)]
    state_json = json.dumps(it.state_dict())  # <- persist this with the model
    it.close()
    print(f"consumed 3 batches, checkpoint = {state_json}")

    # --- simulated restart ---
    it2 = open_pipeline(path)
    it2.load_state(json.loads(state_json))
    resumed = [np.asarray(b[0]) for b in it2]
    it2.close()
    print(f"resumed: {len(resumed)} batches")

    # prove the splice equals an uninterrupted pass
    it3 = open_pipeline(path)
    full = [np.asarray(b[0]) for b in it3]
    it3.close()
    np.testing.assert_array_equal(
        np.concatenate(consumed + resumed), np.concatenate(full))
    print("resumed stream matches the uninterrupted pass — OK")


if __name__ == "__main__":
    main()
