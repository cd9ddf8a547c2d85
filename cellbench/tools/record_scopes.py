"""Run one cell traced and keep what a recorded-trace test needs:

    python3 -m cellbench.tools.record_scopes OUT_DIR --workload W --seed N --seconds S

runs ``cellbench.run`` with ``--trace 1`` in this process and writes
``OUT_DIR/trace.xplane.pb`` (the trace the run wrote; cut it with
``tools/trim_spans.py``) and ``OUT_DIR/hlo_scopes.json`` (what the
learner's ``hlo_scopes()`` gave the scope readers in that run).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time


def main(argv) -> int:
    out_dir, rest = argv[0], argv[1:]
    os.makedirs(out_dir, exist_ok=True)
    from cellbench import run
    from cellbench.readers import _program as P
    from dmlc_tpu.models._loop import TrainLoopMixin

    real = TrainLoopMixin.hlo_scopes

    def recording(self):
        t0 = time.perf_counter()
        scopes = real(self)
        print(f"[record_scopes] hlo_scopes(): {len(scopes)} instructions in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        with open(os.path.join(out_dir, "hlo_scopes.json"), "w") as f:
            json.dump(scopes, f, indent=0, sort_keys=True)
        return scopes

    TrainLoopMixin.hlo_scopes = recording
    rc = run.main(rest + ["--trace", "1"])
    path = P.find_trace()
    if path:
        shutil.copyfile(path, os.path.join(out_dir, "trace.xplane.pb"))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
