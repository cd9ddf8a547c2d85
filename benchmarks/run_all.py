"""Run the whole benchmark suite and record results to BENCHMARKS_<tag>.json.

Covers BASELINE.md's five configs:
  1. libsvm RowBlockIter into HBM      -> bench.py (repo root, the driver's)
  2. CSV parser + prefetch             -> bench_csv_prefetch.py
  3. RecordIO InputSplit multi-part    -> bench_recordio.py
  4. libfm sparse -> device BCOO       -> bench_libfm_bcoo.py (+ the sparse
                                          matvec A/B in bench_sparse_tpu.py,
                                          recorded separately)
  5. sharded InputSplit (pod-shaped)   -> bench_sharded_split.py

Each bench prints ONE JSON line on stdout (same schema as bench.py); this
runner executes them as subprocesses ONE AT A TIME — each takes the chip
in turn, and this process never imports jax, so it holds none — collects
the lines, and writes the aggregate JSON.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

BENCHES = [
    ("bench.py", REPO),
    ("bench_csv_prefetch.py", HERE),
    ("bench_recordio.py", HERE),
    ("bench_libfm_bcoo.py", HERE),
    ("bench_sharded_split.py", HERE),
    # stretch leg: loopback S3 at volume — validates the
    # signed range-GET read stream + NativeFeedParser under GB reads
    ("bench_cloud_read.py", HERE),
]


def _tail(stream) -> str:
    """Last 800 chars of a subprocess stream (str, bytes, or None)."""
    if stream is None:
        return ""
    if isinstance(stream, bytes):
        stream = stream.decode(errors="replace")
    return stream[-800:]


def _extract_json(entry: dict, stdout) -> None:
    """Fold the last '{'-prefixed stdout line into ``entry`` (shared by
    the success and timeout paths so the record shape cannot diverge)."""
    if stdout is None:
        return
    if isinstance(stdout, bytes):
        stdout = stdout.decode(errors="replace")
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    if lines:
        try:
            entry.update(json.loads(lines[-1]))
        except ValueError:
            entry["raw"] = lines[-1][:500]


def main() -> None:
    tag = os.environ.get("DMLC_BENCH_TAG", "r02")
    results = []
    for script, cwd in BENCHES:
        print(f"== {script} ==", file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(cwd, script)],
                cwd=cwd, capture_output=True, text=True, timeout=1800)
        except subprocess.TimeoutExpired as exc:
            # one hung bench must not take the rest of the suite's records
            # down with it — and a JSON line printed before the hang is
            # still a measurement
            entry = {"bench": script, "rc": "timeout_1800s"}
            _extract_json(entry, exc.stdout)
            entry["stderr_tail"] = _tail(exc.stderr)
            results.append(entry)
            print(json.dumps(entry), flush=True)
            continue
        entry = {"bench": script, "rc": proc.returncode}
        _extract_json(entry, proc.stdout)
        if proc.returncode != 0:
            entry["stderr_tail"] = _tail(proc.stderr)
        results.append(entry)
        print(json.dumps(entry), flush=True)
    out = os.path.join(REPO, f"BENCHMARKS_{tag}.json")
    with open(out, "w") as f:
        json.dump({"results": results}, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
