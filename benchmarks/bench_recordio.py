"""BASELINE.md config 3: RecordIO InputSplit multi-part (ImageNet-.rec-shaped).

ImageNet .rec records are ~100KB JPEG payloads; synthesized as random bytes
of that scale across several part files. Metric: record-read throughput
over all parts consumed partition-by-partition with synchronous readers
(a prefetch thread per shard only adds churn on this single-core host);
baseline: single-part sequential read of the same bytes.
"""

import os

import numpy as np

from _common import CACHE_DIR, TARGET_MB, emit, log, paired_times, timed_stats

NPARTS = 4
REC_KB = 100


def _make_parts():
    from dmlc_tpu.io.recordio import RecordIOWriter

    rng = np.random.default_rng(11)
    paths = []
    per_part = max(1, int(TARGET_MB * 2**20 / NPARTS / (REC_KB << 10)))
    for p in range(NPARTS):
        path = os.path.join(CACHE_DIR, f"imagenet_like.part{p}.rec")
        paths.append(path)
        want = per_part * (REC_KB << 10)
        if os.path.exists(path) and os.path.getsize(path) >= want:
            continue  # cached at (or above) the current DMLC_BENCH_MB target
        os.makedirs(CACHE_DIR, exist_ok=True)
        with open(path, "wb") as f:
            w = RecordIOWriter(f)
            for _ in range(per_part):
                w.write_record(rng.bytes(REC_KB << 10))
    return paths


def _make_indexed():
    """A single-file indexed corpus + index (the shuffled-epoch case)."""
    from dmlc_tpu.io.recordio import write_indexed_recordio

    rng = np.random.default_rng(13)
    data_p = os.path.join(CACHE_DIR, "imagenet_like.indexed.rec")
    idx_p = os.path.join(CACHE_DIR, "imagenet_like.indexed.idx")
    n = max(1, int(TARGET_MB * 2**20 / (REC_KB << 10)))
    want = n * (REC_KB << 10)
    if not (os.path.exists(data_p) and os.path.getsize(data_p) >= want
            and os.path.exists(idx_p)):
        os.makedirs(CACHE_DIR, exist_ok=True)
        with open(data_p, "wb") as df, open(idx_p, "wb") as xf:
            write_indexed_recordio(
                df, xf, (rng.bytes(REC_KB << 10) for _ in range(n)))
    return data_p, idx_p


def _consume_indexed(data_p: str, idx_p: str, native: bool) -> int:
    from dmlc_tpu.io.input_split import create_input_split

    u = data_p if native else data_p + "?engine=python"
    s = create_input_split(u, 0, 1, "indexed_recordio", index_uri=idx_p,
                           shuffle=True, seed=7, threaded=native)
    recs = sum(1 for _ in iter(s.next_record, None))
    s.close()
    return recs


def run() -> None:
    from dmlc_tpu.io.input_split import create_input_split

    paths = _make_parts()
    uri = ";".join(paths)
    size_mb = sum(os.path.getsize(p) for p in paths) / 2**20

    def consume(npart: int = 1, native: bool = True) -> int:
        recs = 0
        u = uri if native else uri + "?engine=python"
        for part in range(npart):
            s = create_input_split(u, part, npart, "recordio",
                                   threaded=native)
            while s.next_record() is not None:
                recs += 1
            s.close()
        return recs

    # baseline: single-part sequential read through the Python engine
    n_base = consume(native=False)
    base, base_med, _ = timed_stats(lambda: consume(native=False))
    log(f"recordio python sequential: {n_base} recs, {size_mb / base:.1f} MB/s")
    # measured: the native reader (C++ read + framing scan + reassembly,
    # off-GIL), partition-by-partition
    n = consume(NPARTS)
    assert n == n_base, (n, n_base)  # no dropped/duplicated records
    t, t_med, times = timed_stats(lambda: consume(NPARTS))
    log(f"recordio native {NPARTS}-part: {size_mb / t:.1f} MB/s best, "
        f"{size_mb / t_med:.1f} median")

    # indexed + shuffled epoch: the ImageNet use case the index exists for
    # — native per-record seeks vs the Python engine
    data_p, idx_p = _make_indexed()
    idx_mb = os.path.getsize(data_p) / 2**20
    n_py = _consume_indexed(data_p, idx_p, native=False)
    n_nat = _consume_indexed(data_p, idx_p, native=True)
    assert n_nat == n_py, (n_nat, n_py)
    py_times, nat_times = paired_times(
        lambda: _consume_indexed(data_p, idx_p, False),
        lambda: _consume_indexed(data_p, idx_p, True), pairs=3)
    t_py, t_nat = min(py_times), min(nat_times)
    log(f"indexed shuffled python: {idx_mb / t_py:.1f} MB/s, "
        f"native: {idx_mb / t_nat:.1f} MB/s")
    emit("recordio_multipart_mb_per_sec", size_mb / t, "MB/s", size_mb / base,
         median=size_mb / t_med,
         median_vs_baseline=base_med / t_med,
         spread=[round(size_mb / max(times), 2), round(size_mb / min(times), 2)],
         reps=len(times),
         indexed_shuffled_native_mb_per_sec=idx_mb / t_nat,
         indexed_shuffled_vs_python=t_py / t_nat)


if __name__ == "__main__":
    run()
