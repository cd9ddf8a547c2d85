"""BASELINE.md config 4: libfm sparse -> device BCOO (KDD2012-track2-shaped).

KDD2012 CTR rows: ~10 sparse features over a ~50M index space with field
ids. Metric: end-to-end libfm parse -> BCOO batches resident on device;
baseline: host-only parse of the same corpus.
"""

import os

import jax

from _common import CACHE_DIR, emit, log, synth_text, timed_stats


NNZ = 10
# chunk size sets the natural-block batch size, i.e. the device_put count:
# fewer/larger puts amortize the per-put cost (shape bucketing keeps the
# larger shapes repeating) — A/B without editing via DMLC_BENCH_CHUNK_MB.
# Default 4 MB, chosen from CPU-backend counts (a quarter of the puts);
# the per-put cost on this machine's chip is not measured
CHUNK_BYTES = int(float(os.environ.get("DMLC_BENCH_CHUNK_MB", "4")) * 2**20)
# Wire-format knob (r5): csr ships cols+row_ptr (4 B/nnz) and rebuilds row
# ids on device; pair ships (row, col) int32 pairs (8 B/nnz) with no
# device-side work. csr halves the coordinate bytes over the link; pair
# skips the rebuild. Which wins on a directly attached chip is not
# measured (ROADMAP S7).
# The 64 MB leg A/Bs both on whatever device is present; this knob sets
# the GB leg's production mode.
CSR_WIRE = os.environ.get("DMLC_BENCH_CSR_WIRE", "1") != "0"


def _line(i: int) -> str:
    feats = " ".join(
        f"{j}:{(i * 2654435761 + j * 40503) % 50_000_000}:1"
        for j in range(NNZ))
    return f"{i % 2} {feats}\n"


def run() -> None:
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    path = synth_text(os.path.join(CACHE_DIR, "kdd12_like.libfm"), _line)
    size_mb = os.path.getsize(path) / 2**20
    uri = path + "?format=libfm"

    def host_only(threaded: bool) -> None:
        # same chunk size as the device leg: the knob must A/B the
        # device_put count, not conflate it with parse-rate effects
        p = create_parser(uri, 0, 1, threaded=threaded,
                          chunk_bytes=CHUNK_BYTES)
        rows = sum(len(b) for b in p)
        p.close()
        assert rows > 0

    def to_device(csr_wire: bool = CSR_WIRE) -> None:
        # the real pipeline: C++ parse threads emit device-ready COO blocks
        # (int32 coords, bucket padding, all-ones value elision — the
        # corpus is ":1"-valued, so the value array never crosses the
        # host->HBM link) and the convert thread only issues the async
        # device_put; the consumer pops ready handles — nothing serializes
        # with parsing (r2 weak #1 was this benchmark bypassing DeviceIter)
        p = create_parser(uri, 0, 1, threaded=True,
                          chunk_bytes=CHUNK_BYTES)
        it = DeviceIter(p, num_col=50_000_000, batch_size=None,
                        layout="bcoo", elide_unit_values=True,
                        csr_wire=csr_wire)
        # block on EVERY array of each batch (not just the last value
        # array) so no in-flight transfer escapes the timed region, but
        # release batches as we go — device memory stays O(prefetch), and
        # the prefetch pipeline keeps transfers ahead of the blocking
        for mat, y, w in it:
            jax.block_until_ready((mat.data, mat.indices, y, w))
        it.close()

    # vs_baseline denominator: the single-threaded host-only parse — the
    # same "single-host CPU reference" semantics as config #1 (bench.py).
    # The threaded native parse is ALSO reported (vs_threaded_parse): it
    # saturates this host's one core, so it bounds any into-device pipeline
    # from above here — see benchmarks/README.md for the Amdahl argument.
    # 5 reps (not the suite's 3) for the one leg that touches the device
    base, base_med, _ = timed_stats(lambda: host_only(False))
    log(f"libfm host-only single-thread (CPU reference): {size_mb / base:.1f} MB/s")
    threaded_base, _, _ = timed_stats(lambda: host_only(True))
    log(f"libfm host-only threaded native: {size_mb / threaded_base:.1f} MB/s")
    t, t_med, times = timed_stats(to_device, reps=5)
    log(f"libfm -> device BCOO (DeviceIter prefetch, "
        f"{'csr' if CSR_WIRE else 'pair'} wire): {size_mb / t:.1f} MB/s "
        f"best, {size_mb / t_med:.1f} MB/s median")
    extra = {}
    if size_mb <= 128:
        # wire-format A/B (cheap at this size): time the OTHER mode too so
        # each battery pass records, on the device actually present, which
        # wire the link prefers — the GB leg then runs the winner via
        # DMLC_BENCH_CSR_WIRE
        o, o_med, _ = timed_stats(lambda: to_device(not CSR_WIRE), reps=5)
        key = "pair_wire" if CSR_WIRE else "csr_wire"
        extra[f"{key}_mb_per_sec"] = round(size_mb / o, 2)
        extra[f"{key}_median_mb_per_sec"] = round(size_mb / o_med, 2)
        extra[f"{key}_reps"] = 5
        log(f"libfm -> device BCOO ({'pair' if CSR_WIRE else 'csr'} wire "
            f"A/B): {size_mb / o:.1f} MB/s best, {size_mb / o_med:.1f} median")
    emit("libfm_bcoo_mb_per_sec", size_mb / t, "MB/s", size_mb / base,
         vs_threaded_parse=threaded_base / t,
         median=size_mb / t_med,
         median_vs_baseline=(size_mb / t_med) / (size_mb / base_med),
         spread=[round(size_mb / max(times), 2), round(size_mb / min(times), 2)],
         reps=5, wire="csr" if CSR_WIRE else "pair", **extra)


if __name__ == "__main__":
    run()
