"""Benchmark: RowBlockIter MB/s into HBM (the BASELINE.md north star).

Measures the full path on a HIGGS-like libsvm corpus:
  file -> InputSplit -> parser -> RowBlock -> fixed-shape dense batches ->
  jax.device_put -> HBM (consumer touches every batch on device).

Baseline (vs_baseline denominator): the same corpus through the
single-threaded host-only parse (no device), i.e. BASELINE.json config #1's
"single-host CPU reference". >1.0 means the async pipeline into HBM beats
host-only parsing.

ONE process, on the device it finds. It refuses to start unless that
device is a TPU — or the caller asked for the CPU backend by name
(``JAX_PLATFORMS=cpu``, what ``make bench-smoke`` does to check the
contract, not the speed) — and unless the native parse engine loaded.
Prints ONE JSON line on stdout that names the device
(``platform / device_kind / device_count``) and the engine; everything
else goes to stderr, every line tagged with the device. A leg that fails
is logged with its traceback and listed in ``failed_legs``; the line
still prints, and the exit code is then non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_cache")
CORPUS = os.path.join(CACHE_DIR, "higgs_like.libsvm")
TARGET_MB = float(os.environ.get("DMLC_BENCH_MB", "64"))
NUM_COL = 28  # HIGGS has 28 features
# a larger batch amortizes the per-put cost at the price of coarser overlap
# — tunable for A/B without editing (the framework, not the workload, picks
# batch size). 16384 (1.8 MB dense puts) was chosen on an earlier
# installation; the per-put cost has not been measured on this machine
# (ROADMAP S2)
BATCH = int(os.environ.get("DMLC_BENCH_BATCH", "16384"))

# "platform/device_kind/count", set once the backend is up: every stderr
# line carries it, so no number can be read without its device
_DEVICE_TAG = ""
# legs that raised: listed in the JSON line, and the run exits non-zero
_FAILED_LEGS: list = []


def log(msg: str) -> None:
    tag = f"[{_DEVICE_TAG}] " if _DEVICE_TAG else ""
    print(tag + msg, file=sys.stderr, flush=True)


def _leg_failed(name: str, exc: BaseException) -> None:
    """A leg raised: keep going so the line still prints, but say so in it
    (``failed_legs``) and fail the run at the end."""
    log(f"bench: {name} leg FAILED: {type(exc).__name__}: {exc}\n"
        + "".join(traceback.format_exception(exc)))
    _FAILED_LEGS.append(name)


def make_corpus() -> str:
    """Generate a HIGGS-like dense libsvm corpus once, cached on disk."""
    import numpy as np

    if os.path.exists(CORPUS) and os.path.getsize(CORPUS) >= TARGET_MB * 0.95 * 2**20:
        return CORPUS
    os.makedirs(CACHE_DIR, exist_ok=True)
    rng = np.random.default_rng(42)
    log(f"bench: generating ~{TARGET_MB:.0f} MB corpus at {CORPUS}")
    with open(CORPUS, "w") as f:
        written = 0
        target = TARGET_MB * 2**20
        while written < target:
            rows = []
            vals = rng.standard_normal((2000, NUM_COL)).astype(np.float32)
            labels = rng.integers(0, 2, 2000)
            for lbl, row in zip(labels, vals):
                feats = " ".join(f"{j}:{row[j]:.6f}" for j in range(NUM_COL))
                rows.append(f"{lbl} {feats}")
            chunk = "\n".join(rows) + "\n"
            f.write(chunk)
            written += len(chunk)
    return CORPUS


# 1MB chunks measured fastest for the async pipeline (fine-grained quanta
# interleave parse/convert/transfer best; larger chunks lump the stages and
# stall the device) and equal-or-better for the baseline
CHUNK_BYTES = 1 << 20
# best-of/median-of rep count, to tame shared-host noise: 5 reps make the
# median robust to two outliers. Overridable for quick smokes.
REPS = max(1, int(os.environ.get("DMLC_BENCH_REPS", "5") or 5))


from statistics import median as _median  # noqa: E402


def host_only_mb_per_sec(path: str, size_mb: float, threaded: bool = False,
                         emit_dense: bool = False):
    """Host-only parse (threaded=False: the single-thread CPU reference;
    threaded=True + emit_dense: the PIPELINE'S parse ceiling — the exact
    native dense-emit path the device leg runs, minus the device_put, so
    the binding-bound comparison is like-for-like; a CSR-emitting ceiling
    under-reads it and can even sit below the pipeline itself).

    Returns (best, median) MB/s over REPS runs — ambient host speed swings
    2-4x on this shared machine, so both statistics are recorded.
    """
    from dmlc_tpu.data import create_parser

    rates = []
    for _ in range(REPS):
        parser = create_parser(path, 0, 1, "libsvm", threaded=threaded,
                               chunk_bytes=CHUNK_BYTES)
        if emit_dense and hasattr(parser, "set_emit_dense"):
            # pack_aux matches the device leg's config so this ceiling
            # measures the exact same native repack work
            parser.set_emit_dense(NUM_COL, batch_rows=BATCH, pack_aux=True)
        t0 = time.monotonic()
        rows = 0
        for block in parser:
            rows += len(block)
        dt = time.monotonic() - t0
        parser.close()
        rates.append(size_mb / dt)
        log(f"bench: host-only parse ({'threaded' if threaded else '1-thread'}"
            f"{', dense-emit' if emit_dense else ''})"
            f" {rows} rows in {dt:.2f}s = {size_mb/dt:.1f} MB/s")
    return max(rates), _median(rates)


def parse_fanout_mb_per_sec(path: str, size_mb: float, workers: int) -> float:
    """One drain of the PYTHON-ENGINE parse path at a given fan-out width
    (``parse_workers=1`` is the single-producer parse-ahead thread — the
    pre-fan-out engine; >1 is the ParallelTextParser pool over the
    zero-copy mmap chunk source). ``engine=python`` pins the route so the
    curve measures the fan-out, not the native reader (which keeps its own
    C++ threading and ignores the knob)."""
    from dmlc_tpu.data import create_parser

    parser = create_parser(path + "?engine=python", 0, 1, "libsvm",
                           threaded=True, parse_workers=workers,
                           chunk_bytes=CHUNK_BYTES)
    try:
        t0 = time.monotonic()
        rows = 0
        while (block := parser.next_block()) is not None:
            rows += len(block)
        dt = time.monotonic() - t0
    finally:
        parser.close()  # a mid-drain error must not leak the worker pool
    log(f"bench: parse fan-out workers={workers} {rows} rows in {dt:.2f}s "
        f"= {size_mb/dt:.1f} MB/s")
    return size_mb / dt


def parse_scaling_curve(path: str, size_mb: float, workers=(1, 2, 4)):
    """Host-only parse ceiling at each fan-out width, INTERLEAVED across
    reps so this host's 2-4x ambient swings hit every width evenly —
    the scaling ratio is the stable quantity, not the absolutes. Returns
    {workers: (best, median)}."""
    rates = {w: [] for w in workers}
    for _ in range(REPS):
        for w in workers:
            rates[w].append(parse_fanout_mb_per_sec(path, size_mb, w))
    return {w: (max(v), _median(v)) for w, v in rates.items()}


def into_hbm_mb_per_sec(path: str, size_mb: float, x_dtype: str = "float32"):
    """Full async pipeline into device HBM."""
    import jax

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    dev = jax.devices()[0]
    log(f"bench: device = {dev} (x_dtype={x_dtype})")
    # warm up the transfer path (backend init + first-DMA setup) so the timed
    # region measures the steady-state pipeline, matching the host-only
    # baseline which pays no device-init cost
    import numpy as np

    jax.block_until_ready(
        jax.device_put(np.zeros((BATCH, NUM_COL), np.float32), dev))
    rates = []
    dev_rates = []  # device-side MB/s (bytes_to_device / wall) for the
    # line-rate join: comparable to the raw device_put floor, unlike the
    # corpus MB/s headline whose bytes differ from wire bytes
    best = 0.0
    attribution = None  # per-stage table of the best rep (steady state)
    resilience = None  # retry/resume/restart counters of the best rep
    parallel = None  # parse fan-out sideband of the best rep
    for _ in range(REPS):
        t0 = time.monotonic()
        parser = create_parser(path, 0, 1, "libsvm", threaded=True,
                               chunk_bytes=CHUNK_BYTES)
        # pack_aux: label/weight ride as two trailing x columns — ONE
        # device_put per batch instead of three arrays (the 3-array put
        # measured ~2x slower per byte, bench_transfer_floor.py aux leg).
        # f32 packs automatically (lossless); the bf16 opt-in is sound
        # HERE because this corpus's labels (0/1) and weights (1.0) are
        # bf16-exact — general callers must make that call themselves.
        it = DeviceIter(parser, num_col=NUM_COL, batch_size=BATCH,
                        layout="dense", prefetch=4, convert_ahead=6,
                        x_dtype=x_dtype, pack_aux=True)
        # the FIRST pull carries pipeline spin-up (producer threads
        # starting, first chunk parsed) — a per-epoch constant. Its time
        # stays IN the throughput wall-clock (no free head start), but the
        # stall counters reset after it so the stall metric speaks to the
        # steady state, which is what "zero input-bound stalls" is about.
        nbatches = 1
        last = next(it)
        it.stall_seconds = 0.0
        it.host_stall_seconds = 0.0
        for batch in it:
            last = batch
            nbatches += 1
        # ensure all transfers have actually landed in HBM. device_put is
        # async, so stall_seconds (wait for a batch HANDLE) cannot see
        # transfers still in flight — this drain is that blind spot made
        # visible: the backlog of issued-but-unlanded transfers when the
        # consumer finishes pulling. Pipeline keeping up => ~one batch.
        t_drain = time.monotonic()
        if last is not None:
            jax.block_until_ready(last)
        drain = time.monotonic() - t_drain
        dt = time.monotonic() - t0
        mbps = size_mb / dt
        rates.append(mbps)
        dev_rates.append(it.bytes_to_device / 2**20 / dt)
        if mbps > best:
            best = mbps
            # stage attribution of the winning rep, with the final drain
            # folded into the transfer stage (the sampled sideband only
            # sees every Nth batch; the drain is the end-of-epoch residue)
            stats = it.stats()
            attribution = _bench_common().attribution_line(
                stats, extra_transfer=drain)
            resilience = stats.get("resilience")
            parallel = {
                "parse_workers": stats.get("parse_workers"),
                "parse_parallelism_efficiency":
                    stats.get("parse_parallelism_efficiency"),
                # the trustworthy input-bound counter (ISSUE 10 satellite:
                # handle waits + sampled transfer landings — nonzero on a
                # transfer-bound epoch even when stall_seconds reads 0)
                "input_wait_seconds": round(
                    stats.get("input_wait_seconds") or 0.0, 4),
            }
        it.close()
        log(
            f"bench: into-HBM {nbatches} batches in {dt:.2f}s = "
            f"{mbps:.1f} MB/s, "
            f"device bytes {it.bytes_to_device/2**20:.1f} MB, "
            f"steady-state stall {it.stall_seconds:.3f}s = "
            f"{100*it.stall_seconds/dt:.1f}% of wall "
            f"(host {it.host_stall_seconds:.3f}s, "
            f"final transfer drain {drain:.3f}s)"
        )
    return (best, _median(rates), (min(rates), max(rates)), attribution,
            (max(dev_rates), _median(dev_rates)), resilience, parallel)


def block_cache_epoch_pair(path: str, size_mb: float):
    """Cold+warm epoch pair through the parse-once block cache (ISSUE 5).

    Epoch 1 (cold): parse + shadow-write the columnar block cache while
    feeding HBM. Epoch 2 (warm): the same DeviceIter, re-armed by reset(),
    now streams mmap'd parsed RowBlocks — the parser is bypassed, so warm
    MB/s above the measured parse ceiling is structural proof the cache
    works (the acceptance bar: warm_vs_cold_speedup >= 2 on a quiet host).
    A third leg (ISSUE 8) re-opens the published cache with the epoch
    planner armed (``shuffle_seed=``) and times one PLAN-ORDERED warm
    epoch — seeded block permutation + windowed row shuffle — so the JSON
    line carries ``shuffled_warm_epoch_mb_per_sec`` and
    ``shuffle_overhead_pct`` (the price of shuffling vs sequential warm;
    the acceptance bar: within 20% — make bench-smoke gates the fields).

    Returns (cold_mb_per_sec, warm_mb_per_sec, warm_cache_state,
    warm_cache_read_seconds, shuffled_mb_per_sec, shuffled_stats).
    """
    import jax

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    cache = CORPUS + ".blockcache"
    for stale in (cache, cache + ".tmp"):
        try:
            os.remove(stale)
        except OSError:
            pass

    def one_epoch(it):
        t0 = time.monotonic()
        last = None
        nb = 0
        for batch in it:
            last = batch
            nb += 1
        if last is not None:
            jax.block_until_ready(last)
        return nb, time.monotonic() - t0

    # the cold epoch runs the NEW chunk-batch engine (ISSUE 14): parse
    # emits block-cache segment spans natively, the tee appends them with
    # zero re-encode (falls back loudly to the Python engine on a
    # toolchain-less host — the pair still measures)
    parser = create_parser(path, 0, 1, "libsvm", threaded=True,
                           chunk_bytes=CHUNK_BYTES, block_cache=cache,
                           engine="native-batch")
    it = DeviceIter(parser, num_col=NUM_COL, batch_size=BATCH,
                    layout="dense", prefetch=4, convert_ahead=6,
                    pack_aux=True)
    rates = {}
    warm_stats = None
    warm_cache_read = 0.0
    shuffled = None
    shuffled_stats = None
    it_shuf = None
    try:
        nb, dt = one_epoch(it)
        rates["cold"] = size_mb / dt
        stats = it.stats()
        cr_prev = stats["stages"].get("cache_read", 0.0)
        log(f"bench: block-cache cold epoch {nb} batches in {dt:.2f}s = "
            f"{size_mb/dt:.1f} MB/s (cache_state={stats['cache_state']})")
        it.reset()  # flips the source to the published warm cache
        # shuffled-warm pipeline on the SAME published cache: a
        # warm-at-construction pipeline serves its first pass in plan
        # order (docs/data.md). Sequential and shuffled warm epochs run
        # INTERLEAVED, best-of-2 each, so this host's 2-4x ambient swings
        # hit both legs evenly and the overhead ratio is the stable
        # quantity (same trick as the parse scaling curve).
        sparser = create_parser(path, 0, 1, "libsvm", threaded=True,
                                chunk_bytes=CHUNK_BYTES, block_cache=cache,
                                shuffle_seed=1234, shuffle_window=BATCH)
        it_shuf = DeviceIter(sparser, num_col=NUM_COL, batch_size=BATCH,
                             layout="dense", prefetch=4, convert_ahead=6,
                             pack_aux=True)
        scr_prev = 0.0
        pair_ratios = []
        for _round in range(3):
            nb, dt = one_epoch(it)
            seq_rate = size_mb / dt
            rates["warm"] = max(rates.get("warm", 0.0), seq_rate)
            warm_stats = it.stats()
            # stage counters are registry-backed and CUMULATIVE across
            # reset(): report each epoch's own cache_read delta, not the
            # running sum over both warm epochs
            cr_now = warm_stats["stages"].get("cache_read", 0.0)
            warm_cache_read, cr_prev = cr_now - cr_prev, cr_now
            log(f"bench: block-cache warm epoch {nb} batches in "
                f"{dt:.2f}s = {seq_rate:.1f} MB/s "
                f"(cache_state={warm_stats['cache_state']}, "
                f"cache_read={warm_cache_read:.3f}s)")
            it.reset()
            nb, dt = one_epoch(it_shuf)
            shuf_rate = size_mb / dt
            shuffled = max(shuffled or 0.0, shuf_rate)
            # the overhead estimate pairs ADJACENT epochs (they share the
            # ambient window): the best round's ratio is the structural
            # cost, not the noise floor
            pair_ratios.append(shuf_rate / seq_rate)
            shuffled_stats = it_shuf.stats()
            scr_now = shuffled_stats["stages"].get("cache_read", 0.0)
            scr_epoch, scr_prev = scr_now - scr_prev, scr_now
            log(f"bench: block-cache SHUFFLED warm epoch {nb} batches in "
                f"{dt:.2f}s = {shuf_rate:.1f} MB/s "
                f"(shuffle_seed={shuffled_stats['shuffle_seed']}, "
                f"epoch={shuffled_stats['epoch']}, "
                f"cache_read={scr_epoch:.3f}s, "
                f"round ratio {shuf_rate/seq_rate:.3f})")
            it_shuf.reset()
        shuffled_stats = dict(shuffled_stats,
                              pair_ratio=max(pair_ratios))
    finally:
        it.close()
        if it_shuf is not None:
            it_shuf.close()
        for leftover in (cache, cache + ".tmp"):
            try:
                os.remove(leftover)  # the pair must start cold every run
            except OSError:
                pass
    return (rates["cold"], rates["warm"], warm_stats["cache_state"],
            warm_cache_read, shuffled, shuffled_stats)


def batch_parse_leg(path: str, size_mb: float, rounds: int = 3):
    """Cold-path chunk-batch parse leg (ISSUE 14): the full cold
    cache-build — parse + DMLCBC01 tee + publish — through the new
    ``native-batch`` engine (SIMD chunk scan, segments materialized
    natively, zero Python re-encode) vs the pre-PR cold path (the
    streaming native reader's RowBlocks re-encoded per block by the
    Python writer). Both builds produce byte-identical caches (the
    parity suite pins that), so the ratio isolates the engine.

    The two builds run INTERLEAVED per round and the reported speedup is
    the best ROUND-PAIRED ratio — this host's 2-4x ambient swings hit
    both legs of a pair evenly, so the ratio is the stable quantity
    (same trick as the shuffle-overhead and parse-scaling legs).
    """
    from dmlc_tpu import native as _native
    from dmlc_tpu.data import create_parser

    # keyed by the measured corpus; the writer stages through the store's
    # process-unique tmp names, so a torn build never leaves `cache`
    cache = path + ".batchleg.blockcache"

    def cold_build(engine):
        try:
            os.remove(cache)
        except OSError:
            pass
        parser = create_parser(path, 0, 1, "libsvm", threaded=True,
                               chunk_bytes=CHUNK_BYTES, engine=engine,
                               block_cache=cache)
        try:
            t0 = time.monotonic()
            while parser.next_block() is not None:
                pass
            dt = time.monotonic() - t0
        finally:
            parser.close()
            try:
                os.remove(cache)
            except OSError:
                pass
        return size_mb / dt

    best_batch = best_stream = 0.0
    ratios = []
    for _round in range(max(2, rounds)):
        stream = cold_build("auto")
        batch = cold_build("native-batch")
        best_stream = max(best_stream, stream)
        best_batch = max(best_batch, batch)
        ratios.append(batch / stream)
        log(f"bench: cold cache-build round {_round}: native-batch "
            f"{batch:.1f} MB/s vs stream {stream:.1f} MB/s "
            f"(ratio {batch/stream:.3f})")
    out = {
        "native_batch_parse_mb_per_sec": round(best_batch, 2),
        "stream_cold_build_mb_per_sec": round(best_stream, 2),
        "batch_vs_stream_parse_speedup": round(max(ratios), 3),
        "batch_parse_simd_level": _native.simd_level(),
    }
    log(f"bench: native-batch cold build {best_batch:.1f} MB/s, "
        f"best paired speedup x{max(ratios):.2f}, simd level "
        f"{out['batch_parse_simd_level']}")
    return out


def snapshot_epoch_leg(path: str, size_mb: float):
    """Device-native snapshot store leg (ISSUE 9 tentpole): epoch 1
    parses + converts while shadow-writing the post-convert packed
    batches (``DMLCSN01``); warm epochs then mmap those batches straight
    into ``device_put`` with ZERO host convert work. The structural
    claims the JSON line carries:

    - ``snapshot_warm_mb_per_sec`` above the parse ceiling
      (``snapshot_vs_parse_ceiling > 1``) proves the parser AND the
      convert stage are bypassed, not merely overlapped;
    - ``snapshot_warm_convert_seconds`` ~ 0 with a nonzero
      ``snapshot_read_seconds`` is the stats()-level proof;
    - ``snapshot_wire_bytes_ratio`` (bf16 snapshot file bytes / f32)
      <= 0.55 shows reduced precision halves stored AND wire bytes.

    Returns the field dict to merge into the JSON line.
    """
    import jax

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    snap = CORPUS + ".snapshot"
    snap16 = CORPUS + ".bf16.snapshot"
    for stale in (snap, snap + ".tmp", snap16, snap16 + ".tmp"):
        try:
            os.remove(stale)
        except OSError:
            pass

    def one_epoch(it):
        t0 = time.monotonic()
        last = None
        nb = 0
        for batch in it:
            last = batch
            nb += 1
        if last is not None:
            jax.block_until_ready(last)
        return nb, time.monotonic() - t0

    out = {}
    it = it16 = None
    try:
        parser = create_parser(path, 0, 1, "libsvm", threaded=True,
                               chunk_bytes=CHUNK_BYTES, snapshot=snap)
        it = DeviceIter(parser, num_col=NUM_COL, batch_size=BATCH,
                        layout="dense", prefetch=4, convert_ahead=6,
                        pack_aux=True)
        nb, dt = one_epoch(it)
        stats = it.stats()
        log(f"bench: snapshot cold epoch {nb} batches in {dt:.2f}s = "
            f"{size_mb/dt:.1f} MB/s "
            f"(snapshot_state={stats['snapshot_state']})")
        warm = 0.0
        conv_prev = stats["stage_busy"].get("convert", 0.0)
        sr_prev = stats["stage_busy"].get("snapshot_read", 0.0)
        for _round in range(2):
            it.reset()
            nb, dt = one_epoch(it)
            warm = max(warm, size_mb / dt)
            stats = it.stats()
            # registry counters are cumulative across reset(): report the
            # epoch's own deltas, not the running sum
            conv_now = stats["stage_busy"].get("convert", 0.0)
            sr_now = stats["stage_busy"].get("snapshot_read", 0.0)
            conv_epoch, conv_prev = conv_now - conv_prev, conv_now
            sr_epoch, sr_prev = sr_now - sr_prev, sr_now
            log(f"bench: snapshot WARM epoch {nb} batches in {dt:.2f}s = "
                f"{size_mb/dt:.1f} MB/s "
                f"(snapshot_state={stats['snapshot_state']}, "
                f"convert={conv_epoch:.4f}s, "
                f"snapshot_read={sr_epoch:.4f}s)")
        out["snapshot_warm_mb_per_sec"] = round(warm, 2)
        out["snapshot_state"] = stats["snapshot_state"]
        out["snapshot_warm_convert_seconds"] = round(max(0.0, conv_epoch), 4)
        out["snapshot_read_seconds"] = round(max(0.0, sr_epoch), 4)
        # bf16 snapshot: one cold epoch through the bf16 pipeline writes
        # the half-width store — the file-size ratio IS the stored/wire
        # byte claim (the service ships the same segment encoding)
        parser16 = create_parser(path, 0, 1, "libsvm", threaded=True,
                                 chunk_bytes=CHUNK_BYTES, snapshot=snap16)
        it16 = DeviceIter(parser16, num_col=NUM_COL, batch_size=BATCH,
                          layout="dense", prefetch=4, convert_ahead=6,
                          x_dtype="bfloat16", pack_aux=True)
        one_epoch(it16)
        if os.path.exists(snap) and os.path.exists(snap16):
            ratio = os.path.getsize(snap16) / os.path.getsize(snap)
            out["snapshot_wire_bytes_ratio"] = round(ratio, 3)
            log(f"bench: snapshot bytes f32 "
                f"{os.path.getsize(snap)/2**20:.1f} MB, bf16 "
                f"{os.path.getsize(snap16)/2**20:.1f} MB -> ratio "
                f"{ratio:.3f}")
    finally:
        if it is not None:
            it.close()
        if it16 is not None:
            it16.close()
        for leftover in (snap, snap + ".tmp", snap16, snap16 + ".tmp"):
            try:
                os.remove(leftover)  # the leg must start cold every run
            except OSError:
                pass
    return out


def device_decode_leg(path: str, size_mb: float):
    """Device-side decode leg (ISSUE 18 tentpole): warm snapshot epochs
    with ``device_decode=True`` ship each batch's verbatim container
    span as ONE contiguous u8 transfer and decode it in HBM
    (``ops/device_decode``) — vs the host-decode warm tier, which builds
    numpy views over the mmap before ``device_put``. The JSON claims:

    - ``device_decode_mb_per_sec``: best warm epoch in span mode;
    - ``device_decode_vs_snapshot_speedup``: best ROUND-PAIRED ratio vs
      the host-decode warm epoch (alternating order cancels drift). On a
      real accelerator this is the decode-offload win and bench-smoke
      gates it >= 1.0; on the CPU backend "device" decode runs on the
      same silicon as the host path, so only field presence is gated —
      ``device_decode_backend`` says which case this run was;
    - ``device_decode_transfer_bytes``: verbatim span bytes of one warm
      epoch (the single-transfer contract: > 0 proves spans shipped);
    - ``device_decode_convert_seconds``: host convert busy in span mode,
      ~0 by construction (the zero-host-decode claim at stats() level).
    """
    import jax

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    snap = CORPUS + ".dd.snapshot"
    for stale in (snap, snap + ".tmp"):
        try:
            os.remove(stale)
        except OSError:
            pass

    def one_epoch(it):
        t0 = time.monotonic()
        last = None
        nb = 0
        for batch in it:
            last = batch
            nb += 1
        if last is not None:
            jax.block_until_ready(last)
        return nb, time.monotonic() - t0

    def make(dd):
        parser = create_parser(path, 0, 1, "libsvm", threaded=True,
                               chunk_bytes=CHUNK_BYTES, snapshot=snap)
        return DeviceIter(parser, num_col=NUM_COL, batch_size=BATCH,
                          layout="dense", prefetch=4, convert_ahead=6,
                          pack_aux=True, device_decode=dd)

    out = {}
    it_cold = it_h = it_d = None
    try:
        it_cold = make(False)
        nb, dt = one_epoch(it_cold)  # cold pass publishes the snapshot
        it_cold.close()
        it_cold = None
        log(f"bench: device-decode leg cold publish {nb} batches in "
            f"{dt:.2f}s")
        it_h, it_d = make(False), make(True)
        started = set()
        best_host = best_dev = best_ratio = 0.0
        conv_prev = dd_bytes_prev = 0.0
        for rnd in range(2):
            pairs = [("host", it_h), ("device", it_d)]
            if rnd % 2:
                pairs.reverse()  # rotate order so ambient drift cancels
            mbps = {}
            for name, it_ in pairs:
                if id(it_) in started:
                    it_.reset()
                started.add(id(it_))
                nb, dt = one_epoch(it_)
                mbps[name] = size_mb / dt
            best_host = max(best_host, mbps["host"])
            best_dev = max(best_dev, mbps["device"])
            best_ratio = max(best_ratio, mbps["device"] / mbps["host"])
            stats = it_d.stats()
            # cumulative across reset(): report per-epoch deltas
            conv_now = stats["stage_busy"].get("convert", 0.0)
            dd_now = float(stats["device_decode_bytes"])
            conv_epoch, conv_prev = conv_now - conv_prev, conv_now
            dd_bytes, dd_bytes_prev = dd_now - dd_bytes_prev, dd_now
            log(f"bench: device-decode warm round {rnd}: span "
                f"{mbps['device']:.1f} MB/s vs host-decode "
                f"{mbps['host']:.1f} MB/s (ratio "
                f"{mbps['device']/mbps['host']:.3f}, "
                f"span bytes {dd_bytes/2**20:.1f} MB, "
                f"convert {conv_epoch:.4f}s)")
        check_stats = it_d.stats()
        assert check_stats["snapshot_state"] == "warm", "leg never warmed"
        out["device_decode_mb_per_sec"] = round(best_dev, 2)
        out["device_decode_vs_snapshot_speedup"] = round(best_ratio, 3)
        out["device_decode_transfer_bytes"] = int(dd_bytes)
        out["device_decode_convert_seconds"] = round(max(0.0, conv_epoch), 4)
        out["device_decode_backend"] = jax.devices()[0].platform
        log(f"bench: device-decode warm {best_dev:.1f} MB/s = "
            f"x{best_ratio:.2f} over host-decode warm "
            f"({out['device_decode_backend']} backend)")
    finally:
        for it_ in (it_cold, it_h, it_d):
            if it_ is not None:
                it_.close()
        for leftover in (snap, snap + ".tmp"):
            try:
                os.remove(leftover)  # the leg must start cold every run
            except OSError:
                pass
    return out


def service_leg(path: str, size_mb: float, workers: int = 2):
    """Disaggregated data-service leg (``--service`` / ISSUE 7): a
    localhost 1-dispatcher + N-worker fleet parses the corpus's N
    partitions in parallel and streams the frames to one client, timed
    against the same partitions parsed serially on this host with the
    identical config. ``service_vs_local_speedup > 1`` means the fleet's
    parallel parse beats the single-host serial pass even after paying
    the frame encode + loopback TCP + decode tax — the disaggregation
    claim at smoke scale (arXiv:2210.14826). Also emits the
    control-plane resilience quartet (``dispatcher_restarts`` /
    ``worker_reregistrations`` / ``parts_reclaimed`` /
    ``control_plane_retries``, docs/service.md control-plane recovery)
    AND the elastic-membership sextet (``worker_drains`` /
    ``drain_handoffs`` / ``preemption_notices`` /
    ``speculative_reissues`` / ``speculative_wins`` / ``worker_joins``,
    docs/service.md elastic membership): all ten MUST read zero on a
    clean run — a nonzero value on healthy infrastructure means the
    control plane restarted, a worker was preempted/hedged, or the fleet
    churned mid-bench, any of which taints the throughput numbers.

    The **two-job multi-tenant leg** (ISSUE 15, docs/service.md
    multi-tenant service) then registers the SAME corpus twice on one
    fleet with share-by-signature armed and a knob-paced fleet
    autoscaler attached: job A parses and publishes the shared block
    caches, job B's parts all resolve to the published artifacts --
    ``shared_parse_ratio`` (parses avoided / parts supplied) is 0.5 by
    construction for the identical-corpus pair, gated ``>= 0.5`` by
    ``make bench-smoke``. ``service_jobs`` counts the tenants and
    ``fleet_scale_events`` the autoscaler's scale decisions -- which
    must be ZERO on a clean run (no flapping: a fast healthy smoke run
    gives the controller no sustained starvation to react to)."""
    import tempfile

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.io import resilience as _resilience
    from dmlc_tpu.service import DEFAULT_JOB, LocalFleet, ServiceParser
    from dmlc_tpu.utils import telemetry as _telemetry

    num_parts = workers
    cfg = {"format": "libsvm", "chunk_bytes": CHUNK_BYTES}
    t0 = time.monotonic()
    rows = 0
    for p in range(num_parts):
        parser = create_parser(path, p, num_parts, "libsvm",
                               chunk_bytes=CHUNK_BYTES)
        while parser.next_block() is not None:
            rows += 1
        parser.close()
    local_dt = time.monotonic() - t0
    res_base = _resilience.counters_snapshot()
    # fleet construction is inside the timed region: the workers' parallel
    # parse IS the work being measured, not a warm pre-parse
    t0 = time.monotonic()
    fleet = LocalFleet(path, num_parts, num_workers=workers, parser=cfg)
    client = None
    try:
        client = ServiceParser(fleet.address)
        sblocks = 0
        while client.next_block() is not None:
            sblocks += 1
        service_dt = time.monotonic() - t0
        # merged pod timeline + cross-process trace count (docs/
        # observability.md Distributed tracing): export ONE Chrome/
        # Perfetto JSON for the whole fleet (kept when
        # DMLC_BENCH_TRACE_PATH names a destination), and count the
        # (job, part) traces whose spans link the worker-side
        # encode/send to the client-side recv/decode — the one-trace-
        # per-part acceptance signal bench-smoke gates >= 1
        keep = os.environ.get("DMLC_BENCH_TRACE_PATH", "")
        trace_path = keep or os.path.join(CACHE_DIR, "service_trace.json")
        timeline_events = fleet.dump_trace(trace_path)
        if not keep:
            try:
                os.remove(trace_path)
            except OSError:
                pass
        worker_side = {"service_parse", "service_encode", "service_send"}
        client_side = {"service_recv", "service_decode"}
        by_tid: dict = {}
        for s in _telemetry.spans_snapshot():
            t = s.get("trace_id")
            if t:
                by_tid.setdefault(t, set()).add(s["name"])
        crossproc = sum(1 for names in by_tid.values()
                        if names & worker_side and names & client_side)
    finally:
        if client is not None:
            client.close()
        fleet.close()
    res = _resilience.counters_delta(res_base)
    log(f"bench: service {workers}-worker fleet {sblocks} blocks in "
        f"{service_dt:.2f}s = {size_mb/service_dt:.1f} MB/s vs local "
        f"serial {size_mb/local_dt:.1f} MB/s -> speedup "
        f"x{local_dt/service_dt:.2f} (control plane: "
        f"{res['dispatcher_restarts']} restarts, "
        f"{res['control_plane_retries']} retries; {crossproc} cross-"
        f"process trace(s), {timeline_events} timeline events)")
    # ---- two-job multi-tenant leg (docstring): same corpus, two jobs,
    # share-by-signature, knob-paced autoscaler attached for the ride
    tenant = "tenant-b"
    res2_base = _resilience.counters_snapshot()
    with tempfile.TemporaryDirectory(prefix="dmlc-svc-share-") as share:
        fleet = LocalFleet(path, num_parts, num_workers=workers,
                           parser=cfg, share_dir=share)
        scaler = None
        client = None
        try:
            # the autoscaler rides along on the clients' job-labeled
            # wait counters; a clean smoke run must produce ZERO scale
            # decisions (the fleet_scale_events == 0 gate)
            scaler = fleet.autoscale(
                source=lambda: {
                    j: _telemetry.REGISTRY.sum(
                        _telemetry.SERVICE_JOB_WAIT_METRIC, job=j)
                    for j in (DEFAULT_JOB, tenant)},
                start=True)
            client = ServiceParser(fleet.address)
            jobs_blocks = 0
            while client.next_block() is not None:
                jobs_blocks += 1
            client.close()
            # register the tenant AFTER job A published: its parts must
            # all resolve to the shared artifacts (parse-once)
            fleet.register_job(tenant, path, num_parts, parser=cfg)
            client = ServiceParser(fleet.address, job=tenant)
            tenant_blocks = 0
            while client.next_block() is not None:
                tenant_blocks += 1
        finally:
            if client is not None:
                client.close()
            if scaler is not None:
                scaler.close()
            fleet.close()
    res2 = _resilience.counters_delta(res2_base)
    parsed = res2["service_parts_parsed"]
    shared = res2["service_parts_shared"]
    shared_ratio = shared / max(1, parsed + shared)
    scale_events = res2["fleet_scale_ups"] + res2["fleet_scale_downs"]
    log(f"bench: service two-job leg: {jobs_blocks}+{tenant_blocks} "
        f"blocks, {parsed} parts parsed / {shared} shared -> "
        f"shared_parse_ratio {shared_ratio:.3f}, "
        f"{scale_events} fleet scale events")
    return {
        "service_workers": workers,
        "service_mb_per_sec": round(size_mb / service_dt, 2),
        "service_vs_local_speedup": round(local_dt / service_dt, 3),
        "dispatcher_restarts": res["dispatcher_restarts"],
        "worker_reregistrations": res["worker_reregistrations"],
        "parts_reclaimed": res["parts_reclaimed"],
        "control_plane_retries": res["control_plane_retries"],
        "worker_drains": res["worker_drains"],
        "drain_handoffs": res["drain_handoffs"],
        "preemption_notices": res["preemption_notices"],
        "speculative_reissues": res["speculative_reissues"],
        "speculative_wins": res["speculative_wins"],
        "worker_joins": res["worker_joins"],
        "service_jobs": 2,
        "shared_parse_ratio": round(shared_ratio, 3),
        "fleet_scale_events": scale_events,
        "trace_spans_crossproc": crossproc,
        "trace_timeline_events": timeline_events,
    }


def service_wire_leg(path: str, size_mb: float, workers: int = 2):
    """Wire v2 transport leg (``--service`` / ISSUE 16, docs/service.md
    Wire v2): measures the three transport optimisations separately.

    **Pipelining.** A warm fleet (cold pass untimed) streams the corpus
    over TCP at pipeline depth 1 (strict request/response — the
    one-request-per-frame baseline) and at the configured
    ``service_pipeline_depth``, interleaved, median of 5 each.
    ``service_wire_pipelined_speedup`` carries the ratio; the ``make
    bench-smoke`` gate is ``>= 0.85`` — a no-regression guard with a
    measurement-noise floor, because loopback RTT is microseconds
    against a ~100us/block decode (the window's win is proportional to
    real network latency, which a single-host smoke cannot manufacture;
    keeping the window full must never LOSE to lock-step).

    **Compression.** The worker-side byte ledger
    (``service_wire_bytes_sent / service_wire_bytes_raw``) over the
    timed streams yields ``service_wire_compression_ratio`` — gated
    ``<= 1.0`` because the per-dtype break-even check refuses codecs
    that inflate (f32 value segments ship raw; int offset/index
    segments compress). ``service_wire_gbps`` is the decoded payload
    rate of the best pipelined epoch (raw bytes, i.e. what the client
    actually materialises).

    **Local fast path.** A second share-armed fleet publishes its block
    caches, then a co-located client re-reads the corpus:
    ``service_wire_fastpath`` counts blocks served straight off the
    mmapped artifact (no socket) and must equal ``service_wire_blocks``
    on this single-host bench."""
    import tempfile

    from dmlc_tpu.service import LocalFleet, ServiceParser
    from dmlc_tpu.utils import knobs as _knobs
    from dmlc_tpu.utils import telemetry as _telemetry

    num_parts = workers
    # transport microbench: 16x smaller blocks than the throughput legs
    # so the frame count (and with it the per-request round-trip cost a
    # depth-1 schedule pays) is large enough to measure — the wire is
    # the subject here, not the parser
    cfg = {"format": "libsvm", "chunk_bytes": max(64 * 1024,
                                                  CHUNK_BYTES // 16)}
    depth = _knobs.resolve("service_pipeline_depth")

    def _drain(sp):
        n = 0
        while sp.next_block() is not None:
            n += 1
        return n

    def _wire_bytes():
        return (_telemetry.REGISTRY.counter(
                    _telemetry.SERVICE_WIRE_RAW_METRIC, job="default").value,
                _telemetry.REGISTRY.counter(
                    _telemetry.SERVICE_WIRE_SENT_METRIC, job="default").value)

    # --- TCP timings: no share_dir, so no published cache artifact and
    # no local fast path — every block crosses the socket
    fleet = LocalFleet(path, num_parts, num_workers=workers, parser=cfg)
    try:
        sp = ServiceParser(fleet.address)
        blocks = _drain(sp)  # cold pass (untimed): workers parse once
        sp.close()
        raw0, sent0 = _wire_bytes()

        def _one(d):
            sp = ServiceParser(fleet.address)
            if sp.pipeline_depth != d:
                sp.resize_pipeline_depth(d)
            r0, _s0 = _wire_bytes()
            t0 = time.monotonic()
            n = _drain(sp)
            dt = time.monotonic() - t0
            sp.close()
            if n != blocks:
                raise RuntimeError(
                    f"wire leg streamed {n} blocks, expected {blocks}")
            return dt, _wire_bytes()[0] - r0

        # interleaved pairs + best-of: scheduler hiccups and page-cache
        # drift only ever ADD time, so the per-schedule floor is the
        # noise-robust estimate, and interleaving keeps slow windows
        # from landing on one schedule wholesale
        seq_runs, pipe_runs = [], []
        for i in range(6):
            # alternate which schedule goes first so monotone drift
            # (thermal, page cache) cannot systematically favor one
            if i % 2 == 0:
                seq_runs.append(_one(1))
                pipe_runs.append(_one(depth))
            else:
                pipe_runs.append(_one(depth))
                seq_runs.append(_one(1))
        seq_dt = min(dt for dt, _ in seq_runs)
        pipe_dt, pipe_raw = min(pipe_runs)
        raw1, sent1 = _wire_bytes()
    finally:
        fleet.close()
    raw, sent = raw1 - raw0, sent1 - sent0
    ratio = sent / max(1, raw)
    # --- local fast path: share-armed fleet publishes block caches on
    # the cold pass; the warm co-located client mmaps them (docs/
    # service.md local fast path) and the socket carries zero blocks
    with tempfile.TemporaryDirectory(prefix="dmlc-wire-share-") as share:
        fleet = LocalFleet(path, num_parts, num_workers=workers,
                           parser=cfg, share_dir=share)
        fp_blocks = 0
        try:
            sp = ServiceParser(fleet.address)
            _drain(sp)
            sp.close()
            sp = ServiceParser(fleet.address)
            n = _drain(sp)
            fp_blocks = sp.fastpath_blocks
            sp.close()
            if n != blocks:
                raise RuntimeError(
                    f"fastpath leg streamed {n} blocks, expected {blocks}")
        finally:
            fleet.close()
    log(f"bench: wire v2 {blocks} blocks: sequential {seq_dt:.3f}s vs "
        f"depth-{depth} pipelined {pipe_dt:.3f}s -> "
        f"x{seq_dt / pipe_dt:.2f}, compression {sent}/{raw} bytes = "
        f"{ratio:.3f}, fastpath {fp_blocks}/{blocks} blocks off-socket")
    return {
        "service_wire_blocks": blocks,
        "service_pipeline_depth": depth,
        "service_wire_gbps": round(pipe_raw * 8 / max(pipe_dt, 1e-9) / 1e9,
                                   3),
        "service_wire_sequential_mb_per_sec": round(size_mb / seq_dt, 2),
        "service_wire_pipelined_mb_per_sec": round(size_mb / pipe_dt, 2),
        "service_wire_pipelined_speedup": round(seq_dt / pipe_dt, 3),
        "service_wire_compression_ratio": round(ratio, 3),
        "service_wire_fastpath": fp_blocks,
    }


def service_qos_leg(path: str, size_mb: float, workers: int = 2):
    """Production-QoS leg (``--service`` / ISSUE 17, docs/service.md
    Production QoS): two-class contention on one fleet. A
    latency-critical tenant (priority 1, weight 2, ``slo_wait_frac``)
    and a batch tenant (priority 0, ``max_inflight=1``) read the same
    corpus while ``DMLC_TPU_QOS_MAX_INFLIGHT`` caps the fleet's
    concurrent parses at the worker count. The critical job's cold
    epoch saturates the admission ceiling, so the batch tenant's
    locates shed with retryable ``throttled`` replies
    (``service_qos_throttles`` — gated ``>= 1`` by ``make
    bench-smoke``) that the client backs off on
    (``service_qos_admission_waits``) WITHOUT ever burning toward a
    give-up (``service_qos_giveups`` gated ``== 0``). Both tenants
    drain their full epochs — overload degrades to bounded queueing,
    never to failure.

    ``service_qos_critical_wait_frac`` is the critical job's WARM-epoch
    input-wait fraction (client wait seconds / epoch wall) measured
    while the batch tenant is still cold-parsing beside it, with a
    small per-block consume pause modeling a trainer's step cadence —
    the same job-labeled signal the SLO-driven autoscaler steers on.
    Gated ``< service_qos_critical_slo`` by ``make bench-smoke``: the
    priority band + admission budget must keep the critical tenant
    under its declared SLO despite the saturating sibling."""
    import threading as _threading

    from dmlc_tpu.io import resilience as _resilience
    from dmlc_tpu.service import LocalFleet, ServiceParser
    from dmlc_tpu.utils import telemetry as _telemetry

    num_parts = max(4, workers * 2)
    cfg = {"format": "libsvm", "chunk_bytes": CHUNK_BYTES}
    slo = 0.5
    res_base = _resilience.counters_snapshot()
    # born-empty fleet: both tenants are explicit registrations, so the
    # default job cannot skew the grant rotation under test
    fleet = LocalFleet(None, 0, num_workers=workers, parser=cfg)
    os.environ["DMLC_TPU_QOS_MAX_INFLIGHT"] = str(workers)
    batch_blocks = [0]
    batch_errs: list = []

    def _drain_batch():
        sp = ServiceParser(fleet.address, job="qos-batch")
        try:
            while sp.next_block() is not None:
                batch_blocks[0] += 1
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            batch_errs.append(exc)
        finally:
            sp.close()

    try:
        fleet.register_job("qos-critical", path, num_parts, parser=cfg,
                           priority=1, weight=2, slo_wait_frac=slo)
        fleet.register_job("qos-batch", path, num_parts, parser=cfg,
                           max_inflight=1)
        # critical cold epoch first: its grants preempt and saturate the
        # ceiling, so the batch thread's locates shed deterministically
        crit = ServiceParser(fleet.address, job="qos-critical")
        batch_thread = _threading.Thread(target=_drain_batch, daemon=True)
        crit_blocks = 0
        try:
            batch_thread.start()
            while crit.next_block() is not None:
                crit_blocks += 1
        finally:
            crit.close()
        # warm critical epoch, timed: every part is parsed and served
        # off the workers' stores, so the wait frac is steady-state
        # input starvation, not the cold build
        wait_c = _telemetry.REGISTRY.counter(
            _telemetry.SERVICE_JOB_WAIT_METRIC, job="qos-critical")
        crit = ServiceParser(fleet.address, job="qos-critical")
        warm_blocks = 0
        try:
            wait0 = wait_c.value
            t0 = time.monotonic()
            while crit.next_block() is not None:
                warm_blocks += 1
                time.sleep(0.02)  # the trainer's consume cadence
            warm_dt = time.monotonic() - t0
            crit_wait = wait_c.value - wait0
        finally:
            crit.close()
        batch_thread.join(timeout=600.0)
        if batch_errs:
            raise batch_errs[0]
        if batch_thread.is_alive():
            raise RuntimeError("qos leg: batch tenant never drained")
    finally:
        os.environ.pop("DMLC_TPU_QOS_MAX_INFLIGHT", None)
        fleet.close()
    res = _resilience.counters_delta(res_base)
    wait_frac = crit_wait / max(warm_dt, 1e-9)
    log(f"bench: service qos leg: critical {crit_blocks} cold + "
        f"{warm_blocks} warm blocks (wait frac {wait_frac:.3f} vs slo "
        f"{slo}), batch {batch_blocks[0]} blocks through "
        f"{res['service_throttles']} throttles / "
        f"{res['service_admission_waits']} admission waits, "
        f"{res['service_giveups']} giveups")
    return {
        "service_qos_jobs": 2,
        "service_qos_critical_slo": slo,
        "service_qos_critical_wait_frac": round(wait_frac, 4),
        "service_qos_critical_blocks": warm_blocks,
        "service_qos_batch_blocks": batch_blocks[0],
        "service_qos_throttles": res["service_throttles"],
        "service_qos_admission_waits": res["service_admission_waits"],
        "service_qos_giveups": res["service_giveups"],
    }


def autotune_leg(path: str, size_mb: float, max_epochs: int = 5):
    """Offline controller convergence (``--autotune`` / ISSUE 10): run
    the ingest pipeline with the feedback controller armed at a
    deliberately starved config (prefetch 1, convert_ahead 1) and
    mid-epoch stepping, for repeated epochs until the controller reports
    convergence (two consecutive steady windows — gap_stage == transfer /
    the consumer never waits) or the epoch budget runs out. The JSON
    line then carries the decision count and the CHOSEN CONFIG keyed by
    env variable names, so a converged run is reusable verbatim::

        export DMLC_TPU_PREFETCH=4 DMLC_TPU_CONVERT_AHEAD=8 ...

    (docs/data.md autotune section; make bench-smoke gates the fields).
    """
    import jax

    from dmlc_tpu.data import autotune as _autotune
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    parser = create_parser(path, 0, 1, "libsvm", threaded=True,
                           chunk_bytes=CHUNK_BYTES)
    it = DeviceIter(parser, num_col=NUM_COL, batch_size=BATCH,
                    layout="dense", prefetch=1, convert_ahead=1,
                    pack_aux=True, autotune=True, autotune_interval=16)
    rate = 0.0
    try:
        for ep in range(max_epochs):
            t0 = time.monotonic()
            last = None
            nb = 0
            for batch in it:
                last = batch
                nb += 1
            if last is not None:
                jax.block_until_ready(last)
            dt = time.monotonic() - t0
            rate = max(rate, size_mb / dt)
            snap = it.autotuner.snapshot(history=1)
            log(f"bench: autotune epoch {ep} {nb} batches in {dt:.2f}s = "
                f"{size_mb/dt:.1f} MB/s (steps {snap['steps']}, "
                f"adjustments {snap['adjustments']}, knobs "
                f"{snap['knobs']}, converged {snap['converged']})")
            if it.autotuner.converged and ep >= 1:
                break
            it.reset()
        snap = it.autotuner.snapshot(history=4)
        for d in snap["history"]:
            log(f"bench: autotune decision: {d}")
        return {
            "autotune_enabled": True,
            "autotune_steps": snap["steps"],
            "autotune_adjustments": snap["adjustments"],
            "autotune_converged": snap["converged"],
            "autotune_gap_stage": snap["gap_stage"],
            "autotune_final_config": _autotune.env_config(snap["knobs"]),
            "autotune_mb_per_sec": round(rate, 2),
        }
    finally:
        it.close()


def trace_overhead_leg(path: str, size_mb: float, reps: int = 3):
    """Trace-propagation tax (docs/observability.md Distributed
    tracing): a warm parse-epoch pair — trace context armed (a live
    trace installed, every span stamped) against propagation forced off
    — interleaved, best-of-``reps`` each. ``trace_overhead_pct`` is the
    relative cost of the armed leg; ``make bench-smoke`` gates it < 5%
    (the observability plane must be cheap enough to leave on). Best-of
    because scheduler noise and page-cache drift only ever ADD time;
    interleaved so drift lands on both legs equally."""
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.utils import telemetry as _telemetry

    def _epoch() -> float:
        t0 = time.monotonic()
        parser = create_parser(path, 0, 1, "libsvm",
                               chunk_bytes=CHUNK_BYTES)
        while parser.next_block() is not None:
            pass
        parser.close()
        return time.monotonic() - t0

    _epoch()  # both legs must measure warm page-cache supply
    on = off = float("inf")
    try:
        for _ in range(max(1, int(reps))):
            _telemetry.set_trace_propagation(True)
            with _telemetry.trace(_telemetry.new_trace_id(),
                                  _telemetry.new_span_id()):
                on = min(on, _epoch())
            _telemetry.set_trace_propagation(False)
            off = min(off, _epoch())
    finally:
        _telemetry.set_trace_propagation(None)
    pct = (on - off) / off * 100.0 if off > 0 else 0.0
    log(f"bench: trace overhead: traced {size_mb/on:.1f} MB/s vs "
        f"untraced {size_mb/off:.1f} MB/s -> {pct:+.2f}%")
    return {"trace_overhead_pct": round(pct, 2)}


def als_train_leg(size_mb: float, epochs: int = 4):
    """Pod-scale sparse training (ISSUE 20): ALX-style sharded ALS
    (models/als.py) trained end-to-end off the warm pod-sharded block
    cache, measuring whether the ingest stack keeps the loop
    COMPUTE-bound — tf.data's (arXiv:2101.12127) input-starvation
    failure mode, quantified per epoch:

    - ``als_rows_per_sec``: user rows solved per second, best warm epoch;
    - ``als_step_seconds``: mean jitted-step wall on that epoch;
    - ``als_input_wait_frac``: input_wait_seconds delta / epoch wall —
      the PR 10 trustworthy input-bound counter as a fraction of the
      training wall. The compute-bound bar (< 0.2 on accelerator) is the
      TPU-return criterion; on the CPU host ``make bench-smoke`` gates
      field presence + a completed warm-fed loop only;
    - ``als_overlap_frac``: 1 - input_wait / ingest_busy — the fraction
      of producer busy time hidden under training compute.

    The leg builds its own small fixed-size ratings corpus (label = user
    id, features = item:rating — the models/als.py encoding): overlap
    fractions, not throughput scaling, are the judged signal, so corpus
    size does not track DMLC_BENCH_MB."""
    import shutil

    import jax
    import numpy as np

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import AlsLearner

    users, items, per_row, factors, batch = 2048, 512, 16, 8, 512
    corpus = os.path.join(CACHE_DIR, f"als_{users}x{items}x{per_row}.libsvm")
    if not os.path.exists(corpus):
        rng = np.random.default_rng(0)
        gt_u = rng.normal(size=(users, factors)).astype(np.float32)
        gt_v = rng.normal(size=(items, factors)).astype(np.float32)
        with open(corpus + ".tmp", "w") as f:
            for uid in range(users):
                cols = rng.choice(items, size=per_row, replace=False)
                ratings = gt_u[uid] @ gt_v[cols].T
                feats = " ".join(f"{j}:{r:.6f}"
                                 for j, r in zip(cols, ratings))
                f.write(f"{uid} {feats}\n")
        os.replace(corpus + ".tmp", corpus)
    cache = os.path.join(CACHE_DIR, "als_cache")
    shutil.rmtree(cache, ignore_errors=True)  # deterministic cold->warm

    model = AlsLearner(users, items, num_factors=factors, reg=0.05, seed=0)
    parser = create_parser(corpus, 0, 1, "libsvm", block_cache=cache,
                           shuffle_seed=0, pod_sharding=True,
                           chunk_bytes=32 << 10)
    it = DeviceIter(parser, num_col=model.device_num_col(),
                    batch_size=batch, layout="ell", max_nnz=per_row,
                    drop_remainder=True)
    best = None
    loss = 0.0
    try:
        for ep in range(max(2, int(epochs))):
            st0 = it.stats()
            wait0 = st0["input_wait_seconds"]
            busy0 = sum(st0["stage_busy"].values())
            t0 = time.monotonic()
            rows = steps = 0
            step_s = 0.0
            dloss = None
            for b in it:
                ts = time.monotonic()
                dloss = model.step(b)
                step_s += time.monotonic() - ts
                steps += 1
                rows += b.batch_size
            model.finalize_items()
            # training wall must include the epoch's full device work:
            # the async dispatches drain here, inside the timed window
            jax.block_until_ready((model.params.users, model.params.items))
            wall = time.monotonic() - t0
            loss = float(dloss) if dloss is not None else 0.0
            st1 = it.stats()
            wait = st1["input_wait_seconds"] - wait0
            busy = sum(st1["stage_busy"].values()) - busy0
            it.reset()
            if ep == 0 or steps == 0:
                continue  # cold epoch builds the cache; warm epochs judge
            rec = {
                "als_rows_per_sec": round(rows / max(wall, 1e-9), 1),
                "als_step_seconds": round(step_s / steps, 6),
                "als_input_wait_frac": round(wait / max(wall, 1e-9), 4),
                "als_overlap_frac": round(
                    min(1.0, max(0.0, 1.0 - wait / busy))
                    if busy > 1e-9 else 1.0, 4),
                "als_cache_state": st1.get("cache_state"),
            }
            if best is None or rec["als_rows_per_sec"] > \
                    best["als_rows_per_sec"]:
                best = rec
    finally:
        it.close()
    if best is None:
        raise RuntimeError("als leg: no warm epoch completed")
    best["als_train_loss"] = round(loss, 5)
    log(f"bench: als train: {best['als_rows_per_sec']} rows/s warm, step "
        f"{best['als_step_seconds']*1e3:.2f} ms, input wait frac "
        f"{best['als_input_wait_frac']}, overlap "
        f"{best['als_overlap_frac']}, cache {best['als_cache_state']}, "
        f"loss {best['als_train_loss']}")
    return best


def device_floor_mbps(x_dtype: str = "float32"):
    """Raw repeated-shape device_put floor for bench.py's exact batch
    geometry, measured in THIS process right after the pipeline reps (same
    backend, same host load) so the line-rate join compares rates
    captured minutes — not rounds — apart. Returns
    (best, median, trimmed_best) MB/s.

    This is the denominator of ``pct_of_line_rate``: the BASELINE claim is
    ">=90% of host->HBM line rate with zero input-bound stalls", and the
    line rate IS what device_put of the same bytes sustains with no
    parsing attached (benchmarks/bench_transfer_floor.py standalone form).

    Stability (the bf16 floor once swung 2.8x between best and median,
    on an earlier installation, not re-measured): the first timed rounds
    used to eat lazy backend work —
    the bf16 view wrapper, dtype-specific transfer-plan setup — so the
    path is now WARMED with full untimed put rounds until the rate
    stabilizes (bounded), and ``trimmed_best`` (the best sample after
    dropping the single highest — one fluke window cannot own it) rides
    alongside best/median as the stable denominator snapshot gating
    divides by."""
    import jax
    import numpy as np

    if x_dtype == "bfloat16":
        from dmlc_tpu.native import bf16_dtype

        np_dtype = bf16_dtype()
    else:
        np_dtype = np.dtype(x_dtype)
    rng = np.random.default_rng(0)
    # the SAME put the pipeline issues per batch: since pack_aux, a dense
    # batch is ONE [B, D+2] array (label/weight as trailing columns) —
    # the floor must mirror that exact shape/array-count, or the
    # denominator pays per-array overhead the pipeline no longer pays
    # (the 3-array put measured ~2x slower per byte) and the judged
    # >=90% ratio reads too favorable
    batch = [
        rng.standard_normal((BATCH, NUM_COL + 2)).astype(np_dtype),
    ]
    n = 64
    mb = n * sum(a.nbytes for a in batch) / 2**20
    # warm up until two consecutive untimed rounds agree within 25% (or
    # the bounded budget runs out): first-touch costs — transfer-plan
    # build, dtype wrapper setup, allocator growth — must not land inside
    # a timed sample
    prev = None
    for _ in range(4):
        t0 = time.monotonic()
        jax.block_until_ready([jax.device_put(batch) for _ in range(n)])
        rate = mb / (time.monotonic() - t0)
        if prev is not None and abs(rate - prev) <= 0.25 * max(rate, prev):
            break
        prev = rate
    samples = []
    for _ in range(5):
        t0 = time.monotonic()
        handles = [jax.device_put(batch) for _ in range(n)]
        jax.block_until_ready(handles)
        samples.append(mb / (time.monotonic() - t0))
    trimmed = max(sorted(samples)[:-1])  # best-of after dropping the top
    log(f"bench: device_put floor ({x_dtype}) best {max(samples):.1f} "
        f"trimmed {trimmed:.1f} median {_median(samples):.1f} MB/s")
    return max(samples), _median(samples), trimmed


def _bench_common():
    """The shared benchmark helpers — one module so the attribution table
    cannot diverge between bench.py and benchmarks/*."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "benchmarks"))
    import _common

    return _common


def run(service: bool, autotune: bool) -> int:
    """The measurement: one process, one backend, the device it finds."""
    global _DEVICE_TAG
    from dmlc_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax

    from dmlc_tpu import native

    devs = jax.devices()
    dev = devs[0]
    _DEVICE_TAG = f"{dev.platform}/{dev.device_kind}/{len(devs)}"
    engine = "native" if native.available() else "numpy"
    log(f"bench: engine={engine} compile_cache={cache_dir}")
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        log("bench: FAIL no TPU found and the CPU backend was not asked for "
            "by name (JAX_PLATFORMS=cpu): refusing to measure")
        return 2
    if engine != "native":
        log("bench: FAIL the native parse engine did not load (no g++, a "
            "failed build, or DMLC_TPU_NO_NATIVE): the numpy parsers would "
            "report a slow success")
        return 2
    path = make_corpus()
    size_mb = os.path.getsize(path) / 2**20
    log(f"bench: corpus {size_mb:.1f} MB")
    base_best, base_med = host_only_mb_per_sec(path, size_mb)
    (value, med, spread, attribution, dev_rates, resilience,
     parallel) = into_hbm_mb_per_sec(path, size_mb)
    line = {
        "metric": "rowblockiter_mb_per_sec_into_hbm",
        "value": round(value, 2),
        "unit": "MB/s",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(devs),
        "engine": engine,
        "vs_baseline": round(value / base_best, 3),
        # median + spread alongside best-of: with 2-4x ambient swings on this
        # shared host a single lucky rep can overstate steady state
        "median": round(med, 2),
        "median_vs_baseline": round(med / base_med, 3),
        "spread": [round(spread[0], 2), round(spread[1], 2)],
        "reps": REPS,
    }
    if attribution is not None:
        # per-stage wall attribution of the best rep (the unaccounted
        # share of pipeline bound, decomposed into named costs) — same
        # object in the JSON, human table on stderr
        line["attribution"] = attribution
        log("bench: ingest stage attribution (best rep):")
        log(_bench_common().attribution_table(attribution))
    if resilience is not None:
        # fault-tolerance counters of the best rep (docs/resilience.md):
        # a clean run emits zeros — nonzero retries/resumes on a healthy
        # loopback corpus would flag a regression in the I/O stack
        line["resilience"] = resilience
        hot = {k: v for k, v in resilience.items() if v}
        if hot:
            log(f"bench: resilience events: {hot}")
    if parallel is not None:
        # the pipeline's parse fan-out width + measured parallel efficiency
        # (docs/data.md parse_workers; the native reader reports its C++
        # thread count with no efficiency instrumentation)
        line["parse_workers"] = parallel.get("parse_workers")
        line["parse_parallelism_efficiency"] = parallel.get(
            "parse_parallelism_efficiency")
        line["input_wait_seconds"] = parallel.get("input_wait_seconds")
    # parse fan-out scaling curve (ISSUE 3): the host parse ceiling of the
    # PYTHON engine at 1/2/4 workers, interleaved so ambient drift cancels
    # in the ratio. parse_ceiling_workers_1 is the pre-fan-out engine;
    # parse_ceiling_workers_4 over it is the PR's raised ceiling.
    try:
        curve = parse_scaling_curve(path, size_mb)
        scaling = {}
        for w, (cbest, cmed) in sorted(curve.items()):
            line[f"parse_ceiling_workers_{w}"] = round(cbest, 2)
            scaling[str(w)] = {"best": round(cbest, 2),
                               "median": round(cmed, 2)}
        line["parse_scaling"] = scaling
        ws = sorted(curve)
        lo, hi = curve[ws[0]], curve[ws[-1]]
        line["parse_parallel_speedup"] = round(hi[0] / lo[0], 3)
        line["parse_parallel_speedup_median"] = round(hi[1] / lo[1], 3)
        log(f"bench: parse fan-out scaling (best): "
            + ", ".join(f"{w}w={curve[w][0]:.1f}" for w in ws)
            + f" MB/s -> speedup x{hi[0]/lo[0]:.2f}")
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("parse_scaling", exc)
    # percent-of-line-rate: the BASELINE framing is
    # ">=90% of host->HBM line rate", which vs-parse-baseline does not
    # measure. Join the raw device_put floor for the same shapes/dtype,
    # captured in this same process, and report the pipeline's device-side
    # rate as a fraction of it.
    try:
        floor_best, floor_med, floor_trim = device_floor_mbps("float32")
        line["line_rate_trimmed_mb_per_sec"] = round(floor_trim, 2)
        line["pct_of_line_rate"] = round(dev_rates[0] / floor_best, 3)
        line["pct_of_line_rate_median"] = round(dev_rates[1] / floor_med, 3)
        line["device_mb_per_sec"] = round(dev_rates[0], 2)
        line["line_rate_floor_mb_per_sec"] = round(floor_best, 2)
        # the BINDING bound: the pipeline can go no faster than
        # min(its parse ceiling, the link) — which resource binds can
        # flip with host load, so the ">=90%, zero stalls" claim is
        # judged against the minimum of both, in corpus MB/s.
        # (pct_of_line_rate alone under-reads a parse-bound pipeline and
        # says nothing about a link-bound one's parse headroom.)
        thr_best, thr_med = host_only_mb_per_sec(path, size_mb,
                                                 threaded=True,
                                                 emit_dense=True)
        # overlap check against the host-only parse ceiling measured in
        # THIS run: with convert/dispatch overlapped the pipeline should
        # reach >= 0.95x of it (the device leg runs the same parse plus an
        # async put) — when it does not, name the stage that owns the gap
        # so the shortfall is attributed, never unaccounted. Candidates:
        # every non-parse stage's full seconds, plus parse's EXCESS over
        # the seconds the standalone ceiling needs for the same bytes
        # (parse running over its own ceiling share = core contention /
        # ambient drift, and the honest owner is then parse itself).
        pct_ceiling = value / thr_best
        line["pct_of_parse_ceiling"] = round(pct_ceiling, 3)
        if pct_ceiling < 0.95 and attribution is not None:
            gap = {k: attribution.get(k, 0.0)
                   for k in ("read", "convert", "dispatch", "transfer")}
            gap["parse"] = max(
                0.0, attribution.get("parse", 0.0) - size_mb / thr_best)
            line["gap_stage"] = max(gap, key=gap.get)
            line["gap_stage_seconds"] = round(gap[line["gap_stage"]], 4)
        # floor in corpus units: floor_device * (corpus bytes / device
        # bytes); value/dev_rates[0] is exactly corpus_mb/s per device_mb/s
        floor_corpus = floor_best * value / dev_rates[0]
        bound = min(thr_best, floor_corpus)
        line["parse_ceiling_mb_per_sec"] = round(thr_best, 2)
        line["line_rate_corpus_equiv_mb_per_sec"] = round(floor_corpus, 2)
        line["binding_resource"] = ("link" if floor_corpus < thr_best
                                    else "parse")
        # the ceiling reps run minutes after the pipeline reps on a host
        # whose ambient speed swings 2-4x, so the measured ratio can land
        # above the physical 1.0 — report it CLAMPED (the claim the footer
        # decides is ">= 0.9 of bound", and being at-or-above bound
        # satisfies it) and flag the drift so readers know the ceiling
        # sample ran in a slower ambient window than the pipeline's
        pct = value / bound
        pct_med = med / min(thr_med, floor_med * med / dev_rates[1])
        line["pct_of_pipeline_bound"] = round(min(pct, 1.0), 3)
        line["pct_of_pipeline_bound_median"] = round(min(pct_med, 1.0), 3)
        if pct > 1.0 or pct_med > 1.0:
            line["bound_drift"] = round(max(pct, pct_med), 3)
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("line_rate_floor", exc)
    # parse-once block cache (ISSUE 5): cold epoch parses + shadow-writes,
    # warm epoch streams mmap'd parsed blocks into HBM — the epoch-pair
    # contract make bench-smoke gates (warm_epoch_mb_per_sec /
    # warm_vs_cold_speedup / cache_state). Warm above the parse ceiling
    # proves the parser is actually bypassed, not merely overlapped.
    try:
        (cold_mbps, warm_mbps, cache_state, cache_read_s, shuffled_mbps,
         shuffled_stats) = block_cache_epoch_pair(path, size_mb)
        line["cold_epoch_mb_per_sec"] = round(cold_mbps, 2)
        line["warm_epoch_mb_per_sec"] = round(warm_mbps, 2)
        line["warm_vs_cold_speedup"] = round(warm_mbps / cold_mbps, 3)
        line["cache_state"] = cache_state
        line["warm_cache_read_seconds"] = round(cache_read_s, 4)
        ceiling = line.get("parse_ceiling_mb_per_sec")
        if ceiling:
            line["warm_vs_parse_ceiling"] = round(warm_mbps / ceiling, 3)
        log(f"bench: block-cache warm {warm_mbps:.1f} MB/s vs cold "
            f"{cold_mbps:.1f} MB/s -> speedup x{warm_mbps/cold_mbps:.2f}"
            + (f", x{warm_mbps/ceiling:.2f} of parse ceiling"
               if ceiling else ""))
        if shuffled_mbps is not None:
            # shuffle-native warm epoch (ISSUE 8): plan-ordered serving
            # of the same cache — the overhead vs sequential warm is the
            # price of shuffled SGD epochs (acceptance bar: within 20%).
            # Estimated from the best ROUND-PAIRED ratio of the
            # interleaved epochs, so ambient drift between legs cancels.
            line["shuffled_warm_epoch_mb_per_sec"] = round(shuffled_mbps, 2)
            ratio = shuffled_stats.get("pair_ratio",
                                       shuffled_mbps / warm_mbps)
            line["shuffle_overhead_pct"] = round(
                max(0.0, 100.0 * (1.0 - ratio)), 2)
            line["shuffle_seed"] = shuffled_stats.get("shuffle_seed")
            log(f"bench: shuffled warm {shuffled_mbps:.1f} MB/s vs "
                f"sequential warm {warm_mbps:.1f} MB/s -> overhead "
                f"{line['shuffle_overhead_pct']:.1f}%")
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("block_cache_epoch_pair", exc)
    # chunk-batch cold-parse leg (ISSUE 14): the full cold cache build
    # through the native-batch engine vs the pre-PR stream+re-encode
    # path — batch_vs_stream_parse_speedup >= 1.0 is the bench-smoke
    # gate when batch_parse_simd_level >= 0 (byte-identical caches, so
    # the ratio isolates the engine; on a toolchain-less host both legs
    # run the Python engine and only field presence is gated)
    try:
        line.update(batch_parse_leg(path, size_mb))
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("batch_parse", exc)
    # device-native snapshot store (ISSUE 9): warm epochs skip parse AND
    # convert — mmap'd post-convert batches stream straight into
    # device_put. snapshot_vs_cache_speedup positions the two warm tiers
    # (cache = parser output, snapshot = device layout); above the parse
    # ceiling proves the bypass is structural. make bench-smoke gates the
    # fields.
    try:
        snap_fields = snapshot_epoch_leg(path, size_mb)
        line.update(snap_fields)
        warm_snap = snap_fields.get("snapshot_warm_mb_per_sec")
        cache_warm = line.get("warm_epoch_mb_per_sec")
        if warm_snap and cache_warm:
            line["snapshot_vs_cache_speedup"] = round(
                warm_snap / cache_warm, 3)
        ceiling = line.get("parse_ceiling_mb_per_sec")
        if warm_snap and ceiling:
            line["snapshot_vs_parse_ceiling"] = round(warm_snap / ceiling, 3)
        if warm_snap:
            log(f"bench: snapshot warm {warm_snap:.1f} MB/s"
                + (f" = x{line['snapshot_vs_cache_speedup']:.2f} over the "
                   f"cache's warm epochs" if cache_warm else "")
                + (f", x{line['snapshot_vs_parse_ceiling']:.2f} of parse "
                   f"ceiling" if ceiling else ""))
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("snapshot_epoch", exc)
    # device-side decode (ISSUE 18): warm snapshot epochs shipping the
    # raw container span verbatim and decoding in HBM vs the host-decode
    # warm tier above — the speedup claim only holds on a real
    # accelerator (device_decode_backend), bench-smoke gates accordingly
    try:
        line.update(device_decode_leg(path, size_mb))
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("device_decode", exc)
    # bf16 ingest: the C++ repack emits bfloat16 (the MXU's operand width),
    # halving host->HBM bytes — reported alongside, headline stays f32
    try:
        (bf16_value, bf16_med, _sp, _, bf16_dev, _res,
         _par) = into_hbm_mb_per_sec(path, size_mb, x_dtype="bfloat16")
        line["bf16_mb_per_sec"] = round(bf16_value, 2)
        line["bf16_vs_baseline"] = round(bf16_value / base_best, 3)
        line["bf16_median_vs_baseline"] = round(bf16_med / base_med, 3)
        bf_floor_best, bf_floor_med, bf_floor_trim = \
            device_floor_mbps("bfloat16")
        line["bf16_pct_of_line_rate"] = round(bf16_dev[0] / bf_floor_best, 3)
        line["bf16_pct_of_line_rate_median"] = round(
            bf16_dev[1] / bf_floor_med, 3)
        # the STABLE bf16 denominator (warmed + trimmed best-of): the
        # number snapshot gating divides by, immune to one fluke window
        line["bf16_line_rate_trimmed_mb_per_sec"] = round(bf_floor_trim, 2)
        line["bf16_pct_of_line_rate_trimmed"] = round(
            bf16_dev[0] / bf_floor_trim, 3)
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("bf16", exc)
    # disaggregated data-service leg (docs/service.md): localhost fleet
    # throughput + speedup over the same partitions parsed serially —
    # emitted when --service asked for it (make bench-smoke gates the
    # fields). The fleet's workers are threads of this process and
    # dmlc_tpu/service imports no jax: nothing here starts a second
    # process that could ask for the chip this one holds
    if service:
        try:
            line.update(service_leg(path, size_mb))
        except Exception as exc:  # noqa: BLE001 - listed, fails the run
            _leg_failed("service", exc)
        # wire v2 transport leg (docs/service.md Wire v2): pipelined vs
        # lock-step TCP, compression byte ledger, local fast path
        try:
            line.update(service_wire_leg(path, size_mb))
        except Exception as exc:  # noqa: BLE001 - listed, fails the run
            _leg_failed("service_wire", exc)
        # production-QoS leg (docs/service.md Production QoS): two-class
        # contention — critical tenant under SLO, batch tenant throttled
        try:
            line.update(service_qos_leg(path, size_mb))
        except Exception as exc:  # noqa: BLE001 - listed, fails the run
            _leg_failed("service_qos", exc)
    # online-autotuner convergence leg (docs/data.md autotune): the
    # controller climbs a starved config until gap_stage == transfer and
    # the chosen knobs ride the JSON line as reusable env — emitted when
    # --autotune asked for it (make bench-smoke gates the fields)
    if autotune:
        try:
            line.update(autotune_leg(path, size_mb))
        except Exception as exc:  # noqa: BLE001 - listed, fails the run
            _leg_failed("autotune", exc)
    # tiered artifact store contract (docs/store.md): the cache/snapshot
    # legs above published their artifacts THROUGH the store, so the
    # registry gauge must show managed bytes; evictions/rebuilds are 0 on
    # an unbudgeted bench run and nonzero only under
    # DMLC_TPU_STORE_BUDGET_BYTES (make bench-smoke gates the fields)
    try:
        from dmlc_tpu.store import store_counters

        sc = store_counters()
        line["store_bytes"] = sc["store_bytes"]
        line["store_evictions"] = sc["store_evictions"]
        line["store_rebuilds_after_eviction"] = \
            sc["store_rebuilds_after_eviction"]
        log(f"bench: artifact store: {sc['store_bytes']} managed bytes, "
            f"{sc['store_evictions']} evictions, "
            f"{sc['store_rebuilds_after_eviction']} rebuilds after "
            f"eviction")
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("store_counters", exc)
    # trace-propagation overhead guard (docs/observability.md): warm
    # epoch pair, context armed vs forced off — make bench-smoke gates
    # trace_overhead_pct < 5 so the plane stays cheap enough to leave on
    try:
        line.update(trace_overhead_leg(path, size_mb))
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("trace_overhead", exc)
    # pod-scale sparse-training leg (docs/training.md): ALX-style sharded
    # ALS rides the warm pod-sharded cache end to end; make bench-smoke
    # gates presence of the four als_* fields (the als_input_wait_frac
    # < 0.2 compute-bound bar is the TPU-return criterion)
    try:
        line.update(als_train_leg(size_mb))
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("als_train", exc)
    # always-on telemetry contract (docs/observability.md): the schema
    # version + per-stage span counts ride the JSON line, proving the span
    # tracer covered the whole measurement (make bench-smoke gates these)
    from dmlc_tpu.utils import telemetry as _telemetry

    line["telemetry_schema_version"] = _telemetry.SCHEMA_VERSION
    counts = _telemetry.span_counts()
    line["trace_spans"] = int(sum(counts.values()))
    line["trace_span_counts"] = {k: int(v) for k, v in sorted(counts.items())}
    # Prometheus exposition self-check: the render must round-trip
    # through the text-format parser (what a real scraper does), and the
    # decision ledger's lifetime count rides along — both gated
    try:
        prom = _telemetry.render_prometheus()
        line["prometheus_metrics"] = len(_telemetry.parse_prometheus_text(
            prom))
    except Exception as exc:  # noqa: BLE001 - listed, fails the run
        _leg_failed("prometheus", exc)
        line["prometheus_metrics"] = None
    line["decisions_total"] = _telemetry.decisions_total()
    line["failed_legs"] = list(_FAILED_LEGS)
    print(json.dumps(line))
    if _FAILED_LEGS:
        log(f"bench: FAIL {len(_FAILED_LEGS)} leg(s) failed: {_FAILED_LEGS}")
        return 1
    return 0


def main() -> int:
    return run(service="--service" in sys.argv,
               autotune="--autotune" in sys.argv)


if __name__ == "__main__":
    sys.exit(main())
