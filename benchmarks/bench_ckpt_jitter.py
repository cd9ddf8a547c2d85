"""PR 41: what a checkpoint's drain does to the steps' completions as the
host sees them, by how the chunks leave the device.

    chiprun -- python3 benchmarks/bench_ckpt_jitter.py

The cell's first runs (PERF.md section 6, PR 41) read the device busy
99.98% of the traced window and the step's device time unchanged, yet
`step_gap_p95_ms` 74-78 ms for 71.2: the completions were *stamped* late
while a save drained. This times a stream of dummy steps of about 70 ms
(a harness-like dispatcher and a waiter thread that stamps each
completion) with, beside it: nothing; checksums and file writes of host
buffers alone (no transfer); and the drain of kdd12_ffm's snapshot in
several forms: chunks of whole rows as the table lies (64 MiB and 8 MiB),
the same started one at a time without `copy_to_host_async`, flattened to
one dimension on the device, reshaped to rows of 128 lanes on the device.
For each: median, p95, p99 and widest gap, the gaps more than 0.3 ms over
the median, the drain's rate. The last line is JSON: the forms by the
number of disturbed gaps.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROWS, COLS = 13_671_614, 44
SECONDS = 10.0


def chunks_fn(rows, chunk_rows, wire):
    edges = list(range(0, rows, chunk_rows)) + [rows]

    def shape(c):
        if wire == "flat":
            return c.reshape(-1)
        if wire == "wide" and c.size % 1024 == 0:
            return c.reshape(-1, 128)
        return c

    return jax.jit(lambda *tables: tuple(
        shape(t[a:b]) for t in tables for a, b in zip(edges, edges[1:])))


def main() -> int:
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else ROWS
    seconds = float(sys.argv[2]) if len(sys.argv) > 2 else SECONDS
    print("device", jax.devices()[0].device_kind, flush=True)
    key = jax.random.PRNGKey(0)
    w = jax.jit(lambda k: jax.random.uniform(k, (rows, COLS)))(key)
    g = jax.jit(lambda k: jax.random.uniform(k, (rows, COLS)) + 1)(key)
    x = jnp.zeros((256 << 20) // 4 * (1 if rows == ROWS else 0) + 1024,
                  jnp.float32)
    step = jax.jit(lambda x: jax.lax.fori_loop(
        0, 110, lambda i, x: x * 1.0001 + 1.0, x), donate_argnums=0)
    x = step(x)
    jax.block_until_ready((w, g, x))
    t = time.perf_counter()
    for _ in range(5):
        x = step(x)
    jax.block_until_ready(x)
    print(f"dummy step {1e3 * (time.perf_counter() - t) / 5:.2f} ms",
          flush=True)

    def measure(name, background):
        """Steps for ``seconds`` with ``background(stop)`` beside them."""
        nonlocal x
        import queue

        pending: queue.Queue = queue.Queue(maxsize=32)
        stamps = []

        def waiter():
            while True:
                item = pending.get()
                if item is None:
                    return
                item.block_until_ready()
                stamps.append(time.perf_counter())

        stop = threading.Event()
        out = {}
        bg = threading.Thread(target=lambda: out.update(
            background(stop) or {}))
        wt = threading.Thread(target=waiter)
        wt.start()
        t0 = time.perf_counter()
        bg.start()
        while time.perf_counter() - t0 < seconds:
            x = step(x)
            pending.put(x[:1])
        stop.set()
        pending.put(None)
        wt.join()
        bg.join()
        gaps = 1e3 * np.diff(np.asarray(stamps))[3:]
        med = float(np.median(gaps))
        res = {"form": name, "gaps": len(gaps), "median_ms": med,
               "p95_ms": float(np.percentile(gaps, 95)),
               "p99_ms": float(np.percentile(gaps, 99)),
               "max_ms": float(gaps.max()),
               "disturbed": int((gaps > med + 0.3).sum()), **out}
        print(json.dumps(res), flush=True)
        return res

    results = [measure("nothing", lambda stop: None)]

    def host_io(stop):
        buf = np.random.default_rng(0).random(46 << 18).astype(np.float32)
        n = 0
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/_jitter.bin", "wb") as f:
            while not stop.is_set() and n < 104:
                zlib.crc32(memoryview(buf).cast("B"))
                f.write(memoryview(buf).cast("B"))
                n += 1
        os.remove("chiprun_out/_jitter.bin")
        return {"io_chunks": n}

    results.append(measure("host_crc_and_write_only", host_io))

    def drain_of(chunk_bytes, wire, ahead):
        chunk_rows = 1
        while chunk_rows * 2 * COLS * 4 <= chunk_bytes:
            chunk_rows *= 2
        snap = chunks_fn(rows, chunk_rows, wire)
        out = snap(w, g)             # compiled and run before the steps
        jax.block_until_ready(out)
        del out

        def background(stop):
            t0 = time.perf_counter()
            chunks = list(snap(w, g))
            jax.block_until_ready(chunks[-1])
            snap_s = time.perf_counter() - t0
            t0, total = time.perf_counter(), 0
            for c in chunks[:ahead]:
                c.copy_to_host_async()
            for i, c in enumerate(chunks):
                if stop.is_set():
                    break
                if ahead and i + ahead < len(chunks):
                    chunks[i + ahead].copy_to_host_async()
                total += np.asarray(c).nbytes
                c.delete()
            dt = time.perf_counter() - t0
            return {"snapshot_behind_steps_s": snap_s,
                    "drained_gb": total / 1e9, "drain_gb_per_s":
                    total / 1e9 / dt}

        return background

    for name, cb, wire, ahead in (
            ("rows_64MiB_async3", 64 << 20, "rows", 3),
            ("rows_64MiB_sync", 64 << 20, "rows", 0),
            ("rows_64MiB_async1", 64 << 20, "rows", 1),
            ("rows_8MiB_async3", 8 << 20, "rows", 3),
            ("flat_64MiB_async3", 64 << 20, "flat", 3),
            ("wide_64MiB_async3", 64 << 20, "wide", 3)):
        results.append(measure(name, drain_of(cb, wire, ahead)))
    order = sorted(results[2:], key=lambda r: (r["disturbed"], r["p99_ms"]))
    print(json.dumps({"by_disturbed": [(r["form"], r["disturbed"])
                                       for r in order]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
