"""PR 41: what a checkpoint's snapshot and drain cost on the chip, alone.

    chiprun -- python3 benchmarks/bench_ckpt_drain.py

At kdd12_ffm's table ([13,671,614, 44] float32, twice: W and G) it times
the snapshot program in two shapes (chunks of whole rows as the table
lies, and the same chunks flattened to one dimension) and the drain of
either to the host, chunk by chunk with two in flight, while a stream of
dummy steps keeps the device's queue full: a drain that needed the
compute stream would crawl behind them.
"""

from __future__ import annotations

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS, COLS, CHUNK = 13_671_614, 44, 262_144


def main() -> int:
    rows = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() \
        else ROWS
    print("device", jax.devices()[0].device_kind, flush=True)
    key = jax.random.PRNGKey(0)
    w = jax.jit(lambda k: jax.random.uniform(k, (rows, COLS)))(key)
    g = jax.jit(lambda k: jax.random.uniform(k, (rows, COLS)) + 1)(key)
    jax.block_until_ready((w, g))
    print("layout", getattr(w, "format", None), flush=True)
    edges = list(range(0, rows, CHUNK)) + [rows]

    def as_rows(*tables):
        return tuple(tuple(t[a:b] for a, b in zip(edges, edges[1:]))
                     for t in tables)

    def as_flat(*tables):
        return tuple(tuple(t[a:b].reshape(-1) for a, b in zip(edges, edges[1:]))
                     for t in tables)

    # a dummy step: in place on a donated buffer, ~20 ms of HBM traffic
    x = jnp.zeros((64, 1024, 1024), jnp.float32)
    step = jax.jit(lambda x: x * 1.0001 + 1.0, donate_argnums=0)
    x = step(x)
    jax.block_until_ready(x)
    t = time.perf_counter()
    for _ in range(20):
        x = step(x)
    jax.block_until_ready(x)
    print(f"dummy step {1e3 * (time.perf_counter() - t) / 20:.2f} ms", flush=True)

    # does a transfer to the host wait behind programs already queued?
    out = jax.jit(as_rows)(w, g)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(3000):
        x = step(x)
    t_dispatch = time.perf_counter() - t
    t = time.perf_counter()
    a = np.asarray(out[0][0])
    t_chunk = time.perf_counter() - t
    jax.block_until_ready(x)
    t_all = time.perf_counter() - t
    print(f"queue: 3000 dummy steps dispatched in {t_dispatch:.3f} s; one "
          f"chunk of {a.nbytes / 1e6:.0f} MB then reached the host in "
          f"{t_chunk:.3f} s; the queue drained {t_all:.3f} s after its "
          f"dispatch", flush=True)
    del out, a
    if "--queue-only" in sys.argv:
        return 0

    for name, fn in (("rows", as_rows), ("flat", as_flat)):
        snap = jax.jit(fn)
        t = time.perf_counter()
        out = snap(w, g)
        jax.block_until_ready(out)
        print(f"{name}: compile + first {time.perf_counter() - t:.2f} s",
              flush=True)
        del out
        t = time.perf_counter()
        out = snap(w, g)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t
        nbytes = 2 * rows * COLS * 4
        print(f"{name}: snapshot {1e3 * dt:.2f} ms "
              f"({2 * nbytes / dt / 1e9:.1f} GB/s logical read+write)",
              flush=True)
        del out
        for busy in (False, True):
            out = snap(w, g)
            jax.block_until_ready(out)
            stop = threading.Event()
            steps = [0]

            def pump():
                nonlocal x
                while not stop.is_set():
                    for _ in range(32):
                        x = step(x)
                        steps[0] += 1
                    jax.block_until_ready(x)

            th = threading.Thread(target=pump)
            if busy:
                th.start()
                time.sleep(0.5)
            chunks = [c for table in out for c in table]
            t = time.perf_counter()
            n0 = steps[0]
            for c in chunks[:2]:
                c.copy_to_host_async()
            total = 0
            for i, c in enumerate(chunks):
                if i + 2 < len(chunks):
                    chunks[i + 2].copy_to_host_async()
                a = np.asarray(c)
                total += a.nbytes
            dt = time.perf_counter() - t
            n1 = steps[0]
            stop.set()
            if busy:
                th.join()
            print(f"{name}: drain busy={busy} {total / 1e9:.2f} GB in "
                  f"{dt:.2f} s = {total / dt / 1e9:.2f} GB/s"
                  + (f"; {n1 - n0} dummy steps meanwhile" if busy else ""),
                  flush=True)
            del out, chunks, c, a
    print("peak", (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
