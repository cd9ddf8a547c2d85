"""One run of one cell of the benchmark.

    python3 -m cellbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It reads the cell from ``BENCHMARK.json``, its configuration
and traffic from the files those entries name, makes the seed's corpus,
runs the plain reference over the first steps, builds the program's
learner and feed, drives the first steps and the rest of a warm-up epoch,
measures a window of ``--seconds`` with the learner's own ``step`` over
``DeviceIter`` epochs, verifies what the timed path produced, and prints
one JSON object as the last line of its standard output. Everything else
goes on earlier lines. See ``cellbench/README.md``.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
FIRST_STEPS = 3      # the steps the plain reference follows
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[cellbench] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """The module ``cellbench/<kind>/<name>.py``, found by the name a data
    file gives."""
    if not NAME.match(name) or name.startswith((".", "_")):
        raise ValueError(f"not a plugin name: {name!r}")
    return importlib.import_module(f"cellbench.{kind}.{name}")


def find_cell(name: str, rehearse: bool):
    """``(cell, config, traffic, per_layer entries, end_to_end entries)`` of
    a workload."""
    bench = load_json(HERE, "rehearsal.json") if rehearse else \
        load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    mine = lambda ms: [m for m in ms if name in m.get("workloads", [name])]  # noqa: E731
    return (cell, config, traffic, mine(bench["per_layer"]),
            mine(bench["end_to_end"]))


@dataclass
class Observed:
    """What one window observed; the per-layer readers read it."""
    seconds: float
    rows_done: int = 0           # rows of the steps completed in the window
    rows_dispatched: int = 0     # rows of the steps dispatched in the window
    steps_done: int = 0
    steps_dispatched: int = 0
    steps_raised: int = 0
    gaps_ms: list = field(default_factory=list)
    losses: list = field(default_factory=list)   # every dispatched step's
    host_cpu_s: float = 0.0      # process CPU seconds while host_cpu_rows
    host_cpu_rows: int = 0       # ... were dispatched
    window_s: float = 0.0        # t0 to the last completion before the deadline
    compilations: int = 0
    stats_start: dict = None
    stats_end: dict = None
    trace: dict = None
    adapter: object = None
    peaks: dict = None


def percentile(values: list, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

def run_window(adapter, device_iter, seconds: float, rows_of_step,
               steps_per_epoch: int, trace_dir: str | None) -> Observed:
    """Drive ``adapter.step`` over ``device_iter`` epochs for ``seconds``.

    The dispatching thread never waits for a step inside an epoch; at the
    end of each it waits for the epoch's last loss, as
    ``TrainLoopMixin.fit_epoch`` does. A second thread waits on each step's
    loss in dispatch order and stamps its completion. Dispatch stops at
    the deadline and the queue drains outside the window. The window is
    ``[t0, the last completion before the deadline]``: rows, gaps and the
    process's CPU time are those of the completions inside it.

    A traced run profiles one epoch's length of the window, from the
    middle of its first epoch to the middle of its second (or to the
    deadline), so that one epoch boundary is in the trace at its true
    share of the time.
    """
    import jax
    from jax.profiler import TraceAnnotation

    obs = Observed(seconds=seconds, adapter=adapter)
    pending: queue.Queue = queue.Queue()
    stamps = []   # (completion time or None, process CPU time, loss, rows,
    #                rows dispatched by then)
    trace_from = steps_per_epoch // 2 if trace_dir else None
    tracing = []  # the thread that stops the trace, once it has started

    def trace_edges(completed: int) -> None:
        if trace_from is None:
            return
        if completed == trace_from and not tracing:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing.append(None)
        elif completed == trace_from + steps_per_epoch and tracing == [None]:
            tracing[0] = threading.Thread(target=jax.profiler.stop_trace,
                                          name="cellbench-trace-stop")
            tracing[0].start()

    def waiter():
        completed = 0
        while True:
            item = pending.get()
            if item is None:
                return
            loss, rows = item
            try:
                loss.block_until_ready()
                stamps.append((time.perf_counter(), time.process_time(),
                               loss, rows, obs.rows_dispatched))
                completed += 1
                trace_edges(completed)
            except Exception as exc:  # noqa: BLE001 - a failed step is a result
                log(f"a step failed on the device: {exc!r}")
                stamps.append((None, None, None, rows, 0))
            finally:
                pending.task_done()

    compiles = []
    listener = lambda event, duration, **_: (  # noqa: E731
        compiles.append(duration) if event == COMPILE_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    thread = threading.Thread(target=waiter, name="cellbench-waiter",
                              daemon=True)
    thread.start()
    obs.stats_start = device_iter.stats()
    t0, cpu0 = time.perf_counter(), time.process_time()
    deadline = t0 + seconds
    stop = False
    try:
        while not stop:
            it, step_in_epoch = iter(device_iter), 0
            while True:
                if time.perf_counter() >= deadline:
                    stop = True
                    break
                with TraceAnnotation("cellbench:next_batch"):
                    batch = next(it, None)
                if batch is None:
                    break
                if time.perf_counter() >= deadline:
                    stop = True
                    break
                try:
                    with TraceAnnotation("cellbench:step_dispatch"):
                        loss = adapter.step(batch)
                except Exception as exc:  # noqa: BLE001 - counted, then stop
                    log(f"step raised at dispatch: {exc!r}")
                    obs.steps_raised += 1
                    stop = True
                    break
                rows = rows_of_step(step_in_epoch)
                pending.put((loss, rows))
                obs.steps_dispatched += 1
                obs.rows_dispatched += rows
                step_in_epoch += 1
            if step_in_epoch:   # the producer of this epoch has served
                obs.stats_end = device_iter.stats()
            if not stop:
                # TrainLoopMixin.fit_epoch ends every pass by bringing the
                # summed loss to the host: the one blocking sync of an epoch
                with TraceAnnotation("cellbench:epoch_sync"):
                    pending.join()
            with TraceAnnotation("cellbench:epoch_reset"):
                device_iter.reset()
    finally:
        pending.put(None)
        thread.join()   # the sentinel is never task_done(): nobody joins it
        if tracing == [None]:
            jax.profiler.stop_trace()
        elif tracing:
            tracing[0].join()
        jax.monitoring.unregister_event_duration_listener(listener)
    obs.compilations = len(compiles)
    done = [s for s in stamps if s[0] is not None and t0 <= s[0] <= deadline]
    obs.steps_done = len(done)
    obs.rows_done = sum(s[3] for s in done)
    if done:
        obs.window_s = done[-1][0] - t0
        # the profiler's own host work is several times a warm feed's CPU
        # time, so a traced run reads the process's CPU up to the completion
        # at which the trace starts, and an untraced one up to the window's
        # last; either way over the rows dispatched by then, since the feed
        # works for the steps dispatched, which run ahead of the completions
        quiet = done[min(trace_from or len(done), len(done)) - 1]
        obs.host_cpu_s = quiet[1] - cpu0
        obs.host_cpu_rows = quiet[4]
    times = [s[0] for s in done]
    obs.gaps_ms = [1e3 * (b - a) for a, b in zip(times, times[1:])]
    obs.losses = [float(s[2]) if s[2] is not None else float("nan")
                  for s in stamps]
    return obs


def claim_devices(cell: dict, rehearse: bool):
    """``(devices, {"platform", "kind", "count"})``. Refuses any platform
    but a TPU (the CPU only for a rehearsal) and fewer chips than the cell
    asks for, and places the persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed path in the
    checkout."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise SystemExit("--rehearse is for JAX_PLATFORMS=cpu only")
    else:
        if platform != "tpu":
            raise SystemExit(f"no TPU: JAX found platform {platform!r}; the "
                             "benchmark has no CPU fallback")
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(CACHE, "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if len(devices) < cell["chips"]:
        raise SystemExit(f"cell {cell['name']} needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"device: {platform} / {device['kind']} / {device['count']}")
    return devices, device


def cell_mesh(cell: dict, devices):
    """The mesh of a cell that spans chips (``make_mesh()`` over as many
    as it asks for), else ``None``."""
    if cell["chips"] == 1:
        return None
    from dmlc_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=devices[:cell["chips"]])


def first_steps(adapter, it, reference: dict):
    """Drive the first steps through the window's own call and feed, and
    take the readings the comparison needs: ``(losses, (first gradient's
    norms, update norms, touched rows, untouched rows))``."""
    losses = [adapter.step(next(it))]
    grad_norms = adapter.first_grad_norms()
    losses += [adapter.step(next(it)) for _ in range(FIRST_STEPS - 1)]
    losses = [float(x) for x in losses]
    return losses, (grad_norms, adapter.update_norms(reference),
                    adapter.rows(reference["touched_ids"]),
                    adapter.rows(reference["untouched_ids"]))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal of a cell of cellbench/rehearsal.json:"
                         " checks control flow and results, reports no device"
                         " number")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    books = []   # (phase, seconds) of set-up, for the earlier lines
    mark = [_T_PROCESS]

    def phase(name: str) -> float:
        now = time.perf_counter()
        books.append((name, now - mark[0]))
        mark[0] = now
        return books[-1][1]

    cell, config, traffic, layer_metrics, end_metrics = find_cell(
        args.workload, args.rehearse)
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    import dmlc_tpu  # noqa: F401 - the system under test: no program, no run

    devices, device = claim_devices(cell, args.rehearse)
    log(f"cell {cell['name']} = config {cell['config']} x traffic "
        f"{cell['traffic']} on {cell['chips']} chip(s), seed {args.seed}")
    from cellbench.costs import device_peaks

    peaks = None if args.rehearse else device_peaks(device["kind"])
    phase("imports and device")

    # ---- the seed's corpus ----
    work = os.path.join(CACHE, "work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    gen = plugin("generators", config["generator"]["name"])
    corpus = os.path.join(work, "corpus." + config["format"])
    sums = gen.generate(config["generator"], args.seed, config["rows"],
                        corpus)
    uri = f"{corpus}?format={config['format']}"
    gen_s = phase("corpus")
    log(f"corpus: {sums['rows']} rows, {sums['bytes']} bytes in {gen_s:.2f} s")

    # ---- the plain reference, before the program's state exists ----
    learners = plugin("learners", config["learner"])
    reference = learners.reference_digest(config, args.seed, corpus,
                                          steps=FIRST_STEPS)
    reference_s = phase("reference (not part of setup_s)")

    # ---- the program: learner, feed, first steps, warm-up epoch ----
    adapter = learners.Adapter(config, args.seed,
                               mesh=cell_mesh(cell, devices))
    phase("learner")
    feed = plugin("feeds", traffic["feed"])
    device_iter = feed.open_feed(uri, work, adapter.device_iter_kwargs(),
                                 traffic)
    phase("feed (and its warm tier)")
    batch_rows, total_rows = config["batch_size"], config["rows"]
    steps_per_epoch = -(-total_rows // batch_rows)
    rows_of_step = lambda i: min(batch_rows, total_rows - i * batch_rows)  # noqa: E731
    try:
        it = iter(device_iter)
        first_losses, readings = first_steps(adapter, it, reference)
        phase(f"first {FIRST_STEPS} steps and their readings")
        last = None
        for batch in it:
            last = adapter.step(batch)
        jax.block_until_ready(last)
        device_iter.reset()
        phase("rest of the warm-up epoch")
        setup_s = time.perf_counter() - _T_PROCESS - reference_s

        # ---- the window ----
        trace_dir = os.path.join(work, "trace") if args.trace else None
        obs = run_window(adapter, device_iter, args.seconds, rows_of_step,
                         steps_per_epoch, trace_dir)
        obs.peaks = peaks
        if not obs.steps_dispatched:
            raise SystemExit("the window closed before a step was "
                             "dispatched: nothing to report")
        phase("window and drain")

        # ---- verification, outside the window ----
        zero, fold = adapter.checksum_fold()
        acc = zero
        for batch in device_iter:
            acc = fold(acc, batch)
        got = [int(x) for x in acc]
        stats_verify = device_iter.stats()
        device_iter.reset()
        phase("verification epoch")
    finally:
        device_iter.close()

    for name, seconds in books:
        log(f"phase {name}: {seconds:.3f} s")
    checks = []   # (what, value, limit, ok)

    def check(what: str, value, limit, ok: bool) -> None:
        checks.append(ok)
        log(f"compare {what}: {value} (limit {limit}) "
            f"{'ok' if ok else 'NOT OK'}")

    numbers = learners.compare(reference, first_losses, *readings)
    for key, limit in config["limits"].items():
        check(key, f"{numbers[key]:.6g}", f"<= {limit:g}",
              numbers[key] <= limit)
    log("also read, with no limit: " + json.dumps(
        {k: v for k, v in numbers.items() if k not in config["limits"]}))
    want = [sums["rows"], sums["index_sum"], sums["index_sq_sum"],
            sums["label_sum"]]
    check("epoch rows / index sum / index square sum / label sum", got,
          f"== {want}", got == want)
    not_served = feed.served(obs.stats_start, obs.stats_end) + \
        feed.served(obs.stats_start, stats_verify)
    check(f"tier '{traffic['feed']}' served the window and the verification "
          "epoch", not_served or "yes", "no other tier", not not_served)
    finite = [x for x in obs.losses if np.isfinite(x)]
    failed = obs.steps_raised + len(obs.losses) - len(finite)
    tail = finite[-steps_per_epoch:]
    tail_mean = float(np.mean(tail)) if tail else float("nan")
    check("mean loss of the last epoch's steps", f"{tail_mean:.6f}",
          f"< first step's {first_losses[0]:.6f}",
          bool(tail) and tail_mean < first_losses[0])
    check("steps that raised or gave a non-finite loss", failed, "== 0",
          failed == 0)
    check("compilations inside the window", obs.compilations, "== 0",
          obs.compilations == 0)
    check("steps completed inside the window", obs.steps_done, ">= 2",
          obs.steps_done >= 2)

    # ---- the line ----
    mem = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    device["memory_peak_bytes"] = max(
        (m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    log(f"window: {obs.steps_dispatched} steps dispatched, {obs.steps_done} "
        f"completed inside {args.seconds:g} s ({obs.rows_done} rows, the "
        f"last after {obs.window_s:.4f} s), "
        f"{len(obs.gaps_ms)} gaps"
        + (f", median gap {percentile(obs.gaps_ms, 50):.3f} ms"
           if obs.gaps_ms else ""))
    widest = sorted(enumerate(obs.gaps_ms, start=1), key=lambda g: -g[1])[:3]
    log("widest gaps (ms, before the window's n-th completion; an epoch is "
        f"{steps_per_epoch} steps): "
        + ", ".join(f"{ms:.1f} before #{n + 1}" for n, ms in widest))
    log("stage busy in the window (s): " + json.dumps({
        k: round(v - obs.stats_start["stage_busy"][k], 4)
        for k, v in obs.stats_end["stage_busy"].items()}))
    log(f"staging ring: {obs.stats_end['staging_ring']}; cache_state="
        f"{obs.stats_end['cache_state']} snapshot_state="
        f"{obs.stats_end['snapshot_state']}")
    specs = {m["name"]: load_json(HERE, "metrics", m["name"] + ".json")
             for m in layer_metrics}
    if args.trace:
        units = {m["name"]: m["unit"] for m in layer_metrics}
        from cellbench import trace_reduce

        try:
            obs.trace = trace_reduce.reduce_trace(
                trace_reduce.find_xplane(trace_dir),
                module_pattern=config["step_module"])
        except (FileNotFoundError, ValueError, KeyError) as exc:
            log(f"trace: nothing to reduce: {exc!r}")
        values = {}
        for name, spec in specs.items():
            value = plugin("readers", spec["reader"]).read(obs, spec)
            if value is not None:
                values[name] = value
    else:
        units = {m["name"]: m["unit"] for m in end_metrics}
        values = {
            "rows_per_s": (obs.rows_done / obs.window_s
                           if obs.window_s else None),
            "step_gap_p95_ms": (percentile(obs.gaps_ms, 95)
                                if obs.gaps_ms else None),
            "setup_s": setup_s,
        }
        values = {k: v for k, v in values.items()
                  if k in units and v is not None}
    if args.rehearse:
        # a CPU run says what was counted, never how fast anything is
        values = {k: (v if specs.get(k, {}).get("a_count") else None)
                  for k, v in values.items()}
    line = {
        "correct": all(checks),
        "attempted": obs.steps_dispatched + obs.steps_raised,
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": device,
    }
    if obs.trace is not None:
        device["busy_s"] = obs.trace["busy_s"]
        device["window_s"] = obs.trace["window_s"]
        line["breakdown"] = {"device_ops": obs.trace["device_ops"],
                             "idle_gaps": obs.trace["idle_gaps"]}
        log("trace modules: " + json.dumps(obs.trace["modules"]))
        log(f"trace collectives: {obs.trace['collective_s']:.6f} s a chip, "
            f"exposed {obs.trace['collective_exposed_s']:.6f} s")
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
