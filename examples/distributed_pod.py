"""Multi-process distributed training via dmlc-submit --cluster tpu-pod.

The full distributed recipe in one file, runnable WITHOUT a pod (local
multi-process simulation on the CPU backend — the same code path a real
TPU pod slice takes, where each host runs one process and collectives ride
ICI instead of a loopback mesh):

    python examples/distributed_pod.py            # launches itself 2-way

What happens (SURVEY.md §2.4's control/data-plane split):
 1. the launcher starts the rabit tracker and spawns one worker process
    per "host" with the DMLC_* env contract
    (tracker/dmlc_tracker/tracker.py:178-184 is the reference analog);
 2. each worker calls :func:`dmlc_tpu.parallel.init_from_env`, which maps
    that contract onto ``jax.distributed.initialize`` (coordinator =
    tracker host, port + 1) — the whole rank-brokering protocol the
    reference runs over sockets collapses into this one call;
 3. each worker parses ITS OWN InputSplit shard (shard index = process
    index, SURVEY.md §2.3 row 1), feeds batches through DeviceIter, and
    the jitted SGD step psums gradients across all processes' devices.

Elastic recovery demo (the reference's retry + recover contract,
tracker/dmlc_tracker/local.py:26-49 + tracker.py:288-301, on the jax
plane):

    CRASH=1 python examples/distributed_pod.py

Worker 1's first life joins the job, heartbeats, and dies hard mid-job.
The tracker OBSERVES the death (missed heartbeats), the launcher
relaunches the worker with the same DMLC_TASK_ID (DMLC_NUM_ATTEMPT
contract), and the second life rabit-``recover``s its prior rank, joins
``jax.distributed``, and the job completes normally.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_COL = 8
ROWS = 2048


def make_corpus(path: str) -> None:
    import numpy as np

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=NUM_COL)
    with open(path, "w") as f:
        for _ in range(ROWS):
            x = rng.normal(size=NUM_COL)
            y = int(x @ w_true > 0)
            feats = " ".join(f"{j}:{x[j]:.5f}" for j in range(NUM_COL))
            f.write(f"{y} {feats}\n")


def worker() -> None:
    from dmlc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import time

    import jax

    from dmlc_tpu.parallel.distributed import init_from_env, pod_identity
    from dmlc_tpu.tracker.client import WorkerClient

    task_id = int(os.environ["DMLC_TASK_ID"])
    attempt = int(os.environ.get("DMLC_NUM_ATTEMPT", "0"))
    # rabit plane: rank-stable rendezvous, liveness heartbeats, and
    # job-completion bookkeeping (the tracker waits for every rank's
    # shutdown). The rabit rendezvous runs BEFORE jax.distributed so a
    # crashing first life never blocks the pod's collective init.
    client = WorkerClient(os.environ["DMLC_TRACKER_URI"],
                          int(os.environ["DMLC_TRACKER_PORT"]))
    rank_file = os.environ["DATA"] + f".rank{task_id}"
    if os.environ.get("CRASH") == "1" and task_id == 1 and attempt == 0:
        client.start()
        with open(rank_file, "w") as f:
            f.write(str(client.rank))  # "checkpoint" the assigned rank
        client.start_heartbeat(0.2)
        time.sleep(0.6)
        print(f"[worker {task_id}] simulating mid-job crash", flush=True)
        os._exit(17)  # hard death: heartbeats stop, no shutdown sent
    if attempt > 0 and os.path.exists(rank_file):
        # a relaunched worker whose previous life checkpointed a rank
        # rejoins rank-stable; other relaunches (transient failures with no
        # checkpoint) just start fresh
        time.sleep(1.6)  # stay silent past the liveness window: the
        #                  tracker must OBSERVE the death, not just a retry
        with open(rank_file) as f:
            old_rank = int(f.read())
        client.recover(old_rank)  # rank-stable rejoin
        print(f"[worker {task_id}] recovered rabit rank {old_rank} "
              f"(attempt {attempt})", flush=True)
    else:
        client.start()
    # beat well inside the liveness window (1.0s in the demo): an interval
    # equal to the timeout would flag healthy-but-jittery ranks as lost.
    # metrics=True makes each beat carry this worker's telemetry snapshot,
    # so the tracker logs the merged per-rank × per-stage ingest table
    # (docs/observability.md pod aggregation)
    client.start_heartbeat(0.25, metrics=True)

    init_from_env()  # DMLC_* -> jax.distributed.initialize
    # resolve rank/world through pod_identity — the SAME env contract
    # (DMLC_TASK_ID/DMLC_NUM_WORKER first, jax backend as fallback) that
    # parallel/distributed.py and pod_sharding= use, so the example and
    # the library can never disagree about which shard a host owns
    rank, world = pod_identity()
    print(f"[worker {rank}/{world}] backend up", flush=True)

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import LinearLearner
    from dmlc_tpu.parallel import make_mesh, sync_min
    mesh = make_mesh({"data": jax.device_count()})
    model = LinearLearner(num_col=NUM_COL, objective="logistic",
                          learning_rate=0.5, mesh=mesh)
    # shard index = process index: each worker reads only its byte range
    batch = 64
    probe = create_parser(os.environ["DATA"], rank, world, "libsvm",
                          threaded=False)
    local_rows = sum(len(b) for b in probe)
    probe.close()
    # SPMD safety: byte-range shards rarely hold EQUAL batch counts, and a
    # process running one extra collective step deadlocks the pod — agree
    # on min(local_steps) before training (dmlc_tpu.parallel.sync_min)
    steps = sync_min(local_rows // batch)
    parser = create_parser(os.environ["DATA"], rank, world, "libsvm")
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=batch,
                    layout="dense", mesh=mesh, drop_remainder=True)
    model.fit(it, epochs=5, steps_per_epoch=steps)
    acc = model.accuracy(it, max_steps=steps)
    it.close()
    print(f"[worker {rank}/{world}] accuracy {float(acc):.3f} "
          f"({steps} steps/epoch)", flush=True)
    client.stop_heartbeat()
    client.shutdown()


def main() -> None:
    if os.environ.get("DMLC_ROLE") == "worker":
        worker()
        return
    import tempfile

    from dmlc_tpu.tracker.submit import main as submit

    data = os.path.join(tempfile.mkdtemp(), "pod.libsvm")
    make_corpus(data)
    os.environ["DATA"] = data
    # The platform is the caller's. JAX_PLATFORMS=cpu makes this a local
    # simulation (one CPU device per worker — the flag below is inert on a
    # TPU); on a TPU host the launcher hands each of NWORKER=<chips> local
    # workers its own chip (tracker/tpu_pod.py) and the same code runs
    # over ICI. The env must be in place before the worker interpreters
    # start, so it goes in the launcher.
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    nworker = int(os.environ.get("NWORKER", "2"))
    argv = ["--cluster", "tpu-pod", "--num-workers", str(nworker),
            "--host-ip", "127.0.0.1"]
    if os.environ.get("CRASH") == "1":
        # recovery demo: arm heartbeat failure detection + the relaunch
        # contract (see module docstring)
        os.environ["DMLC_LIVENESS_TIMEOUT"] = "1.0"
        argv += ["--local-num-attempt", "3"]
    submit(argv + ["--", sys.executable, os.path.abspath(__file__)])
    print("pod job finished")


if __name__ == "__main__":
    main()
