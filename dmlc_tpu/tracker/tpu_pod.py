"""tpu-pod backend: the TPU-native launcher (BASELINE.json north star).

The reference's YARN/MPI backends place processes and let rabit broker
ranks over sockets. On a TPU pod slice the placement is per-host
(one process per TPU-VM worker) and rank brokering is
``jax.distributed.initialize`` — so this backend:

1. starts the rabit tracker (rank-stable coordination + the env contract),
2. launches one process per pod host — over ssh when a ``--host-file``
   lists the TPU-VM workers, or locally otherwise. A chip belongs to one
   process at a time, so on a host with TPU chips N local workers get one
   chip each (:func:`local_chip_env`); a worker count the host's chips
   cannot be dealt out to is refused before anything starts. With
   ``JAX_PLATFORMS=cpu`` the local workers are a multi-process simulation
   and share nothing,
3. exports ``DMLC_TRACKER_URI/PORT``, ``DMLC_NUM_WORKER``,
   ``DMLC_TASK_ID``; workers call
   :func:`dmlc_tpu.parallel.init_from_env`, which maps that contract onto
   the JAX coordinator (coordinator = tracker host, port + 1), and their
   InputSplit shard index is their process index (SURVEY.md §2.3 row 1).
   The same ``DMLC_TASK_ID``/``DMLC_NUM_WORKER`` pair doubles as the pod
   identity the deterministic epoch planner's ``pod_sharding`` resolves
   (:func:`dmlc_tpu.parallel.distributed.pod_identity`): each launched
   worker reads its disjoint shard of one globally consistent shuffled
   epoch straight from the launcher env (docs/data.md).

The job's data plane is XLA collectives over ICI — no peer sockets to
broker, which is why this backend needs nothing beyond placement + env.
"""

from __future__ import annotations

import glob
import os
import subprocess
import threading
from typing import Dict, List

from dmlc_tpu.tracker.local import run_with_retry
from dmlc_tpu.tracker.opts import read_host_file
from dmlc_tpu.tracker.ssh import build_remote_command, build_ssh_argv, parse_host
from dmlc_tpu.utils.check import get_logger


def worker_env(envs: Dict[str, str], task_id: int) -> Dict[str, str]:
    env = dict(envs)
    env["DMLC_ROLE"] = "worker"
    env["DMLC_TASK_ID"] = str(task_id)
    env["DMLC_JOB_CLUSTER"] = "tpu-pod"
    # jax.distributed.initialize args are derived from DMLC_TRACKER_URI/PORT
    # by dmlc_tpu.parallel.init_from_env; nothing else to export.
    return env


def local_tpu_chips() -> int:
    """TPU chips this host exposes, counted from their device nodes
    (``/dev/vfio/<n>`` on v5e and later, ``/dev/accel<n>`` before) — the
    launcher never initialises a backend to find out: it would take the
    chips its workers need."""
    nodes = [p for p in glob.glob("/dev/vfio/*") + glob.glob("/dev/accel*")
             if p.rstrip("0123456789") != p]
    return len(nodes)


def local_chip_env(task_id: int, nworker: int, chips: int,
                   environ=None) -> Dict[str, str]:
    """Environment that hands local worker ``task_id`` chip ``task_id``
    and joins the ``nworker`` one-chip processes into one slice — libtpu's
    multi-process-per-host variables, as JAX's own multi-process test
    launcher sets them. Empty when the workers need no chips: none on
    this host, a job pinned off the TPU, or a single worker (which may
    keep every chip). Refuses a worker count that is not the chip count —
    without per-process chips, worker 0 would claim them all and the rest
    would fail or hang."""
    environ = os.environ if environ is None else environ
    platforms = environ.get("JAX_PLATFORMS", "")
    if (chips == 0 or nworker <= 1
            or (platforms and "tpu" not in platforms.split(","))):
        return {}
    bounds = environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
    if nworker != chips or not bounds:
        raise RuntimeError(
            f"tpu-pod: {nworker} local workers on a host with {chips} TPU "
            f"chip(s) (TPU_CHIPS_PER_HOST_BOUNDS={bounds!r}): a chip "
            f"belongs to one process, so local workers run one per chip — "
            f"use --num-workers {chips} (or 1), list hosts in --host-file, "
            f"or set JAX_PLATFORMS=cpu for a CPU simulation")
    base_port = 8476
    return {
        "TPU_VISIBLE_CHIPS": str(task_id),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{base_port + i}" for i in range(nworker)),
        "TPU_PROCESS_PORT": str(base_port + task_id),
        "CLOUD_TPU_TASK_ID": str(task_id),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def submit(args):
    hosts: List[str] = []
    if args.host_file:
        hosts = read_host_file(args.host_file)

    def run(nworker: int, nserver: int, envs: Dict[str, str]):
        assert nserver == 0, "tpu-pod jobs are allreduce-style (no PS role)"
        threads = []
        errors: List[BaseException] = []
        base = dict(envs)
        base.update(args.pass_envs)

        def guarded(fn, *fn_args) -> None:
            try:
                fn(*fn_args)
            except BaseException as exc:  # noqa: BLE001 - reported to launcher
                errors.append(exc)

        if hosts:
            assert len(hosts) >= nworker, (
                f"tpu-pod: host file lists {len(hosts)} hosts < {nworker} workers")
            for i in range(nworker):
                host, port = parse_host(hosts[i])
                env = worker_env(base, i)
                remote = build_remote_command(
                    args.command, env, host, args.sync_dst_dir or os.getcwd())
                argv = build_ssh_argv(host, port, remote)
                t = threading.Thread(
                    target=guarded, args=(subprocess.check_call, argv))
                t.daemon = True
                t.start()
                threads.append(t)
        else:
            get_logger().info(
                "tpu-pod: no --host-file, launching %d local processes", nworker)
            num_attempt = max(1, getattr(args, "local_num_attempt", 1))
            chips = local_tpu_chips()
            chip_envs = [local_chip_env(i, nworker, chips, {**os.environ,
                                                            **base})
                         for i in range(nworker)]  # refuses before any start
            for i in range(nworker):
                env = os.environ.copy()
                env.update(worker_env(base, i))
                env.update(chip_envs[i])
                t = threading.Thread(
                    target=guarded,
                    args=(run_with_retry, args.command, env,
                          f"tpu-pod worker {i}", num_attempt))
                t.daemon = True
                t.start()
                threads.append(t)
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(
                f"tpu-pod job failed ({len(errors)} worker thread(s)): "
                f"{'; '.join(str(e) for e in errors)}") from errors[0]

    return run
