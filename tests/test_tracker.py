"""Tracker tests: topology, protocol integration, backends, CLI.

The reference has zero tracker tests (SURVEY.md §4 gap); these use real
in-process sockets with WorkerClient fakes, the pattern SURVEY recommends.
"""

import os
import subprocess
import sys
import threading

import pytest

from dmlc_tpu.tracker import RabitTracker, WorkerClient
from dmlc_tpu.tracker import tracker as T
from dmlc_tpu.tracker.opts import parse_opts, read_host_file


# ---------------- topology ----------------

@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 17, 64])
def test_tree_and_ring_invariants(n):
    tree_map, parent_map = T.get_tree(n)
    # parent consistency + symmetry
    for r in range(n):
        if parent_map[r] >= 0:
            assert r in tree_map[parent_map[r]]
            assert parent_map[r] in tree_map[r]
    ring = T.get_ring(tree_map, parent_map)
    # ring covers all nodes exactly once
    seen = [0]
    cur = 0
    for _ in range(n - 1):
        cur = ring[cur][1]
        seen.append(cur)
    assert sorted(seen) == list(range(n))
    # prev/next are inverse
    for r in range(n):
        prev, nxt = ring[r]
        assert ring[nxt][0] == r
        assert ring[prev][1] == r


@pytest.mark.parametrize("n", [2, 4, 9, 16])
def test_link_map_renumbering(n):
    tree_map, parent_map, ring_map = T.get_link_map(n)
    assert sorted(tree_map) == list(range(n))
    # ring walks through all ranks
    cur = 0
    seen = {0}
    for _ in range(n - 1):
        cur = ring_map[cur][1]
        seen.add(cur)
    assert seen == set(range(n))
    for r, neighbors in tree_map.items():
        for x in neighbors:
            assert 0 <= x < n and x != r


# ---------------- protocol integration ----------------

def _run_workers(tracker, n, world_size_from_first=True, jobids=None):
    """Spawn n WorkerClients in threads; return their assignments."""
    results = [None] * n
    errors = []

    def work(i):
        try:
            client = WorkerClient("127.0.0.1", tracker.port,
                                  jobid=(jobids[i] if jobids else "NULL"))
            ws = n if (world_size_from_first) else -1
            results[i] = (client, client.start(world_size=ws))
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    return results


@pytest.mark.parametrize("n", [1, 2, 4, 7])
def test_tracker_assigns_unique_ranks(n):
    tracker = RabitTracker("127.0.0.1", n, port=19000)
    tracker.start(n)
    results = _run_workers(tracker, n)
    ranks = sorted(a.rank for _, a in results)
    assert ranks == list(range(n))
    for _, a in results:
        assert a.world_size == n
        assert a.parent < n
        for x in a.tree_neighbors:
            assert 0 <= x < n and x != a.rank
    # total dialed links == total expected incoming links
    dialed = sum(len(a.connected_peers) for _, a in results)
    incoming = sum(a.num_incoming for _, a in results)
    assert dialed == incoming
    for client, _ in results:
        client.shutdown()
    tracker.join(timeout=30)
    assert tracker.alive() is False
    tracker.close()


def test_tracker_lazy_world_size():
    # tracker started with a wrong count; first worker's world_size wins
    tracker = RabitTracker("127.0.0.1", 999, port=19100)
    tracker.start(999)
    results = _run_workers(tracker, 3)
    assert sorted(a.rank for _, a in results) == [0, 1, 2]
    assert all(a.world_size == 3 for _, a in results)
    for client, _ in results:
        client.shutdown()
    tracker.join(timeout=30)
    tracker.close()


def test_tracker_print_and_jobid_rank_stability():
    tracker = RabitTracker("127.0.0.1", 2, port=19200)
    tracker.start(2)
    results = _run_workers(tracker, 2, jobids=["job-a", "job-b"])
    rank_of = {("job-a" if i == 0 else "job-b"): a.rank
               for i, (_, a) in enumerate(results)}
    probe = WorkerClient("127.0.0.1", tracker.port)
    probe.print_to_tracker("hello from test")
    for client, _ in results:
        client.shutdown()
    tracker.join(timeout=30)
    tracker.close()
    assert sorted(rank_of.values()) == [0, 1]


def test_tracker_multi_round_brokering_accounting():
    """A client that reports nerr (dial failure) in its first brokering
    round and links in round 2 must still settle the peer's wait_accept —
    the final-round-only accounting left the peer in wait_conn forever and
    its shutdown then killed the accept loop (r4 regression test for the
    client's nerr-retry protocol)."""
    tracker = RabitTracker("127.0.0.1", 2, port=19400)
    tracker.start(2)

    # worker A: a real client (connects first -> rank 0, enters wait_conn)
    a = WorkerClient("127.0.0.1", tracker.port)
    a_result = {}

    def run_a():
        a_result["assign"] = a.start()

    ta = threading.Thread(target=run_a, daemon=True)
    ta.start()
    # A must CONNECT first (pending order = arrival order): the tracker
    # then assigns A rank 0 into wait_conn and hands B its address — the
    # scenario this test scripts. A's start() blocks until B also joins,
    # so "A connected" cannot be observed via the client; a short delay
    # before B's hello makes the arrival order deterministic.
    import time as _time

    _time.sleep(0.5)

    # worker B: manual protocol — round 1 reports a dial failure, round 2
    # claims the link succeeded (goodset includes A's rank)
    b = WorkerClient("127.0.0.1", tracker.port)
    port = b._listen()
    conn = b._hello("start", -1, -1)
    b.rank = conn.recv_int()
    conn.recv_int()            # parent
    conn.recv_int()            # world
    num_nn = conn.recv_int()
    neighbors = [conn.recv_int() for _ in range(num_nn)]
    rprev, rnext = conn.recv_int(), conn.recv_int()
    linkset = {r for r in neighbors + [rprev, rnext] if r >= 0}
    # round 1: nothing linked; tracker hands out A's address; fail it
    conn.send_int(0)
    nconn = conn.recv_int()
    conn.recv_int()            # nwait
    for _ in range(nconn):
        conn.recv_str(), conn.recv_int(), conn.recv_int()
    assert nconn >= 1
    conn.send_int(nconn)       # every dial "failed"
    # round 2: claim all links made (protocol trusts the client's goodset)
    conn.send_int(len(linkset))
    for r in linkset:
        conn.send_int(r)
    nconn2 = conn.recv_int()
    conn.recv_int()
    assert nconn2 == 0         # nothing left to hand out
    conn.send_int(0)           # no errors
    conn.send_int(port)
    conn.close()
    ta.join(timeout=30)
    assert not ta.is_alive()

    a.shutdown()
    sh = b._hello("shutdown", b.rank, -1)
    sh.close()
    tracker.join(timeout=30)
    # clean completion: with the stale wait_conn entry the accept loop died
    # on `assert worker.rank not in wait_conn` and never set end_time
    assert tracker.end_time is not None
    tracker.close()
    b.close()
    a.close()


def test_tracker_recover_keeps_rank():
    tracker = RabitTracker("127.0.0.1", 2, port=19300)
    tracker.start(2)
    results = _run_workers(tracker, 2)
    by_rank = {a.rank: client for client, a in results}
    # rank 1 "dies" and recovers: same rank, fresh topology
    by_rank[1].close()
    recovered = WorkerClient("127.0.0.1", tracker.port)
    a1 = recovered.recover(1)
    assert a1.rank == 1 and a1.world_size == 2
    # its peer re-links too (real rabit peers redial on link failure)
    by_rank[0].close()
    relinked = WorkerClient("127.0.0.1", tracker.port)
    a0 = relinked.recover(0)
    assert a0.rank == 0
    recovered.shutdown()
    relinked.shutdown()
    tracker.join(timeout=30)
    tracker.close()


# ---------------- opts + backends ----------------

def test_parse_opts_and_env():
    args = parse_opts([
        "--cluster", "local", "--num-workers", "3",
        "--env", "FOO=bar", "--env", "X=1",
        "--", "python", "train.py", "--lr", "0.1",
    ])
    assert args.cluster == "local"
    assert args.num_workers == 3
    assert args.pass_envs == {"FOO": "bar", "X": "1"}
    assert args.command == ["python", "train.py", "--lr", "0.1"]
    with pytest.raises(SystemExit):
        parse_opts(["--num-workers", "2", "cmd"])  # no cluster
    with pytest.raises(SystemExit):
        parse_opts(["--cluster", "local", "--num-workers", "2",
                    "--env", "BAD", "cmd"])


def test_host_file(tmp_path):
    p = tmp_path / "hosts"
    p.write_text("10.0.0.1\n# comment\n10.0.0.2:2222\n\n")
    assert read_host_file(str(p)) == ["10.0.0.1", "10.0.0.2:2222"]
    from dmlc_tpu.tracker.ssh import parse_host

    assert parse_host("10.0.0.2:2222") == ("10.0.0.2", 2222)
    assert parse_host("10.0.0.1") == ("10.0.0.1", 22)


def test_ssh_command_construction():
    from dmlc_tpu.tracker.ssh import build_remote_command, build_ssh_argv

    remote = build_remote_command(
        ["python", "train.py"], {"DMLC_ROLE": "worker", "DMLC_TASK_ID": "3"},
        "10.0.0.5", "/work")
    assert "export DMLC_ROLE='worker';" in remote
    assert "export DMLC_NODE_HOST='10.0.0.5';" in remote
    assert remote.endswith("cd '/work'; python train.py")
    argv = build_ssh_argv("10.0.0.5", 22, remote)
    assert argv[0] == "ssh" and argv[-1] == remote


def test_slurm_mpi_sge_command_construction():
    from dmlc_tpu.tracker.slurm import build_srun_argv
    from dmlc_tpu.tracker.mpi import build_mpirun_argv, detect_mpi_dialect
    from dmlc_tpu.tracker.sge import build_run_script, build_qsub_argv

    srun = build_srun_argv(["./train"], 2, 8, "job-worker")
    assert srun[:1] == ["srun"] and "--ntasks=8" in srun

    assert detect_mpi_dialect("mpirun (Open MPI) 4.1.2") == "openmpi"
    assert detect_mpi_dialect("HYDRA build details: mpich") == "mpich"
    ompi = build_mpirun_argv(["./train"], 4, {"A": "1"}, "openmpi")
    assert ["-x", "A=1"] == ompi[3:5]
    mpich = build_mpirun_argv(["./train"], 4, {"A": "1"}, "mpich")
    assert ["-env", "A", "1"] == mpich[3:6]

    script = build_run_script(["./train"], {"DMLC_NUM_WORKER": "4"}, "worker")
    assert "export DMLC_TASK_ID=$((SGE_TASK_ID - 1))" in script
    qsub = build_qsub_argv("run.sh", 4, "j", "default", 2)
    assert "-t" in qsub and "1-4" in qsub


def test_kubernetes_manifests():
    from dmlc_tpu.tracker.kubernetes import build_manifests

    args = parse_opts([
        "--cluster", "kubernetes", "--num-workers", "2", "--num-servers", "1",
        "--jobname", "my_job", "--", "python", "train.py"])
    manifests = build_manifests(args, {"DMLC_PS_ROOT_URI": "h",
                                       "DMLC_PS_ROOT_PORT": "9091"})
    kinds = [(m["kind"], m["metadata"]["name"]) for m in manifests]
    assert ("Service", "my-job-scheduler") in kinds
    worker = [m for m in manifests if m["metadata"]["name"] == "my-job-worker"][0]
    assert worker["spec"]["parallelism"] == 2
    envs = {e["name"]: e["value"]
            for e in worker["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert envs["DMLC_ROLE"] == "worker"


def test_tpu_pod_worker_env():
    from dmlc_tpu.tracker.tpu_pod import worker_env

    env = worker_env({"DMLC_TRACKER_URI": "10.0.0.1",
                      "DMLC_TRACKER_PORT": "9091",
                      "DMLC_NUM_WORKER": "4"}, 2)
    assert env["DMLC_TASK_ID"] == "2"
    assert env["DMLC_ROLE"] == "worker"
    assert env["DMLC_JOB_CLUSTER"] == "tpu-pod"
    # init_from_env maps this contract onto the jax coordinator
    from dmlc_tpu.parallel.distributed import EnvContract

    contract = EnvContract.from_env(env)
    assert contract.task_id == 2 and contract.num_worker == 4
    assert contract.tracker_uri == "10.0.0.1"


def test_tpu_pod_local_workers_get_one_chip_each():
    """A chip belongs to one process: four local workers on a four-chip
    host each see their own chip; a count the chips cannot be dealt out
    to is refused; CPU-pinned and chipless jobs are left alone."""
    from dmlc_tpu.tracker.tpu_pod import local_chip_env

    host = {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1", "JAX_PLATFORMS": "tpu,cpu"}
    envs = [local_chip_env(i, 4, 4, host) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert all(e["TPU_PROCESS_BOUNDS"] == "2,2,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_ADDRESSES"].count("localhost:") == 4
               for e in envs)
    with pytest.raises(RuntimeError, match="one per chip"):
        local_chip_env(0, 2, 4, host)
    with pytest.raises(RuntimeError, match="one per chip"):
        local_chip_env(0, 4, 4, {})  # chip bounds unknown
    assert local_chip_env(0, 1, 4, host) == {}   # one worker keeps them all
    assert local_chip_env(0, 4, 0, host) == {}   # no chips on this host
    assert local_chip_env(0, 4, 4, dict(host, JAX_PLATFORMS="cpu")) == {}


def test_local_exec_retry(tmp_path):
    from dmlc_tpu.tracker.local import exec_cmd

    marker = tmp_path / "tries"
    cmd = [sys.executable, "-c",
           f"import os,sys; p={str(marker)!r}; "
           "n = int(open(p).read()) if os.path.exists(p) else 0; "
           "open(p, 'w').write(str(n + 1)); sys.exit(0 if n >= 2 else 1)"]
    exec_cmd(cmd, "worker", 0, {}, num_attempt=5)
    assert marker.read_text() == "3"
    with pytest.raises(RuntimeError, match="failed"):
        exec_cmd([sys.executable, "-c", "import sys; sys.exit(1)"],
                 "worker", 0, {}, num_attempt=2)


def test_submit_local_end_to_end(tmp_path):
    """Full dmlc-submit local job: workers rendezvous via the tracker."""
    out_dir = tmp_path
    worker_code = (
        "import os, sys; sys.path.insert(0, os.environ['REPO']);\n"
        "from dmlc_tpu.tracker.client import WorkerClient\n"
        "c = WorkerClient(os.environ['DMLC_TRACKER_URI'],"
        " int(os.environ['DMLC_TRACKER_PORT']))\n"
        "a = c.start()\n"
        "open(os.path.join(os.environ['OUT'],"
        " f'rank_{a.rank}'), 'w').write(os.environ['DMLC_TASK_ID'])\n"
        "c.shutdown()\n"
    )
    from dmlc_tpu.tracker.submit import main

    env_backup = dict(os.environ)
    os.environ["REPO"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ["OUT"] = str(out_dir)
    try:
        main(["--cluster", "local", "--num-workers", "3", "--host-ip", "127.0.0.1",
              "--", sys.executable, "-c", worker_code])
    finally:
        os.environ.clear()
        os.environ.update(env_backup)
    ranks = sorted(p.name for p in out_dir.glob("rank_*"))
    assert ranks == ["rank_0", "rank_1", "rank_2"]


class TestLauncher:
    def test_unpack_archives_with_alias(self, tmp_path):
        import zipfile

        from dmlc_tpu.tracker.launcher import unpack_archives

        z = tmp_path / "code.zip"
        with zipfile.ZipFile(z, "w") as zf:
            zf.writestr("pkg/mod.py", "X = 1\n")
        dirs = unpack_archives(f"{z}#libs", dest=str(tmp_path))
        assert dirs == [str(tmp_path / "libs")]
        assert (tmp_path / "libs" / "pkg" / "mod.py").read_text() == "X = 1\n"
        # missing archives are skipped, not fatal
        assert unpack_archives(str(tmp_path / "nope.zip")) == []

    def test_build_env_maps_tracker_contract(self):
        from dmlc_tpu.tracker.launcher import build_env

        env = build_env({
            "DMLC_TRACKER_URI": "10.0.0.1", "DMLC_TRACKER_PORT": "9091",
            "DMLC_NUM_WORKER": "8", "DMLC_TASK_ID": "3",
            "DMLC_EXTRA_PYTHONPATH": "/opt/extra",
            "PYTHONPATH": "/base",
        })
        assert env["JAX_COORDINATOR_ADDRESS"] == "10.0.0.1:9091"
        assert env["JAX_NUM_PROCESSES"] == "8"
        assert env["JAX_PROCESS_ID"] == "3"
        assert env["PYTHONPATH"] == "/opt/extra:/base"

    def test_launcher_main_execs_command(self, tmp_path):
        from dmlc_tpu.tracker.launcher import main

        marker = tmp_path / "ran.txt"
        rc = main(["python", "-c",
                   f"open(r'{marker}', 'w').write('ok')"], use_exec=False)
        assert rc == 0
        assert marker.read_text() == "ok"


class TestStartPathWorkerDeath:
    def test_worker_dying_mid_start_brokering_fails_alone(self):
        """A worker that hangs up during start brokering must not take the
        rendezvous down with an unhandled EOF (ADVICE r4 #5); its relaunch
        (same jobid) re-claims the rank via job_map and completes the
        world. Settles applied before the death are rolled back (ADVICE r4
        #1), so the survivor's wait_accept stays exact."""
        import socket as _socket
        import struct
        import threading
        import time as _time

        from dmlc_tpu.tracker.client import WorkerClient
        from dmlc_tpu.tracker.tracker import MAGIC, RabitTracker

        tracker = RabitTracker("127.0.0.1", 2)
        tracker.start()
        a = b = None
        try:
            # half-dead worker: completes the hello for jobid "b" then
            # hangs up — the tracker hits EOF inside its assign_rank
            sock = _socket.create_connection(("127.0.0.1", tracker.port), 5)
            sock.sendall(struct.pack("@i", MAGIC))
            assert struct.unpack("@i", sock.recv(4))[0] == MAGIC
            sock.sendall(struct.pack("@i", -1))       # rank
            sock.sendall(struct.pack("@i", 2))        # world_size
            for s in (b"b", b"start"):
                sock.sendall(struct.pack("@i", len(s)) + s)

            a = WorkerClient("127.0.0.1", tracker.port, jobid="a")
            ra = {}
            ta = threading.Thread(
                target=lambda: ra.setdefault("a", a.start(world_size=2)))
            ta.start()
            _time.sleep(0.3)  # let the batch assignment begin
            sock.close()      # die mid-brokering

            # relaunch of jobid "b": re-claims its rank, links the survivor
            b = WorkerClient("127.0.0.1", tracker.port, jobid="b")
            assn_b = b.start(world_size=2)
            ta.join(10)
            assn_a = ra.get("a")
            assert assn_a is not None and assn_a.world_size == 2
            assert {assn_a.rank, assn_b.rank} == {0, 1}
            a.shutdown()
            b.shutdown()
            tracker.join(5)
        finally:
            if a is not None:
                a.close()
            if b is not None:
                b.close()
            tracker.close()


class TestPodMetrics:
    def test_multiprocess_workers_merge_per_rank_stage_table(self, tmp_path):
        """ISSUE 6 pod aggregation: ≥2 REAL worker processes rendezvous,
        each records telemetry and ships a registry snapshot over the
        `metrics` command; the tracker merges them into the per-rank ×
        per-stage table."""
        import time as _time

        os.environ["DMLC_METRICS_LOG_EVERY"] = "0"
        tracker = RabitTracker("127.0.0.1", 2)
        tracker.start(2)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker_code = (
            "import sys, os; sys.path.insert(0, os.environ['REPO'])\n"
            "from dmlc_tpu.tracker.client import WorkerClient\n"
            "from dmlc_tpu.utils import telemetry\n"
            "from dmlc_tpu.io import resilience\n"
            "c = WorkerClient('127.0.0.1', int(os.environ['PORT']))\n"
            "a = c.start(world_size=2)\n"
            "# stage seconds + a scoped resilience event + a span, as a\n"
            "# real pipeline would record them\n"
            "telemetry.REGISTRY.counter(telemetry.STAGE_BUSY_METRIC,\n"
            "    stage='parse', pipeline='p').inc(1.5)\n"
            "telemetry.REGISTRY.counter(telemetry.STAGE_BUSY_METRIC,\n"
            "    stage='read', pipeline='p').inc(0.25 * a.rank)\n"
            "with telemetry.scope('p'):\n"
            "    resilience.record_event('retries', a.rank)\n"
            "telemetry.record_span('parse', 0.0, 1.5)\n"
            "c.report_metrics()\n"
            "c.shutdown()\n"
        )
        env = dict(os.environ, REPO=repo, PORT=str(tracker.port),
                   JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen([sys.executable, "-c", worker_code],
                                  env=env, stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True)
                 for _ in range(2)]
        try:
            for p in procs:
                out, err = p.communicate(timeout=60)
                assert p.returncode == 0, err
            tracker.join(timeout=30)
            # a metrics send can race the shutdown accept: wait briefly
            deadline = _time.monotonic() + 5
            while (len(tracker.pod_metrics()) < 2
                   and _time.monotonic() < deadline):
                _time.sleep(0.05)
            pod = tracker.pod_metrics()
            assert sorted(pod) == [0, 1]
            for rank in (0, 1):
                snap = pod[rank]
                assert snap["telemetry_schema_version"] >= 1
                assert snap["stages"]["parse"] == pytest.approx(1.5)
                assert snap["spans"]["parse"] >= 1
            assert pod[1]["stages"]["read"] == pytest.approx(0.25)
            assert pod[1]["resilience"]["retries"] == 1
            table = tracker.format_pod_table()
            lines = table.splitlines()
            assert "rank" in lines[0] and "parse" in lines[0]
            assert any(ln.strip().startswith("0") for ln in lines[1:])
            assert any(ln.strip().startswith("1") for ln in lines[1:])
            assert "3.000" in lines[-1]  # merged parse sum across ranks
        finally:
            os.environ.pop("DMLC_METRICS_LOG_EVERY", None)
            for p in procs:
                if p.poll() is None:
                    p.kill()
            tracker.close()

    def test_heartbeat_thread_with_metrics(self):
        """start_heartbeat(metrics=True): the periodic ping doubles as a
        snapshot feed and still counts for liveness."""
        import time as _time

        tracker = RabitTracker("127.0.0.1", 1, liveness_timeout=5.0)
        tracker.start(1)
        w = WorkerClient("127.0.0.1", tracker.port)
        try:
            a = w.start(world_size=1)
            w.start_heartbeat(interval=0.1, metrics=True)
            deadline = _time.monotonic() + 5
            while (a.rank not in tracker.pod_metrics()
                   and _time.monotonic() < deadline):
                _time.sleep(0.05)
            snap = tracker.pod_metrics().get(a.rank)
            assert snap is not None
            assert snap["telemetry_schema_version"] >= 1
            assert a.rank in tracker.last_seen  # metrics == liveness ping
            w.stop_heartbeat()
            w.shutdown()
            tracker.join(10)
        finally:
            w.close()
            tracker.close()


class TestLiveness:
    def test_silent_worker_flagged_heartbeater_not(self):
        import time as _time

        from dmlc_tpu.tracker.client import WorkerClient
        from dmlc_tpu.tracker.tracker import RabitTracker

        lost = []
        tracker = RabitTracker("127.0.0.1", 2, liveness_timeout=0.6,
                               on_worker_lost=lost.append)
        tracker.start()
        try:
            a = WorkerClient("127.0.0.1", tracker.port, jobid="a")
            b = WorkerClient("127.0.0.1", tracker.port, jobid="b")
            ra = {}
            import threading

            ta = threading.Thread(
                target=lambda: ra.setdefault("a", a.start(world_size=2)))
            ta.start()
            assn_b = b.start(world_size=2)
            ta.join(5)
            assn_a = ra["a"]
            # detection is opt-in per worker: b heartbeats once (enrolling
            # itself) then goes silent; a keeps heartbeating
            a.start_heartbeat(interval=0.2)
            b.heartbeat()
            _time.sleep(1.5)
            assert assn_b.rank in tracker.lost_workers
            assert assn_a.rank not in tracker.lost_workers
            assert lost == [assn_b.rank]
            # b comes back (recover semantics revive liveness)
            b.heartbeat()
            _time.sleep(0.1)
            assert assn_b.rank not in tracker.lost_workers
            a.stop_heartbeat()
            a.shutdown()
            b.shutdown()
            tracker.join(5)
        finally:
            a.close()
            b.close()
            tracker.close()

    def test_never_heartbeating_worker_not_flagged(self):
        # legacy rabit clients send no heartbeats and must never be flagged
        import time as _time

        from dmlc_tpu.tracker.client import WorkerClient
        from dmlc_tpu.tracker.tracker import RabitTracker

        tracker = RabitTracker("127.0.0.1", 1, liveness_timeout=0.3)
        tracker.start()
        try:
            w = WorkerClient("127.0.0.1", tracker.port)
            w.start(world_size=1)
            _time.sleep(1.0)
            assert tracker.lost_workers == set()
            w.shutdown()
            tracker.join(5)
        finally:
            w.close()
            tracker.close()
