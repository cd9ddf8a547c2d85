"""Service feed: split and parse run outside the trainer, in parse-worker
processes beside a dispatcher process on the same host
(``python3 -m dmlc_tpu.service``, docs/service.md "Deploying"), and parsed
blocks reach the trainer as frames over loopback TCP. The trainer's side is
``DeviceIter(ServiceParser(address), **iter_kwargs)`` on every default.

The traffic file gives the fleet's shape (``workers``, ``num_parts``,
``wire``, ``compression``, ``frame_store_bytes``); the configuration's
``deployment`` states the same numbers. The fleet is started inside
``open_feed``, so inside ``setup_s``: it is what a job pays. The workers'
frame stores are bounded (``worker --frame-store-bytes``, docs/service.md
"Memory model"), well under a worker's share of the corpus: a worker gives
a part back to the dispatcher once the trainer has read it, and every epoch
is granted, split and parsed from the text again.

Closing the iterator stops the fleet; so does the interpreter's exit, and
the death of the process itself (each component runs under a
parent-death signal). ``stats()`` adds three keys to the program's
``service`` entry: ``fleet_workers``, the ids of the workers this feed
started, ``fleet_cpu_seconds``, the sum of the dispatcher's and the
workers' own ``process_cpu_seconds`` as the program reports them, and
``parts_granted``, the dispatcher's count of the parts it has granted to
workers for parsing (its ``status`` reply).
"""

from __future__ import annotations

import atexit
import os
import select
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READY_TIMEOUT_S = 120.0
# a component dies with the trainer, however the trainer dies: the
# parent-death signal is set, then the component's program replaces this one
_UNDER_PARENT = ("import ctypes, os, signal, sys; "
                 "ctypes.CDLL(None).prctl(1, signal.SIGTERM);"
                 " os.execv(sys.argv[1], sys.argv[1:])")


def log(msg: str) -> None:
    print(f"[cellbench] {msg}", flush=True)


class Fleet:
    """One dispatcher and ``workers`` parse workers, a process each."""

    def __init__(self, uri: str, work_dir: str, params: dict):
        self.work_dir = work_dir
        self.processes = []      # (name, Popen, path of its stderr)
        self.worker_ids = []
        self.address = None
        env = dict(os.environ)
        # what the traffic file states about the wire is set where an
        # operator sets it: on the workers' side (docs/service.md knobs)
        env["DMLC_TPU_WIRE_COMPRESSION"] = params["compression"]
        atexit.register(self.stop)
        try:
            t0 = time.perf_counter()
            self.address = self._ready(self._start(
                "dispatcher", env, "dispatcher", "--uri", uri,
                "--num-parts", str(params["num_parts"])))
            bound = params.get("frame_store_bytes")
            workers = [self._start(
                f"worker{i}", env, "worker", self.address,
                *(["--frame-store-bytes", str(bound)] if bound else []))
                for i in range(params["workers"])]
            self.worker_ids = [self._ready(w) for w in workers]
            log(f"service fleet: dispatcher {self.address}, workers "
                f"{self.worker_ids}, up in {time.perf_counter() - t0:.3f} s")
        except BaseException:
            self.stop()
            raise

    def _start(self, name: str, env: dict, *args: str):
        err_path = os.path.join(self.work_dir, f"service_{name}.log")
        with open(err_path, "wb") as err:
            proc = subprocess.Popen(
                [sys.executable, "-c", _UNDER_PARENT, sys.executable, "-m",
                 "dmlc_tpu.service", *args],
                cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                stdout=subprocess.PIPE, stderr=err, start_new_session=True)
        self.processes.append((name, proc, err_path))
        return name, proc, err_path

    def _ready(self, started) -> str:
        """The address on the component's ready line. Waits on the pipe,
        not on a clock: the line, the component's exit, or the time limit."""
        name, proc, err_path = started
        ready, _, _ = select.select([proc.stdout], [], [], READY_TIMEOUT_S)
        words = proc.stdout.readline().decode().split() if ready else []
        if len(words) == 3 and words[0] == "ready":
            return words[2]
        with open(err_path, errors="replace") as f:
            tail = f.read()[-2000:]
        raise SystemExit(
            f"service {name} gave no ready line (exit code {proc.poll()}, "
            f"stdout {words}); the end of its stderr:\n{tail}")

    def stop(self) -> None:
        """SIGTERM to every component that still runs, workers first;
        each has to exit 0 by closing its listener. Called more than once
        without harm."""
        processes, self.processes = self.processes, []
        for _, proc, _ in reversed(processes):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc, _ in reversed(processes):
            try:
                proc.wait(timeout=20.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            proc.stdout.close()
            if proc.returncode != 0:
                log(f"service {name} exited {proc.returncode}, not 0")
        atexit.unregister(self.stop)


def open_feed(uri: str, work_dir: str, iter_kwargs: dict, params: dict):
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.service import ServiceParser
    from dmlc_tpu.service.dispatcher import request

    class FleetFed(DeviceIter):
        """``DeviceIter`` as it is, with the fleet's lifetime tied to the
        iterator's and the fleet's books beside the client's."""

        def stats(self) -> dict:
            out = super().stats()
            out["service"]["fleet_workers"] = list(fleet.worker_ids)
            out["service"]["fleet_cpu_seconds"] = sum(
                self.source.fleet_cpu_seconds().values())
            out["service"]["parts_granted"] = request(
                fleet.address, {"cmd": "status"})["jobs"]["default"]["grants"]
            return out

        def close(self) -> None:
            try:
                super().close()
            finally:
                fleet.stop()

    if (params["wire"] != 2 or params["fastpath"] or params["warm_tier"]
            or not params.get("frame_store_bytes")):
        raise SystemExit(f"this feed runs wire v2 over TCP with no warm "
                         f"tier and bounded frame stores; the traffic file "
                         f"asks for {params}")
    fleet = Fleet(uri, work_dir, params)
    try:
        return FleetFed(ServiceParser(fleet.address), **iter_kwargs)
    except BaseException:
        fleet.stop()
        raise


def served(before: dict, after: dict) -> list:
    """Reasons why the window was not served by this deployment (none: it
    was): every stream on wire v2, frames and bytes over the wire, none off
    the co-located fast path, a part from every worker, every part that
    was streamed parsed for that epoch and not kept from an earlier one, no
    fault healed on the way, no warm tier in the trainer."""
    a, b = after.get("service"), before.get("service")
    if a is None or b is None:
        return ["the iterator's source is no ServiceParser: it parses in "
                "this process"]
    bad = []
    if a["wire_version"] != 2:
        bad.append(f"wire version {a['wire_version']!r} was negotiated "
                   "on a stream, not 2 on every one")
    for key in ("frames", "wire_bytes"):
        if a[key] - b[key] <= 0:
            bad.append(f"no {key} crossed the wire in the window")
    if a["fastpath_blocks"]:
        bad.append(f"{a['fastpath_blocks']} blocks came off the co-located "
                   "fast path, not the wire")
    workers = a.get("fleet_workers", [])
    idle = [w for w in workers if a["parts_by_worker"].get(w, 0)
            - b["parts_by_worker"].get(w, 0) < 1]
    if idle or not workers:
        bad.append(f"workers that served no part in the window: {idle} of "
                   f"{workers}")
    unknown = sorted(set(a["parts_by_worker"]) - set(workers))
    if unknown:
        bad.append(f"parts came from workers this feed did not start: "
                   f"{unknown}")
    # a worker parses a part when the dispatcher grants it, and a part is
    # granted again only after its worker has given it back: as many grants
    # as parts streamed to their end says that no epoch was served from
    # frames kept (the workers parse a few parts ahead of the trainer)
    streamed = sum(a["parts_by_worker"].values())
    if a.get("parts_granted", 0) < streamed:
        bad.append(f"{streamed} parts were streamed since the client was "
                   f"built and {a.get('parts_granted', 0)} granted for "
                   "parsing: an epoch was served from frames the workers "
                   "kept, not parsed again")
    if a.get("parts_granted", 0) - b.get("parts_granted", 0) < 1:
        bad.append("no part was granted for parsing in the window")
    for key in ("retries", "failovers", "giveups"):
        if a[key]:
            bad.append(f"{a[key]} {key} since the client was built: the run "
                       "healed a fault and did not measure this deployment")
    if after["cache_state"] is not None or after["snapshot_state"] is not None:
        bad.append(f"a warm tier is armed in the trainer: cache="
                   f"{after['cache_state']} snapshot="
                   f"{after['snapshot_state']}")
    return bad
