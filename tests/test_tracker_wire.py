"""Rabit wire-compatibility + standalone tracker CLI (satellites of the
elastic-membership PR).

- ``tests/data/rabit_rendezvous_v1.json`` pins one two-worker rendezvous
  byte exchange (magic handshake, hello, rank assignment + topology
  ints, connect brokering, shutdown) as a transcript fixture. The replay
  harness here drives it against a live :class:`RabitTracker` with
  **plain sockets** — native-endian int32 framing and length-prefixed
  utf-8 strings built with ``struct``, no ``tracker/client.py`` anywhere
  — so "wire-compatible with the reference tracker protocol" is a tested
  claim, not a co-authored one. Any drift in the handshake, the
  assignment int sequence, or the brokering dialog breaks the replay.
- ``python -m dmlc_tpu.tracker.tracker --num-workers N`` must print the
  reference's ``DMLC_TRACKER_ENV_START``/``END`` env block on stdout so
  external launchers can scrape rank/coordinator env; the test launches
  the CLI as a real subprocess, parses the block, rendezvous a worker
  against it, and watches the process exit cleanly.
"""

from __future__ import annotations

import json
import os
import socket
import struct
import subprocess
import sys
import threading

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "rabit_rendezvous_v1.json")


# ---------------------------------------------------------------------------
# plain-socket transcript replay (deliberately NOT tracker/client.py)

def _send_int(sock: socket.socket, value: int) -> None:
    sock.sendall(struct.pack("@i", value))


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        assert chunk, "tracker closed mid-message"
        buf += chunk
    return buf


def _recv_int(sock: socket.socket) -> int:
    return struct.unpack("@i", _recv_exact(sock, 4))[0]


def _send_str(sock: socket.socket, value: str) -> None:
    raw = value.encode()
    _send_int(sock, len(raw))
    sock.sendall(raw)


def _recv_str(sock: socket.socket) -> str:
    return _recv_exact(sock, _recv_int(sock)).decode()


class _TranscriptWorker:
    """Replays one worker's fixture transcript over plain sockets."""

    def __init__(self, name: str, spec: dict, tracker_addr):
        self.name = name
        self.spec = spec
        self.tracker_addr = tracker_addr
        self.captured: dict = {}
        self.listen_sock = None
        self.listen_port = None
        self.peer_socks = []
        self.errors: list = []
        if spec.get("listen"):
            self.listen_sock = socket.socket(socket.AF_INET,
                                             socket.SOCK_STREAM)
            self.listen_sock.bind(("127.0.0.1", 0))
            self.listen_sock.listen(4)
            self.listen_port = self.listen_sock.getsockname()[1]

    def _resolve(self, value):
        if isinstance(value, str) and value.startswith("$"):
            assert value in self.captured, f"{value} not captured yet"
            return self.captured[value]
        return value

    def _run_steps(self, sock: socket.socket, steps) -> None:
        for step in steps:
            op, *args = step
            if op == "send_int":
                _send_int(sock, int(self._resolve(args[0])))
            elif op == "send_str":
                _send_str(sock, str(self._resolve(args[0])))
            elif op == "send_port":
                _send_int(sock, self.listen_port)
            elif op == "recv_int":
                got = _recv_int(sock)
                want = args[0]
                if isinstance(want, str) and want.startswith("$"):
                    self.captured[want] = got
                else:
                    assert got == int(want), (
                        f"{self.name}: recv_int {got} != expected {want}")
            elif op == "recv_str":
                got = _recv_str(sock)
                want = args[0]
                if isinstance(want, str) and want.startswith("$"):
                    self.captured[want] = got
                else:
                    assert got == want, (
                        f"{self.name}: recv_str {got!r} != {want!r}")
            elif op == "dial":
                host = str(self._resolve(args[0]))
                port = int(self._resolve(args[1]))
                peer = socket.create_connection((host, port), timeout=10)
                self.peer_socks.append(peer)
            else:  # pragma: no cover - fixture schema guard
                raise AssertionError(f"unknown transcript op {op!r}")

    def connect_and_hello(self) -> socket.socket:
        sock = socket.create_connection(self.tracker_addr, timeout=10)
        sock.settimeout(20)
        self._run_steps(sock, self.spec["hello"])
        return sock

    def broker(self, sock: socket.socket) -> None:
        try:
            self._run_steps(sock, self.spec["broker"])
            for _ in range(int(self.spec.get("accept_peers", 0))):
                self.listen_sock.settimeout(10)
                peer, _ = self.listen_sock.accept()
                self.peer_socks.append(peer)
        except BaseException as exc:  # noqa: BLE001 - reported by the test
            self.errors.append(exc)
        finally:
            sock.close()

    def shutdown(self) -> None:
        sock = socket.create_connection(self.tracker_addr, timeout=10)
        sock.settimeout(20)
        try:
            self._run_steps(sock, self.spec["shutdown"])
        finally:
            sock.close()

    def close(self) -> None:
        for s in self.peer_socks:
            try:
                s.close()
            except OSError:
                pass
        if self.listen_sock is not None:
            try:
                self.listen_sock.close()
            except OSError:
                pass


def test_rabit_rendezvous_transcript_replays_with_plain_sockets():
    """The recorded two-worker rendezvous replays byte-for-byte against
    a live tracker using nothing but struct-packed sockets: magic both
    ways, hello, the exact rank/parent/world/topology int sequence,
    brokering (B dials A at the tracker-brokered address), shutdown."""
    from dmlc_tpu.tracker.tracker import RabitTracker

    with open(FIXTURE, encoding="utf-8") as f:
        fixture = json.load(f)
    assert fixture["version"] == 1
    tracker = RabitTracker("127.0.0.1", 2)
    tracker.start(2)
    addr = ("127.0.0.1", tracker.port)
    first, second = fixture["order"]
    wa = _TranscriptWorker(first, fixture["workers"][first], addr)
    wb = _TranscriptWorker(second, fixture["workers"][second], addr)
    try:
        # arrival order pins rank order: A's hello is fully consumed by
        # the tracker's accept loop before B's connection is accepted
        sock_a = wa.connect_and_hello()
        sock_b = wb.connect_and_hello()
        # assignment is batched once both arrive; A's brokering dialog
        # completes before B's begins (single-threaded accept loop), so
        # the two replay threads interlock exactly like real clients
        ta = threading.Thread(target=wa.broker, args=(sock_a,))
        tb = threading.Thread(target=wb.broker, args=(sock_b,))
        ta.start()
        tb.start()
        ta.join(timeout=20)
        tb.join(timeout=20)
        assert not ta.is_alive() and not tb.is_alive(), "brokering hung"
        assert not wa.errors, wa.errors
        assert not wb.errors, wb.errors
        # the tracker brokered B a dial to A's REAL listener
        assert wb.captured["$HOST_A"] == "127.0.0.1"
        assert wb.captured["$PORT_A"] == wa.listen_port
        assert len(wa.peer_socks) == 1  # B's incoming link accepted
        assert len(wb.peer_socks) == 1  # the dialed link to A
        # shutdown from both ranks ends the accept loop (job complete)
        wa.shutdown()
        wb.shutdown()
        tracker.join(timeout=10)
        assert not tracker.alive()
    finally:
        wa.close()
        wb.close()
        tracker.close()


# ---------------------------------------------------------------------------
# standalone tracker CLI

def _read_env_block(stdout) -> dict:
    envs = {}
    inside = False
    for line in stdout:
        line = line.strip()
        if line == "DMLC_TRACKER_ENV_START":
            inside = True
            continue
        if line == "DMLC_TRACKER_ENV_END":
            return envs
        if inside and "=" in line:
            key, _, value = line.partition("=")
            envs[key] = value
    raise AssertionError("no DMLC_TRACKER_ENV_START/END block on stdout")


@pytest.mark.parametrize("num_workers", [1])
def test_tracker_cli_env_block_and_rendezvous(num_workers):
    """`python -m dmlc_tpu.tracker.tracker --num-workers N` prints the
    reference env block (DMLC_NUM_WORKER / DMLC_NUM_SERVER /
    DMLC_TRACKER_URI / DMLC_TRACKER_PORT between the START/END
    sentinels); a worker launched from the parsed env rendezvous + shuts
    down, and the tracker process exits 0."""
    from dmlc_tpu.tracker.client import WorkerClient

    proc = subprocess.Popen(
        [sys.executable, "-m", "dmlc_tpu.tracker.tracker",
         "--num-workers", str(num_workers), "--host-ip", "127.0.0.1"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO_ROOT)
    try:
        envs = _read_env_block(proc.stdout)
        # the exact reference env contract, launcher-scrapeable
        assert envs["DMLC_NUM_WORKER"] == str(num_workers)
        assert envs["DMLC_NUM_SERVER"] == "0"
        assert envs["DMLC_TRACKER_URI"] == "127.0.0.1"
        port = int(envs["DMLC_TRACKER_PORT"])
        client = WorkerClient(envs["DMLC_TRACKER_URI"], port)
        assignment = client.start(world_size=num_workers)
        assert assignment.rank == 0
        assert assignment.world_size == num_workers
        client.shutdown()
        assert proc.wait(timeout=20) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdout.close()
        proc.stderr.close()


def test_tracker_cli_rejects_ps_mode():
    proc = subprocess.run(
        [sys.executable, "-m", "dmlc_tpu.tracker.tracker",
         "--num-workers", "1", "--num-servers", "1",
         "--host-ip", "127.0.0.1"],
        capture_output=True, text=True, timeout=30, cwd=REPO_ROOT)
    assert proc.returncode != 0
    assert "standalone" in proc.stderr
