"""The data service as a deployment (docs/service.md "Deploying"): a
dispatcher and parse workers started as OS processes from
``python3 -m dmlc_tpu.service``, a trainer-side ``ServiceParser`` in this
process. The delivery's reference is local parsing: every block the
process fleet delivers is byte-identical, and in the same order, to the
parts parsed one after another in one process with the same configuration.
Beside it, what the deployment's cell reads: ``DeviceIter.stats()
["service"]``, the ``service_recv`` / ``service_decode`` spans on both
clocks, and the fleet's own CPU seconds."""

from __future__ import annotations

import contextlib
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.io import resilience
from dmlc_tpu.service import LocalFleet, ServiceParser
from dmlc_tpu.service import dispatcher as svc_dispatcher
from dmlc_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 8192
NUM_PARTS = 8
PARSER_CFG = {"format": "libfm", "threaded": False, "chunk_bytes": CHUNK}
SERVICE_KEYS = {"wire_bytes", "frames", "wire_version", "fastpath_blocks",
                "parts_by_worker", "retries", "failovers", "giveups",
                "recv_seconds", "decode_seconds",
                # recv_seconds by what was waited for (ISSUE 35)
                "locate_seconds", "connect_seconds", "frame_seconds",
                "drain_seconds"}
FAST_RETRY = dict(max_attempts=8, base_delay=0.01, max_delay=0.05,
                  attempt_timeout=20.0)


def _write_corpus(path, rows: int = 12000, seed: int = 0) -> str:
    """libfm text with rows of 1 to 11 ``field:id:value`` tokens: lines of
    uneven length, so the byte cuts of ``InputSplit`` fall inside lines."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(rows):
            k = int(rng.integers(1, 12))
            ids = rng.integers(0, 5000, k)
            f.write(f"{i % 2} " + " ".join(
                f"{j}:{ids[j]}:{1 + (i + j) % 3}" for j in range(k)) + "\n")
    return str(path)


def _local_blocks(path: str):
    out = []
    for p in range(NUM_PARTS):
        parser = create_parser(path, p, NUM_PARTS, "libfm", threaded=False,
                               chunk_bytes=CHUNK)
        while (blk := parser.next_block()) is not None:
            out.append(blk)
        parser.close()
    return out


def _drain(parser):
    out = []
    while (blk := parser.next_block()) is not None:
        out.append(blk)
    return out


def _assert_blocks_identical(got, want):
    assert len(got) == len(want)
    for a, b in zip(want, got):
        for name in ("offset", "label", "index", "value", "field", "weight",
                     "qid"):
            va, vb = getattr(a, name), getattr(b, name)
            assert (va is None) == (vb is None), name
            if va is not None:
                assert va.dtype == vb.dtype, name
                assert va.tobytes() == np.asarray(vb).tobytes(), name
        assert json.dumps(getattr(a, "resume_state", None), sort_keys=True) \
            == json.dumps(getattr(b, "resume_state", None), sort_keys=True)


def _start(*args, code: str | None = None):
    """One component of the service as a process; ``(process, address)``
    once it has printed its ready line."""
    head = [sys.executable, "-c", code] if code else \
        [sys.executable, "-m", "dmlc_tpu.service"]
    proc = subprocess.Popen(head + list(args), cwd=ROOT, text=True,
                            stdout=subprocess.PIPE)
    line = proc.stdout.readline().split()
    if line[:1] != ["ready"]:
        proc.kill()
        proc.wait()
        raise AssertionError(f"no ready line from {args}: {line}")
    assert line[1] == args[0]
    return proc, line[2]


def _stop(proc, expect: int | None = 0) -> str:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    try:
        rest, _ = proc.communicate(timeout=20)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise
    if expect is not None:
        assert proc.returncode == expect
    return rest


@contextlib.contextmanager
def _process_fleet(corpus: str, workers: int = 2, worker_args=()):
    """``(dispatcher address, {worker id: its process})`` of a dispatcher
    and ``workers`` parse workers, each a process of its own; all are
    stopped at exit and have to exit 0 unless the test killed them."""
    started = []
    try:
        disp, address = _start("dispatcher", "--uri", corpus, "--num-parts",
                               str(NUM_PARTS), "--parser",
                               json.dumps(PARSER_CFG))
        started.append(disp)
        by_id = {}
        for _ in range(workers):
            proc, listens = _start("worker", address, *worker_args)
            started.append(proc)
            by_id[listens] = proc
        yield address, by_id
    finally:
        for proc in reversed(started):
            _stop(proc, expect=None if proc.returncode == -signal.SIGKILL
                  else 0)


def _refuses(address: str) -> bool:
    host, port = address.rsplit(":", 1)
    try:
        socket.create_connection((host, int(port)), timeout=2.0).close()
    except ConnectionRefusedError:
        return True
    return False


@pytest.fixture
def corpus(tmp_path):
    return _write_corpus(tmp_path / "c.libfm")


# ---------------- the entry points ----------------

@pytest.mark.parametrize("component", ["dispatcher", "worker"])
def test_entry_point_ready_line_sigterm_and_nothing_left(corpus, component):
    disp, address = _start("dispatcher", "--uri", corpus, "--num-parts",
                           str(NUM_PARTS), "--parser", json.dumps(PARSER_CFG))
    try:
        proc, listens = (disp, address) if component == "dispatcher" else \
            _start("worker", address)
        assert not _refuses(listens)    # the ready line names a live listener
        # the component is one process: it has started no child
        with open(f"/proc/{proc.pid}/task/{proc.pid}/children") as f:
            assert f.read().split() == []
        assert _stop(proc) == ""        # one line on stdout, no more; exit 0
        assert _refuses(listens)        # and the port is free again
    finally:
        _stop(disp, expect=None)
    assert disp.returncode == 0


_WORKER_THEN_MODULES = """
import sys
from dmlc_tpu.service.__main__ import main
rc = main(sys.argv[1:])
print("jax_modules", sorted(m for m in sys.modules
                            if m == "jax" or m.startswith(("jax.", "jaxlib"))),
      flush=True)
sys.exit(rc)
"""


def test_a_worker_process_never_imports_jax(corpus):
    """Asked of the worker's own ``sys.modules`` after it has parsed and
    served every part: the trainer holds the chip, and a second process
    that loads the accelerator's runtime fails or hangs the run."""
    disp, address = _start("dispatcher", "--uri", corpus, "--num-parts",
                           str(NUM_PARTS), "--parser", json.dumps(PARSER_CFG))
    worker = None
    try:
        worker, listens = _start("worker", address,
                                 code=_WORKER_THEN_MODULES)
        client = ServiceParser(address)
        try:
            blocks = _drain(client)
            assert client.service_stats()["parts_by_worker"] == {
                listens: NUM_PARTS}
        finally:
            client.close()
        assert sum(len(b) for b in blocks) == 12000
        assert _stop(worker).strip() == "jax_modules []"
    finally:
        if worker is not None:
            _stop(worker, expect=None)   # a failed assertion leaves none
        _stop(disp, expect=None)


# ---------------- delivery against local parsing ----------------

# a part of the test corpus is about 205 kB of frames: under this bound a
# worker holds one part and the part it parses next, never a third
PART_BYTES = 210000
BOUNDED = ("--frame-store-bytes", "250000")


@pytest.mark.parametrize("worker_args", [(), BOUNDED],
                         ids=["stores_keep_all", "stores_bounded"])
def test_process_fleet_is_byte_identical_to_local_parsing_over_two_epochs(
        corpus, worker_args):
    local = _local_blocks(corpus)
    # the cuts fall inside lines, and the parts differ in their rows
    assert len(local) > 2 * NUM_PARTS
    assert len({len(b) for b in local}) > 1
    with _process_fleet(corpus, worker_args=worker_args) as (address,
                                                             workers):
        client = ServiceParser(address)
        try:
            for epoch in range(2):
                client.before_first()
                _assert_blocks_identical(_drain(client), local)
            stats = client.service_stats()
            granted = svc_dispatcher.request(
                address, {"cmd": "status"})["jobs"]["default"]["grants"]
        finally:
            client.close()
    # workers that keep every frame parse the corpus once; bounded ones
    # are granted, and parse, every part again in every epoch (and run a
    # few parts ahead of the reader)
    if worker_args:
        assert 2 * NUM_PARTS <= granted <= 2 * NUM_PARTS + 4
    else:
        assert granted == NUM_PARTS
    assert set(stats) == SERVICE_KEYS
    assert stats["wire_version"] == 2 and stats["fastpath_blocks"] == 0
    assert (stats["retries"], stats["failovers"], stats["giveups"]) == (0, 0, 0)
    # every part exactly once an epoch, whichever worker was granted it
    # (parts this small can all go to the worker that polled first)
    assert set(stats["parts_by_worker"]) <= set(workers)
    assert sum(stats["parts_by_worker"].values()) == 2 * NUM_PARTS
    assert stats["frames"] > 2 * len(local) and stats["wire_bytes"] > 0


@pytest.mark.parametrize("worker_args", [(), BOUNDED],
                         ids=["stores_keep_all", "stores_bounded"])
def test_a_killed_worker_process_loses_and_repeats_nothing(corpus,
                                                           worker_args):
    local = _local_blocks(corpus)
    with _process_fleet(corpus, worker_args=worker_args) as (address,
                                                             workers):
        client = ServiceParser(address, retry_policy=resilience.RetryPolicy(
            **FAST_RETRY))
        try:
            got = [client.next_block() for _ in range(3)]
            # the owner of the last part granted so far (bounded stores
            # run two parts a worker ahead of the reader, the others the
            # whole epoch): a stream that is not open yet
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                assigned = svc_dispatcher.request(
                    address, {"cmd": "status"})["assigned"]
                if len(assigned) >= (4 if worker_args else NUM_PARTS):
                    break
                time.sleep(0.02)
            victim = assigned[max(assigned, key=int)]
            workers[victim].kill()
            workers[victim].wait(timeout=10)
            got.extend(_drain(client))
            stats = client.service_stats()
        finally:
            client.close()
    _assert_blocks_identical(got, local)
    # the part was granted again and the stream went on at the block
    # cursor, from the surviving worker
    assert stats["retries"] >= 1 and stats["giveups"] == 0
    survivor = next(w for w in workers if w != victim)
    assert stats["parts_by_worker"][survivor] >= NUM_PARTS // 2
    assert sum(stats["parts_by_worker"].values()) == NUM_PARTS


# ---------------- bounded frame stores ----------------

def _bounded_fleet(corpus, frame_store_bytes=250000, **kwargs):
    return LocalFleet(corpus, NUM_PARTS, num_workers=2, parser=PARSER_CFG,
                      frame_store_bytes=frame_store_bytes, **kwargs)


def _grants(fleet) -> int:
    return svc_dispatcher.request(
        fleet.address, {"cmd": "status"})["jobs"]["default"]["grants"]


@pytest.mark.parametrize("bound", [1, 250000, 10**9],
                         ids=["one_part", "two_parts", "whole_corpus"])
def test_a_bounded_store_parses_every_epoch_again(corpus, bound):
    """Under the bound a worker gives a part back once it was read and
    the part is granted and parsed again in the next epoch, byte for
    byte; a bound the worker's share fits under changes nothing."""
    local = _local_blocks(corpus)
    fleet = _bounded_fleet(corpus, bound)
    try:
        client = ServiceParser(fleet.address)
        try:
            for epoch in range(3):
                client.before_first()
                _assert_blocks_identical(_drain(client), local)
            stats = client.service_stats()
        finally:
            client.close()
        # (parsed before granted: the workers still run ahead, and a part
        # is granted before it is parsed)
        parsed = sum(len(w.parts_cold) for w in fleet.workers)
        granted = _grants(fleet)
        held = [sum(st.nbytes for st in w._store.values())
                for w in fleet.workers]
        parts = [len(w._store) for w in fleet.workers]
    finally:
        fleet.close()
    assert (stats["retries"], stats["failovers"], stats["giveups"]) == (0, 0, 0)
    assert sum(stats["parts_by_worker"].values()) == 3 * NUM_PARTS
    if bound == 10**9:
        assert granted == parsed == NUM_PARTS
        return
    ahead = 2 if bound == 1 else 4      # parts the workers run ahead
    assert 3 * NUM_PARTS <= granted <= 3 * NUM_PARTS + ahead
    assert granted - ahead <= parsed <= granted
    # the bound, and the one part granted under it
    assert max(parts) <= (1 if bound == 1 else 2)
    assert max(held) < bound + PART_BYTES


@pytest.mark.parametrize("cut", [1, 7, 20])
def test_a_bounded_store_gives_way_when_the_epoch_starts_over(corpus, cut):
    """A reader that starts its epoch over leaves the stores full of parts
    parsed ahead for where it was: they give way to the part it now waits
    for, and nothing is lost, repeated or retried."""
    local = _local_blocks(corpus)
    fleet = _bounded_fleet(corpus)
    try:
        client = ServiceParser(fleet.address)
        try:
            _assert_blocks_identical(_drain(client), local)
            client.before_first()
            for _ in range(cut):
                client.next_block()
            t0 = time.monotonic()
            client.before_first()
            _assert_blocks_identical(_drain(client), local)
            took = time.monotonic() - t0
            stats = client.service_stats()
        finally:
            client.close()
    finally:
        fleet.close()
    assert (stats["retries"], stats["failovers"], stats["giveups"]) == (0, 0, 0)
    assert took < 10.0


def test_a_bounded_store_restores_a_foreign_checkpoint(corpus):
    """A delivered-block count maps onto (part, block) through the workers'
    per-part counts: every part before the position is parsed to be
    counted, and a part that was counted may be evicted like one that was
    read."""
    local = _local_blocks(corpus)
    at = len(local) - 3
    fleet = _bounded_fleet(corpus)
    try:
        client = ServiceParser(fleet.address)
        try:
            client.load_state({"kind": "blocks", "blocks": at})
            _assert_blocks_identical(_drain(client), local[at:])
            stats = client.service_stats()
        finally:
            client.close()
    finally:
        fleet.close()
    assert (stats["retries"], stats["giveups"]) == (0, 0)


def test_a_reader_located_at_an_evicted_part_is_sent_on_not_failed(corpus):
    """The race a bounded store opens: the dispatcher names a worker, the
    worker gives the part back, the reader's request arrives. The worker
    says ``evicted``; the reader locates again and blames nobody."""
    local = _local_blocks(corpus)
    fleet = _bounded_fleet(corpus, 10**9)
    try:
        client = ServiceParser(fleet.address)
        try:
            _assert_blocks_identical(_drain(client), local)
            client.before_first()
            stale = client._locate_owner()
            holder = next(w for w in fleet.workers
                          if w.worker_id == stale["worker"])
            assert holder._evict("default", 0)
            assert ("default", 0) not in holder._store
            locate = client._locate_owner
            answers = [stale]
            client._locate_owner = lambda: (answers.pop() if answers
                                            else locate())
            _assert_blocks_identical(_drain(client), local)
            stats = client.service_stats()
            status = svc_dispatcher.request(fleet.address,
                                            {"cmd": "status"})
        finally:
            client.close()
        assert _grants(fleet) == NUM_PARTS + 1
    finally:
        fleet.close()
    assert not answers
    assert (stats["retries"], stats["failovers"], stats["giveups"]) == (0, 0, 0)
    # the ERROR that answered the stale open is an answer, not a peer of
    # an older protocol: every stream that delivered a block said HELLO
    assert stats["wire_version"] == 2
    assert all(w["alive"] for w in status["workers"].values())


def test_evict_is_the_owners_to_ask(corpus):
    fleet = _bounded_fleet(corpus, 10**9)
    try:
        client = ServiceParser(fleet.address)
        try:
            _drain(client)
        finally:
            client.close()
        before = svc_dispatcher.request(fleet.address, {"cmd": "status"})
        owner = before["assigned"]["3"]
        other = next(w for w in before["workers"] if w != owner)
        ask = {"cmd": "evict", "job": "default", "part": 3}
        # not the owner: nothing moves, and the asker holds nothing to drop
        assert svc_dispatcher.request(
            fleet.address, dict(ask, worker=other))["ok"] is True
        same = svc_dispatcher.request(fleet.address, {"cmd": "status"})
        assert same["assigned"] == before["assigned"] and same["todo"] == []
        assert svc_dispatcher.request(
            fleet.address, dict(ask, worker=owner))["ok"] is True
        after = svc_dispatcher.request(fleet.address, {"cmd": "status"})
        grants = [s["jobs"]["default"]["grants"] for s in (before, after)]
        if after["todo"] == [3]:            # queued again
            assert grants[1] == grants[0]
            assert "3" not in after["assigned"]
            assert 3 not in after["completed"]
        else:
            # an idle worker asked for work between the two requests: the
            # part is granted again already, and parsed again or not (a
            # parsed part stays ``assigned`` to its owner, as ``before``
            # shows, so ``completed`` decides nothing here)
            assert after["todo"] == [] and grants[1] == grants[0] + 1
            assert "3" in after["assigned"]
    finally:
        fleet.close()


def test_frame_store_bytes_is_checked():
    from dmlc_tpu.service import ParseWorker
    from dmlc_tpu.utils.check import DMLCError

    with pytest.raises(DMLCError, match="frame_store_bytes"):
        ParseWorker("127.0.0.1:1", frame_store_bytes=0)


# ---------------- what the cell reads ----------------

def test_fleet_cpu_seconds_grow_while_the_workers_parse(corpus):
    with _process_fleet(corpus) as (address, workers):
        client = ServiceParser(address)
        try:
            before = client.fleet_cpu_seconds()
            assert sum(len(b) for b in _drain(client)) == 12000
            after = client.fleet_cpu_seconds()
        finally:
            client.close()
    assert set(before) == set(after) == {"dispatcher", *workers}
    assert all(after[peer] >= before[peer] for peer in before)
    # the parts were parsed, encoded and sent in between
    assert sum(after[w] - before[w] for w in workers) > 0


def test_device_iter_stats_carry_a_service_entry_for_a_service_source_only(
        corpus):
    kwargs = dict(num_col=5000, batch_size=512, layout="ell", max_nnz=16)
    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2, parser=PARSER_CFG)
    try:
        it = DeviceIter(ServiceParser(fleet.address), **kwargs)
        batches = sum(1 for _ in it)
        stats = it.stats()
        it.close()
    finally:
        fleet.close()
    assert batches == -(-12000 // 512)
    service = stats["service"]
    assert set(service) == SERVICE_KEYS
    assert service["wire_version"] == 2 and service["frames"] > 0
    assert service["wire_bytes"] > 12000 * 12   # offset and label alone
    assert sum(service["parts_by_worker"].values()) == NUM_PARTS
    # the local keys keep their names and mean the wire here
    assert stats["stage_busy"]["read"] == pytest.approx(
        service["recv_seconds"], rel=0.05, abs=5e-3)
    local = DeviceIter(create_parser(corpus, 0, 1, "libfm"), **kwargs)
    try:
        assert sum(1 for _ in local) == batches
        assert "service" not in local.stats()
    finally:
        local.close()


def test_service_recv_and_decode_are_spans_on_both_clocks(corpus, tmp_path):
    import jax
    from jax.profiler import ProfileData

    fleet = LocalFleet(corpus, NUM_PARTS, num_workers=2, parser=PARSER_CFG)
    telemetry.reset_spans()
    trace_dir = str(tmp_path / "trace")
    try:
        client = ServiceParser(fleet.address)
        jax.profiler.start_trace(trace_dir)
        try:
            blocks = _drain(client)
        finally:
            jax.profiler.stop_trace()
            client.close()
    finally:
        fleet.close()
    ring = {}
    for s in telemetry.spans_snapshot():
        ring.setdefault(s["name"], []).append(s)
    assert len(ring["service_decode"]) == len(blocks)
    assert len(ring["service_recv"]) > len(blocks)   # HELLOs and ENDs too
    assert sum(s["labels"]["rows"] for s in ring["service_decode"]) == 12000
    assert all(s["labels"]["nbytes"] > 0 for s in ring["service_recv"])
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    annotated = [e.name for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for e in line.events
                 if e.name.startswith("dmlc_tpu:service_")]
    assert annotated.count("dmlc_tpu:service_decode") == len(blocks)
    assert annotated.count("dmlc_tpu:service_recv") == len(
        ring["service_recv"])


# ---------------- the cell, rehearsed ----------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_service_cell_rehearses_through_the_harness(monkeypatch, capsys,
                                                        trace):
    """``kdd12_fm_service`` at the tiny size on the CPU, the whole of
    ``cellbench.run`` through the process fleet (``BENCHMARK.json`` read as
    ``tiny_*`` in memory: ``rehearsal.json`` has no mirror of the cell).
    ``cellbench/tests/test_service_cell.py`` holds ``served`` and the
    broken fleets."""
    from cellbench import run as R
    from cellbench.readers import _program as P

    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kdd12_", "tiny_"))
        if parts[-1] == "service_text_epochs.json":
            # the bound cut with the corpus: under a part, as the cell's is
            return dict(real(*parts), frame_store_bytes=real(
                R.HERE, "configs", "tiny_fm_svc.json")["service"][
                    "frame_store_bytes"])
        return real(*parts)

    monkeypatch.setattr(R, "load_json", load_json)
    P._cache.clear()
    assert R.main(["--workload", "tiny_fm_service", "--seed", "2147483999",
                   "--seconds", "1", "--trace", str(trace),
                   "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    # every comparison holds, `served` among them: both workers serve
    # (a bounded store takes one part at a time here) and every epoch is
    # parsed again
    bad = [ln for ln in out.splitlines() if ln.endswith("NOT OK")]
    assert not bad, bad
    assert line["failed"] == 0 and line["rehearsal"] is True
    values = {k: v["value"] for k, v in line["metrics"].items()}
    if trace:
        assert 100.0 < values.pop("wire_bytes_per_row") < 400.0
        assert values.pop("put_bytes_per_row") > 100.0
        assert {"service_recv_busy_s_per_mrow", "service_fleet_cpu_s_per_mrow",
                "service_decode_busy_s_per_mrow"} <= set(values)
    assert all(v is None for v in values.values()), values
