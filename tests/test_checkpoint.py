"""Save and resume of the learners (PR 41; docs/checkpoint.md): the
``DMLCCK01`` container and its golden file, the ``checkpoint`` tier that
no byte budget evicts, ``TrainLoopMixin.save`` / ``save_async`` /
``restore`` / ``latest`` on every learner, a consistent state under
steps dispatched behind the save, resume of model and iterator mid-epoch,
a dealt table restored under another deal, a writer killed half way, the
refusals, and the ``dmlc-submit`` worker that dies and resumes. All on
the CPU backend's virtual devices."""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from cellbench.reference.ckpt_plain_read import PlainCheckpoint
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.io import checkpoint as ck
from dmlc_tpu.io import faults
from dmlc_tpu.models import FFMLearner, FMLearner, LinearLearner
from dmlc_tpu.models import _checkpoint as mc
from dmlc_tpu.parallel import make_mesh
from dmlc_tpu.store import reset_stores, store_for
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, FIELDS, B, K = 600, 5, 32, 8
SMALL = 1 << 14        # chunks that cut these tables into many


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """The program cuts chunks of one size, its own; these tables are
    tiny, so the tests make it tiny too."""
    monkeypatch.setattr(mc, "CHUNK_BYTES", SMALL)


def _corpus(path, fmt, rows=256, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for r in range(rows):
            n = int(rng.integers(2, K + 1))
            ids = rng.choice(N, n, replace=False)
            if fmt == "libfm":
                toks = [f"{int(rng.integers(0, FIELDS))}:{i}:1" for i in ids]
            else:
                toks = [f"{i}:{float(rng.integers(1, 9)) / 4}" for i in ids]
            f.write(f"{r % 2} " + " ".join(toks) + "\n")
    return f"{path}?format={fmt}"


def _make(kind, mesh=None, seed=1, **kw):
    """``(learner, DeviceIter kwargs, corpus format)`` of a test case."""
    if kind == "ffm":
        learner = FFMLearner(num_col=N, num_fields=FIELDS, seed=seed,
                             mesh=mesh, **kw)
        return learner, dict(layout="ell", max_nnz=K, fields=True,
                             mesh=mesh,
                             shardings=learner.batch_shardings()), "libfm"
    layout = kind.split("_")[1]
    if kind.startswith("fm_"):
        learner = FMLearner(num_col=N, layout=layout, seed=seed, mesh=mesh,
                            **kw)
    else:
        learner = LinearLearner(num_col=N, layout=layout,
                                optimizer=optax.adam(0.05), **kw)
    how = dict(layout=layout)
    if mesh is not None:
        how.update(mesh=mesh, shardings=learner.batch_shardings())
    if layout == "ell":
        how["max_nnz"] = K
    return learner, how, "libsvm"


def _feed(learner, how, uri):
    return DeviceIter(create_parser(uri), num_col=learner.device_num_col(),
                      batch_size=B, **how)


def _leaves(learner):
    names, leaves, _ = mc.named_leaves(learner._checkpoint_spec().tree)
    return {n: np.asarray(x) for n, x in zip(names, leaves)}


def _same_state(a, b):
    a, b = _leaves(a), _leaves(b)
    assert a.keys() == b.keys()
    for name in a:
        assert a[name].dtype == b[name].dtype, name
        assert np.array_equal(a[name], b[name]), name


KINDS = ["linear_dense", "fm_dense", "fm_ell", "fm_bcoo", "ffm"]


# ---------------- round trips ----------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_learners_state_comes_back_bit_for_bit(tmp_path, kind):
    learner, how, fmt = _make(kind)
    uri = _corpus(tmp_path / "c.txt", fmt)
    it = _feed(learner, how, uri)
    for _ in range(3):
        learner.step(next(it))
    paths = learner.save(str(tmp_path / "ck"), step=3)
    it.close()
    assert [os.path.basename(p) for p in paths] == [ck.checkpoint_name(3)]
    other, _, _ = _make(kind, seed=99)
    got = other.restore(str(tmp_path / "ck"))
    assert got["step"] == 3 and got["paths"] == paths
    _same_state(learner, other)
    # the restored learner steps on: its buffers are its own again
    it = _feed(other, how, uri)
    assert np.isfinite(float(other.step(next(it))))
    it.close()


@pytest.mark.parametrize("kind", ["fm_ell", "ffm"])
def test_a_resumed_run_ends_as_the_run_that_never_stopped(tmp_path, kind):
    learner, how, fmt = _make(kind)
    uri = _corpus(tmp_path / "c.txt", fmt)
    it = _feed(learner, how, uri)
    want = [float(learner.step(next(it))) for _ in range(7)]
    it.close()

    first, _, _ = _make(kind)
    it = _feed(first, how, uri)
    got = [float(first.step(next(it))) for _ in range(3)]
    first.save(str(tmp_path / "ck"), step=3, device_iter=it)   # mid-epoch
    it.close()
    del first, it

    second, _, _ = _make(kind, seed=5)
    it = _feed(second, how, uri)
    back = second.restore(str(tmp_path / "ck"), device_iter=it)
    assert back["step"] == 3 and back["iterator"]["batches"] == 3
    got += [float(second.step(next(it))) for _ in range(4)]
    it.close()
    assert got == want
    _same_state(learner, second)


def test_save_async_holds_the_state_after_exactly_its_step(tmp_path):
    """Steps dispatched behind the save, on donated buffers, do not reach
    the checkpoint: it holds the state after step 2, not a mixture."""
    learner, how, fmt = _make("ffm")
    uri = _corpus(tmp_path / "c.txt", fmt)
    it = _feed(learner, how, uri)
    batches = [next(it) for _ in range(6)]
    for b in batches[:2]:
        learner.step(b)
    handle = learner.save_async(str(tmp_path / "ck"), step=2)
    for b in batches[2:]:
        learner.step(b)          # behind the save, never waiting for it
    handle.wait()
    it.close()
    want, _, _ = _make("ffm")
    for b in batches[:2]:
        want.step(b)
    got, _, _ = _make("ffm", seed=3)
    assert got.restore(str(tmp_path / "ck"))["step"] == 2
    _same_state(want, got)
    assert not np.array_equal(np.asarray(learner.params.w),
                              np.asarray(got.params.w))


def test_a_second_save_waits_for_the_first_and_counts_the_wait(
        tmp_path, monkeypatch):
    learner, _, _ = _make("ffm")
    gate = threading.Event()
    real = ck.CheckpointWriter.finish

    def slow_finish(self):
        gate.wait(5)
        return real(self)

    monkeypatch.setattr(ck.CheckpointWriter, "finish", slow_finish)
    waited = telemetry.checkpoint_counters()["ckpt_wait_previous_seconds"]
    first = learner.save_async(str(tmp_path / "ck"), step=1)
    assert telemetry.checkpoint_counters()["ckpt_saves_in_flight"] == 1
    threading.Timer(0.2, gate.set).start()
    second = learner.save_async(str(tmp_path / "ck"), step=2)
    assert first.done()          # nothing dropped: the first was finished
    second.wait()
    now = telemetry.checkpoint_counters()
    assert now["ckpt_wait_previous_seconds"] - waited >= 0.1
    assert now["ckpt_saves_in_flight"] == 0
    assert learner.latest(str(tmp_path / "ck"))["step"] == 2


def test_the_surface_takes_keep_last_and_nothing_else_of_its_own():
    """No knob of the drain and no way to a save without a snapshot from
    the surface: a caller that steps cannot ask for the live buffers."""
    import inspect

    from dmlc_tpu.models._loop import TrainLoopMixin

    for method in (TrainLoopMixin.save, TrainLoopMixin.save_async):
        assert list(inspect.signature(method).parameters) == [
            "self", "uri", "step", "device_iter", "keep_last"]
    learner, _, _ = _make("ffm")
    with pytest.raises(TypeError):
        learner.save_async("nowhere", step=0, snapshot=False)


def test_the_gauge_of_saves_in_flight_loses_no_update():
    """The dispatching thread adds and the saver takes away: one locked
    read-modify-write each (``Gauge.add``)."""
    gauge = telemetry.REGISTRY.gauge("test_ckpt_gauge_add")
    threads = [threading.Thread(target=lambda d=d: [gauge.add(d) for _ in
                                                    range(20_000)])
               for d in (+1, -1, +1, -1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert gauge.value == 0.0


def test_no_room_for_a_copy_refuses_and_save_goes_chunk_by_chunk(
        tmp_path, monkeypatch):
    learner, _, _ = _make("ffm")
    monkeypatch.setattr(mc, "_room", lambda leaves: 1000)
    refused = telemetry.checkpoint_counters()["ckpt_saves_total"].get(
        "refused", 0)
    with pytest.raises(mc.CheckpointRefused, match="1,000 are free"):
        learner.save_async(str(tmp_path / "ck"), step=0)
    learner.save(str(tmp_path / "ck"), step=0)
    assert telemetry.checkpoint_counters()["ckpt_saves_total"][
        "refused"] == refused + 2
    other, _, _ = _make("ffm", seed=4)
    other.restore(str(tmp_path / "ck"))
    _same_state(learner, other)


def test_another_learners_file_is_refused_by_what_differs(tmp_path):
    learner, _, _ = _make("ffm")
    learner.save(str(tmp_path / "ck"), step=0)
    other = FFMLearner(num_col=N, num_fields=FIELDS, learning_rate=0.1)
    with pytest.raises(DMLCError, match="optimizer"):
        other.restore(str(tmp_path / "ck"))
    with pytest.raises(DMLCError, match="nothing published"):
        learner.restore(str(tmp_path))


# ---------------- another layout ----------------

def _mesh(n):
    return None if n == 1 else make_mesh(devices=jax.devices()[:n])


@pytest.mark.parametrize("src,dst", [(4, 1), (4, 2), (4, 4), (1, 4), (2, 4),
                                     (1, 1)])
def test_a_dealt_table_restores_under_another_deal(tmp_path, src, dst):
    """Saved by ``src`` shards, restored on ``dst``: every chip reads the
    rows ``RowDeal.place`` gives it from whichever files hold them."""
    saver, how, fmt = _make("ffm", mesh=_mesh(src))
    uri = _corpus(tmp_path / "c.txt", fmt)
    it = _feed(saver, how, uri)
    for _ in range(2):
        saver.step(next(it))
    it.close()
    paths = saver.save(str(tmp_path / "ck"), step=2)
    assert len(paths) == src
    ids = np.arange(N + 1)
    want = [np.asarray(x) for x in saver.rows(ids)]
    taker, how, _ = _make("ffm", mesh=_mesh(dst), seed=8)
    taker.restore(str(tmp_path / "ck"))
    got = [np.asarray(x) for x in taker.rows(ids)]
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    if dst > 1:     # the deal's padding stands for no id and stays zero
        pad = np.asarray(taker.params.w).reshape(
            dst, -1, FIELDS * 4)[:, -1][(N + 1) % dst or dst:]
        assert not pad.any()
        # the books are the layout's own: kept where the deal is the same
        assert (taker.shard_slots() == saver.shard_slots()) == (src == dst)
    it = _feed(taker, how, uri)       # and the restored learner trains on
    assert np.isfinite(float(taker.step(next(it))))
    it.close()


@pytest.mark.parametrize("src,dst", [(4, 1), (1, 4), (4, 2), (2, 4)])
def test_an_fm_crosses_the_layouts(tmp_path, src, dst):
    """(PR 54) ``FMLearner(mesh=)`` lays its tables and moments by rows in
    id order, a contiguous range a chip: saved under one layout, restored
    under another, rows by id; the layout's padding rows are neither
    written as ids nor required on restore."""
    saver, how, fmt = _make("fm_ell", mesh=_mesh(src))
    uri = _corpus(tmp_path / "c.txt", fmt)
    it = _feed(saver, how, uri)
    for _ in range(2):
        saver.step(next(it))
    it.close()
    paths = saver.save(str(tmp_path / "ck"), step=2)
    assert len(paths) == src
    rows = N + 1
    if src > 1:         # a file holds its chip's range of ids, and no more
        local = saver.deal.local_rows
        for chip, path in enumerate(sorted(paths)):
            t = ck.CheckpointReader(path).header["tables"]["params.v"]
            assert (t["first_id"], t["id_stride"], t["global_rows"]) == (
                chip * local, 1, rows)
            assert t["shape"][0] == min(local, rows - chip * local)
    taker, how, _ = _make("fm_ell", mesh=_mesh(dst), seed=8)
    assert not np.array_equal(np.asarray(taker.params.v)[:rows],
                              np.asarray(saver.params.v)[:rows])
    taker.restore(str(tmp_path / "ck"))
    want, got = _leaves(saver), _leaves(taker)
    books = {f"opt_state.{len(x.opt_state) - 1}"
             for x in (saver, taker) if x.deal is not None}
    assert set(want) - books == set(got) - books
    for name in set(want) - books:
        a, b = want[name], got[name]
        if a.ndim:
            assert not a[rows:].any() and not b[rows:].any(), name
            a, b = a[:rows], b[:rows]
        assert np.array_equal(a, b), name
    it = _feed(taker, how, uri)       # and the restored learner trains on
    assert np.isfinite(float(taker.step(next(it))))
    it.close()


# ---------------- the container ----------------

def _golden_writer(path):
    header = {"format": 1, "learner": {"class": "golden"}, "step": 7,
              "iterator": {"kind": "batches", "batches": 7}, "deal": None,
              "shard": 0, "shards": 1, "chunk_bytes": 64,
              "tables": {
                  "w": {"dtype": "float32", "shape": [5, 3], "first_id": 0,
                        "id_stride": 1, "global_rows": 5},
                  "count": {"dtype": "int32", "shape": [], "first_id": 0,
                            "id_stride": 1, "global_rows": None}}}
    w = ck.CheckpointWriter(str(path), header)
    table = np.arange(15, dtype=np.float32).reshape(5, 3) / 4
    w.add_chunk("w", 0, table[:4])
    w.add_chunk("w", 4, table[4:])
    w.add_chunk("count", 0, np.asarray(7, np.int32))
    w.finish()
    return table


def test_the_containers_layout_is_pinned_by_its_golden_file(tmp_path):
    """v1 is frozen: a writer that lays a byte elsewhere fails here, and
    the file written then must still be read."""
    golden = os.path.join(ROOT, "tests", "data", "checkpoint_v1.golden")
    rebuilt = tmp_path / ck.checkpoint_name(7)
    table = _golden_writer(rebuilt)
    with open(golden, "rb") as f, open(rebuilt, "rb") as g:
        assert f.read() == g.read()
    with ck.CheckpointReader(golden) as r:
        assert r.header["step"] == 7 and len(r.chunks) == 3
        assert np.array_equal(np.concatenate(
            [r.read_chunk(k) for k in r.chunks_of("w")]), table)
        assert r.read_chunk(r.chunks_of("count")[0]) == 7
    assert open(golden, "rb").read(8) == ck.CHECKPOINT_MAGIC == b"DMLCCK01"
    plain = PlainCheckpoint([golden])
    assert np.array_equal(plain.rows("w", [4, 0]), table[[4, 0]])


def test_the_plain_reader_reads_what_the_program_wrote(tmp_path):
    saver, _, _ = _make("ffm", mesh=_mesh(4))
    paths = saver.save(str(tmp_path / "ck"), step=0)
    plain = PlainCheckpoint(paths)
    assert plain.header["step"] == 0
    assert plain.header["deal"]["shards"] == 4
    assert plain.header["deal"]["place"] == {"chip": "id % shards",
                                            "row": "id // shards"}
    ids = np.asarray([0, 1, 2, 3, 17, N - 1, N])
    w, g = (np.asarray(x) for x in saver.rows(ids))
    assert np.array_equal(plain.rows("params.w", ids), w)
    assert np.array_equal(plain.rows("opt_state.0.sum_of_squares.w", ids), g)
    every = np.asarray(saver.rows(np.arange(N + 1))[0])
    assert plain.bit_sums()["params.w"] == int(
        every.view(np.uint32).sum(dtype=np.uint64)) % (1 << 32)
    # the journal's publish records, read without the store
    from cellbench.reference.ckpt_plain_read import published

    assert published(str(tmp_path / "ck")) == {
        os.path.basename(p) for p in paths}
    saver.save(str(tmp_path / "ck"), step=1, keep_last=1)
    assert published(str(tmp_path / "ck")) == {
        ck.checkpoint_name(1, c, 4) for c in range(4)}
    assert published(str(tmp_path / "nothing")) == set()


@pytest.mark.parametrize("what", ["crc", "truncated", "index"])
def test_a_damaged_file_is_refused_by_name(tmp_path, what):
    learner, _, _ = _make("ffm")
    (path,) = learner.save(str(tmp_path / "ck"), step=0)
    raw = bytearray(open(path, "rb").read())
    with ck.CheckpointReader(path) as r:
        third = r.chunks[3]
    if what == "crc":
        raw[third["offset"] + 5] ^= 0x40
        match = r"crc mismatch in chunk 3 \(table \S+, rows 384\.\.512\)"
    elif what == "truncated":
        raw = raw[:len(raw) // 2]
        match = "truncated"
    else:
        raw[-30] ^= 0x01
        match = "footer|crc|range"
    open(path, "wb").write(raw)
    other, _, _ = _make("ffm", seed=2)
    with pytest.raises(DMLCError, match=match):
        other.restore(str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="chunk 3|truncated|index|past"):
        PlainCheckpoint([path])


# ---------------- the tier ----------------

def test_keep_last_bounds_the_tier_by_count(tmp_path):
    learner, _, _ = _make("ffm")
    root = str(tmp_path / "ck")
    assert learner.latest(root) is None
    for step in (1, 2, 3):
        learner.save(root, step=step, keep_last=2)
    names = sorted(n for n in os.listdir(root) if n.endswith(".dmlcck"))
    assert names == [ck.checkpoint_name(2), ck.checkpoint_name(3)]
    assert learner.latest(root)["step"] == 3
    learner.save(root, step=4, keep_last=1)
    assert sorted(n for n in os.listdir(root) if n.endswith(".dmlcck")) \
        == [ck.checkpoint_name(4)]
    live = [e for e in store_for(os.path.join(root, "x")).entries()]
    assert [e["path"] for e in live] == [ck.checkpoint_name(4)]
    assert live[0]["tier"] == "checkpoint"


def test_a_byte_budget_evicts_caches_and_never_model_state(
        tmp_path, monkeypatch):
    learner, _, _ = _make("ffm")
    root = str(tmp_path / "tier")
    (path,) = learner.save(root, step=1)
    store = store_for(path)
    monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "100")
    for name in ("a.bc", "b.bc"):
        tmp = store.stage_path(os.path.join(root, name))
        with open(tmp, "wb") as f:
            f.write(b"DMLCBC01" + b"x" * 200)
        store.publish_file(tmp, os.path.join(root, name), "block_cache")
    state = {e["path"]: e for e in store.entries()}
    assert state["a.bc"]["evicted"] and not os.path.exists(
        os.path.join(root, "a.bc"))
    assert not state[os.path.basename(path)]["evicted"]
    assert os.path.getsize(path) > 100
    other, _, _ = _make("ffm", seed=6)
    other.restore(root)
    _same_state(learner, other)
    with pytest.raises(DMLCError, match="retain"):
        store.retain("block_cache", 1)


@pytest.mark.parametrize("point", ["ckpt_write@3", "ckpt_publish@1",
                                   "ckpt_sync@1"])
def test_a_writer_killed_half_way_leaves_the_previous_newest(tmp_path,
                                                             point):
    """The process dies at the seam (``=kill``: no clean-up): the earlier
    checkpoint stays the newest, the dead writer's file is an orphan that
    the next open of the store collects."""
    root = str(tmp_path / "ck")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from dmlc_tpu.io import faults\n"
        "from dmlc_tpu.models import FFMLearner\n"
        "from dmlc_tpu.models import _checkpoint\n"
        "_checkpoint.CHUNK_BYTES = %d\n"
        "l = FFMLearner(num_col=%d, num_fields=%d, seed=1)\n"
        "l.save(%r, step=1)\n"
        "with faults.inject(%r):\n"
        "    l.save(%r, step=2)\n"
        % (ROOT, SMALL, N, FIELDS, root, point + "=kill", root))
    done = subprocess.run([sys.executable, "-c", code], timeout=240,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 137
    orphans = [n for n in os.listdir(root) if n.endswith(".tmp")]
    assert len(orphans) == 1 and ck.checkpoint_name(2) in orphans[0]
    old = os.path.getmtime(os.path.join(root, orphans[0])) - 10_000
    os.utime(os.path.join(root, orphans[0]), (old, old))
    reset_stores()
    learner, _, _ = _make("ffm", seed=2)
    assert learner.latest(root)["step"] == 1     # opens the store: the gc
    assert not [n for n in os.listdir(root) if n.endswith(".tmp")]
    assert learner.restore(root)["step"] == 1
    want, _, _ = _make("ffm", seed=1)
    _same_state(want, learner)


def test_a_failed_save_raises_from_wait_and_publishes_nothing(tmp_path):
    learner, _, _ = _make("ffm")
    root = str(tmp_path / "ck")
    failed = telemetry.checkpoint_counters()["ckpt_saves_total"].get(
        "failed", 0)
    with faults.inject("ckpt_write@2=reset"):
        handle = learner.save_async(root, step=1)
        with pytest.raises(ConnectionResetError):
            handle.wait()
    assert learner.latest(root) is None
    assert not [n for n in os.listdir(root) if not n.startswith(".")]
    assert telemetry.checkpoint_counters()["ckpt_saves_total"][
        "failed"] == failed + 1


# ---------------- the launcher's contract ----------------

def test_a_worker_under_dmlc_submit_dies_and_resumes(tmp_path):
    """``--local-num-attempt 2``: the worker dies after step 11, comes up
    with ``DMLC_NUM_ATTEMPT=1``, resumes model and iterator from
    ``latest()`` and ends with the tables of the run that never died."""
    worker = os.path.join(ROOT, "examples", "train_ffm_resume.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DMLC_NUM_ATTEMPT", None)
    subprocess.run([sys.executable, worker, "--work", str(tmp_path / "ref")],
                   check=True, env=env, timeout=240)
    subprocess.run(
        [sys.executable, "-m", "dmlc_tpu.tracker.submit", "--cluster",
         "local", "--num-workers", "1", "--local-num-attempt", "2",
         "--host-ip", "127.0.0.1", "--", sys.executable, worker, "--work",
         str(tmp_path / "job"), "--die-at", "11"],
        check=True, env=env, cwd=ROOT, timeout=240)
    ref = json.load(open(tmp_path / "ref" / "result.json"))
    job = json.load(open(tmp_path / "job" / "result.json"))
    assert job["attempt"] == 1 and ref["attempt"] == 0
    assert job["steps"] == ref["steps"] == 25
    assert len(job["losses"]) == 25 - 8      # resumed after the save at 8
    assert job["losses"] == ref["losses"][8:]
    assert job["digest"] == ref["digest"]
