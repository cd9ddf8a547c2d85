"""The epoch plan on the path a chip is fed by (PR 45, configuration
``kdd12_ffm_rand``): the plain reference of the order
(``cellbench/reference/epoch_plan_plain.py``) against ``data/epoch.py``
and against the parser's own blocks; the plan under
``DeviceIter(fields=True)`` (every epoch the file's rows, no two epochs
alike, two processes agree, the epoch's head start starts the next plan);
its books (``stats()["plan"]``, the ``plan_permute`` and ``plan_wait``
spans); a mid-epoch ``learner.save(device_iter=)`` in a planned epoch; the
two cells this PR adds, tiny, through the whole harness; and the controls
of the comparison. The broken paths are ``cellbench/tests/test_plan_cell.py``'s.
"""

import ast
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cellbench.learners import ffm_rand
from cellbench.reference import epoch_plan_plain as plain
from dmlc_tpu.data import create_parser
from dmlc_tpu.data import epoch as program
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.models import FFMLearner
from dmlc_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, FIELDS, K, B = 5000, 11, 16, 512
ROWS = 32_000          # 3.2 MB of libfm text: four blocks at 1 MiB a chunk


# ---------------- the plain reference against the program ----------------

@pytest.mark.parametrize("seed", [0, 7, 2_147_528_011, 2 ** 40 + 3])
@pytest.mark.parametrize("epoch", [0, 1, 2, 77])
@pytest.mark.parametrize("blocks", [1, 2, 9, 574])
def test_block_order_is_the_programs(seed, epoch, blocks):
    assert np.array_equal(plain.block_order(seed, epoch, blocks),
                          program.block_permutation(seed, epoch, blocks))


@pytest.mark.parametrize("seed", [0, 2_147_528_011])
@pytest.mark.parametrize("epoch", [1, 5])
@pytest.mark.parametrize("rows,window", [(1, 8), (7313, 0), (7313, 1),
                                         (7313, 100), (7313, 7313),
                                         (7313, 16384), (450, 449)])
def test_row_order_is_the_programs(seed, epoch, rows, window):
    for block in (0, 3, 573):
        want = program.row_permutation(seed, epoch, block, rows, window)
        got = plain.row_order(seed, epoch, block, rows, window)
        assert np.array_equal(
            got, np.arange(rows) if want is None else want)


@pytest.mark.parametrize("window", [0, 64, 16384])
@pytest.mark.parametrize("seed,epoch", [(3, 1), (2_147_528_011, 4)])
def test_epoch_rows_is_the_plan_served_block_by_block(seed, epoch, window):
    block_rows = [700, 1, 450, 450, 449, 1024]
    first = np.concatenate([[0], np.cumsum(block_rows)])
    plan = program.EpochPlan(seed, epoch, len(block_rows), window=window)
    want = []
    for pos in range(len(plan)):
        b = plan.block_at(pos)
        order = plan.row_order(b, block_rows[b])
        want.append(first[b] + (np.arange(block_rows[b]) if order is None
                                else order))
    want = np.concatenate(want)
    got = plain.epoch_rows(seed, epoch, block_rows, window)
    assert np.array_equal(got, want)
    assert sorted(got) == list(range(sum(block_rows)))
    assert np.array_equal(
        plain.epoch_rows(seed, epoch, block_rows, window, limit=800),
        want[:800])


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "cellbench", "reference",
                           "epoch_plan_plain.py")) as f:
        tree = ast.parse(f.read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            roots.add((node.module or "").split(".")[0])
    assert roots <= {"__future__", "json", "struct", "zlib", "numpy"}, roots


# ---------------- a corpus, its cache, its rows ----------------

def _write_corpus(path, rows=ROWS, seed=0, ragged=False):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, N, (rows, FIELDS))
    with open(path, "w") as f:
        for r in range(rows):
            n = int(rng.integers(1, FIELDS + 1)) if ragged else FIELDS
            f.write(f"{r % 2} " + " ".join(
                f"{k}:{ids[r, k]}:1" for k in range(n)) + "\n")
    return str(path)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    return _write_corpus(tmp_path_factory.mktemp("plan") / "c.libfm")


def _planned(corpus, cache, seed, window=16384):
    return create_parser(corpus + "?format=libfm", block_cache=cache,
                         shuffle_seed=seed, shuffle_window=window)


def _feed(corpus, cache, seed, window=16384, **kw):
    return DeviceIter(_planned(corpus, cache, seed, window), num_col=N,
                      batch_size=B, layout="ell", max_nnz=K, fields=True,
                      **kw)


def _epoch(it, claimed=None):
    """One epoch's rows as the plain reference holds them, and a reset.
    ``claimed`` takes the plan's epoch as the program gives it while the
    epoch's first batch is out (before the epoch there is no producer yet,
    after it the head start may have begun the next)."""
    batches = []
    for b in it:
        if claimed is not None and not batches:
            claimed.append(it.stats()["plan"]["epoch"])
        batches.append(b)
    it.reset()
    return ffm_rand._host_rows(batches)


def _text(path):
    with open(path, "rb") as f:
        return f.read()


def _digest(rows):
    return hashlib.sha256(b"".join(
        np.ascontiguousarray(x).tobytes() for x in rows)).hexdigest()


def test_the_text_cut_is_the_parsers_blocks(corpus, tmp_path):
    cache = str(tmp_path / "c.blockcache")
    parser = _planned(corpus, cache, 5)
    served = []
    while (block := parser.next_block()) is not None:
        served.append(len(block))
    parser.before_first()            # the cold pass published
    block_rows, starts = plain.cut_text(_text(corpus))
    assert len(block_rows) == 4 and list(block_rows) == served
    assert np.array_equal(plain.cache_block_rows(cache), block_rows)
    assert len(starts) == ROWS + 1 and starts[-1] == os.path.getsize(corpus)
    parser.close()


@pytest.mark.parametrize("ragged", [False, True])
def test_parse_libfm_is_the_parsers_rows(tmp_path, ragged):
    path = _write_corpus(tmp_path / "r.libfm", rows=300, seed=3,
                         ragged=ragged)
    data = _text(path)
    ids, fields, labels = plain.parse_libfm(data, K)
    parser = create_parser(path + "?format=libfm")
    block = parser.next_block()
    parser.close()
    assert len(block) == 300 and np.array_equal(labels, block.label)
    for r in (0, 1, 150, 299):
        s, e = block.offset[r], block.offset[r + 1]
        assert list(ids[r, :e - s]) == list(block.index[s:e])
        assert list(fields[r, :e - s]) == list(block.field[s:e])
        assert (ids[r, e - s:] == -1).all() and (fields[r, e - s:] == -1).all()
    _, starts = plain.cut_text(data)
    assert plain.read_rows(data, starts, [2, 0]) == \
        data[starts[2]:starts[3]] + data[:starts[1]]


# ---------------- the plan under DeviceIter(fields=True) ----------------

def test_every_epoch_is_the_files_rows_in_the_plans_order(corpus, tmp_path):
    seed = 2_147_528_011
    it = _feed(corpus, str(tmp_path / "c.blockcache"), seed)
    in_file = plain.parse_libfm(_text(corpus), K)
    block_rows, _ = plain.cut_text(_text(corpus))
    assert _digest(_epoch(it)) == _digest(in_file)     # epoch 0: cold
    seen = []
    claimed = []
    for epoch in (1, 2, 3):
        got = _epoch(it, claimed)
        order = plain.epoch_rows(seed, epoch, block_rows, 16384)
        assert _digest(got) == _digest(tuple(x[order] for x in in_file))
        assert sorted(plain.row_hashes(*got)) == sorted(
            plain.row_hashes(*in_file))
        seen.append(_digest(got))
    assert len(set(seen)) == 3 and _digest(in_file) not in seen
    assert claimed == [1, 2, 3]
    # the device's fold of an epoch against the plain plan's sum
    assert plain.order_sum(plain.row_hashes(*got)) == plain.order_sum(
        plain.row_hashes(*in_file)[order])
    assert it.stats()["epochs_prestarted"] >= 2     # the head start ran
    it.close()


_CHILD = """
import sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
import test_plan_feed as t
it = t._feed({corpus!r}, {cache!r}, {seed})
print("digest", t._digest(t._epoch(it)), t._digest(t._epoch(it)))
it.close()
"""


def test_two_processes_agree_on_every_epoch(corpus, tmp_path):
    """Another process, over a cache of its own, serves epoch for epoch
    what this one serves: the order is a function of (seed, epoch)."""
    seed = 77
    it = _feed(corpus, str(tmp_path / "mine.blockcache"), seed)
    _epoch(it)                                         # the cold pass
    mine = [_digest(_epoch(it)), _digest(_epoch(it))]
    it.close()
    # the child runs its cold pass too: epochs 0 and 1
    it = _feed(corpus, str(tmp_path / "mine.blockcache"), seed)
    again = _digest(_epoch(it))      # a cache that is there: planned epoch 0
    it.close()
    child = subprocess.run(
        [sys.executable, "-c", _CHILD.format(
            root=ROOT, tests=os.path.join(ROOT, "tests"), corpus=corpus,
            cache=str(tmp_path / "theirs.blockcache"), seed=seed)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert child.returncode == 0, child.stderr[-2000:]
    cold, first = [ln for ln in child.stdout.splitlines()
                   if ln.startswith("digest")][-1].split()[1:]
    assert first == mine[0] and cold != first and again not in mine


def test_the_plans_books_and_spans(corpus, tmp_path):
    it = _feed(corpus, str(tmp_path / "c.blockcache"), 9)
    assert it.stats()["plan"]["order"] == "sequential"
    _epoch(it)
    before = it.stats()["plan"]
    assert set(before) == {"blocks", "rows_permuted", "permute_seconds",
                           "wait_seconds", "uniform_blocks", "epoch",
                           "order"}
    assert (before["blocks"], before["rows_permuted"]) == (0, 0)
    t0 = it.stats()["now"]
    _epoch(it)
    after = it.stats()["plan"]
    assert after["order"] == "plan" and after["uniform_blocks"] == 0
    assert after["blocks"] - before["blocks"] >= 4
    assert after["rows_permuted"] - before["rows_permuted"] >= ROWS
    assert after["permute_seconds"] > before["permute_seconds"] >= 0.0
    assert after["wait_seconds"] > before["wait_seconds"] >= 0.0
    spans = [s for s in telemetry.spans_snapshot(it.pipeline_label)
             if s["start_ns"] >= t0 * 1e9]
    permutes = [s for s in spans if s["name"] == "plan_permute"]
    waits = [s for s in spans if s["name"] == "plan_wait"]
    assert len(permutes) >= 4 and len(waits) >= 4
    assert {s["labels"]["epoch"] for s in permutes} >= {1}
    assert sum(s["labels"]["rows"] for s in permutes
               if s["labels"]["epoch"] == 1) == ROWS
    # a permute runs inside a cache_read span of its worker
    reads = [s for s in spans if s["name"] == "cache_read"]
    for p in permutes:
        assert any(r["tid"] == p["tid"] and r["start_ns"] <= p["start_ns"]
                   and p["start_ns"] + p["dur_ns"]
                   <= r["start_ns"] + r["dur_ns"] for r in reads)
    it.close()
    plain_feed = DeviceIter(create_parser(corpus + "?format=libfm"),
                            num_col=N, batch_size=B, layout="ell",
                            max_nnz=K, fields=True)
    assert plain_feed.stats()["plan"] is None
    plain_feed.close()


# ---------------- a save in a planned epoch ----------------

def _learner(seed=1):
    return FFMLearner(num_col=N, num_fields=FIELDS, seed=seed)


def _leaves(learner):
    from dmlc_tpu.models import _checkpoint as mc

    names, leaves, _ = mc.named_leaves(learner._checkpoint_spec().tree)
    return {n: np.asarray(x) for n, x in zip(names, leaves)}


def test_a_save_in_a_planned_epoch_restores_to_the_same_continuation(
        corpus, tmp_path):
    """``learner.save(device_iter=)`` mid-way through a planned epoch
    records the plan's position ``(seed, epoch, pos)``; a fresh learner
    and a fresh pipeline restored from it step through byte for byte what
    the run that never stopped steps through."""
    seed, cache = 41, str(tmp_path / "c.blockcache")
    it = _feed(corpus, cache, seed)
    _epoch(it)                                          # cold: epoch 0
    _epoch(it)                                          # epoch 1
    learner = _learner()
    taken = 7     # 3,584 rows: inside the first block of epoch 2, mid-way
    for _ in range(taken):
        learner.step(next(it))
    learner.save(str(tmp_path / "ck"), step=taken, device_iter=it)
    rest, losses = [], []
    for b in it:
        rest.append(_digest((np.asarray(b.indices), np.asarray(b.fields),
                             np.asarray(b.values), np.asarray(b.label))))
        losses.append(float(learner.step(b)))
    it.close()

    other = _learner(seed=9)
    fresh = _feed(corpus, cache, seed + 1, window=0)   # other knobs: the
    back = other.restore(str(tmp_path / "ck"), device_iter=fresh)  # state's win
    state = back["iterator"]
    assert back["step"] == taken and state["batches"] == taken
    assert (state["source"]["kind"], state["source"]["seed"],
            state["source"]["epoch"]) == ("epoch_plan", seed, 2)
    got, got_losses = [], []
    for b in fresh:
        got.append(_digest((np.asarray(b.indices), np.asarray(b.fields),
                            np.asarray(b.values), np.asarray(b.label))))
        got_losses.append(float(other.step(b)))
    assert got == rest and got_losses == losses
    a, b = _leaves(learner), _leaves(other)
    assert a.keys() == b.keys() and all(
        np.array_equal(a[k], b[k]) for k in a)
    # and the epoch after the restored one is the next plan's
    fresh.reset()
    block_rows, _ = plain.cut_text(_text(corpus))
    in_file = plain.parse_libfm(_text(corpus), K)
    order = plain.epoch_rows(seed, 3, block_rows, 16384)
    claimed = []
    assert _digest(_epoch(fresh, claimed)) == _digest(
        tuple(x[order] for x in in_file))
    assert claimed == [3]
    fresh.close()


# ---------------- the cells, tiny, through the harness ----------------

def _mirrored(R):
    """``BENCHMARK.json`` with ``kdd12_`` read as ``tiny_`` and ``kddb_fm``
    as ``tiny_kddb_fm``, in memory: ``rehearsal.json`` is the benchmark's
    own file and is left as it is. The ragged configuration's text cell is
    put in beside them: on the chip its ``rows_per_s`` spread 0.86% over
    six seeds where a new cell may spread 0.5%, so ``BENCHMARK.json`` does
    not have it (PERF.md section 6, PR 45), and the path stays rehearsed
    for the issue that takes it up."""
    real = R.load_json

    def load_json(*parts):
        if parts[-1] != "rehearsal.json":
            return real(*parts)
        bench = real(R.ROOT, "BENCHMARK.json")
        bench["workloads"].append({
            "name": "kddb_fm_text", "config": "kddb_fm",
            "traffic": "text_epochs", "chips": 1, "why": "not a cell"})
        for m in bench["per_layer"]:
            if m["name"] == "parse_busy_s_per_mrow" or (
                    "kddb_fm_bcache" in m["workloads"]
                    and m["name"] != "cache_read_busy_s_per_mrow"):
                m["workloads"].append("kddb_fm_text")
        return json.loads(json.dumps(bench).replace("kdd12_", "tiny_")
                          .replace("kddb_fm", "tiny_kddb_fm"))

    return load_json


def _rehearse(monkeypatch, capsys, cell, seed, trace):
    from cellbench import run as R
    from cellbench.readers import _program as P

    monkeypatch.setattr(R, "load_json", _mirrored(R))
    P._cache.clear()
    # three seconds, not one (PR 48): on a loaded host a one-second window
    # closed inside its first epoch, whose batches the producer had read
    # before the window opened, and the feed's `served` then found "no
    # cache_read work in the window" (the [5-0] / [2147528011-1] failures
    # of whole runs under -n 6)
    assert R.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "3", "--trace", str(trace), "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True, [ln for ln in out.splitlines()
                                     if ln.endswith("NOT OK")]
    assert line["failed"] == 0 and line["rehearsal"] is True
    assert "compilations inside the window: 0 (limit == 0) ok" in out
    return line, out


@pytest.mark.parametrize("seed,trace", [(2_147_528_011, 1), (5, 0)])
def test_the_plan_cell_rehearses_correct_on_the_cpu(monkeypatch, capsys,
                                                    seed, trace):
    line, out = _rehearse(monkeypatch, capsys, "tiny_ffm_rand_bcache", seed,
                          trace)
    for name in ("order_gap", "epoch_order_gap", "order_repeat"):
        assert f"compare {name}: 0 (limit <= 0) ok" in out
    assert "the published cache's index agrees with that cut" in out
    assert "tier 'block_cache_plan' served the window and the " \
        "verification epoch: yes" in out
    values = {k: v["value"] for k, v in line["metrics"].items()}
    if trace:
        # a CPU run reports what was counted, never a time
        assert {"plan_permute_busy_s_per_mrow", "plan_wait_s_per_mrow",
                "cache_read_busy_s_per_mrow"} <= set(values)
        assert values.pop("field_plane_bytes_per_row") == 16.0
        assert values.pop("put_bytes_per_row") == 152.0
    assert values and all(v is None for v in values.values()), values


def test_the_text_cell_of_the_ragged_fm_rehearses_correct_on_the_cpu(
        monkeypatch, capsys):
    line, out = _rehearse(monkeypatch, capsys, "tiny_kddb_fm_text",
                          2_147_528_012, 1)
    assert "tier 'text' served the window and the verification epoch: " \
        "yes" in out
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"parse_busy_s_per_mrow", "nnz_pad_share",
            "convert_busy_s_per_mrow"} <= set(values)
    assert "cache_read_busy_s_per_mrow" not in values


def test_the_new_entries_are_appended_and_lawful():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = bench["workloads"]
    # by name, not by position: later PRs append after these entries
    cell = {w["name"]: w for w in cells}["kdd12_ffm_rand_bcache"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kdd12_ffm_rand", "plan_block_cache_epochs",
        1) and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in cells) == 2 <= len(cells) // 4
    # measured and not added: its rows_per_s spread 0.86% over six seeds
    assert "kddb_fm_text" not in [w["name"] for w in cells]
    entry = {c["name"]: c for c in bench["configs"]}["kdd12_ffm_rand"]
    assert len(entry["source"]) <= 200
    assert entry["reduced"] == ["num_features", "rows"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "cellbench/configs/kdd12_ffm.json")) as f:
        base = json.load(f)
    # every shape is kdd12_ffm's: no width, no size, no cut differs
    same = set(base) - {"name", "source", "deployment", "learner",
                        "assumed", "guarantees", "limits", "limit_readings"}
    assert all(config[k] == base[k] for k in same)
    assert config["source"] == entry["source"]
    assert list(config["reduced"]) == entry["reduced"]
    assert config["assumed"][:len(base["assumed"])] == base["assumed"]
    assert config["plan"]["shuffle_window"] >= 7314
    limits = config["limits"]
    assert set(limits) == set(base["limits"]) | {
        "order_gap", "epoch_order_gap", "order_repeat"}
    assert all(limits[k] == 0.0 for k in (
        "order_gap", "epoch_order_gap", "order_repeat", "untouched_gap"))
    # every limit lies between its two readings, with room on both sides
    for name, read in config["limit_readings"].items():
        if name == "what" or not limits[name]:
            continue
        low = min(v for v in read["control_min"].values() if v is not None)
        assert 3 * read["sound_max"] < limits[name] < low / 3, name
    assert any("(seed, epoch)" in g for g in config["guarantees"])
    assert not any("file order" in g for g in config["guarantees"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("plan_permute_busy_s_per_mrow", "plan_wait_s_per_mrow"):
        m = by_name[name]       # the plan's two metrics list its cell alone
        assert m["workloads"] == ["kdd12_ffm_rand_bcache"]
        assert (m["layer"], m["moves"]) == ("block cache", "rows_per_s")
        assert os.path.exists(os.path.join(
            ROOT, "cellbench", "metrics", m["name"] + ".json"))
    for m in bench["per_layer"]:
        # the rand cell stands in every list kdd12_ffm_bcache stands in,
        # after it
        if "kdd12_ffm_bcache" in m["workloads"]:
            assert "kdd12_ffm_rand_bcache" in m["workloads"][
                m["workloads"].index("kdd12_ffm_bcache") + 1:], m["name"]


def test_the_plan_reader_gives_nothing_where_the_program_has_no_books():
    from types import SimpleNamespace

    from cellbench.readers import plan_seconds_per_mrow as reader

    spec = {"counter": "permute_seconds"}
    ctx = SimpleNamespace(rows_dispatched=2_000_000,
                          stats_start={"plan": {"permute_seconds": 1.0}},
                          stats_end={"plan": {"permute_seconds": 1.5}})
    assert reader.read(ctx, spec) == pytest.approx(0.25)
    for start, end in (({}, {}), ({"plan": None}, {"plan": None}),
                       (None, None)):                # a parent; no plan armed
        ctx.stats_start, ctx.stats_end = start, end
        assert reader.read(ctx, spec) is None


# ---------------- the controls ----------------

@pytest.mark.parametrize("seed", [2_147_528_011, 5])
def test_each_control_fails_its_limits_and_the_reference_passes(tmp_path,
                                                                seed):
    from cellbench import run as R
    from cellbench.generators import fields_zipf_libfm as gen
    config = R.load_json(R.HERE, "configs", "tiny_ffm_rand.json")
    corpus = str(tmp_path / "c.libfm")
    gen.generate(config["generator"], seed, config["rows"], corpus)
    ffm_rand._RUN.clear()
    ref = ffm_rand.reference_digest(config, seed, corpus)
    numbers = ffm_rand.control_numbers(config, seed, corpus, ref)
    limits = config["limits"]
    # bfloat16 fails the six's (one of them at least, not each); a feed
    # with no plan armed fails the order's three, each
    assert [k for k in limits if not k.startswith("order")
            and "order_" not in k and numbers[k] > limits[k]]
    assert all(numbers[k] > limits[k] for k in ffm_rand.ORDER_NUMBERS)
    assert numbers["order_gap"] >= 0.99 * 3 * config["batch_size"]
    # the reference in its own place passes, and with no adapter the
    # order's numbers cannot
    same = ffm_rand.compare(ref, ref["losses"], ref["grad_norms"],
                            ref["update_norms"], ref["touched"],
                            {"w": ref["untouched_w"],
                             "g": np.ones_like(ref["untouched_w"])})
    assert all(same[k] <= limits[k] for k in limits
               if k not in ffm_rand.ORDER_NUMBERS)
    assert all(same[k] == float("inf") for k in ffm_rand.ORDER_NUMBERS)
    # the head handed to the mathematics is the plan's first three batches
    data = _text(corpus)
    block_rows, starts = plain.cut_text(data)
    head = plain.epoch_rows(seed, 1, block_rows,
                            config["plan"]["shuffle_window"],
                            limit=3 * config["batch_size"])
    with open(corpus + ".plan_head", "rb") as f:
        assert f.read() == plain.read_rows(data, starts, head)
    assert not np.array_equal(head, np.arange(len(head)))
