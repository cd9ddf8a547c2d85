"""The tiny mirror of ``kdd12_ffm_rand_bcache`` (PR 45): the cell run
through the whole harness on the CPU (``tiny_ffm_rand.json``; the mirror
entries are made in memory, ``rehearsal.json`` is the benchmark's own),
and seven broken paths underneath that must each read ``correct`` false:
no plan armed (file order), one order for every epoch, ``shuffle_window``
0 (blocks permuted, rows not), a block served twice and one dropped, the
plan's epoch counter one off, the plan's own books lost, bfloat16 tables.
``tests/test_plan_feed.py`` (tier-1) holds the sound runs' detail, the
reference against ``data/epoch.py`` and the controls."""

import json

import numpy as np
import pytest

import dmlc_tpu.data as program_data
from cellbench import run as R
from cellbench.learners import ffm_rand
from dmlc_tpu.data import epoch as program_epoch
from dmlc_tpu.data import parsers


@pytest.fixture(autouse=True)
def mirrored(monkeypatch):
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kdd12_", "tiny_"))
        return real(*parts)

    monkeypatch.setattr(R, "load_json", load_json)


def _run(capsys, seed=11, trace=0):
    rc = R.main(["--workload", "tiny_ffm_rand_bcache", "--seed", str(seed),
                 "--seconds", "1", "--trace", str(trace), "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out


def _not_ok(out):
    return "\n".join(ln for ln in out.splitlines() if ln.endswith("NOT OK"))


@pytest.mark.parametrize("seed,trace", [(11, 1), (2_147_483_999, 0)])
def test_sound_run_is_correct(capsys, seed, trace):
    line, out = _run(capsys, seed, trace)
    assert line["correct"] is True, _not_ok(out)
    assert line["failed"] == 0
    for name in ffm_rand.ORDER_NUMBERS:
        assert f"compare {name}: 0 (limit <= 0) ok" in out
    if trace:
        assert {"plan_permute_busy_s_per_mrow",
                "plan_wait_s_per_mrow"} <= set(line["metrics"])


def _with_parser_knobs(monkeypatch, **over):
    """``create_parser`` as the feed calls it, with some knobs replaced
    (``None`` takes a knob away)."""
    sound = program_data.create_parser

    def create_parser(uri, *args, **kw):
        kw.update(over)
        return sound(uri, *args, **{k: v for k, v in kw.items()
                                    if v is not None})

    monkeypatch.setattr(program_data, "create_parser", create_parser)


def test_no_plan_armed(capsys, monkeypatch):
    """The feed every other cell has: the cache served in file order."""
    _with_parser_knobs(monkeypatch, shuffle_seed=None, shuffle_window=None)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "order_gap" in bad and "order_repeat" in bad \
        and "epoch_order_gap" in bad and "reports no plan" in bad
    # the rows are all there, only their order is the file's
    assert "label sum" not in bad


def test_one_order_for_every_epoch(capsys, monkeypatch):
    """A shuffle made once: every epoch is served in the first one's."""
    sound = parsers.BlockCacheIter._ensure_plan

    def ensure_plan(self):
        if self._plan is None:
            sound(self)
            self._plan.epoch = 1
        return self._plan

    monkeypatch.setattr(parsers.BlockCacheIter, "_ensure_plan", ensure_plan)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "epoch_order_gap" in bad and "order_repeat" in bad
    assert "compare order_gap: 0 (limit <= 0) ok" in out    # the first is right


def test_blocks_permuted_rows_not(capsys, monkeypatch):
    _with_parser_knobs(monkeypatch, shuffle_window=0)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "order_gap" in bad and "epoch_order_gap" in bad
    assert "0 rows through the row permutation" in bad
    assert "label sum" not in bad


def test_a_block_served_twice_and_one_dropped(capsys, monkeypatch):
    sound = program_epoch.block_permutation

    def block_permutation(seed, epoch, num_blocks):
        order = sound(seed, epoch, num_blocks).copy()
        if num_blocks > 2:
            order[-1] = order[-2]
        return order

    monkeypatch.setattr(program_epoch, "block_permutation",
                        block_permutation)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "epoch_order_gap" in bad and "label sum" in bad
    # the first three batches come from the epoch's first block: sound
    assert "compare order_gap: 0 (limit <= 0) ok" in out


def test_the_plans_epoch_counter_one_off(capsys, monkeypatch):
    """Every epoch is served in the order of the one after it."""
    sound = parsers.BlockCacheIter._ensure_plan

    def ensure_plan(self):
        if self._plan is None:
            sound(self)
            self._plan.epoch += 1
        return self._plan

    monkeypatch.setattr(parsers.BlockCacheIter, "_ensure_plan", ensure_plan)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "order_gap" in bad and "epoch_order_gap" in bad
    assert "compare order_repeat: 0 (limit <= 0) ok" in out


def test_the_plans_books_lost(capsys, monkeypatch):
    """A program that serves in plan order and says nothing of it: the
    feed cannot tell that the tier it names served."""
    monkeypatch.setattr(parsers.BlockCacheIter, "plan_stats",
                        lambda self: None)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "reports no plan" in _not_ok(out)


def test_tables_in_bfloat16(capsys, monkeypatch):
    import jax.numpy as jnp

    sound = ffm_rand.Adapter.step

    def step(self, batch):
        loss = sound(self, batch)
        lr = self.learner
        lr.params = lr.params._replace(
            w=lr.params.w.astype(jnp.bfloat16).astype(jnp.float32))
        return loss

    monkeypatch.setattr(ffm_rand.Adapter, "step", step)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "untouched_gap" in bad
    for name in ffm_rand.ORDER_NUMBERS:          # the order is sound
        assert f"compare {name}: 0 (limit <= 0) ok" in out


def test_the_order_sum_tells_two_rows_swapped():
    """What ``epoch_order_gap`` can see: the four checksums cannot."""
    from cellbench.reference import epoch_plan_plain as plain

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 5000, (100, 16))
    fields = np.tile(np.arange(16), (100, 1))
    labels = rng.integers(0, 2, 100)
    hashes = plain.row_hashes(ids, fields, labels)
    order = np.arange(100)
    order[[3, 60]] = 60, 3
    assert plain.order_sum(hashes) != plain.order_sum(hashes[order])
    assert sorted(hashes) == sorted(hashes[order])
