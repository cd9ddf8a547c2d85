"""The tiny mirror of ``kdd12_ffm_ps4_text`` (PR 32): the cell run through
the whole harness on four virtual CPU devices (``tiny_ffm_ps4.json``; the
mirror entries are made in memory, ``rehearsal.json`` is the benchmark's
own), four timed paths broken underneath that must each read ``correct``
false, and the blockwise start against ``ffm_adagrad.initial_rows``."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import run as R
from cellbench.learners import ffm_ps
from cellbench.reference import ffm_adagrad, ffm_start_blocks
from dmlc_tpu.ops import grad_scatter as gs
from dmlc_tpu.parallel.mesh import RowDeal


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
    rc = R.main(["--workload", "tiny_ffm_ps4_text", "--seed", str(seed),
                 "--seconds", "0.5", "--trace", str(trace), "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out


def _not_ok(out):
    return "\n".join(ln for ln in out.splitlines() if ln.endswith("NOT OK"))


@pytest.mark.parametrize("seed", [11, 2_147_483_999])
def test_sound_run_is_correct(capsys, seed):
    line, out = _run(capsys, seed, trace=1)
    assert line["correct"] is True, _not_ok(out)
    assert line["failed"] == 0 and line["device"]["count"] == 4
    assert 1.0 <= line["metrics"]["table_shard_slot_skew"]["value"] < 1.6
    assert all(m["value"] is None for k, m in line["metrics"].items()
               if k.endswith(("_ms", "_roofline", "_s_per_mrow")))


def _on_chip(learner, chip):
    """Mask of the dealt array's rows that chip ``chip`` holds."""
    deal = learner.deal
    rows = jnp.arange(deal.padded_rows)[:, None]
    return (rows >= chip * deal.local_rows) & (rows < (chip + 1)
                                               * deal.local_rows)


def test_one_chips_update_dropped(capsys, monkeypatch):
    sound = ffm_ps.Adapter.step

    def step(self, batch):
        lr = self.learner
        w, g = jnp.copy(lr.params.w), jnp.copy(lr.accumulators)
        loss = sound(self, batch)
        keep = _on_chip(lr, 2)
        lr.params = lr.params._replace(w=jnp.where(keep, w, lr.params.w))
        rss = lr.opt_state[0]
        lr.opt_state = (rss._replace(sum_of_squares=type(lr.params)(
            w=jnp.where(keep, g, lr.accumulators))),) + lr.opt_state[1:]
        return loss

    monkeypatch.setattr(ffm_ps.Adapter, "step", step)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "update_norm_gap" in _not_ok(out)


def test_a_slot_sent_to_the_wrong_owner(capsys, monkeypatch):
    """Every fifth id is claimed by the chip after its owner: that chip
    reads, and later updates, another id's row."""
    def local_slots(self, ids):
        ids = jax.lax.all_gather(ids, self.axis, tiled=True)
        chip, row = self.place(ids)
        chip = jnp.where(ids % 5 == 0, (chip + 1) % self.shards, chip)
        return jnp.where(chip == jax.lax.axis_index(self.axis), row,
                         self.local_rows)

    monkeypatch.setattr(RowDeal, "local_slots", local_slots)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "loss_gap" in _not_ok(out)


def test_three_shards_gradients_lost(capsys, monkeypatch):
    """What a replicated table without its all-reduce would do: only the
    first chip's rows of the batch reach the update."""
    sound = gs.dense_table_grad

    def lossy(indices, cotangents, num_rows, **how):
        first = jax.lax.axis_index(how["deal"].axis) == 0
        return sound(indices, tuple(jnp.where(first, g, 0.0)
                                    for g in cotangents), num_rows, **how)

    monkeypatch.setattr(gs, "dense_table_grad", lossy)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "grad_norm_gap" in _not_ok(out)


def test_a_shard_in_bfloat16(capsys, monkeypatch):
    sound = ffm_ps.Adapter.step

    def step(self, batch):
        loss = sound(self, batch)
        lr = self.learner
        w = lr.params.w
        lr.params = lr.params._replace(w=jnp.where(
            _on_chip(lr, 1), w.astype(jnp.bfloat16).astype(jnp.float32), w))
        return loss

    monkeypatch.setattr(ffm_ps.Adapter, "step", step)
    line, out = _run(capsys)
    assert line["correct"] is False, out


@pytest.mark.parametrize("control", ["bfloat16", "zero_fields"])
def test_each_control_fails_a_limit(tmp_path, control):
    from cellbench.generators import fields_zipf_libfm as gen

    config = R.load_json(R.HERE, "configs", "tiny_ffm_ps4.json")
    corpus = str(tmp_path / "c.libfm")
    gen.generate(config["generator"], 5, 3 * config["batch_size"], corpus)
    ref = ffm_ps.reference_digest(config, 5, corpus)
    numbers = ffm_ps.control_numbers(config, 5, corpus, ref)
    prefix = "" if control == "bfloat16" else "zero_fields."
    assert any(numbers[prefix + k] > lim
               for k, lim in config["limits"].items()), numbers
    assert ffm_adagrad.initial_rows is not ffm_start_blocks.initial_rows


def test_the_blockwise_start_is_initial_rows(tmp_path):
    rows = 5001
    lists = [np.random.default_rng(1).integers(0, rows, 3000),
             np.array([0, rows - 1, rows - 2])]
    want = ffm_adagrad.initial_rows(7, rows, 11, 4, *lists)
    got = ffm_start_blocks.initial_rows(7, rows, 11, 4, *lists,
                                        block_ids=1024)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
