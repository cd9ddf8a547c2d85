"""The tiny mirror of ``kdd12_ffm_ckpt_bcache`` (PR 41): the cell run
through the whole harness on the CPU (``tiny_ffm_ckpt.json``; the mirror
entries are made in memory, ``rehearsal.json`` is the benchmark's own),
the control, and six broken paths underneath that must each read
``correct`` false: the tables written as bfloat16 and widened on restore,
a save that reads the live buffers and not a snapshot, a snapshot taken
a step late, a save whose last
chunk is never written, a restore that hands back the seed's start, and a
publish that skips the manifest. One run of a block-cache cell a test
process used to be the limit (ROADMAP D17); since PR 41 a directory made
again is a new store, and this file makes eight."""

import json
import os

import numpy as np
import pytest

from cellbench import run as R
from cellbench.learners import ffm_ckpt
from dmlc_tpu.io import checkpoint as ck
from dmlc_tpu.models import _checkpoint as mc
from dmlc_tpu.store import manager


@pytest.fixture(autouse=True)
def mirrored(monkeypatch):
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kdd12_", "tiny_"))
        return real(*parts)

    monkeypatch.setattr(R, "load_json", load_json)
    # the program cuts chunks of one size; at the toy's size that would
    # be one chunk a table
    monkeypatch.setattr(mc, "CHUNK_BYTES", 1 << 16)


def _run(capsys, seed=11, trace=0):
    rc = R.main(["--workload", "tiny_ffm_ckpt_bcache", "--seed", str(seed),
                 "--seconds", "1.5", "--trace", str(trace), "--rehearse"])
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
    for name in ffm_ckpt.CKPT_NUMBERS:
        assert f"compare {name}: 0 (limit <= 0) ok" in out
    assert "checkpoint: step 16 read back by the plain reader" in out
    assert "position {'batches': 8," in out     # the epoch's end
    if trace:
        names = set(line["metrics"])
        assert {"ckpt_stall_ms", "ckpt_drain_gb_per_s", "ckpt_publish_s",
                "ckpt_restore_s", "ckpt_setup_save_s"} <= names
        assert all(m["value"] is None for m in line["metrics"].values()
                   if m["unit"] in ("ms", "s", "GB/s"))
    # the store keeps the newest two: the start and the window's
    kept = sorted(n for n in os.listdir(
        os.path.join(R.CACHE, "ckpt", "tiny_ffm_ckpt")) if n.endswith(
        ck.CHECKPOINT_SUFFIX))
    assert kept == [ck.checkpoint_name(0), ck.checkpoint_name(16)]


def test_tables_written_as_bfloat16(capsys, monkeypatch):
    import jax.numpy as jnp

    sound = ck.CheckpointWriter.add_chunk

    def add_chunk(self, table, row0, rows):
        if rows.dtype == np.float32 and rows.ndim == 2:
            rows = np.asarray(jnp.asarray(rows).astype(jnp.bfloat16),
                              np.float32)     # widened on the way back
        return sound(self, table, row0, rows)

    monkeypatch.setattr(ck.CheckpointWriter, "add_chunk", add_chunk)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "untouched_gap" in bad and "ckpt_rows_gap" in bad \
        and "ckpt_sum_gap" in bad


def test_a_save_that_reads_the_live_buffers(capsys, monkeypatch):
    """No snapshot: the drain reads whatever the learner holds when it
    gets there, which the steps dispatched behind the save have moved or
    taken away (donated buffers: a mixture of two steps, or none)."""
    import time

    sound = mc._drain

    def drain(handle, spec, plan, source, uri, *rest):
        if handle.step:
            adapter = ffm_ckpt._RUN["adapter"]
            while adapter.steps < handle.step + 3:
                time.sleep(0.01)    # steps go on behind the save
            learner = adapter.learner
            _, leaves, _ = mc.named_leaves(learner._checkpoint_spec().tree)
            source = mc._LiveChunks(plan, leaves)
        return sound(handle, spec, plan, source, uri, *rest)

    monkeypatch.setattr(mc, "_drain", drain)
    line, out = _run(capsys)
    assert line["correct"] is False
    # the buffers the drain meets were donated to a later step (the save
    # fails: nothing published) or hold that step's numbers (the sums)
    assert "Array has been deleted" in out or "ckpt_sum_gap" in _not_ok(out)
    assert "ckpt_" in _not_ok(out)


def test_a_snapshot_taken_a_step_late(capsys, monkeypatch):
    """The copy is whole and its file sound, but it holds the state after
    one step more than the header says: only a probe that does not read
    the save's own copy can tell."""
    from dmlc_tpu.models import FFMLearner

    sound_step, sound_save = FFMLearner.step, mc.begin_save

    def step(self, batch):
        self._last_batch = batch
        return sound_step(self, batch)

    def begin_save(learner, uri, step, *rest, **kw):
        if step:
            sound_step(learner, learner._last_batch)
        return sound_save(learner, uri, step, *rest, **kw)

    monkeypatch.setattr(FFMLearner, "step", step)
    monkeypatch.setattr(mc, "begin_save", begin_save)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "ckpt_rows_gap" in bad and "ckpt_sum_gap" in bad
    assert "ckpt_step_gap" not in bad and "ckpt_unpublished" not in bad


def test_a_save_whose_last_chunk_is_never_written(capsys, monkeypatch):
    sound = ck.CheckpointWriter.finish

    def finish(self):
        if self._chunks and '"step":16' in json.dumps(
                self._header_json, separators=(",", ":")):
            self._chunks.pop()
        return sound(self)

    init = ck.CheckpointWriter.__init__

    def keep_header(self, path, header):
        self._header_json = header
        init(self, path, header)

    monkeypatch.setattr(ck.CheckpointWriter, "__init__", keep_header)
    monkeypatch.setattr(ck.CheckpointWriter, "finish", finish)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "the plain reader refuses" in out and "rows missing" in out
    assert "ckpt_unpublished" in _not_ok(out)


def test_a_restore_that_hands_back_the_seeds_start(capsys, monkeypatch):
    monkeypatch.setattr(
        mc, "restore", lambda learner, uri, device_iter=None: {
            "step": 0, "iterator": None, "paths": [uri]})
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "untouched_gap" in _not_ok(out)


def test_a_publish_that_skips_the_manifest(capsys, monkeypatch):
    sound = manager.ArtifactStore._append_locked

    def append(self, event, sync=False):
        if event.get("op") == "publish" and event.get("tier") == "checkpoint":
            return None
        return sound(self, event, sync=sync)

    monkeypatch.setattr(manager.ArtifactStore, "_append_locked", append)
    monkeypatch.setattr(manager.ArtifactStore, "_adopt_strays_locked",
                        lambda self, state: None)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "ckpt_unpublished" in _not_ok(out)


def test_the_control_fails_the_checkpoints_limits_too(tmp_path):
    from cellbench.generators import fields_zipf_libfm as gen

    config = R.load_json(R.HERE, "configs", "tiny_ffm_ckpt.json")
    corpus = str(tmp_path / "c.libfm")
    gen.generate(config["generator"], 5, 3 * config["batch_size"], corpus)
    ref = ffm_ckpt.reference_digest(config, 5, corpus)
    numbers = ffm_ckpt.control_numbers(config, 5, corpus, ref)
    over = [k for k, limit in config["limits"].items() if numbers[k] > limit]
    assert "ckpt_rows_gap" in over and "ckpt_sum_gap" in over
    assert "untouched_gap" in over and len(over) >= 7
    assert numbers["ckpt_step_gap"] == numbers["ckpt_unpublished"] == 0.0


def test_the_configuration_is_kdd12_ffms_shapes(tmp_path):
    base = R.load_json(R.HERE, "configs", "kdd12_ffm.json")
    mine = R.load_json(R.HERE, "configs", "kdd12_ffm_ckpt.json")
    same = [k for k in base if k not in (
        "name", "source", "deployment", "learner", "assumed", "guarantees",
        "limits", "limit_readings")]
    assert all(mine[k] == base[k] for k in same)
    assert mine["learner"] == "ffm_ckpt" and len(mine["source"]) <= 200
    assert {k: v for k, v in mine["limits"].items()
            if not k.startswith("ckpt_")} == base["limits"]
    assert all(mine["limits"][k] == 0.0 for k in ffm_ckpt.CKPT_NUMBERS)
    assert mine["assumed"][:len(base["assumed"])] == base["assumed"]
    assert mine["guarantees"][:len(base["guarantees"])] == base["guarantees"]
