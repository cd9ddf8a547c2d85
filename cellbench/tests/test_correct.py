"""``correct`` has to come out false when it should: for the control (the
plain reference in bfloat16, put in the program's place) and for a timed
path broken underneath. Each case skips the harness's look for a chip
(``--rehearse``) and drives the rest of a run at the tiny size."""

import json

import numpy as np
import pytest

from cellbench import run as R
from cellbench.generators import fields_zipf_libfm as gen
from cellbench.learners import fm


def _run(capsys, workload="tiny_fm_text", seed=11, seconds=0.5):
    rc = R.main(["--workload", workload, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out


def _not_ok(out):
    return [ln for ln in out.splitlines() if ln.endswith("NOT OK")]


@pytest.mark.parametrize("workload", ["tiny_fm_text", "tiny_fm_snap",
                                      "tiny_fm_dp4_bcache"])
def test_sound_run_is_correct(capsys, workload):
    line, out = _run(capsys, workload)
    assert line["correct"] is True, _not_ok(out)
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["rehearsal"] is True
    # a CPU run reports no number under a timing metric's name
    assert all(m["value"] is None for m in line["metrics"].values())


@pytest.mark.parametrize("seed", [2_147_483_999, 5, 77])
def test_control_in_bfloat16_is_not_correct(tmp_path, seed):
    config = R.load_json(R.HERE, "configs", "tiny_fm.json")
    corpus = str(tmp_path / "c.libfm")
    gen.generate(config["generator"], seed, 3 * config["batch_size"], corpus)
    ref = fm.reference_digest(config, seed, corpus)
    numbers = fm.control_numbers(config, seed, corpus, ref)
    over = {k: numbers[k] for k, lim in config["limits"].items()
            if numbers[k] > lim}
    assert over, numbers
    # and the reference against itself is inside every limit
    same = fm.compare(ref, ref["losses"], ref["grad_norms"],
                      ref["update_norms"], ref["touched"],
                      dict({k: np.zeros_like(v)
                            for k, v in ref["touched"].items()},
                           v=ref["untouched_v"]))
    assert all(same[k] <= lim for k, lim in config["limits"].items()), same


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    def step(self, batch):
        lr = self.learner   # the step donates its state: hand it copies
        copy = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)  # noqa: E731
        return lr._step(copy(lr.params), copy(lr.opt_state), batch)[2]

    monkeypatch.setattr(fm.Adapter, "step", step)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = "\n".join(_not_ok(out))
    assert "update_norm_gap" in bad


def test_part_of_the_batch_left_out(capsys, monkeypatch):
    sound = fm.Adapter.step

    def step(self, batch):
        half = batch.weight.shape[0] // 2
        return sound(self, batch._replace(
            weight=batch.weight.at[half:].set(0.0)))

    monkeypatch.setattr(fm.Adapter, "step", step)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = "\n".join(_not_ok(out))
    assert "loss_gap" in bad or "grad_norm_gap" in bad


def test_tables_in_bfloat16_inside_the_program(capsys, monkeypatch):
    """The program's own lower-precision path, switched on: parameters
    rounded to bfloat16 after every step."""
    import jax
    import jax.numpy as jnp

    sound = fm.Adapter.step

    def step(self, batch):
        loss = sound(self, batch)
        lr = self.learner
        lr.params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), lr.params)
        return loss

    monkeypatch.setattr(fm.Adapter, "step", step)
    line, out = _run(capsys)
    assert line["correct"] is False, out


class _Altered:
    """A feed that alters one index, drops or repeats one batch."""

    def __init__(self, inner, how):
        self.inner, self.how, self.n = inner, how, 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __iter__(self):
        self.n = 0
        for batch in self.inner:
            self.n += 1
            if self.n == 5 and self.how == "alter":
                batch = batch._replace(
                    indices=batch.indices.at[3, 0].add(1))
            if self.n == 5 and self.how == "drop":
                continue
            yield batch
            if self.n == 5 and self.how == "repeat":
                yield batch


@pytest.mark.parametrize("how", ["alter", "drop", "repeat"])
def test_a_row_altered_dropped_or_repeated(capsys, monkeypatch, how):
    from cellbench.feeds import text

    sound = text.open_feed
    monkeypatch.setattr(text, "open_feed",
                        lambda *a, **k: _Altered(sound(*a, **k), how))
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "epoch rows / index sum" in "\n".join(_not_ok(out))


def test_warm_cell_served_cold(capsys, monkeypatch):
    from cellbench.feeds import snapshot, text

    monkeypatch.setattr(snapshot, "open_feed", text.open_feed)
    line, out = _run(capsys, "tiny_fm_snap")
    assert line["correct"] is False
    assert "tier 'snapshot' served" in "\n".join(_not_ok(out))


def test_refuses_to_start_without_a_tpu():
    with pytest.raises(SystemExit) as exc:
        R.main(["--workload", "kdd12_fm_text", "--seed", "1", "--seconds",
                "1", "--trace", "0"])
    assert "no TPU" in str(exc.value)
