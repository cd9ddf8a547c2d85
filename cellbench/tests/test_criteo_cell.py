"""The tiny mirror of ``criteo_ffm_csv_text`` (PR 55): the cell run through
the whole harness on the CPU (``tiny_criteo_ffm.json``; the mirror
entries are made in memory, ``rehearsal.json`` is the benchmark's own),
and four broken timed paths underneath that must each
read ``correct`` false: the hash without the position byte, empty cells
dropped from the step, ``hash_bins`` one off between the parser and the
learner, bfloat16 tables. ``tests/test_csv_hashed.py`` (tier-1) holds the
parser's detail and the controls."""

import json

import numpy as np
import pytest

from cellbench import run as R
from cellbench.learners import ffm_criteo
from cellbench.reference import criteo_plain_read as plain
from dmlc_tpu.data import parsers
from dmlc_tpu.models import FFMLearner

CELL = "tiny_criteo_ffm_csv_text"


@pytest.fixture(autouse=True)
def mirrored(monkeypatch):
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("criteo_ffm", "tiny_criteo_ffm"))
        return real(*parts)

    monkeypatch.setattr(R, "load_json", load_json)


def _run(capsys, seed=11, trace=0):
    rc = R.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                 "--trace", str(trace), "--rehearse"])
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
    if trace:
        share = line["metrics"]["csv_empty_cell_share"]["value"]
        assert abs(share - (13 * 0.25 + 26 * 0.05) / 39) < 0.01
        assert line["metrics"]["dense_plane_bytes_per_row"]["value"] == 156


def test_the_hash_without_the_position_byte(capsys, monkeypatch):
    """Equal texts of two columns reach one table row: the numpy scanner
    with the plain reader's broken hash in its place."""
    def scan(rows, delim, label_column, weight_column, hash_bins, dtype):
        cells = np.empty((len(rows), rows[0].count(delim) + 1), dtype)
        for r, row in enumerate(rows):
            toks = row.split(delim)
            cells[r, 0] = int(toks[0])
            cells[r, 1:] = [plain.cell_id(c, tok, hash_bins, False)
                            for c, tok in enumerate(toks[1:])]
        return cells, sum(row.count(delim + delim) for row in rows)

    monkeypatch.setattr(parsers, "csv_hash_cells", scan)
    monkeypatch.setattr(parsers.CSVParser, "_native_supported",
                        lambda self: False)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "index sum" in bad and "loss_gap" in bad


def test_empty_cells_dropped(capsys, monkeypatch):
    """A row trains on fewer than 39 slots: the parser is sound (the four
    checksums hold), the step gives an empty cell's slot the sink and
    value 0."""
    import jax.numpy as jnp

    sound = FFMLearner._slots

    def slots(self, batch):
        got = sound(self, batch)
        of_empty = jnp.asarray([plain.cell_id(c, b"", self.num_col)
                                for c in range(self.num_fields)], jnp.int32)
        gone = got.indices == of_empty
        return got._replace(
            indices=jnp.where(gone, self.weight_dim - 1, got.indices),
            values=jnp.where(gone, 0.0, got.values))

    monkeypatch.setattr(FFMLearner, "_slots", slots)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = _not_ok(out)
    assert "loss_gap" in bad and "index sum" not in bad


def test_hash_bins_one_off_between_parser_and_learner(capsys, monkeypatch):
    sound = ffm_criteo.Adapter.device_iter_kwargs

    def kwargs(self):
        out = sound(self)
        out["parser_args"]["hash_bins"] -= 1
        return out

    monkeypatch.setattr(ffm_criteo.Adapter, "device_iter_kwargs", kwargs)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "index sum" in _not_ok(out)


def test_tables_in_bfloat16(capsys, monkeypatch):
    import jax.numpy as jnp

    sound = ffm_criteo.Adapter.step

    def step(self, batch):
        loss = sound(self, batch)
        lr = self.learner
        lr.params = lr.params._replace(
            w=lr.params.w.astype(jnp.bfloat16).astype(jnp.float32))
        return loss

    monkeypatch.setattr(ffm_criteo.Adapter, "step", step)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "untouched_gap" in _not_ok(out)


@pytest.mark.parametrize("control", ["", "zero_fields.", *ffm_criteo.CONTROLS])
def test_every_control_fails_a_limit(tmp_path, control):
    from cellbench.generators import criteo_tsv

    config = R.load_json(R.HERE, "configs", "tiny_criteo_ffm.json")
    corpus = str(tmp_path / "corpus.csv")
    criteo_tsv.generate(config["generator"], 5, 3 * config["batch_size"],
                        corpus)
    ref = ffm_criteo.reference_digest(config, 5, corpus)
    numbers = ffm_criteo.control_numbers(config, 5, corpus, ref)
    assert any(numbers[control + k] > limit
               for k, limit in config["limits"].items())
