"""Adapter between the harness and ``dmlc_tpu.models.FFMLearner`` fed by a
Criteo-format click log whose cells the CSV parser hashes (PR 55,
configuration ``criteo_ffm``).

It wraps ``learners/ffm_csv.py`` as ``ffm_rand.py`` wraps ``ffm.py``: the
mathematics, the reference's arithmetic (``reference/ffm_adagrad.py``), the
comparison's six numbers and the bfloat16 and zero-field controls are
``learners/ffm.py``'s, the dense int32 plane and its step are
``learners/ffm_csv.py``'s. What differs is what a cell is: any bytes, an
empty cell too, hashed to one of ``hash_bins`` table rows by the parser
(``create_parser(...&hash_bins=N)``; docs/data.md, "Hashed cells"), all 39
columns in one id space (``column_offsets`` all 0: the hash already mixed
the column in). The plain reference splits and hashes the same text on its
own (``reference/criteo_plain_read.py``); while one of ``learners/ffm.py``'s
functions runs here, the name it asks ``ffm_adagrad`` for its rows by reads
hashed cells.

Two more controls join ``learners/ffm.py``'s two, each a broken reading of
the text put in the place of the sound one, both trained by the same
reference over the whole table: ``no_position.<name>`` (the hash without
the position byte: equal texts of two columns collide) and
``dropped_empties.<name>`` (an empty cell gets no slot: a row trains on
fewer than 39 slots and its ``r`` changes).
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from cellbench.learners import ffm as _ffm
from cellbench.learners import ffm_csv as _csv
from cellbench.learners.ffm import compare  # noqa: F401 - the harness reads it
from cellbench.reference import criteo_plain_read as plain
from cellbench.reference import ffm_adagrad
# at import, not in Adapter: a program whose CSV parser hashes no cell (the
# parent of PR 55) fails here, at once, before the corpus's reference is run
from dmlc_tpu.data.parsers import check_hash_bins  # noqa: F401
from dmlc_tpu.models import FFMLearner

CONTROLS = {"no_position.": dict(position_byte=False),
            "dropped_empties.": dict(drop_empty=True)}
_SPLIT: dict = {}      # the corpus's first rows, split once a reference


def _check(config: dict) -> None:
    csv = config["csv"]
    if (config["optimizer"] != "adagrad" or config["dtype"] != "float32"
            or not config["normalize"] or config["layout"] != "dense"
            or config["format"] != "csv" or csv["dtype"] != "int32"
            or config["x_dtype"] != "int32"
            or config["columns"] != config["num_fields"]
            or config["max_nnz"] != config["columns"]
            or csv["hash_bins"] != config["num_features"]
            or config["generator"]["hash_bins"] != config["num_features"]):
        raise ValueError("ffm_criteo adapter: the configuration must state "
                         "float32 tables, AdaGrad, libffm's normalisation, a "
                         "dense int32 plane of one hashed column a field "
                         "from an int32 CSV, and hash_bins = num_features "
                         "for the parser and the generator alike")


def _reading(config: dict, path: str, rows: int, **broken):
    key = (path, rows)
    if key not in _SPLIT:
        _SPLIT[key] = plain.split_rows(path, rows, config["columns"],
                                       config["csv"]["delimiter"])
    return plain.hashed_rows(*_SPLIT[key], config["csv"]["hash_bins"],
                             **broken)


def _reads_hashed_cells(config: dict):
    """``learners/ffm.py`` asks ``ffm_adagrad`` for the corpus's rows by
    name; while one of its functions runs here, that name reads hashed
    cells."""
    def parse(path, rows, max_nnz):
        assert max_nnz == config["columns"]
        return _reading(config, path, rows)

    return mock.patch.object(ffm_adagrad, "parse_libfm_rows", parse)


def reference_digest(config: dict, seed: int, corpus_path: str, **how):
    _check(config)
    _SPLIT.clear()      # a new corpus, whatever its path
    with _reads_hashed_cells(config):
        return _ffm.reference_digest(config, seed, corpus_path, **how)


def control_numbers(config: dict, seed: int, corpus_path: str,
                    ref: dict) -> dict:
    with _reads_hashed_cells(config):
        out = _ffm.control_numbers(config, seed, corpus_path, ref)
    for prefix, broken in CONTROLS.items():
        out.update({prefix + k: v for k, v in broken_reading_control(
            config, seed, corpus_path, **broken).items()})
    return out


def broken_reading_control(config: dict, seed: int, corpus_path: str,
                           steps: int = 3, **broken) -> dict:
    """The comparison's numbers for a broken reading of the text put in
    the sound one's place: both trained by ``ffm_adagrad`` from the same
    seeded start over the whole table (10**6 rows: no compact one), the
    sampled rows those the sound reading touches."""
    seed = int(seed) % (2 ** 31 - 1)
    batch, m, f = (config["batch_size"], config["num_fields"],
                   config["num_factors"])
    w_rows = config["num_features"] + 1
    sound = _reading(config, corpus_path, steps * batch)
    other = _reading(config, corpus_path, steps * batch, **broken)
    (w0,) = ffm_adagrad.initial_rows(seed, w_rows, m, f, np.arange(w_rows))
    cut = lambda x, s: x[s * batch:(s + 1) * batch]  # noqa: E731

    def run(idx, fld, val, lab):
        idx = np.where(idx >= 0, idx, w_rows - 1)      # no slot: the sink
        return ffm_adagrad.train(
            w0, [tuple(cut(x, s) for x in (idx, fld, val, lab))
                 for s in range(steps)], config["learning_rate"],
            config["l2"], m, f)

    norm = lambda x: float(np.sqrt(np.sum(x, dtype=np.float64)))  # noqa: E731
    touched = np.unique(sound[0])
    rng = np.random.default_rng(seed)
    at = rng.choice(touched, min(_ffm.SAMPLE_ROWS, len(touched)),
                    replace=False)
    either = np.union1d(touched, other[0][other[0] >= 0])
    spare = np.setdiff1d(rng.integers(0, w_rows - 1, 4 * _ffm.SAMPLE_ROWS),
                         either)[:_ffm.SAMPLE_ROWS]

    def digest(trace):
        _, w_end, g_end = trace[-1]
        return {"losses": [t[0] for t in trace],
                "grad_norms": [norm(trace[0][2].astype(np.float64) - 1.0)],
                "update_norms": [norm(np.square(
                    w_end[touched] - w0[touched], dtype=np.float64))],
                "touched": {"w": w_end[at], "g": g_end[at]},
                "untouched": {"w": w_end[spare], "g": g_end[spare]}}

    want, got = digest(run(*sound)), digest(run(*other))
    return _ffm.compare(
        dict(want, untouched_w=want["untouched"]["w"]), got["losses"],
        got["grad_norms"], got["update_norms"], got["touched"],
        got["untouched"])


class Adapter(_csv.Adapter):
    def __init__(self, config: dict, seed: int, mesh=None):
        _check(config)
        if mesh is not None:
            raise ValueError("ffm_criteo adapter: one chip, no mesh")
        self.config = config
        self.seed = int(seed) % (2 ** 31 - 1)
        self.mesh = None
        # learners/ffm_csv.py's checksum fold reads these two: a cell's
        # table row is the id the parser hashed it to, and an id outside
        # [0, hash_bins) is taken off the row count
        self.offsets = np.zeros(config["columns"], np.int64)
        self.vocabs = np.full(config["columns"], config["csv"]["hash_bins"],
                              np.int64)
        self.learner = FFMLearner(
            num_col=config["num_features"], num_fields=config["num_fields"],
            num_factors=config["num_factors"],
            learning_rate=config["learning_rate"], l2=config["l2"],
            seed=self.seed, layout="dense",
            column_offsets=self.offsets.astype(np.int32))
        self._probes = None

    # ---- how to feed it ----
    def device_iter_kwargs(self) -> dict:
        csv = self.config["csv"]
        return dict(num_col=self.config["columns"],
                    batch_size=self.config["batch_size"],
                    layout="dense", x_dtype=self.config["x_dtype"],
                    parser_args={k: csv[k] for k in (
                        "label_column", "delimiter", "dtype", "hash_bins")})
