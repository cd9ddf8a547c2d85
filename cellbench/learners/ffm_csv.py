"""Adapter between the harness and ``dmlc_tpu.models.FFMLearner`` fed by a
delimited table of id columns (PR 48, configuration ``kdd12_ffm_csv``).

The mathematics, the reference's arithmetic (``reference/ffm_adagrad.py``),
the comparison's six numbers and the bfloat16 control are
``learners/ffm.py``'s. What differs is the input: the batch is the file's
columns, ``(x [B, 11] int32, label, weight)`` from ``DeviceIter(layout=
"dense", x_dtype="int32")`` over ``create_parser(...?format=csv&
label_column=0&delimiter=<tab>&dtype=int32)``, and the learner is built
with ``layout="dense"`` and the columns' offsets. The plain reference reads
the same text on its own (``reference/ffm_columns.py``: split on tabs by
plain Python, column and offset to a slot's field and table row); while one
of ``learners/ffm.py``'s functions runs here, the name it asks
``ffm_adagrad`` for its rows by reads columns.

One more control joins ``learners/ffm.py``'s two, under
``float32_columns.<name>``: the reference with every cell and offset held
in float32 on the way to its table row, at the **uncut** vocabulary
(``source_num_features``: at the cut one no row passes 2**24 and a float32
holds them all), put in the place of the reference that reads whole
numbers.
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from cellbench.generators.columns_zipf_csv import offsets_of as _offsets
from cellbench.generators.fields_zipf_libfm import _field_vocabs
from cellbench.learners import ffm as _ffm
from cellbench.learners.ffm import compare  # noqa: F401 - the harness reads it
from cellbench.reference import ffm_adagrad, ffm_columns, ffm_start_blocks
# at import, not in Adapter: a program whose dense plane has no integer
# dtype (the parent of PR 48) fails here, at once, before the corpus's
# reference is run
from dmlc_tpu.data.parsers import check_dense_plane_dtype  # noqa: F401
from dmlc_tpu.models import FFMLearner

CONTROL = "float32_columns."


def _check(config: dict) -> None:
    csv = config["csv"]
    if (config["optimizer"] != "adagrad" or config["dtype"] != "float32"
            or not config["normalize"] or config["layout"] != "dense"
            or config["format"] != "csv" or csv["dtype"] != "int32"
            or config["x_dtype"] != "int32"
            or config["columns"] != config["num_fields"]
            or config["max_nnz"] != config["columns"]
            or sum(config["column_vocabs"]) != config["num_features"]
            or len(config["column_vocabs"]) != config["columns"]):
        raise ValueError("ffm_csv adapter: the configuration must state "
                         "float32 tables, AdaGrad, libffm's normalisation, "
                         "a dense int32 plane of one id column a field "
                         "from an int32 CSV, and vocabularies that sum to "
                         "num_features")


def _reads_columns(config: dict):
    """``learners/ffm.py`` asks ``ffm_adagrad`` for the corpus's rows by
    name; while one of its functions runs here, that name reads columns."""
    offsets = _offsets(config["column_vocabs"])

    def parse(path, rows, max_nnz):
        assert max_nnz == len(offsets)
        return ffm_columns.parse_column_rows(
            path, rows, offsets, config["csv"]["delimiter"])

    return mock.patch.object(ffm_adagrad, "parse_libfm_rows", parse)


def reference_digest(config: dict, seed: int, corpus_path: str, **how):
    _check(config)
    with _reads_columns(config):
        return _ffm.reference_digest(config, seed, corpus_path, **how)


def control_numbers(config: dict, seed: int, corpus_path: str,
                    ref: dict) -> dict:
    with _reads_columns(config):
        out = _ffm.control_numbers(config, seed, corpus_path, ref)
    out.update({CONTROL + k: v for k, v in float32_columns_control(
        config, seed, corpus_path).items()})
    return out


def float32_columns_control(config: dict, seed: int, corpus_path: str,
                            steps: int = 3) -> dict:
    """The comparison's numbers for a reading of the columns through
    float32, at the uncut vocabulary: the text's ids under the offsets of
    ``source_num_features`` rows (the columns' vocabularies grow with the
    table; an id of the cut column is one of the uncut column too), trained
    once with the rows as whole numbers and once with the rows a float32
    sum gives, over one compact table of the rows either touches."""
    seed = int(seed) % (2 ** 31 - 1)
    batch, m, f = (config["batch_size"], config["num_fields"],
                   config["num_factors"])
    w_rows = config["source_num_features"] + 1
    offsets = _offsets(_field_vocabs(config["source_num_features"],
                                     config["columns"]))
    delim = config["csv"]["delimiter"]
    exact, fld, val, lab = ffm_columns.parse_column_rows(
        corpus_path, steps * batch, offsets, delim)
    rounded = ffm_columns.parse_column_rows(
        corpus_path, steps * batch, offsets, delim, through="float32")[0]
    union = np.union1d(exact, rounded)
    # one fixed size, as learners/ffm.py pads its compact table: every
    # seed compiles the same programs; then the padding sink
    size = 2 * steps * batch * config["columns"]
    pad = np.full(size - len(union), w_rows - 1, np.int64)
    (w0,) = ffm_start_blocks.initial_rows(
        seed, w_rows, m, f, np.concatenate([union, pad, [w_rows - 1]]))
    cut = lambda x, s: x[s * batch:(s + 1) * batch]  # noqa: E731

    def run(idx):
        compact = np.searchsorted(union, idx)
        return ffm_adagrad.train(
            w0, [(cut(compact, s), cut(fld, s), cut(val, s), cut(lab, s))
                 for s in range(steps)], config["learning_rate"],
            config["l2"], m, f)

    norm = lambda x: float(np.sqrt(np.sum(x, dtype=np.float64)))  # noqa: E731
    touched = np.unique(exact)
    rng = np.random.default_rng(seed)
    at = np.searchsorted(union, rng.choice(
        touched, min(_ffm.SAMPLE_ROWS, len(touched)), replace=False))
    # rows the whole-number reading leaves alone and the float32 one names
    spare = np.searchsorted(union, np.setdiff1d(union, touched)
                            [:_ffm.SAMPLE_ROWS])
    rows_at = np.searchsorted(union, touched)

    def digest(trace):
        _, w_end, g_end = trace[-1]
        return {"losses": [t[0] for t in trace],
                "grad_norms": [norm(trace[0][2].astype(np.float64) - 1.0)],
                "update_norms": [norm(np.square(
                    w_end[rows_at] - w0[rows_at], dtype=np.float64))],
                "touched": {"w": w_end[at], "g": g_end[at]},
                "untouched": {"w": w_end[spare], "g": g_end[spare]}}

    want, got = digest(run(exact)), digest(run(rounded))
    if not len(spare):      # no row was rounded: nothing else to hold
        want["untouched"] = got["untouched"] = {
            "w": np.zeros((1, m * f), np.float32),
            "g": np.ones((1, m * f), np.float32)}
    return _ffm.compare(
        dict(want, untouched_w=want["untouched"]["w"]), got["losses"],
        got["grad_norms"], got["update_norms"], got["touched"],
        got["untouched"])


class Adapter(_ffm.Adapter):
    def __init__(self, config: dict, seed: int, mesh=None):
        _check(config)
        if mesh is not None:
            raise ValueError("ffm_csv adapter: one chip, no mesh")
        self.config = config
        self.seed = int(seed) % (2 ** 31 - 1)
        self.mesh = None
        self.vocabs = np.asarray(config["column_vocabs"], np.int64)
        self.offsets = _offsets(self.vocabs)
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
                        "label_column", "delimiter", "dtype")})

    def step_min_bytes(self) -> int:
        from cellbench.costs_ffm_csv import ffm_csv_adagrad_step_min_bytes

        c = self.config
        return ffm_csv_adagrad_step_min_bytes(
            c["num_fields"], c["num_factors"], c["batch_size"], c["columns"])

    def checksum_fold(self):
        """``(zero, fold)``: the FM cells' sums over an epoch (rows, table
        rows, their squares, labels) from the dense batch, the table row of
        a cell being its id plus its column's offset. A cell outside its
        column's vocabulary is taken off the row count, so the sums no
        longer match: the text gives every column its own range."""
        import jax
        import jax.numpy as jnp

        offsets = jnp.asarray(self.offsets, jnp.int32)
        vocabs = jnp.asarray(self.vocabs, jnp.int32)
        zero = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.uint32),
                jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.int32))

        def fold(acc, batch):
            x, label, weight = batch
            live = weight > 0
            idx = jnp.where(live[:, None], x + offsets, 0).astype(jnp.uint32)
            wrong = live[:, None] & ((x < 0) | (x >= vocabs))
            return (acc[0] + jnp.sum(live, dtype=jnp.int32)
                    - jnp.sum(wrong, dtype=jnp.int32),
                    acc[1] + jnp.sum(idx, dtype=jnp.uint32),
                    acc[2] + jnp.sum(idx * idx, dtype=jnp.uint32),
                    acc[3] + jnp.sum(jnp.where(live, label, 0.0)
                                     ).astype(jnp.int32))

        return zero, jax.jit(fold)
