"""PR 48: a delimited table of id columns from the text to the table row.

The CSV parser's ``dtype=int32|int64`` cells stay integers through both
scan engines, ``DenseBlock`` / ``RowBlock``, every tier that carries them
(the block cache, the snapshot store with either decode, the service's
block frames) and ``DeviceIter(layout="dense", x_dtype="int32")``; what
cannot carry them refuses by name. ``FFMLearner(layout="dense",
column_offsets=)`` turns the columns into the ELL step's slots, and the
configuration ``kdd12_ffm_csv`` rehearses through the harness at
``tiny_ffm_csv``'s size.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu import native
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter, pack_dense_batches
from dmlc_tpu.data.row_block import DenseBlock, RowBlock
from dmlc_tpu.models import FFMLearner
from dmlc_tpu.ops.sparse import EllBatch
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = (2 ** 24 + 1, 54_686_451, 2 ** 31 - 1)
ENGINES = ["native", "python"]


def _table(tmp_path, rows=700, cols=5, seed=0, name="t.csv"):
    """A tab-separated table ``label, id...``: ``(path, ids, labels)``."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 2 ** 31 - 1, size=(rows, cols))
    ids[0, :3] = BIG
    labels = rng.integers(0, 2, rows)
    path = tmp_path / name
    with open(path, "w") as f:
        for lab, row in zip(labels, ids):
            f.write(f"{lab}\t" + "\t".join(map(str, row)) + "\n")
    return str(path), ids, labels


def _uri(path, dtype="int32"):
    return f"{path}?format=csv&label_column=0&delimiter=\t&dtype={dtype}"


def _parser(path, engine, dtype="int32", **kw):
    # "native": the registry stack over the native int scanner (the fused
    # reader scans float cells only); "python": numpy all the way down
    return create_parser(_uri(path, dtype),
                         engine="python" if engine == "python" else None,
                         **kw)


def _epoch(it, rows):
    xs, ys, ws = zip(*[(np.asarray(x), np.asarray(y), np.asarray(w))
                       for x, y, w in it])
    it.reset()
    w = np.concatenate(ws)
    assert w[:rows].all() and not w[rows:].any()
    return np.concatenate(xs)[:rows], np.concatenate(ys)[:rows]


# ---------------------------------------------------------------------------
# the text to the device, exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("big", BIG)
def test_an_id_above_a_float32s_whole_numbers_reaches_its_table_row(
        tmp_path, engine, big):
    path, ids, labels = _table(tmp_path)
    parser = _parser(path, engine)
    if engine == "native":
        assert native.available()
    it = DeviceIter(parser, num_col=5, batch_size=256, layout="dense",
                    x_dtype="int32")
    first = next(iter(it))
    assert isinstance(first[0], jax.Array) and first[0].dtype == jnp.int32
    it.reset()
    x, y = _epoch(it, len(ids))
    it.close()
    assert x.dtype == np.int32 and np.array_equal(x, ids)
    assert np.array_equal(y, labels)
    col = BIG.index(big)
    assert int(x[0, col]) == big != int(np.float32(big)) or big == 2 ** 31 - 1
    # the table row: the step's own sum, in int32 on the device
    offsets = np.zeros(5, np.int32)
    offsets[col] = 2 ** 31 - 1 - big        # the row lands on the last id
    rows = np.asarray(jnp.asarray(x[:1]) + offsets)
    assert np.array_equal(rows, ids[:1] + offsets.astype(np.int64))
    assert rows[0, col] == 2 ** 31 - 1


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_the_parsers_blocks_keep_the_cells_dtype(tmp_path, engine, dtype):
    path, ids, _ = _table(tmp_path)
    before = telemetry.csv_cells().get(dtype, 0)
    blocks = list(_parser(path, engine, dtype))
    assert all(isinstance(b, RowBlock) for b in blocks)
    assert all(b.value.dtype == np.dtype(dtype) for b in blocks)
    got = np.concatenate([b.value.reshape(len(b), -1) for b in blocks])
    assert np.array_equal(got, ids)
    assert np.array_equal(np.concatenate([b.to_dense(5) for b in blocks]),
                          ids)
    # counted once a cell, the label's too, under the dtype asked for
    assert telemetry.csv_cells()[dtype] - before == ids.size + len(ids)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("text,what", [
    (b"1\t2147483648\t3\n", "out of range for int32"),
    (b"1\t-2147483649\t3\n", "out of range for int32"),
    (b"1\tabc\t3\n", "non-integer"),
    (b"1\t2.5\t3\n", "non-integer"),
    (b"1\t1e3\t3\n", "non-integer"),
])
def test_a_cell_that_is_no_int32_is_an_error_not_a_zero(tmp_path, engine,
                                                        text, what):
    path = tmp_path / "bad.csv"
    path.write_bytes(b"0\t5\t6\n" + text)
    with pytest.raises(DMLCError, match=what):
        list(_parser(str(path), engine))


def test_the_native_scanner_holds_int64_to_its_last_value():
    cells, _ = native.parse_csv(
        b"9223372036854775807,-9223372036854775808,+7\n", dtype="int64")
    assert cells.dtype == np.int64
    assert cells.tolist() == [[2 ** 63 - 1, -2 ** 63, 7]]
    cells, _ = native.parse_csv(b"-2147483648,2147483647\n", dtype="int32")
    assert cells.dtype == np.int32
    assert cells.tolist() == [[-2 ** 31, 2 ** 31 - 1]]
    with pytest.raises(DMLCError, match="out of range for int64"):
        native.parse_csv(b"9223372036854775808\n", dtype="int64")
    with pytest.raises(DMLCError, match="no scanner"):
        native.parse_csv(b"1\n", dtype="int16")


@pytest.mark.parametrize("engine", ENGINES + ["fused"])
def test_float32_csv_is_what_it_was(tmp_path, engine):
    path = tmp_path / "f.csv"
    path.write_text("1,0.5,2.25,3\n0,1.5,-2,16777217\n")
    uri = f"{path}?format=csv&label_column=0"
    parser = create_parser(uri, engine={"python": "python", "native": None,
                                        "fused": "native"}[engine],
                           threaded=engine == "fused")
    (block,) = list(parser)
    assert block.value.dtype == np.float32
    # a float32 rounds 2**24 + 1: float cells are reals, as before
    assert block.value.tolist() == [0.5, 2.25, 3.0, 1.5, -2.0, 16777216.0]
    it = DeviceIter(create_parser(uri), num_col=3, batch_size=2,
                    layout="dense")
    (batch,) = list(it)
    st = it.stats()
    it.close()
    assert batch.x.dtype == jnp.float32 and it.pack_aux
    assert st["x_dtype"] == "float32"
    assert st["dense_plane_bytes"] == st["bytes_to_device"] == 2 * 5 * 4


# ---------------------------------------------------------------------------
# every tier carries the plane bit for bit, or refuses by name
# ---------------------------------------------------------------------------

def test_the_block_cache_serves_integer_cells_bit_for_bit(tmp_path):
    path, ids, _ = _table(tmp_path)
    parser = create_parser(_uri(path), block_cache=str(tmp_path / "bc"))
    it = DeviceIter(parser, num_col=5, batch_size=256, layout="dense",
                    x_dtype="int32")
    states = []
    for _ in range(3):
        x, _ = _epoch(it, len(ids))
        assert x.dtype == np.int32 and np.array_equal(x, ids)
        states.append(it.stats()["cache_state"])
    it.close()
    assert states[-1] == "warm"


@pytest.mark.parametrize("device_decode", [False, True])
def test_the_snapshot_serves_an_integer_plane_bit_for_bit(tmp_path,
                                                          device_decode):
    path, ids, _ = _table(tmp_path)
    it = DeviceIter(create_parser(_uri(path)), num_col=5, batch_size=256,
                    layout="dense", x_dtype="int32",
                    snapshot=str(tmp_path / "snap"),
                    device_decode=device_decode)
    x, _ = _epoch(it, len(ids))
    cells = telemetry.csv_cells()["int32"]
    for _ in range(2):      # warm: nothing is parsed again
        x, _ = _epoch(it, len(ids))
        assert x.dtype == np.int32 and np.array_equal(x, ids)
    assert telemetry.csv_cells()["int32"] == cells
    assert it._snapshot_geometry()["x_dtype"] == "int32"
    it.close()
    # another plane's snapshot is not this one's
    with pytest.raises(DMLCError, match="snapshot_quant"):
        DeviceIter(create_parser(_uri(path)), num_col=5, batch_size=256,
                   layout="dense", x_dtype="int32", pack_aux=True,
                   snapshot=str(tmp_path / "q"), snapshot_quant="int8")


def test_the_services_block_frames_carry_integer_cells(tmp_path):
    from dmlc_tpu.service import frame

    path, ids, _ = _table(tmp_path)
    (block,) = list(create_parser(_uri(path)))
    raw = frame.encode_block_frame(block)
    kind, meta, payload = frame.decode_frame(raw)
    assert kind == frame.KIND_BLOCK
    back = frame.block_from_frame(meta, payload)
    assert back.value.dtype == np.int32
    assert np.array_equal(back.value, block.value)
    assert np.array_equal(back.value.reshape(len(ids), -1), ids)


def test_the_services_snapshot_frames_refuse_integer_cells(tmp_path):
    path, _, _ = _table(tmp_path)
    blocks = list(create_parser(_uri(path)))
    with pytest.raises(DMLCError, match="service's snapshot frames"):
        list(pack_dense_batches(blocks, 256, 5))


@pytest.mark.parametrize("how,match", [
    (dict(layout="ell", max_nnz=5), "layout='ell'.*int32 cells"),
    (dict(layout="bcoo"), "layout='bcoo'.*int32 cells"),
])
def test_no_other_batch_kind_takes_integer_cells(tmp_path, how, match):
    path, _, _ = _table(tmp_path)
    it = DeviceIter(create_parser(_uri(path)), num_col=5, batch_size=256,
                    **how)
    with pytest.raises(DMLCError, match=match):
        next(iter(it))
    it.close()


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("x_dtype", ["float32", "bfloat16"])
def test_a_float_plane_refuses_integer_cells_at_construction(
        tmp_path, engine, x_dtype):
    path, _, _ = _table(tmp_path)
    parser = _parser(path, engine)
    with pytest.raises(DMLCError, match="would cross a float"):
        DeviceIter(parser, num_col=5, batch_size=256, layout="dense",
                   x_dtype=x_dtype, pack_aux=False)


@pytest.mark.parametrize("fmt", ["csv", "libsvm"])
def test_an_integer_plane_refuses_float_cells_at_construction(tmp_path, fmt):
    path = tmp_path / ("f." + fmt)
    path.write_text("1,0.5,2\n" if fmt == "csv" else "1 0:0.5 1:2\n")
    parser = create_parser(f"{path}?format={fmt}&label_column=0")
    with pytest.raises(DMLCError, match="cannot fill a int32 plane"):
        DeviceIter(parser, num_col=2, batch_size=2, layout="dense",
                   x_dtype="int32")


def test_an_integer_plane_is_the_dense_kinds_and_packs_no_aux(tmp_path):
    path, _, _ = _table(tmp_path)
    with pytest.raises(DMLCError, match="dense layout only"):
        DeviceIter(create_parser(_uri(path)), num_col=5, batch_size=256,
                   layout="ell", max_nnz=5, x_dtype="int32")
    with pytest.raises(DMLCError, match="unknown x_dtype"):
        DeviceIter(create_parser(_uri(path)), num_col=5, batch_size=256,
                   layout="dense", x_dtype="int64")
    it = DeviceIter(create_parser(_uri(path)), num_col=5, batch_size=256,
                    layout="dense", x_dtype="int32", pack_aux=True)
    assert it.pack_aux is False     # by the code: no float column in an int x
    x, y, w = next(iter(it))
    st = it.stats()
    it.close()
    assert (x.dtype, y.dtype, w.dtype) == (jnp.int32, jnp.float32,
                                           jnp.float32)
    assert st["x_dtype"] == "int32"
    assert st["dense_plane_bytes"] * 7 == st["bytes_to_device"] * 5
    assert st["csv_cells"]["int32"] >= 700 * 6


def test_an_int64_table_feeds_no_int32_plane(tmp_path):
    path, _, _ = _table(tmp_path)
    with pytest.raises(DMLCError, match="int64 cells cannot fill a int32"):
        DeviceIter(_parser(path, "native", "int64"), num_col=5,
                   batch_size=256, layout="dense", x_dtype="int32")


def test_dense_blocks_from_the_native_scanner_keep_their_dtype(tmp_path):
    path, ids, labels = _table(tmp_path)
    parser = _parser(path, "native")
    assert parser.set_emit_dense(5, 256, "int32", False)
    blocks = list(parser)
    assert all(isinstance(b, DenseBlock) for b in blocks)
    assert all(b.x.dtype == np.int32 and b.label.dtype == np.float32
               for b in blocks)
    assert np.array_equal(np.concatenate([b.x for b in blocks]), ids)
    assert np.array_equal(np.concatenate([b.label for b in blocks]), labels)


# ---------------------------------------------------------------------------
# the learner on id columns
# ---------------------------------------------------------------------------

VOCABS = np.array([50, 40, 30, 20, 10, 9, 8, 7, 6, 5, 5])
OFFSETS = np.concatenate([[0], np.cumsum(VOCABS)[:-1]])
N, C, B = int(VOCABS.sum()), len(VOCABS), 128


def _columns(step, tail=0):
    rng = np.random.default_rng(100 + step)
    x = np.stack([rng.integers(0, v, B) for v in VOCABS], 1).astype(np.int32)
    y = rng.integers(0, 2, B).astype(np.float32)
    w = np.ones(B, np.float32)
    if tail:                # a short last batch: rows of no weight
        x[-tail:], y[-tail:], w[-tail:] = 0, 0, 0
    return x, y, w


def _as_ell(x, y, w, k=16):
    """The same rows as the libfm text's ELL batch: K = 16, a field each."""
    live = w > 0
    idx = np.full((B, k), N, np.int32)
    val = np.zeros((B, k), np.float32)
    fld = np.zeros((B, k), np.uint8)
    idx[live, :C] = (x + OFFSETS)[live]
    val[live, :C] = 1
    fld[live, :C] = np.arange(C)
    return EllBatch(idx, val, y, w, fld)


def _pair(seed=3):
    return (FFMLearner(N, C, seed=seed),
            FFMLearner(N, C, seed=seed, layout="dense",
                       column_offsets=OFFSETS))


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_dense_input_and_ell_input_of_the_same_rows_give_the_same_model(
        request, route):
    if route == "kernels":
        calls = request.getfixturevalue("kernels")
    ell, dense = _pair()
    for step in range(3):
        cols = _columns(step, tail=5 if step == 2 else 0)
        a, b = ell.step(_as_ell(*cols)), dense.step(cols)
        assert abs(float(a) - float(b)) <= 2e-6 * abs(float(a))
    for got, want in ((dense.params.w, ell.params.w),
                      (dense.accumulators, ell.accumulators)):
        got, want = np.asarray(got), np.asarray(want)
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()
    # the padded rows named the sink alone, which stays zero and inert
    assert not np.asarray(dense.params.w)[N].any()
    assert np.all(np.asarray(dense.accumulators)[N] == 1.0)
    if route == "kernels":
        # the step ran on 11 slots a row, on the kernels, nothing padded
        assert calls["gather"] >= 1 and calls["scatter"] >= 1
        assert dense.table_update_route(B * C)[0] == "fused"
    cols = _columns(7)
    assert np.allclose(np.asarray(dense.predict(cols)),
                       np.asarray(ell.predict(_as_ell(*cols))), atol=1e-6)


def test_the_columns_become_slots_under_their_own_scope():
    _, dense = _pair()
    x, y, w = _columns(0, tail=3)
    slots = jax.jit(dense._slots)((x, y, w))
    assert slots.indices.dtype == jnp.int32
    assert np.array_equal(np.asarray(slots.indices)[:-3],
                          (x + OFFSETS)[:-3])
    assert np.all(np.asarray(slots.indices)[-3:] == N)
    # no plane of fields: column c is field c, which the pair terms read
    # off the slot's position (PR 56)
    assert slots.fields is None and slots.indices.shape[1] == C
    assert np.all(np.asarray(slots.values)[:-3] == 1)
    assert not np.asarray(slots.values)[-3:].any()
    dense.step((x, y, w))
    scopes = set(dense.hlo_scopes().values())
    assert any("ffm_columns" in s for s in scopes)
    assert any("ffm_gather" in s for s in scopes)


def test_the_dense_learner_says_what_it_takes():
    with pytest.raises(DMLCError, match="column_offsets"):
        FFMLearner(N, C, layout="dense")
    with pytest.raises(DMLCError, match="column_offsets"):
        FFMLearner(N, C, column_offsets=OFFSETS)
    with pytest.raises(DMLCError, match="num_fields whole numbers"):
        FFMLearner(N, C, layout="dense", column_offsets=OFFSETS[:-1])
    with pytest.raises(DMLCError, match="num_fields whole numbers"):
        FFMLearner(N, C, layout="dense",
                   column_offsets=OFFSETS.astype(np.float32))
    with pytest.raises(DMLCError, match="layout must be"):
        FFMLearner(N, C, layout="bcoo")
    from dmlc_tpu.parallel import make_mesh

    with pytest.raises(DMLCError, match="takes no mesh"):
        FFMLearner(N, C, layout="dense", column_offsets=OFFSETS,
                   mesh=make_mesh(devices=jax.devices()[:2]))
    _, dense = _pair()
    x, y, w = _columns(0)
    with pytest.raises(DMLCError, match="integer id columns"):
        dense.step((x.astype(np.float32), y, w))
    with pytest.raises(DMLCError, match="integer id columns"):
        dense.step((x[:, :5], y, w))


def _table_of_columns(tmp_path, steps=3):
    path = tmp_path / "cols.csv"
    with open(path, "w") as f:
        for step in range(steps):
            x, y, _ = _columns(step)
            for lab, row in zip(y, x):
                f.write(f"{int(lab)}\t" + "\t".join(map(str, row)) + "\n")
    return str(path)


def test_the_loop_surface_works_on_the_new_input(tmp_path):
    path = _table_of_columns(tmp_path)

    def feed():
        return DeviceIter(create_parser(_uri(path)), num_col=C,
                          batch_size=B, layout="dense", x_dtype="int32")

    _, dense = _pair(seed=5)
    it = feed()
    first, n = dense.fit_epoch(it)
    second, _ = dense.fit_epoch(it)
    assert n == 3 and second < first
    acc = dense.accuracy(it)
    assert 0.5 < acc <= 1.0
    dense.save(str(tmp_path / "ckpt"), step=6, device_iter=it)
    _, other = _pair(seed=9)
    other.restore(str(tmp_path / "ckpt"))
    assert np.array_equal(np.asarray(other.params.w),
                          np.asarray(dense.params.w))
    assert np.array_equal(np.asarray(other.accumulators),
                          np.asarray(dense.accumulators))
    assert other.accuracy(it) == acc
    # the table is the ELL learner's: a checkpoint crosses the two inputs
    ell, _ = _pair(seed=1)
    ell.restore(str(tmp_path / "ckpt"))
    assert np.array_equal(np.asarray(ell.params.w),
                          np.asarray(dense.params.w))
    it.close()


# ---------------------------------------------------------------------------
# the configuration, its generator and reference, and the rehearsal
# ---------------------------------------------------------------------------

def _config(name):
    with open(os.path.join(ROOT, "cellbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_the_two_generators_write_the_same_rows_in_two_encodings(tmp_path):
    from cellbench.generators import columns_zipf_csv, fields_zipf_libfm
    from cellbench.reference import ffm_adagrad, ffm_columns

    cfg = _config("tiny_ffm_csv")
    gen, rows = cfg["generator"], 3000
    csv, libfm = str(tmp_path / "c.csv"), str(tmp_path / "c.libfm")
    a = columns_zipf_csv.generate(gen, 2_147_528_011, rows, csv)
    b = fields_zipf_libfm.generate(dict(gen, name="fields_zipf_libfm"),
                                   2_147_528_011, rows, libfm)
    assert {k: a[k] for k in a if k != "bytes"} == {
        k: b[k] for k in b if k != "bytes"}
    assert a["bytes"] < b["bytes"] / 2
    assert columns_zipf_csv.column_vocabs(gen) == cfg["column_vocabs"]
    offsets = columns_zipf_csv.column_offsets(gen)
    idx, fld, val, lab = ffm_columns.parse_column_rows(csv, rows, offsets)
    idx2, fld2, val2, lab2 = ffm_adagrad.parse_libfm_rows(libfm, rows, 11)
    assert np.array_equal(idx, idx2) and np.array_equal(fld, fld2)
    assert np.array_equal(val, val2) and np.array_equal(lab, lab2)
    # every cell lies inside its column's vocabulary
    local = idx - offsets
    assert (local >= 0).all() and (local < cfg["column_vocabs"]).all()
    # the control's reading differs only above a float32's whole numbers
    through = ffm_columns.parse_column_rows(csv, rows, offsets,
                                            through="float32")[0]
    assert np.array_equal(through, idx)
    far = ffm_columns.parse_column_rows(csv, 64, offsets + 2 ** 25,
                                        through="float32")[0]
    assert (far != idx[:64] + 2 ** 25).any()


def test_the_costs_count_the_slots_the_step_runs_on():
    from cellbench.costs_ffm import ffm_adagrad_step_min_bytes
    from cellbench.costs_ffm_csv import ffm_csv_adagrad_step_min_bytes

    cfg = _config("kdd12_ffm_csv")
    got = ffm_csv_adagrad_step_min_bytes(
        cfg["num_fields"], cfg["num_factors"], cfg["batch_size"],
        cfg["columns"])
    slots = 720_896
    assert cfg["batch_size"] * cfg["columns"] == slots == 5_632 * 128
    assert got == 6 * slots * 44 * 4 + slots * 4 + 65_536 * 8
    assert got < ffm_adagrad_step_min_bytes(11, 4, 65_536, 16) * 11 / 16


def test_the_program_and_the_plain_reference_agree_at_the_tiny_size(
        tmp_path):
    from cellbench import run as R
    from cellbench.generators import columns_zipf_csv
    from cellbench.learners import ffm_csv

    cfg = _config("tiny_ffm_csv")
    corpus = str(tmp_path / "corpus.csv")
    columns_zipf_csv.generate(cfg["generator"], 77, cfg["rows"], corpus)
    ref = ffm_csv.reference_digest(cfg, 77, corpus)
    adapter = ffm_csv.Adapter(cfg, 77)
    kwargs = adapter.device_iter_kwargs()
    assert kwargs["parser_args"] == {"label_column": 0, "delimiter": "\t",
                                     "dtype": "int32"}
    from cellbench.feeds import csv_text

    it = csv_text.open_feed(f"{corpus}?format=csv", str(tmp_path), kwargs, {})
    try:
        losses, readings = R.first_steps(adapter, iter(it), ref)
    finally:
        it.close()
    numbers = ffm_csv.compare(ref, losses, *readings)
    base = _config("kdd12_ffm")["limits"]
    assert set(base) == set(cfg["limits"])
    for name, limit in base.items():        # kdd12_ffm's six, its limits
        assert numbers[name] <= limit, (name, numbers[name])


def _mirrored(R):
    real = R.load_json

    def load_json(*parts):
        if parts[-1] != "rehearsal.json":
            return real(*parts)
        bench = real(R.ROOT, "BENCHMARK.json")
        return json.loads(json.dumps(bench).replace("kdd12_", "tiny_")
                          .replace("kddb_fm", "tiny_kddb_fm"))

    return load_json


def _rehearse(monkeypatch, capsys, seed, trace):
    # three seconds, not one: on a loaded host a one-second window can close
    # inside its first epoch, whose eight batches the producer parsed before
    # the window opened, and `served` then finds no parse work in it
    from cellbench import run as R
    from cellbench.readers import _program as P

    monkeypatch.setattr(R, "load_json", _mirrored(R))
    P._cache.clear()
    assert R.main(["--workload", "tiny_ffm_csv_text", "--seed", str(seed),
                   "--seconds", "3", "--trace", str(trace),
                   "--rehearse"]) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("seed,trace", [(2_147_528_048, 1), (48, 0)])
def test_the_csv_cell_rehearses_correct_on_the_cpu(monkeypatch, capsys, seed,
                                                   trace):
    line, out = _rehearse(monkeypatch, capsys, seed, trace)
    assert line["correct"] is True, [ln for ln in out.splitlines()
                                     if ln.endswith("NOT OK")]
    assert line["failed"] == 0 and line["rehearsal"] is True
    assert "compilations inside the window: 0 (limit == 0) ok" in out
    assert "tier 'csv_text' served the window and the verification " \
        "epoch: yes" in out
    values = {k: v["value"] for k, v in line["metrics"].items()}
    if trace:
        # a CPU run reports what was counted, never a time
        assert values.pop("put_bytes_per_row") == 52.0
        assert values.pop("dense_plane_bytes_per_row") == 44.0
        assert "parse_busy_s_per_mrow" in values
        assert "field_plane_bytes_per_row" not in values
    assert values and all(v is None for v in values.values()), values


def test_offsets_shifted_by_a_column_read_correct_false(monkeypatch, capsys):
    from cellbench.learners import ffm_csv

    real = ffm_csv.Adapter.__init__

    def shifted(self, config, seed, mesh=None):
        real(self, config, seed, mesh=mesh)
        self.learner.column_offsets = np.roll(self.learner.column_offsets, 1)

    monkeypatch.setattr(ffm_csv.Adapter, "__init__", shifted)
    line, out = _rehearse(monkeypatch, capsys, 2_147_528_049, 0)
    assert line["correct"] is False
    bad = [ln for ln in out.splitlines() if ln.endswith("NOT OK")]
    assert any("loss_gap" in ln for ln in bad)
    # the feed and the epoch's sums are sound: the learner alone is broken
    assert not any("index sum" in ln for ln in bad)


def test_the_new_entries_are_lawful_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell = cells["kdd12_ffm_csv_text"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kdd12_ffm_csv", "csv_text_epochs", 1) and len(cell["why"]) <= 200
    assert len(cells) == 13        # PR 55 appended criteo_ffm_csv_text
    assert sum(w["chips"] == 4 for w in cells.values()) == 2 <= len(cells) // 4
    entry = {c["name"]: c for c in bench["configs"]}["kdd12_ffm_csv"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_features", "rows"]
    config, base = _config("kdd12_ffm_csv"), _config("kdd12_ffm")
    assert config["source"] == entry["source"]
    assert list(config["reduced"]) == entry["reduced"]
    # every shape is kdd12_ffm's but the input's encoding
    differ = {"name", "source", "deployment", "learner", "assumed",
              "guarantees", "limit_readings", "reduced", "generator",
              "format", "layout", "fields", "max_nnz"}
    csv_keys = {"csv", "x_dtype", "columns", "column_vocabs"}
    assert set(config) - set(base) <= csv_keys | {"limit_readings"}
    assert all(config[k] == base[k] for k in set(base) - differ)
    assert config["limits"] == base["limits"]
    assert config["reduced"]["num_features"] == base["reduced"][
        "num_features"]
    assert config["generator"] == dict(base["generator"],
                                       name="columns_zipf_csv")
    assert (config["format"], config["layout"], config["fields"],
            config["max_nnz"]) == ("csv", "dense", False, 11)
    assert config["csv"]["dtype"] == config["x_dtype"] == "int32"
    assert (config["csv"]["delimiter"], config["csv"]["label_column"]) == (
        "\t", 0)
    assert sum(config["column_vocabs"]) == config["num_features"]
    assert config["assumed"][:len(base["assumed"])] == base["assumed"]
    assert any("no id passes through a float" in g
               for g in config["guarantees"])
    tiny = _config("tiny_ffm_csv")
    assert all(tiny[k] == config[k] for k in (
        "learner", "format", "layout", "csv", "x_dtype", "columns",
        "max_nnz", "fields", "num_fields", "num_factors"))
    mine = [m for m in bench["per_layer"]
            if "kdd12_ffm_csv_text" in m["workloads"]]
    # (PR 55's cell, which takes this one's feed and step, follows it in
    # every list it is in)
    own = {m["name"]: m for m in mine
           if m["workloads"][0] == "kdd12_ffm_csv_text"}
    assert set(own) == {"dense_plane_bytes_per_row", "ffm_columns_device_ms",
                        "ffm_csv_adagrad_step_roofline"}
    # the cell's own three stand together, in this order; what other
    # cells' metrics come before or after them is those cells' business
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("dense_plane_bytes_per_row")
    assert names[at:at + 3] == list(own) == [
        "dense_plane_bytes_per_row", "ffm_columns_device_ms",
        "ffm_csv_adagrad_step_roofline"]
    for m in mine:
        cells = m["workloads"]
        assert cells[cells.index("kdd12_ffm_csv_text") + 1] == \
            "criteo_ffm_csv_text", m["name"]
        assert os.path.exists(os.path.join(
            ROOT, "cellbench", "metrics", m["name"] + ".json"))
    names = {m["name"] for m in mine}
    assert {"parse_busy_s_per_mrow", "put_bytes_per_row", "step_device_ms",
            "ffm_gather_device_ms", "ffm_optimizer_device_ms",
            "ffm_grad_scatter_kernel_roofline",
            "feed_backpressure_share"} <= names
    # no field plane crosses, and the ELL step's roofline counts 16 slots
    assert not {"field_plane_bytes_per_row", "ffm_adagrad_step_roofline",
                "cache_read_busy_s_per_mrow"} & names
    for path in ("traffic/csv_text_epochs.json", "feeds/csv_text.py",
                 "learners/ffm_csv.py", "generators/columns_zipf_csv.py",
                 "reference/ffm_columns.py", "costs_ffm_csv.py"):
        assert os.path.exists(os.path.join(ROOT, "cellbench", path)), path


def test_a_mesh_shards_an_integer_plane_exactly(tmp_path):
    from dmlc_tpu.parallel import make_mesh

    path, ids, labels = _table(tmp_path, rows=512)
    mesh = make_mesh(devices=jax.devices()[:2])
    it = DeviceIter(create_parser(_uri(path)), num_col=5, batch_size=256,
                    layout="dense", x_dtype="int32", mesh=mesh)
    batches = list(it)
    it.close()
    x = np.concatenate([np.asarray(b[0]) for b in batches])
    assert batches[0][0].dtype == jnp.int32
    assert len(batches[0][0].sharding.device_set) == 2
    assert np.array_equal(x, ids)
    assert np.array_equal(np.concatenate([np.asarray(b[1])
                                          for b in batches]), labels)
