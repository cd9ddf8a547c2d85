"""PR 55: a click log whose cells are any bytes, hashed in the CSV scanner.

``create_parser(...?format=csv&dtype=int32&hash_bins=N)`` turns every cell
but the label's (and the weight's) into the int32 id ``FNV-1a-64(position
byte, the cell's bytes) % N`` (docs/data.md, "Hashed cells"), an empty cell
included: both scan engines against the plain reader of
``cellbench/reference/criteo_plain_read.py`` cell for cell, the refusals
by name, the tiers, ``FFMLearner(layout="dense")`` on 39 columns of one
shared id space against ``cellbench/reference/ffm_adagrad.py``, and the
pair kernels at 39 fields and 39 slots, whose blocks are cut to fit VMEM.
"""

from __future__ import annotations

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.reference import criteo_plain_read as plain
from cellbench.reference import ffm_adagrad
from dmlc_tpu import native
from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.data.native_parser import NativeStreamParser
from dmlc_tpu.models import FFMLearner
from dmlc_tpu.ops import ffm_pairs as fp
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINES = ["native", "python"]
BINS = 1_000_003
C = 39
EMPTY_ROW = [b""] * C
# rows of 39 cells, by what they hold and where their empty cells are
ROWS = {
    "full": [b"%d" % (c - 2) if c < 13 else b"%08x" % (c * 0x9e3779b1 % 2**32)
             for c in range(C)],
    "empty_at_the_start": [b""] + [b"x%d" % c for c in range(1, C)],
    "empty_in_the_middle": [b"7"] * 5 + [b"", b""] + [b"68fd1e64"] * 32,
    "empty_at_the_end": [b"a"] * (C - 1) + [b""],
    "all_39_empty": EMPTY_ROW,
    "spaces_and_case_are_bytes": [b" 1", b"1 ", b"1", b"AB", b"ab"]
    + [b"-1"] * (C - 5),
}


def _text(rows, eol=b"\n", labels=None):
    labels = labels or [i % 2 for i in range(len(rows))]
    return b"".join(b"%d\t" % lab + b"\t".join(row) + eol
                    for lab, row in zip(labels, rows))


def _uri(path, bins=BINS, dtype="int32", **more):
    extra = "".join(f"&{k}={v}" for k, v in more.items())
    return (f"{path}?format=csv&label_column=0&delimiter=\t&dtype={dtype}"
            f"&hash_bins={bins}{extra}")


def _parse(path, engine, **how):
    parser = create_parser(_uri(path, **how),
                           engine="python" if engine == "python" else None)
    try:
        blocks = list(parser)
    finally:
        parser.close()
    ids = np.concatenate([b.value for b in blocks]).reshape(-1, C)
    return ids, np.concatenate([b.label for b in blocks])


def _want(rows, bins=BINS):
    return np.array([[plain.cell_id(c, cell, bins)
                      for c, cell in enumerate(row)] for row in rows])


# ---------------------------------------------------------------------------
# both engines, the plain reader's ids cell for cell
# ---------------------------------------------------------------------------

def test_the_hash_is_the_one_docs_data_md_spells_out():
    # three worked cells of docs/data.md, "Hashed cells"
    assert plain.cell_id(13, b"68fd1e64", 10 ** 6) == 958_108
    assert plain.cell_id(1, b"-1", 10 ** 6) == 459_430
    assert plain.cell_id(2, b"", 10 ** 6) == 423_877
    with open(os.path.join(ROOT, "docs", "data.md")) as f:
        text = f.read()
    for worked in ("958,108", "459,430", "423,877", "0xcbf29ce484222325",
                   "0x100000001b3"):
        assert worked in text


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("eol", [b"\n", b"\r\n"], ids=["lf", "crlf"])
@pytest.mark.parametrize("kind", list(ROWS))
def test_both_engines_give_the_plain_readers_ids(tmp_path, engine, eol, kind):
    if engine == "native" and not native.available():
        pytest.skip("no native library")
    rows = [ROWS["full"], ROWS[kind], ROWS["full"][::-1], ROWS[kind]]
    path = tmp_path / "t.tsv"
    path.write_bytes(_text(rows, eol))
    ids, labels = _parse(path, engine)
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < BINS
    assert np.array_equal(ids, _want(rows))
    assert np.array_equal(labels, [0, 1, 0, 1])
    # and the reader the benchmark's reference uses, from the file
    got = plain.parse_hashed_rows(str(path), 4, C, BINS)
    assert np.array_equal(got[0], ids) and np.array_equal(got[3], labels)
    assert np.array_equal(got[1][0], np.arange(C)) and np.all(got[2] == 1)


@pytest.mark.parametrize("engine", ENGINES)
def test_an_empty_cell_is_a_value_of_its_column(tmp_path, engine):
    if engine == "native" and not native.available():
        pytest.skip("no native library")
    path = tmp_path / "t.tsv"
    path.write_bytes(_text([EMPTY_ROW, ROWS["full"]]))
    before = (telemetry.csv_cells().get("hashed", 0),
              telemetry.csv_cells().get("int32", 0),
              telemetry.csv_empty_cells())
    ids, _ = _parse(path, engine)
    # 39 slots, each its own column's: no two columns' empties collide by
    # construction of the hash, and none equals another's by chance here
    assert len(set(ids[0])) == C
    assert telemetry.csv_cells()["hashed"] - before[0] == 2 * C
    assert telemetry.csv_cells()["int32"] - before[1] == 2     # the labels
    assert telemetry.csv_empty_cells() - before[2] == C
    # the position byte: the same text in two columns is two table rows
    same = _want([[b"68fd1e64"] * C])[0]
    assert len(set(same)) == C


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("dtype", ["int32", "int64"])
@pytest.mark.parametrize("bins", [1, 97, 2 ** 31 - 1])
def test_ids_are_inside_the_bins_in_either_integer_dtype(tmp_path, engine,
                                                         dtype, bins):
    if engine == "native" and not native.available():
        pytest.skip("no native library")
    rows = list(ROWS.values())
    path = tmp_path / "t.tsv"
    path.write_bytes(_text(rows))
    ids, _ = _parse(path, engine, bins=bins, dtype=dtype)
    assert ids.dtype == np.dtype(dtype)
    assert np.array_equal(ids, _want(rows, bins))


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("bad,row", [("short", 2), ("long", 1)])
def test_a_short_or_long_row_is_an_error_that_names_the_row(tmp_path, engine,
                                                            bad, row):
    if engine == "native" and not native.available():
        pytest.skip("no native library")
    rows = [ROWS["full"]] * 4
    rows[row] = ROWS["full"][:-1] if bad == "short" else ROWS["full"] + [b"x"]
    path = tmp_path / "t.tsv"
    path.write_bytes(_text(rows))
    with pytest.raises(DMLCError, match=f"ragged rows.*row {row} of the"):
        _parse(path, engine)


@pytest.mark.parametrize("engine", ENGINES)
def test_the_label_is_still_a_number(tmp_path, engine):
    if engine == "native" and not native.available():
        pytest.skip("no native library")
    path = tmp_path / "t.tsv"
    path.write_bytes(_text([ROWS["full"]]) + b"\t" + b"\t".join(ROWS["full"])
                     + b"\n")
    with pytest.raises(DMLCError, match="empty label.*row 1, cell 0"):
        _parse(path, engine)
    path.write_bytes(b"abc\t" + b"\t".join(ROWS["full"]) + b"\n")
    with pytest.raises(DMLCError, match="non-integer"):
        _parse(path, engine)


def test_without_hash_bins_an_empty_cell_is_the_parents_error_with_its_place(
        tmp_path):
    if not native.available():
        pytest.skip("no native library")
    path = tmp_path / "t.csv"
    path.write_bytes(b"1,2,3\n4,,6\n")
    for dtype in ("float32", "int32"):
        with pytest.raises(DMLCError,
                           match=r"csv: empty cell in row \(row 1, cell 1"):
            list(create_parser(f"{path}?format=csv&dtype={dtype}"))


# ---------------------------------------------------------------------------
# the refusals, by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("args,match", [
    ("format=csv&dtype=float32&hash_bins=10", "hash_bins.*dtype=float32"),
    ("format=csv&hash_bins=10", "hash_bins.*dtype=float32"),
    ("format=csv&dtype=int32&hash_bins=2147483648", "hash_bins=2147483648"),
    ("format=csv&dtype=int32&hash_bins=-3", "hash_bins=-3"),
    ("format=libsvm&hash_bins=10", "hash_bins is an argument of format=csv"),
    ("format=libfm&hash_bins=10", "hash_bins is an argument of format=csv"),
])
def test_hash_bins_is_refused_where_it_cannot_hold(tmp_path, args, match):
    path = tmp_path / "t.txt"
    path.write_bytes(b"1 1:2\n")
    with pytest.raises(DMLCError, match=match):
        create_parser(f"{path}?{args}")


def test_the_fused_reader_keeps_its_checked_refusal(tmp_path):
    if not native.available():
        pytest.skip("no native library")
    path = tmp_path / "t.tsv"
    path.write_bytes(_text([ROWS["full"]]))
    for dtype, match in (("int32", "dtype must be float32"),
                         ("float32", "hash_bins")):
        with pytest.raises(DMLCError, match=match):
            NativeStreamParser(str(path), {
                "format": "csv", "dtype": dtype, "hash_bins": "10",
                "delimiter": "\t", "label_column": "0"}, 0, 1, "csv")
    # so `auto` serves hashed cells from the per-chunk scanner
    parser = create_parser(_uri(path))
    assert not isinstance(parser, NativeStreamParser)
    parser.close()


def test_more_than_256_hashed_columns_are_refused(tmp_path):
    path = tmp_path / "t.tsv"
    path.write_bytes(b"1\t" + b"\t".join([b"a"] * 257) + b"\n")
    for engine in ENGINES:
        if engine == "native" and not native.available():
            continue
        with pytest.raises(DMLCError, match="at most 256 hashed columns"):
            list(create_parser(_uri(path), engine=(
                "python" if engine == "python" else None)))


# ---------------------------------------------------------------------------
# the tiers and the device plane
# ---------------------------------------------------------------------------

def _log(tmp_path, rows=700, seed=0):
    rng = np.random.default_rng(seed)
    cells = [[b"" if rng.random() < 0.2 else
              (b"%d" % rng.integers(-2, 50) if c < 13
               else b"%08x" % rng.integers(0, 40))
              for c in range(C)] for _ in range(rows)]
    path = tmp_path / "log.tsv"
    path.write_bytes(_text(cells))
    return str(path), cells


def _epoch(it, rows):
    xs, ws = zip(*[(np.asarray(x), np.asarray(w)) for x, _, w in it])
    it.reset()
    w = np.concatenate(ws)
    assert w[:rows].all() and not w[rows:].any()
    return np.concatenate(xs)[:rows]


def test_a_block_cache_written_at_other_bins_does_not_serve(tmp_path):
    path, cells = _log(tmp_path)
    cache = str(tmp_path / "bc")
    planes = {}
    for bins in (1000, 1001, 1000):
        it = DeviceIter(create_parser(_uri(path, bins), block_cache=cache),
                        num_col=C, batch_size=256, layout="dense",
                        x_dtype="int32")
        cold = _epoch(it, len(cells))
        state = it.stats()["cache_state"]
        warm = _epoch(it, len(cells))
        it.close()
        assert np.array_equal(cold, _want(cells, bins))
        # the warm plane is the cold one, byte for byte
        assert cold.tobytes() == warm.tobytes() and cold.dtype == np.int32
        planes.setdefault(bins, []).append((state, cold))
    # 1001 found 1000's cache and did not serve it: it parsed, and wrote anew
    assert not np.array_equal(planes[1000][0][1], planes[1001][0][1])
    assert np.array_equal(planes[1000][0][1], planes[1000][1][1])


def test_device_iter_counts_hashed_and_empty_cells(tmp_path):
    path, cells = _log(tmp_path)
    it = DeviceIter(create_parser(_uri(path)), num_col=C, batch_size=256,
                    layout="dense", x_dtype="int32")
    before = it.stats()
    x = _epoch(it, len(cells))
    after = it.stats()
    it.close()
    assert np.array_equal(x, _want(cells))
    assert (after["csv_cells"]["hashed"]
            - before["csv_cells"].get("hashed", 0)) == len(cells) * C
    empties = sum(not cell for row in cells for cell in row)
    assert after["csv_empty_cells"] - before["csv_empty_cells"] == empties
    assert after["dense_plane_bytes"] - before["dense_plane_bytes"] \
        == 3 * 256 * C * 4


# ---------------------------------------------------------------------------
# the learner on 39 columns of one id space
# ---------------------------------------------------------------------------

N, B = 997, 64


def _shared_columns(step):
    rng = np.random.default_rng(200 + step)
    x = rng.integers(0, N, (B, C)).astype(np.int32)
    x[0, 7] = x[0, 3]           # one row, the same id under two fields
    x[1, :] = x[1, 0]           # and one under all 39
    return x, rng.integers(0, 2, B).astype(np.float32), np.ones(B, np.float32)


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_39_shared_space_columns_train_as_the_plain_reference_does(request,
                                                                   route):
    if route == "kernels":
        # the table's two kernels interpreted, on lines of 256 lanes; the
        # pair terms stay on the plain route (an interpreted backward block
        # at 39 x 39 is half a minute: the next section runs it once)
        calls = request.getfixturevalue("kernels")
        request.getfixturevalue("monkeypatch").setattr(
            fp, "ffm_interaction_route", lambda rows, dtype: ("xla", "test"))
    model = FFMLearner(N, C, seed=5, layout="dense",
                       column_offsets=np.zeros(C, np.int32))
    w0 = np.asarray(model.params.w)
    batches = [_shared_columns(step) for step in range(3)]
    losses = [float(model.step(batch)) for batch in batches]
    fld = np.broadcast_to(np.arange(C), (B, C))
    trace = ffm_adagrad.train(
        w0, [(x, fld, np.ones((B, C), np.float32), y) for x, y, _ in batches],
        0.2, 2e-5, C, 4)
    for got, (want, _, _) in zip(losses, trace):
        assert abs(got - want) <= 1e-5 * abs(want)
    for got, want in ((model.params.w, trace[-1][1]),
                      (model.accumulators, trace[-1][2])):
        assert np.abs(np.asarray(got) - want).max() \
            <= 1e-5 * np.abs(want).max()
    if route == "kernels":
        assert calls["gather"] >= 1 and calls["scatter"] >= 1
        assert model.table_update_route(B * C)[0] == "fused"


# ---------------------------------------------------------------------------
# the pair kernels at 39 fields and 39 slots
# ---------------------------------------------------------------------------

def _pair_operands(m, slots, batch, seed):
    rng = np.random.default_rng(seed)
    rows = rng.normal(size=(slots, batch, m * 4)).astype(np.float32)
    fields = np.tile((np.arange(slots) % m)[:, None],
                     (1, batch)).astype(np.int32)
    fields[5, ::3] = 4                       # two slots in one field
    values = np.ones((slots, batch), np.float32)
    values[:, 7] = 0.0                       # an empty row
    return jnp.asarray(rows), jnp.asarray(fields), jnp.asarray(values)


# (fields, slots, rows, VMEM budget in MiB). 39 x 39 on one line of 128
# rows: an interpreted backward block is half a minute, so the cut is run
# at 5 x 8, where 384 rows are one block of three lines, or three blocks of
# one where the budget does not hold three (what the budget holds at 39 x
# 39 is the next test's, and tests/test_chip_compile.py compiles it)
@pytest.mark.parametrize("m,slots,batch,budget", [
    (C, C, 128, 100), (5, 8, 384, 100), (5, 8, 384 + 128, 8)],
    ids=["39x39_whole", "5x8_whole", "5x8_cut_to_one_line"])
def test_the_pair_kernels_match_the_plain_form(monkeypatch, pair_kernels, m,
                                               slots, batch, budget):
    monkeypatch.setattr(fp, "VMEM_BUDGET", budget << 20)
    rows, fields, values = _pair_operands(m, slots, batch, budget)
    rng = np.random.default_rng(1)
    w_phi, w_reg = (jnp.asarray(rng.normal(size=batch).astype(np.float32))
                    for _ in range(2))
    out = {}
    for route, fn in (("kernel", fp.ffm_pair_terms_kernel),
                      ("xla", fp.ffm_pair_terms_xla)):
        def loss(r, fn=fn):
            phi, reg = fn(r, fields, values, m)
            return jnp.sum(phi * w_phi) + jnp.sum(reg * w_reg), (phi, reg)

        (_, (phi, reg)), grad = jax.value_and_grad(loss, has_aux=True)(rows)
        out[route] = [np.asarray(x) for x in (phi, reg, grad)]
    assert pair_kernels == {"terms": 1, "grads": 1}
    for got, want in zip(out["kernel"], out["xla"]):
        assert np.abs(want).max() > 0.1
        assert np.abs(got - want).max() <= 5e-6 * np.abs(want).max()
    assert not out["kernel"][0][7] and not out["kernel"][2][:, 7].any()


def test_blocks_are_cut_only_where_vmem_does_not_hold_them(monkeypatch):
    """What ``_call`` asks of VMEM, from the shapes alone: the cells' 11
    fields keep blocks of eight lines, forward and backward; 39 fields and
    39 slots keep eight forward and are cut to four backward."""
    from jax.experimental import pallas as pl

    class Seen(Exception):
        pass

    def spy(kernel, grid, **kw):
        raise Seen(128 // grid[0])

    monkeypatch.setattr(pl, "pallas_call", spy)
    sds = jax.ShapeDtypeStruct
    vec = sds((128, 128), jnp.float32)

    def lines_of(m, slots, lined):
        wg = sds((m * 4, slots, 128, 128), jnp.float32)
        plane = sds((slots, 128, 128), jnp.float32)
        seen = []
        for fn, args, kw in (
                (fp.pair_terms_pallas, (wg, plane, plane, vec), {}),
                (fp.pair_grads_pallas, (wg, plane, plane, vec, vec, vec),
                 dict(lines=lined))):
            with pytest.raises(Seen) as lines:
                jax.eval_shape(lambda *a, fn=fn, kw=kw: fn.__wrapped__(
                    *a, num_fields=m, **kw), *args)
            seen.append(lines.value.args[0])
        return seen

    assert lines_of(11, 16, True) == [8, 8]
    assert lines_of(11, 11, True) == [8, 8]
    assert lines_of(39, 39, True) == [8, 4]
    assert lines_of(39, 39, False) == [8, 4]


# str(make_jaxpr) of value_and_grad through ``ffm_pair_terms_kernel`` at
# (fields, slots, rows, lanes of a gathered row), taken with the parent's
# own code (3717afa, jax 0.9.0): the general kernels' programs, which every
# ELL kdd12_ffm* cell runs (16 slots; a chip of kdd12_ffm_ps4's 16,384
# rows; the column side) and the digest tests' toy. ``(11, 11, 65536,
# 128)`` is the general program at the csv cell's shape: what that cell ran
# until PR 56, and what 11 ELL slots of 11 fields would run today
PARENT_PAIR_PROGRAMS = {
    (11, 16, 65536, 128): "95e1f754ded037c5",
    (11, 11, 65536, 128): "7bd0e38084809cec",
    (11, 16, 16384, 128): "f54734926f65cfe3",
    (11, 16, 65536, 44): "e5fd6f794805065f",
    (5, 8, 64, 20): "2fc6fd6866edbcf8",
}
# the same with no field plane (PR 56's tree): the positional programs of
# kdd12_ffm_csv_text and criteo_ffm_csv_text
POSITIONAL_PAIR_PROGRAMS = {
    (11, 11, 65536, 128): "ccc7db98913c4f43",
    (39, 39, 16384, 256): "feb7768bcb128fe0",
}


def _pair_program_digest(case, plane: bool) -> str:
    m, slots, batch, lanes = case
    sds = jax.ShapeDtypeStruct

    def both(rows, fields, values):
        def loss(rows):
            phi, reg = fp.ffm_pair_terms_kernel(rows, fields, values, m,
                                                m * 4)
            return jnp.sum(phi) + jnp.sum(reg)

        return jax.value_and_grad(loss)(rows)

    text = str(jax.make_jaxpr(both)(
        sds((slots, batch, lanes), jnp.float32),
        sds((slots, batch), jnp.int32) if plane else None,
        sds((slots, batch), jnp.float32)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("case", list(PARENT_PAIR_PROGRAMS),
                         ids=["-".join(map(str, c))
                              for c in PARENT_PAIR_PROGRAMS])
def test_the_11_field_pair_programs_are_the_parents(case):
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    assert _pair_program_digest(case, True) == PARENT_PAIR_PROGRAMS[case]


@pytest.mark.parametrize("case", list(POSITIONAL_PAIR_PROGRAMS),
                         ids=["-".join(map(str, c))
                              for c in POSITIONAL_PAIR_PROGRAMS])
def test_the_positional_pair_programs_are_pinned(case):
    """The dense cells' cached executables stay valid while these hold."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    assert _pair_program_digest(case, False) \
        == POSITIONAL_PAIR_PROGRAMS[case]


# ---------------------------------------------------------------------------
# the cell, rehearsed through the whole harness at the tiny size
# ---------------------------------------------------------------------------

def _rehearse(monkeypatch, capsys, seed, trace):
    """``criteo_ffm_csv_text`` read as ``tiny_criteo_ffm_csv_text`` in
    memory (``rehearsal.json`` is the benchmark's own file), three seconds
    of it on the CPU."""
    from cellbench import run as R
    from cellbench.readers import _program as P

    real = R.load_json

    def load_json(*parts):
        if parts[-1] != "rehearsal.json":
            return real(*parts)
        return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                          .replace("criteo_ffm", "tiny_criteo_ffm"))

    monkeypatch.setattr(R, "load_json", load_json)
    P._cache.clear()
    assert R.main(["--workload", "tiny_criteo_ffm_csv_text", "--seed",
                   str(seed), "--seconds", "3", "--trace", str(trace),
                   "--rehearse"]) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("seed,trace", [(2_147_555_055, 1), (55, 0)])
def test_the_criteo_cell_rehearses_correct_on_the_cpu(monkeypatch, capsys,
                                                      seed, trace):
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
        assert values.pop("put_bytes_per_row") == 164.0
        assert values.pop("dense_plane_bytes_per_row") == 156.0
        share = values.pop("csv_empty_cell_share")
        assert abs(share - (13 * 0.25 + 26 * 0.05) / 39) < 0.01
        assert "parse_busy_s_per_mrow" in values
    assert values and all(v is None for v in values.values()), values


def test_hash_bins_one_off_at_the_learner_reads_correct_false(monkeypatch,
                                                              capsys):
    from cellbench.learners import ffm_criteo

    sound = ffm_criteo.Adapter.device_iter_kwargs

    def kwargs(self):
        out = sound(self)
        out["parser_args"]["hash_bins"] -= 1
        return out

    monkeypatch.setattr(ffm_criteo.Adapter, "device_iter_kwargs", kwargs)
    line, out = _rehearse(monkeypatch, capsys, 2_147_555_056, 0)
    assert line["correct"] is False
    bad = [ln for ln in out.splitlines() if ln.endswith("NOT OK")]
    assert any("index sum" in ln for ln in bad)
    assert any("loss_gap" in ln for ln in bad)


@pytest.fixture(scope="module")
def tiny_controls(tmp_path_factory):
    """``(config, the four controls' numbers)`` of one seed's first three
    batches at the tiny size, with the generator's sums held to the ids the
    plain reader hashes on the way."""
    from cellbench import run as R
    from cellbench.generators import criteo_tsv
    from cellbench.learners import ffm_criteo

    cfg = R.load_json(R.HERE, "configs", "tiny_criteo_ffm.json")
    corpus = str(tmp_path_factory.mktemp("criteo") / "corpus.csv")
    sums = criteo_tsv.generate(cfg["generator"], 5, 3 * cfg["batch_size"],
                               corpus)
    ids, _, _, labels = plain.parse_hashed_rows(
        corpus, sums["rows"], C, cfg["csv"]["hash_bins"])
    assert sums["index_sum"] == int(ids.sum()) % 2 ** 32
    assert sums["label_sum"] == int(labels.sum())
    ref = ffm_criteo.reference_digest(cfg, 5, corpus)
    return cfg, ffm_criteo.control_numbers(cfg, 5, corpus, ref)


@pytest.mark.parametrize("control", ["", "zero_fields.", "no_position.",
                                     "dropped_empties."])
def test_every_control_fails_a_limit_at_the_tiny_size(tiny_controls, control):
    cfg, numbers = tiny_controls
    assert any(numbers[control + k] > limit
               for k, limit in cfg["limits"].items())


# ---------------------------------------------------------------------------
# the benchmark's new entries
# ---------------------------------------------------------------------------

def test_the_new_entries_are_lawful_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["criteo_ffm_csv_text"]
    assert cell == dict(cell, config="criteo_ffm", traffic="csv_text_epochs",
                        chips=1)
    entry = {c["name"]: c for c in bench["configs"]}["criteo_ffm"]
    assert entry["reduced"] == ["rows"] and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"] and list(cfg["reduced"]) == ["rows"]
    assert (cfg["num_features"], cfg["num_fields"], cfg["num_factors"],
            cfg["columns"], cfg["max_nnz"], cfg["csv"]["hash_bins"]) \
        == (1_000_000, 39, 4, 39, 39, 1_000_000)
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry
    mine = [m["name"] for m in bench["per_layer"]
            if "criteo_ffm_csv_text" in m.get("workloads", ())]
    assert mine[-3:] == ["ffm_interaction_device_ms",
                         "ffm_pair_kernels_roofline", "csv_empty_cell_share"]
    for name in mine:
        assert os.path.exists(os.path.join(
            ROOT, "cellbench", "metrics", name + ".json"))
    quota = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(bench["workloads"]) == 13 and len(quota) == 2


def test_the_pair_kernels_bytes_are_three_passes_over_the_rows():
    from cellbench.costs_ffm_criteo import ffm_pair_kernels_bytes

    slots = 16_384 * 39
    assert ffm_pair_kernels_bytes(39, 4, 16_384, 39) \
        == 3 * slots * 156 * 4 + 2 * slots * 5
