import numpy as np

from cellbench.generators import fields_zipf_libfm as gen

PARAMS = dict(num_features=54_686_452, fields=11, zipf_s=1.1, label_noise=1.0)


def _python_text(ids, labels):
    return "".join(
        str(int(lab)) + "".join(f" {f}:{int(i)}:1" for f, i in enumerate(row))
        + "\n" for row, lab in zip(ids, labels)).encode()


def test_vocabularies_cover_the_table():
    vocabs = gen._field_vocabs(PARAMS["num_features"], PARAMS["fields"])
    assert vocabs.sum() == PARAMS["num_features"] and (vocabs > 0).all()


def test_format_matches_a_python_loop_at_every_digit_count():
    ids, labels = gen.draw_rows(PARAMS, np.random.SeedSequence(1), 2000)
    ids[:9, 0] = [0, 7, 10, 999, 9_999, 10_000, 100_005, 9_999_999, 54_686_451]
    assert ids.max() < PARAMS["num_features"]
    assert gen.format_rows(ids, labels) == _python_text(ids, labels)


def test_same_seed_same_bytes_whatever_the_threads(tmp_path):
    rows = gen.CHUNK_ROWS + 1000   # two chunks
    a, b, c = (str(tmp_path / n) for n in "abc")
    big = 2 ** 31 + 12345
    sums_a = gen.generate(PARAMS, big, rows, a, threads=1)
    sums_b = gen.generate(PARAMS, big, rows, b, threads=4)
    gen.generate(PARAMS, big + 1, rows, c, threads=4)
    assert open(a, "rb").read() == open(b, "rb").read()
    assert open(a, "rb").read() != open(c, "rb").read()
    assert sums_a == sums_b and sums_a["rows"] == rows


def test_checksums_are_the_files(tmp_path):
    path = str(tmp_path / "t.libfm")
    sums = gen.generate(PARAMS, 3, 5000, path)
    idx, labels = [], []
    for line in open(path, "rb"):
        toks = line.split()
        labels.append(int(toks[0]))
        idx += [int(t.split(b":")[1]) for t in toks[1:]]
        assert len(toks) == 1 + PARAMS["fields"]
    assert sums["rows"] == 5000 and sums["label_sum"] == sum(labels)
    assert sums["index_sum"] == sum(idx) % 2 ** 32
    assert sums["index_sq_sum"] == sum(i * i for i in idx) % 2 ** 32
    assert 0.2 < np.mean(labels) < 0.8
