"""``BENCHMARK.json`` against the shape its contract fixes, and against the
files it names."""

import json
import os
import re

import pytest

from cellbench import run as R

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["cellbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(R.ROOT, "BENCHMARK.json")) <= 64 << 10
    cells = len(bench["workloads"])
    assert 1 <= cells <= 24 and 1 <= len(bench["configs"]) <= 24
    # a full check with all 24 cells fits the driver's 43200 s
    full = (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert full <= 43200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, cells // 4)


def test_entries_have_exactly_their_keys_and_lawful_names(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("cellbench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for text in [c["source"] for c in bench["configs"]] + \
            [x["why"] for x in bench["configs"] + bench["workloads"]] + \
            [m["layer"] for m in bench["per_layer"]] + bench["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_name_resolves_to_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cell, config, traffic, layer, end = R.find_cell(w["name"], False)
        used.add(w["config"])
        assert config["chips"] == w["chips"]
        R.plugin("feeds", traffic["feed"])
        R.plugin("learners", config["learner"])
        R.plugin("generators", config["generator"]["name"])
        assert {"loss_gap", "grad_norm_gap", "update_norm_gap",
                "untouched_gap"} <= set(config["limits"])
        assert set(configs[w["config"]]["reduced"]) == set(config["reduced"])
        assert layer and len(end) >= 2
        for m in layer:
            spec = R.load_json(R.HERE, "metrics", m["name"] + ".json")
            assert hasattr(R.plugin("readers", spec["reader"]), "read")
    assert used == set(configs)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
    layers = {m["layer"] for m in bench["per_layer"]}
    perf = open(os.path.join(R.ROOT, "PERF.md")).read()
    assert all(layer in perf for layer in layers)


def test_no_width_is_cut(bench):
    for c in bench["configs"]:
        config = R.load_json(R.ROOT, c["file"])
        assert config["num_features"] == 54_686_452
        assert config["num_factors"] == 8 and config["dtype"] == "float32"
        assert config["batch_size"] == 65_536 and config["max_nnz"] == 16
        assert config["optimizer"] == "adam"
