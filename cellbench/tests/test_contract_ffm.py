"""``kdd12_ffm``'s own pin (PR 26). ``test_contract.py::test_no_width_is_cut``
pins the factorization machine's widths (54,686,452 features, 8 factors,
``adam``) on *every* configuration, so it fails on this one by
construction; a ``model_config`` PR may not edit it, and the next
``benchmark`` issue scopes it by ``learner`` (PERF.md §7, ROADMAP S0).
Until then this file holds the field-aware configuration to what its
source publishes."""

import json
import os

from cellbench import run as R


def _bench():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_kdd12_ffm_keeps_libffms_widths():
    config = R.load_json(R.HERE, "configs", "kdd12_ffm.json")
    assert config["learner"] == "ffm" and config["optimizer"] == "adagrad"
    assert config["num_fields"] == 11 and config["num_factors"] == 4
    assert config["max_nnz"] == 16 and config["batch_size"] == 65_536
    assert config["dtype"] == "float32" and config["normalize"] is True
    assert config["learning_rate"] == 0.2 and config["l2"] == 2e-5
    assert config["fields"] is True and config["layout"] == "ell"
    # the published vocabulary beside the share one chip of four holds
    assert config["source_num_features"] == 54_686_452
    assert config["num_features"] == 13_671_613 == 54_686_452 // 4
    assert config["generator"]["num_features"] == config["num_features"]
    assert config["generator"]["fields"] == config["num_fields"]
    assert set(config["reduced"]) == {"num_features", "rows"}


def test_kdd12_ffm_entry_names_its_cuts_and_one_chip():
    bench = _bench()
    entry = {c["name"]: c for c in bench["configs"]}["kdd12_ffm"]
    assert entry["reduced"] == ["num_features", "rows"]
    assert entry["file"] == "cellbench/configs/kdd12_ffm.json"
    cells = [w for w in bench["workloads"] if w["config"] == "kdd12_ffm"]
    assert [w["name"] for w in cells] == ["kdd12_ffm_text"]
    assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "text_epochs"


def test_every_limit_stands_between_its_two_readings():
    """The configuration writes each limit with the readings it was set
    from: the largest of sound runs below it, the smallest of each control
    that it has to refuse above it (``None``: that control passes this
    number and fails by another)."""
    config = R.load_json(R.HERE, "configs", "kdd12_ffm.json")
    readings = dict(config["limit_readings"])
    assert readings.pop("what")
    assert set(readings) == set(config["limits"])
    refused = {"bfloat16": 0, "zero_fields": 0}
    for name, limit in config["limits"].items():
        r = readings[name]
        assert r["sound_max"] <= limit
        for control in refused:
            low = r["control_min"][control]
            if low is not None and low > limit:
                refused[control] += 1
    assert all(refused.values()), refused
    assert config["limits"]["untouched_gap"] == 0.0
    assert readings["untouched_gap"]["sound_max"] == 0.0
