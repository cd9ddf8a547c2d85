"""The service-fed cell (``kdd12_fm_service``) at the tiny size on the CPU:
the whole harness through the process fleet, the traffic file against the
configuration, ``served``'s reasons one by one, and three broken fleets that
have to read ``correct`` false. ``rehearsal.json`` is the benchmark's own
file and has no tiny mirror of this cell, so ``BENCHMARK.json`` is mirrored
onto ``tiny_*`` names in memory, as ``tests/test_ffm.py`` does."""

import copy
import json

import pytest

from cellbench import run as R
from cellbench.feeds import service as feed
from cellbench.readers import _program as P

IDLE_WORKER = "workers that served no part in the window"


@pytest.fixture
def mirrored(monkeypatch):
    real = R.load_json

    def load_json(*parts):
        if parts[-1] == "rehearsal.json":
            return json.loads(json.dumps(real(R.ROOT, "BENCHMARK.json"))
                              .replace("kdd12_", "tiny_"))
        if parts[-1] == "service_text_epochs.json":
            # the bound cut with the corpus: under a part, as the cell's is
            return dict(real(*parts), frame_store_bytes=real(
                R.HERE, "configs", "tiny_fm_svc.json")["service"][
                    "frame_store_bytes"])
        return real(*parts)

    monkeypatch.setattr(R, "load_json", load_json)
    P._cache.clear()


def _run(capsys, trace=0, seconds=1.0):
    rc = R.main(["--workload", "tiny_fm_service", "--seed", "2147483999",
                 "--seconds", str(seconds), "--trace", str(trace),
                 "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out


def _not_ok(out):
    return [ln for ln in out.splitlines() if ln.endswith("NOT OK")]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_rehearses_on_the_cpu(mirrored, capsys, trace):
    line, out = _run(capsys, trace)
    # `served` among the comparisons: both workers serve (a bounded store
    # takes one part at a time at this size) and every epoch is parsed again
    assert not _not_ok(out), _not_ok(out)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["rehearsal"] is True
    assert "service fleet: dispatcher" in out
    values = {k: v["value"] for k, v in line["metrics"].items()}
    if trace:
        # a CPU run reports what was counted, never a time: the frames'
        # bytes a row, 8 of offset, 4 of label and 16 a slot (index and
        # field at 8 bytes? the reading says) plus header, meta and crc
        wire = values.pop("wire_bytes_per_row")
        assert 100.0 < wire < 400.0
        assert values.pop("put_bytes_per_row") > 100.0
        assert {"service_recv_busy_s_per_mrow",
                "service_decode_busy_s_per_mrow",
                "service_fleet_cpu_s_per_mrow"} <= set(values)
    assert all(v is None for v in values.values()), values


def test_traffic_file_and_configuration_state_one_fleet():
    traffic = R.load_json(R.HERE, "traffic", "service_text_epochs.json")
    for name in ("kdd12_fm_svc", "tiny_fm_svc"):
        stated = R.load_json(R.HERE, "configs", name + ".json")["service"]
        shared = set(traffic) & set(stated)
        assert {"workers", "num_parts", "wire", "compression", "fastpath",
                "warm_tier", "frame_store_bytes"} <= shared
        # the tiny mirror's bound is cut with its corpus (the fixture
        # puts it in the traffic's place); everything else is one fleet
        assert all(traffic[k] == stated[k] for k in shared
                   if (name, k) != ("tiny_fm_svc", "frame_store_bytes"))
    # under a part of the encoded corpus, so that a worker holds the part
    # it serves and the part it parses next, and no epoch
    real = R.load_json(R.HERE, "configs", "kdd12_fm_svc.json")
    part_rows = real["rows"] // traffic["num_parts"]
    assert 200 * part_rows < traffic["frame_store_bytes"] < 2 * 200 * part_rows
    assert traffic["feed"] == "service" and traffic["workers"] == 2
    assert traffic["num_parts"] == 8 and traffic["wire"] == 2
    real = R.load_json(R.HERE, "configs", "kdd12_fm_svc.json")
    base = R.load_json(R.HERE, "configs", "kdd12_fm.json")
    # the model, the corpus and the limits are kdd12_fm's
    for key in ("learner", "num_features", "num_factors", "dtype",
                "optimizer", "learning_rate", "layout", "max_nnz",
                "batch_size", "format", "rows", "generator", "limits",
                "reduced", "chips"):
        assert real[key] == base[key], key


def _stats(workers=("a:1", "b:2"), **service):
    entry = {"wire_version": 2, "frames": 0, "wire_bytes": 0,
             "fastpath_blocks": 0, "parts_by_worker": {}, "retries": 0,
             "failovers": 0, "giveups": 0, "fleet_workers": list(workers),
             "parts_granted": 0}
    entry.update(service)
    return {"service": entry, "cache_state": None, "snapshot_state": None}


def test_served_gives_each_reason():
    before = _stats()
    sound = _stats(frames=600, wire_bytes=10**9,
                   parts_by_worker={"a:1": 4, "b:2": 4}, parts_granted=12)
    assert feed.served(before, sound) == []

    def reasons(**change):
        after = copy.deepcopy(sound)
        for key, value in change.items():
            if key in after:
                after[key] = value
            else:
                after["service"][key] = value
        return " | ".join(feed.served(before, after))

    assert "wire version 1" in reasons(wire_version=1)
    assert "no frames" in reasons(frames=0)
    assert "no wire_bytes" in reasons(wire_bytes=0)
    assert "fast path" in reasons(fastpath_blocks=3)
    assert IDLE_WORKER in reasons(parts_by_worker={"a:1": 8})
    assert "did not start" in reasons(
        parts_by_worker={"a:1": 4, "b:2": 3, "c:3": 1})
    # eight parts streamed and seven parsed: one came from frames kept
    assert "frames the workers kept" in reasons(parts_granted=7)
    assert "no part was granted" in reasons(parts_granted=0)
    for key in ("retries", "failovers", "giveups"):
        assert key in reasons(**{key: 1})
    assert "warm tier" in reasons(cache_state="warm")
    local = {"cache_state": None, "snapshot_state": None}
    assert "no ServiceParser" in " ".join(feed.served(local, local))


# ---------------- broken fleets: ``correct`` has to come out false --------

def test_a_fleet_that_serves_a_part_twice(mirrored, capsys, monkeypatch):
    from dmlc_tpu.service import client

    sound = client.ServiceParser.next_block

    def next_block(self):
        again = self.__dict__.get("_again")
        if again:
            return again.pop(0)
        blk = sound(self)
        if blk is not None and self._part == 3:
            self.__dict__.setdefault("_part3", []).append(blk)
        elif self.__dict__.get("_part3"):
            # part 3 has closed: the fleet serves it once more
            self._again = self.__dict__.pop("_part3") + [blk]
            return self._again.pop(0)
        return blk

    monkeypatch.setattr(client.ServiceParser, "next_block", next_block)
    line, out = _run(capsys)
    assert line["correct"] is False
    assert "epoch rows / index sum" in "\n".join(_not_ok(out))


def test_a_client_that_fell_back_to_parsing_in_process(mirrored, capsys,
                                                      monkeypatch):
    from cellbench.feeds import text

    monkeypatch.setattr(feed, "open_feed", text.open_feed)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = "\n".join(_not_ok(out))
    assert "tier 'service' served" in bad and "no ServiceParser" in bad


def test_blocks_off_the_fast_path(mirrored, capsys, monkeypatch):
    from dmlc_tpu.service import client

    sound = client.ServiceParser.service_stats
    monkeypatch.setattr(
        client.ServiceParser, "service_stats",
        lambda self: dict(sound(self), fastpath_blocks=5))
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = "\n".join(_not_ok(out))
    assert "tier 'service' served" in bad and "fast path" in bad


def test_workers_that_keep_every_frame(mirrored, capsys, monkeypatch):
    """The fleet as the service runs by default: the corpus parsed once,
    in set-up, and the window served from the workers' frame stores."""
    sound = feed.Fleet._start

    def start(self, name, env, *args):
        if "--frame-store-bytes" in args:
            args = args[:args.index("--frame-store-bytes")]
        return sound(self, name, env, *args)

    monkeypatch.setattr(feed.Fleet, "_start", start)
    line, out = _run(capsys)
    assert line["correct"] is False
    bad = "\n".join(_not_ok(out))
    assert "tier 'service' served" in bad and "frames the workers kept" in bad
