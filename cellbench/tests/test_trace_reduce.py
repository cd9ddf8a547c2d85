"""The reduction from a profiler trace to numbers: the interval
arithmetic on made-up intervals, and the whole reduction on a small trace
recorded on the chip (``recorded/epoch_boundary.xplane.pb``: the device's
``Steps`` / ``XLA Modules`` / ``XLA Ops`` lines and the harness's own host
spans around one epoch boundary of ``kdd12_fm_text``, cut out of a run of
PR 23 with ``tools/trim_trace.py``)."""

import os

import pytest

from cellbench import trace_reduce as T

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded", "epoch_boundary.xplane.pb")


def test_merge_length_subtract_gaps():
    merged = T.merge([(5, 7), (0, 2), (1, 3), (7, 7), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert T.length(merged) == 7
    assert T.subtract([(0, 10)], merged) == [(3, 5), (9, 10)]
    assert T.subtract([(1, 2), (4, 6), (8, 12)], merged) == [(4, 5), (9, 12)]
    assert T.gaps(merged, -1, 9) == [(-1, 0), (3, 5)]
    assert T.clip(merged, 2, 6) == [(2, 3), (5, 6)]


def test_short_names():
    assert T.short_name(
        "%fusion.3 = f32[54686453,8]{0,1:T(8,128)} fusion(f32[54686453,8]{0,1}"
        " %b, s32[1048576]{0} %c), kind=kCustom, calls=%fused_computation.3"
    ) == "fusion.3 f32[54686453,8] fusion kCustom"
    name = T.short_name("%all-reduce.1 = f32[54686453,8]{0,1} all-reduce("
                        "f32[54686453,8]{0,1} %fusion.3), channel_id=1")
    assert name == "all-reduce.1 f32[54686453,8] all-reduce"
    assert T.COLLECTIVE.match(name)
    assert not T.COLLECTIVE.match(T.short_name(
        "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), kind=kLoop"))
    assert T.short_name("jit_step(123)") == "jit_step(123)"


def test_label_takes_the_span_that_covers_most():
    spans = [("cellbench:step_dispatch", 0.0, 10.0),
             ("cellbench:epoch_sync", 10.0, 50.0),
             ("cellbench:epoch_reset", 50.0, 60.0)]
    assert T._label((8.0, 30.0), spans, "cellbench:") == "epoch_sync"
    assert T._label((52.0, 58.0), spans, "cellbench:") == "epoch_reset"
    assert T._label((70.0, 80.0), spans, "cellbench:") == "(no host span)"


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace in this checkout")
    return T.reduce_trace(RECORDED, module_pattern="^jit_step$")


def test_recorded_trace_reduces_to_what_was_read_by_hand(recorded):
    expect = __import__("json").load(open(RECORDED + ".expect.json"))
    assert recorded["chips"] == 1
    assert recorded["step"]["executions_per_chip"] == expect["steps"]
    assert recorded["step"]["device_s_per_execution"] == pytest.approx(
        expect["step_device_s"], rel=1e-6)
    assert recorded["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert recorded["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert 0 < recorded["busy_s"] < recorded["window_s"]
    assert recorded["collective_s"] == 0.0
    assert recorded["device_ops"][0][0] == expect["top_op"]
    assert len(recorded["device_ops"]) <= 10 and len(recorded["idle_gaps"]) <= 10
    # the one long gap of the cut lies under the epoch boundary's spans
    gaps = dict(recorded["idle_gaps"])
    assert max(gaps, key=gaps.get) == expect["top_gap"]
    idle = recorded["window_s"] - recorded["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_trace_without_device_operations_is_refused(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no operation ran on a TPU"):
        T.reduce_trace(T.find_xplane(str(tmp_path)))
