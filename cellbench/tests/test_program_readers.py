"""The readers of the program's own tracing (PR 24), on a cut of a chip
trace that holds ``dmlc_tpu:`` spans
(``recorded/program_spans.xplane.pb``: one epoch boundary of
``kdd12_fm_text``, cut with ``tools/trim_spans.py``; its ``.expect.json``
holds the learner's ``hlo_scopes()`` of that run and what was read off
the trace by hand), on the program's span ring, and on a program that
lacks what they read (the parent commit)."""

import json
import os
import types

import pytest

from cellbench import run as R
from cellbench import trace_reduce as T
from cellbench.readers import _program as P

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "program_spans.xplane.pb")
OLD = os.path.join(HERE, "recorded", "epoch_boundary.xplane.pb")
NEW = ("fm_gather_device_ms", "fm_grad_scatter_device_ms",
       "fm_optimizer_device_ms", "epoch_turnaround_ms",
       "idle_unattributed_share", "jit_compile_s")


def spec(name):
    return R.load_json(R.HERE, "metrics", name + ".json")


def read(name, ctx):
    s = spec(name)
    return R.plugin("readers", s["reader"]).read(ctx, s)


@pytest.fixture
def expect():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace in this checkout")
    with open(RECORDED + ".expect.json") as f:
        return json.load(f)


def ctx_for(path, monkeypatch, scopes=None, pipeline=None):
    P._cache.clear()
    monkeypatch.setattr(P, "find_trace", lambda ctx=None: path)
    learner = types.SimpleNamespace()
    if scopes is not None:
        learner.hlo_scopes = lambda: scopes
    adapter = types.SimpleNamespace(learner=learner,
                                    config={"step_module": "^jit_step$"})
    return types.SimpleNamespace(trace={}, adapter=adapter,
                                 stats_end={"pipeline": pipeline})


def test_scope_metrics_on_the_recorded_trace(expect, monkeypatch, capsys):
    ctx = ctx_for(RECORDED, monkeypatch, expect["hlo_scopes"])
    got = {n: read(n, ctx) for n in NEW[:3]}
    for name, value in got.items():
        assert value == pytest.approx(expect[name], rel=1e-6), name
    step_ms = 1e3 * T.reduce_trace(
        RECORDED, module_pattern="^jit_step$")["step"][
            "device_s_per_execution"]
    assert step_ms == pytest.approx(expect["step_device_ms"], rel=1e-6)
    # the three are disjoint parts of the step, and nearly all of it
    assert 0.95 * step_ms <= sum(got.values()) <= step_ms
    assert got["fm_grad_scatter_device_ms"] > got["fm_gather_device_ms"] \
        > got["fm_optimizer_device_ms"] > 0
    # the reader knows no model: what it counts is what the metric files
    # that name it include, and the remainder's line comes from those
    from cellbench.readers import scope_device_ms

    assert set(scope_device_ms.scope_metrics()) == set(NEW[:3])
    out = capsys.readouterr().out
    assert "step operations that no scope metric counts: " \
        f"{expect['outside_scope_metrics_ms']:.3f} ms a step" in out
    assert sum(got.values()) + expect["outside_scope_metrics_ms"] == \
        pytest.approx(step_ms, rel=1e-6)


def test_scope_metrics_refuse_names_of_another_program(expect, monkeypatch,
                                                       capsys):
    """``hlo_scopes()`` compiles the step a second time; if an operation
    the step ran is not among its instructions, the names belong to
    another program and no scope metric is given."""
    scopes = dict(expect["hlo_scopes"])
    gone = next(k for k, v in scopes.items() if "fm_optimizer" in v)
    del scopes[gone]
    ctx = ctx_for(RECORDED, monkeypatch, scopes)
    assert [read(n, ctx) for n in NEW[:3]] == [None, None, None]
    out = capsys.readouterr().out
    assert "hlo_scopes() does not hold 1 of the" in out and gone in out


def test_idle_by_program_span_on_the_recorded_trace(expect, monkeypatch,
                                                    capsys):
    ctx = ctx_for(RECORDED, monkeypatch)
    share = read("idle_unattributed_share", ctx)
    # the expectation was read off the trace at 0.1 us a sample
    assert share == pytest.approx(expect["idle_unattributed_share"],
                                  rel=2e-3)
    by_span = P.idle_by_span(P.loaded(RECORDED))
    reduced = T.reduce_trace(RECORDED, module_pattern="^jit_step$")
    assert sum(by_span.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    # a share of the window; `next` and `first_batch` only wrap the stages
    # and cover nothing themselves
    containers = spec("idle_unattributed_share")["containers"]
    assert containers == ["next", "first_batch"]
    assert share == pytest.approx(100 * sum(
        v for k, v in by_span.items()
        if k in containers + ["(no host span)"]) / reduced["window_s"])
    assert by_span["next"] > by_span["(no host span)"] > 0
    # the epoch boundary's gap is split over the reset, the producer's
    # start and the first batch's stages, the producer threads' included
    assert {"epoch_reset", "producer_start"} <= set(by_span)
    assert set(by_span) & {"parse", "convert", "dispatch"}
    out = capsys.readouterr().out
    assert "idle seconds by program span" in out
    assert "under no stage span" in out


def test_trim_spans_keeps_the_program_spans_of_every_thread(expect):
    names = {s[0] for s in P.loaded(RECORDED)["host_spans"]}
    assert {"dmlc_tpu:epoch_reset", "dmlc_tpu:first_batch",
            "dmlc_tpu:producer_start", "dmlc_tpu:next", "dmlc_tpu:parse",
            "dmlc_tpu:convert", "dmlc_tpu:dispatch",
            "dmlc_tpu:step_dispatch"} <= names
    from cellbench.tools import trim_spans, trim_trace

    with open(OLD, "rb") as f:
        raw = f.read()     # holds the harness's spans only: both tools agree
    assert trim_spans.trim(raw, 0.0, 10.0) == trim_trace.trim(raw, 0.0, 10.0)
    assert len(trim_spans.trim(raw, 0.0, 10.0, ("dmlc_tpu:",))) < len(raw)


def test_epoch_turnaround_pairs_reset_with_the_next_first_batch(
        monkeypatch, capsys):
    from dmlc_tpu.utils import telemetry

    ctx = ctx_for(None, monkeypatch, pipeline="cellbench-test-pipe")
    with telemetry.scope("cellbench-test-pipe"):
        t = 100.0
        for reset_s, first_s in ((0.002, 0.160), (0.004, 0.170),
                                 (0.003, 0.200)):
            telemetry.record_span("next", t, 0.001)
            telemetry.record_span("epoch_reset", t + 1, reset_s)
            telemetry.record_span("first_batch", t + 2, first_s)
            t += 10
        telemetry.record_span("epoch_reset", t, 0.5)   # no first batch after
    with telemetry.scope("another-pipe"):
        telemetry.record_span("epoch_reset", 50.0, 9.0)
        telemetry.record_span("first_batch", 51.0, 9.0)
    assert read("epoch_turnaround_ms", ctx) == pytest.approx(174.0)
    assert "3 pairs" in capsys.readouterr().out


def test_jit_compile_s_is_the_counter_at_the_first_read(monkeypatch):
    import jax
    import jax.numpy as jnp

    from dmlc_tpu.utils import telemetry

    telemetry.arm_compile_counters()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    ctx = ctx_for(None, monkeypatch)
    first = read("jit_compile_s", ctx)
    assert first == telemetry.compile_counters()["jit_compile_seconds"] > 0
    jax.jit(lambda x: x * 5 - 2)(jnp.ones(9)).block_until_ready()
    assert read("jit_compile_s", ctx) == first   # a reader's own compiles stay out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reads_nothing(name, monkeypatch):
    """On the parent commit the readers return ``None`` and do not raise:
    a learner without ``hlo_scopes``, a trace without a ``dmlc_tpu:`` span,
    a ring without ``epoch_reset``, a telemetry without the counters."""
    from dmlc_tpu.utils import telemetry

    ctx = ctx_for(OLD, monkeypatch, pipeline="no-such-pipeline")
    monkeypatch.delattr(telemetry, "compile_counters")
    assert read(name, ctx) is None
    untraced = ctx_for(None, monkeypatch, scopes={"fusion.3": "x"},
                       pipeline="no-such-pipeline")
    assert read(name, untraced) is None


def test_cpu_rehearsal_prints_no_device_number_for_the_new_metrics(
        monkeypatch, capsys):
    """One tiny cell, traced, on the CPU backend, with this PR's entries
    of ``BENCHMARK.json`` mirrored onto the tiny cells (``rehearsal.json``
    is a file the benchmark already had; a PR of this kind may not edit
    it): the run is correct and no new metric carries a value."""
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    mirrored = [dict(m, workloads=[w.replace("kdd12_", "tiny_")
                                   for w in m["workloads"]])
                for m in bench["per_layer"] if m["name"] in NEW]
    assert len(mirrored) == len(NEW)
    real = R.load_json

    def load_json(*parts):
        found = real(*parts)
        if parts[-1] == "rehearsal.json":
            found["per_layer"] += mirrored
        return found

    monkeypatch.setattr(R, "load_json", load_json)
    P._cache.clear()
    assert R.main(["--workload", "tiny_fm_text", "--seed", "3", "--seconds",
                   "1", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    for name in NEW:
        assert line["metrics"].get(name, {"value": None})["value"] is None
