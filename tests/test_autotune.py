"""Attribution-driven online pipeline autotuner (ISSUE 10).

Covers the controller's behavior on synthetic stage profiles (parse-bound
grows parse_workers, convert-bound grows convert_ahead, transfer-bound
no-ops, hysteresis damps oscillation, resilience cooldown, env bounds),
the validated knob-table env parsing, the live-resize primitives
(OrderedWorkerPool / ParallelTextParser) with order preserved, the
consumer-side input-wait counter (the "stall reads 0.000" artifact,
closed), byte-identical delivery and checkpoints across mid-epoch knob
changes, DeviceIter(autotune=True) end-to-end convergence, the service
worker's parse-tier self-tune, and the lint gate for ad-hoc tunable env
reads.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np
import pytest

from dmlc_tpu.data import autotune, create_parser, create_row_block_iter
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.io.threaded_iter import OrderedWorkerPool, ThreadedIter
from dmlc_tpu.utils import knobs, telemetry
from dmlc_tpu.utils.check import DMLCError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for name in ("DMLC_TPU_PARSE_WORKERS", "DMLC_TPU_CONVERT_WORKERS",
                 "DMLC_TPU_PLAN_READ_WORKERS",
                 "DMLC_TPU_SNAPSHOT_READ_WORKERS", "DMLC_TPU_PREFETCH",
                 "DMLC_TPU_CONVERT_AHEAD", "DMLC_TPU_AUTOTUNE",
                 "DMLC_TPU_AUTOTUNE_INTERVAL"):
        monkeypatch.delenv(name, raising=False)
    for name in list(os.environ):
        if name.startswith(("DMLC_TPU_AUTOTUNE_MIN_",
                            "DMLC_TPU_AUTOTUNE_MAX_")):
            monkeypatch.delenv(name, raising=False)
    # worker-knob caps default to this host's CPU count (1 in CI): raise
    # them so growth paths are exercisable — which also exercises the
    # DMLC_TPU_AUTOTUNE_MAX_* bound machinery itself
    monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PARSE_WORKERS", "6")
    monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PLAN_READ_WORKERS", "4")
    monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_SNAPSHOT_READ_WORKERS", "4")
    yield


# ---------------- corpora ----------------

def _write_libsvm(path, n=2000, d=12, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(n):
            feats = " ".join(f"{j}:{rng.standard_normal():.5f}"
                             for j in range(d))
            f.write(f"{i % 2} {feats}\n")
    return str(path)


# ---------------- knob table / env validation (satellite 2) ----------------

class TestKnobTable:
    @pytest.mark.parametrize("name,env", [
        ("parse_workers", "DMLC_TPU_PARSE_WORKERS"),
        ("convert_workers", "DMLC_TPU_CONVERT_WORKERS"),
        ("plan_read_workers", "DMLC_TPU_PLAN_READ_WORKERS"),
        ("snapshot_read_workers", "DMLC_TPU_SNAPSHOT_READ_WORKERS"),
        ("prefetch", "DMLC_TPU_PREFETCH"),
        ("convert_ahead", "DMLC_TPU_CONVERT_AHEAD"),
        ("hedge_factor", "DMLC_TPU_HEDGE_FACTOR"),
        ("drain_deadline", "DMLC_TPU_DRAIN_DEADLINE"),
    ])
    def test_env_garbage_zero_negative_reject_loudly(self, name, env,
                                                     monkeypatch):
        for bad in ("garbage", "0", "-3", "2.5", ""):
            monkeypatch.setenv(env, bad)
            if bad == "":
                assert knobs.resolve(name) >= 1  # unset/blank -> default
            else:
                with pytest.raises(DMLCError) as exc:
                    knobs.resolve(name)
                assert env in str(exc.value)

    def test_env_and_explicit_resolution(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PARSE_WORKERS", "3")
        assert knobs.resolve("parse_workers") == 3
        # explicit arg wins over env, keeps the historical clamp floor
        assert knobs.resolve("parse_workers", 5) == 5
        assert knobs.resolve("parse_workers", 0) == 1

    def test_unknown_knob_rejects(self):
        with pytest.raises(DMLCError):
            knobs.resolve("no_such_knob")
        with pytest.raises(DMLCError):
            knobs.bounds("no_such_knob")

    def test_use_site_parse_workers(self, tmp_path, monkeypatch):
        # the historical per-site `or`-default parse silently fell back
        # on garbage; the consolidated helper fails the build loudly
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=50, d=4)
        monkeypatch.setenv("DMLC_TPU_PARSE_WORKERS", "zero")
        with pytest.raises(DMLCError):
            # engine=python pins the route that sizes the fan-out (the
            # native reader keeps its own C++ threading and never reads
            # the knob)
            create_parser(corpus + "?engine=python", 0, 1, "libsvm",
                          threaded=True)

    def test_autotune_bounds_env(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PREFETCH", "8")
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MIN_PREFETCH", "2")
        assert knobs.bounds("prefetch") == (2, 8)
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PREFETCH", "junk")
        with pytest.raises(DMLCError):
            knobs.bounds("prefetch")
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PREFETCH", "1")
        with pytest.raises(DMLCError):  # inverted pair
            knobs.bounds("prefetch")

    def test_autotune_interval_validation(self, monkeypatch):
        assert knobs.autotune_interval() == 0
        assert knobs.autotune_interval(7) == 7
        with pytest.raises(DMLCError):
            knobs.autotune_interval(-1)
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_INTERVAL", "x")
        with pytest.raises(DMLCError):
            knobs.autotune_interval()
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_INTERVAL", "32")
        assert knobs.autotune_interval() == 32

    def test_master_switch(self, monkeypatch):
        assert knobs.autotune_enabled() is False
        assert knobs.autotune_enabled(True) is True
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE", "1")
        assert knobs.autotune_enabled() is True
        assert knobs.autotune_enabled(False) is False


# ---------------- controller on synthetic stage profiles ----------------

def _mk_tuner(store, names, **kw):
    built = []
    for n in names:
        def apply(v, n=n):
            store[n] = int(v)
            return True

        built.append(autotune.Knob(n, get=lambda n=n: store[n],
                                   apply=apply))
    kw.setdefault("scope", "test-tuner")
    kw.setdefault("min_batches", 4)
    return autotune.AutoTuner(built, **kw)


def _win(wall=1.0, batches=100, wait_frac=0.5, transfer=0.0, events=0,
         **busy):
    return {"wall": wall, "batches": batches,
            "input_wait": wait_frac * wall, "busy": busy,
            "transfer_est": transfer, "resilience_events": events}


class TestControllerProfiles:
    def test_parse_bound_grows_parse_workers(self):
        store = {"parse_workers": 2, "convert_ahead": 4}
        tuner = _mk_tuner(store, ("parse_workers", "convert_ahead"))
        for _ in range(3):
            d = tuner.step(_win(parse=0.8, convert=0.1))
        assert store["parse_workers"] > 2
        grows = [h for h in tuner.history if h["action"] == "grow"]
        assert grows and all(h["knob"] == "parse_workers" for h in grows)
        assert grows[0]["gap_stage"] == "parse"
        assert "rationale" in d

    def test_read_bound_also_grows_parse_workers(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",))
        tuner.step(_win(read=0.9))
        assert store["parse_workers"] == 3

    def test_convert_bound_grows_convert_ahead_and_ring(self):
        store = {"parse_workers": 2, "convert_ahead": 2}
        tuner = _mk_tuner(store, ("parse_workers", "convert_ahead"))
        for _ in range(3):
            tuner.step(_win(convert=0.9, parse=0.05))
        assert store["convert_ahead"] > 2
        assert store["parse_workers"] == 2

    def test_cache_and_snapshot_read_map_to_their_pools(self):
        store = {"plan_read_workers": 2, "snapshot_read_workers": 2}
        tuner = _mk_tuner(store, ("plan_read_workers",
                                  "snapshot_read_workers"))
        tuner.step(_win(cache_read=0.9))
        assert store["plan_read_workers"] == 3
        tuner.step(_win(snapshot_read=0.9))
        assert store["snapshot_read_workers"] == 3

    def test_dispatch_bound_grows_prefetch(self):
        store = {"prefetch": 2}
        tuner = _mk_tuner(store, ("prefetch",))
        tuner.step(_win(dispatch=0.9))
        assert store["prefetch"] == 3

    def test_transfer_bound_is_steady_no_op(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",))
        # consumer never waits: nothing to tune regardless of busy shape
        d1 = tuner.step(_win(wait_frac=0.01, parse=0.5))
        # waits exist but transfer dominates every supply stage: the
        # pipeline is device-bound — also steady
        d2 = tuner.step(_win(wait_frac=0.5, parse=0.2, transfer=0.8))
        assert d1["action"] == d2["action"] == "steady"
        assert d1["gap_stage"] == d2["gap_stage"] == "transfer"
        assert store["parse_workers"] == 2
        assert tuner.converged

    def test_hysteresis_reverts_and_damps_oscillation(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",), hold_steps=3)
        tuner.step(_win(batches=100, parse=0.8))       # grow 2 -> 3
        assert store["parse_workers"] == 3
        d = tuner.step(_win(batches=80, parse=0.8))    # -20%: revert
        assert d["action"] == "revert"
        assert store["parse_workers"] == 2
        # the reverted move is held for exactly hold_steps windows:
        # parse-bound windows cannot re-grow inside it (damped) ...
        for _ in range(3):
            d = tuner.step(_win(batches=100, parse=0.8))
            assert d["action"] == "bound"
            assert store["parse_workers"] == 2
        # ... and may retry after it expires
        d = tuner.step(_win(batches=100, parse=0.8))
        assert d["action"] == "grow"
        assert store["parse_workers"] == 3

    def test_hold_steps_one_still_holds_one_window(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",), hold_steps=1)
        tuner.step(_win(batches=100, parse=0.8))     # grow 2 -> 3
        tuner.step(_win(batches=50, parse=0.8))      # revert
        assert store["parse_workers"] == 2
        d = tuner.step(_win(batches=100, parse=0.8))  # held this window
        assert d["action"] == "bound"
        d = tuner.step(_win(batches=100, parse=0.8))  # then may retry
        assert d["action"] == "grow"

    def test_improvement_commits_and_keeps_climbing(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",))
        tuner.step(_win(batches=100, parse=0.8))       # grow 2 -> 3
        d = tuner.step(_win(batches=130, parse=0.8))   # +30%: commit+grow
        assert d["action"] == "grow"
        assert store["parse_workers"] == 4

    def test_resilience_event_cooldown(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",), cooldown_steps=2)
        d = tuner.step(_win(parse=0.9, events=3))
        assert d["action"] == "cooldown"
        d = tuner.step(_win(parse=0.9))
        assert d["action"] == "hold"
        assert store["parse_workers"] == 2
        d = tuner.step(_win(parse=0.9))
        assert d["action"] == "grow"

    def test_env_bounds_respected(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_AUTOTUNE_MAX_PARSE_WORKERS", "3")
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",))
        for _ in range(5):
            d = tuner.step(_win(parse=0.9))
        assert store["parse_workers"] == 3  # capped
        assert d["action"] == "bound"
        assert "DMLC_TPU_AUTOTUNE_MAX" in d["rationale"]

    def test_unavailable_knob_is_held_not_spun(self):
        calls = []

        def refuse(v):
            calls.append(v)
            return False

        k = autotune.Knob("parse_workers", get=lambda: 2, apply=refuse)
        tuner = autotune.AutoTuner([k], scope="t", min_batches=4)
        d = tuner.step(_win(parse=0.9))
        assert d["action"] == "bound"
        for _ in range(3):
            tuner.step(_win(parse=0.9))
        assert len(calls) == 1  # held, not retried every window

    def test_failed_revert_recorded_honestly(self):
        """A revert the component refuses (tier became unresizable
        between windows) must not be logged as a successful revert."""
        state = {"v": 2, "accept": True}

        def apply(v):
            if not state["accept"]:
                return False
            state["v"] = int(v)
            return True

        k = autotune.Knob("parse_workers", get=lambda: state["v"],
                          apply=apply)
        tuner = autotune.AutoTuner([k], scope="t", min_batches=4)
        tuner.step(_win(batches=100, parse=0.9))   # grow 2 -> 3
        assert state["v"] == 3
        state["accept"] = False                    # tier goes warm
        d = tuner.step(_win(batches=50, parse=0.9))  # -50%: revert fails
        assert d["action"] == "revert_failed"
        assert d["to"] == 3 and state["v"] == 3    # history == reality
        assert "REFUSED" in d["rationale"]

    def test_tiny_window_skips(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",))
        d = tuner.step(_win(batches=1, parse=0.9))
        assert d["action"] == "skip"
        assert store["parse_workers"] == 2

    def test_snapshot_schema_and_telemetry_mirrors(self):
        store = {"parse_workers": 2}
        tuner = _mk_tuner(store, ("parse_workers",), scope="snap-scope")
        tuner.step(_win(parse=0.9))
        snap = tuner.snapshot()
        assert snap["enabled"] is True
        assert snap["steps"] == 1 and snap["adjustments"] == 1
        assert snap["knobs"] == {"parse_workers": 3}
        assert snap["history"][-1]["action"] == "grow"
        rows = telemetry.REGISTRY.snapshot(
            telemetry.AUTOTUNE_KNOB_METRIC, pipeline="snap-scope")
        assert {r["labels"]["knob"]: r["value"] for r in rows} == {
            "parse_workers": 3.0}
        assert telemetry.REGISTRY.sum(
            telemetry.AUTOTUNE_STEP_METRIC, pipeline="snap-scope") >= 1
        assert telemetry.span_counts().get("autotune_step", 0) >= 1

    def test_efficiency_window_differences_cumulative_sideband(self):
        """Mid-stream re-deciders must see per-window efficiency: the
        cumulative sideband divides by the CURRENT width, so after a
        resize it mixes widths and goes stale."""
        # window 1: 2 workers fully busy for 1s
        s1 = {"parse_busy_seconds": 2.0, "parse_span_seconds": 1.0,
              "parse_workers": 2, "parse_parallelism_efficiency": 1.0}
        eff, prev = autotune.efficiency_window(None, s1)
        assert eff == pytest.approx(1.0)
        # window 2: resized to 3, again fully busy (busy += 3, span += 1)
        s2 = {"parse_busy_seconds": 5.0, "parse_span_seconds": 2.0,
              "parse_workers": 3,
              # the raw cumulative number is biased low (5 / (2*3)):
              "parse_parallelism_efficiency": 0.833}
        eff, prev = autotune.efficiency_window(prev, s2)
        assert eff == pytest.approx(1.0)  # the window was saturated
        # no progress in the window -> no measurement, never a div/0
        eff, _ = autotune.efficiency_window(prev, s2)
        assert eff is None
        assert autotune.efficiency_window(None, None) == (
            None, {"busy": 0.0, "span": 0.0})


# ---------------- live-resize primitives ----------------

class TestLiveResize:
    def test_pool_resize_preserves_order_and_content(self):
        pool = OrderedWorkerPool(lambda: iter(range(300)),
                                 lambda x: x * 2, num_workers=1,
                                 max_ahead=4)
        try:
            out = [pool.next() for _ in range(100)]
            assert pool.resize(4) == 4
            assert pool.num_workers == 4
            out += [pool.next() for _ in range(100)]
            pool.resize(1)
            pool.set_max_ahead(2)
            while (v := pool.next()) is not None:
                out.append(v)
            assert out == [2 * i for i in range(300)]
        finally:
            pool.destroy()

    def test_pool_shrink_then_grow_cancels_exit_credits(self):
        pool = OrderedWorkerPool(lambda: iter(range(50)), lambda x: x,
                                 num_workers=3, max_ahead=4)
        try:
            pool.resize(1)
            pool.resize(3)  # cancels pending exits / respawns
            assert [pool.next() for _ in range(50)] == list(range(50))
            assert pool.next() is None
        finally:
            pool.destroy()

    def test_threaded_iter_set_capacity(self):
        it = ThreadedIter.from_factory(lambda: iter(range(100)),
                                       max_capacity=2)
        try:
            out = [it.next() for _ in range(10)]
            it.set_capacity(8)
            while (v := it.next()) is not None:
                out.append(v)
            assert out == list(range(100))
        finally:
            it.destroy()

    def test_parallel_parser_resize_byte_identical(self, tmp_path):
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=1200, d=6)
        uri = corpus + "?engine=python"

        def drain(parser, resize_at=None, to=None):
            rows = []
            n = 0
            while (blk := parser.next_block()) is not None:
                rows.append(np.asarray(blk.value).copy())
                n += 1
                if resize_at is not None and n == resize_at:
                    assert parser.resize_parse_workers(to)
            parser.close()
            return np.concatenate(rows)

        static = drain(create_parser(uri, 0, 1, "libsvm", threaded=True,
                                     parse_workers=2, chunk_bytes=2048))
        resized = drain(create_parser(uri, 0, 1, "libsvm", threaded=True,
                                      parse_workers=2, chunk_bytes=2048),
                        resize_at=3, to=4)
        shrunk = drain(create_parser(uri, 0, 1, "libsvm", threaded=True,
                                     parse_workers=4, chunk_bytes=2048),
                       resize_at=2, to=1)
        np.testing.assert_array_equal(static, resized)
        np.testing.assert_array_equal(static, shrunk)


# ---------------- input-wait counter (satellite 1) ----------------

class TestInputWaitCounter:
    def test_transfer_bound_epoch_has_visible_input_wait(self, tmp_path,
                                                         monkeypatch):
        """The stall artifact: a transfer-bound epoch used
        to read stall_seconds ~0.000 while half the wall hid in the
        async blind spot. The sampled landings now feed a trustworthy
        input_wait counter the tuner reads."""
        import jax

        import dmlc_tpu.data.device as device_mod

        corpus = _write_libsvm(tmp_path / "c.libsvm", n=1000, d=6)
        real = jax.block_until_ready
        sleep_s = 0.004

        def slow(x):
            time.sleep(sleep_s)  # a slow link: every landing waits
            return real(x)

        monkeypatch.setattr(device_mod.jax, "block_until_ready", slow)
        parser = create_parser(corpus, 0, 1, "libsvm", threaded=True,
                               chunk_bytes=4096)
        it = DeviceIter(parser, num_col=6, batch_size=100, layout="dense",
                        transfer_sample=1)  # sample EVERY landing
        try:
            n = sum(1 for _ in it)
            stats = it.stats()
        finally:
            it.close()
        assert n == 10
        # the waiting is visible where the tuner looks...
        assert stats["input_wait_seconds"] >= 0.8 * n * sleep_s
        assert stats["stages"]["transfer"] >= 0.8 * n * sleep_s
        # ...even though the handle-wait stall metric alone barely moves
        # (the artifact: the producer runs ahead while landings block)
        assert stats["stall_seconds"] < stats["input_wait_seconds"]

    def test_stats_carry_input_wait_and_autotune_fields(self, tmp_path):
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=200, d=4)
        parser = create_parser(corpus, 0, 1, "libsvm", threaded=True)
        it = DeviceIter(parser, num_col=4, batch_size=64, layout="dense")
        try:
            for _ in it:
                pass
            stats = it.stats()
        finally:
            it.close()
        assert isinstance(stats["input_wait_seconds"], float)
        assert stats["autotune"] is None  # off by default


# ---------------- DeviceIter integration ----------------

class TestDeviceIterAutotune:
    def _packed(self, batch):
        return np.asarray(batch.packed)

    def test_checkpoint_byte_identical_across_live_knob_change(
            self, tmp_path):
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=3000, d=8)
        uri = corpus + "?engine=python"

        def build():
            parser = create_parser(uri, 0, 1, "libsvm", threaded=True,
                                   parse_workers=2, chunk_bytes=2048)
            return DeviceIter(parser, num_col=8, batch_size=128,
                              layout="dense", prefetch=2, convert_ahead=2)

        it = build()
        static = [self._packed(b) for b in it]
        it.close()

        # dynamic pipeline: resize EVERY tuned knob mid-epoch through the
        # same apply paths the controller uses, checkpoint right after
        it = build()
        dyn = []
        state = None
        for i, b in enumerate(it):
            dyn.append(self._packed(b))
            if i == 4:
                assert it._apply_convert_ahead(8)
                assert it._apply_prefetch(5)
                assert it._apply_parse_workers(4)
                state = it.state_dict()
        it.close()
        assert len(dyn) == len(static)
        for a, b in zip(static, dyn):
            np.testing.assert_array_equal(a, b)

        # the checkpoint taken across the live resize restores into a
        # FRESH statically-knobbed pipeline byte-identically
        it = build()
        it.load_state(state)
        tail = [self._packed(b) for b in it]
        it.close()
        assert len(tail) == len(static) - 5
        for a, b in zip(static[5:], tail):
            np.testing.assert_array_equal(a, b)

    def test_autotune_converges_to_transfer_bound(self, tmp_path):
        """Acceptance: from a deliberately starved config the controller
        reaches, within a bounded number of adjustment steps, a steady
        state whose gap_stage is transfer (the consumer stops waiting on
        the host pipeline)."""
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=4000, d=8)
        parser = create_parser(corpus + "?engine=python", 0, 1, "libsvm",
                               threaded=True, parse_workers=2,
                               chunk_bytes=8192)
        it = DeviceIter(parser, num_col=8, batch_size=128, layout="dense",
                        prefetch=1, convert_ahead=1,
                        autotune=True, autotune_interval=4)
        try:
            assert it.autotuner is not None
            for _ in range(10):
                for _ in it:
                    pass
                if it.autotuner.converged:
                    break
                it.reset()
            snap = it.stats()["autotune"]
        finally:
            it.close()
        assert snap["steps"] > 0
        steady = [h for h in snap["history"]
                  if h["action"] == "steady"]
        assert snap["converged"] and steady, snap
        assert all(h["gap_stage"] == "transfer" for h in steady)
        # bounded: the whole run adjusted knobs a sane number of times
        assert snap["adjustments"] <= 32
        # decisions are mirrored on the registry under the pipeline label
        rows = telemetry.REGISTRY.snapshot(
            telemetry.AUTOTUNE_KNOB_METRIC,
            pipeline=it.pipeline_label)
        assert {r["labels"]["knob"] for r in rows} >= {"prefetch",
                                                       "convert_ahead"}

    def test_parse_knob_seeds_from_explicit_width_on_cold_cache(
            self, tmp_path):
        """A cold BlockCacheIter builds its parser lazily — the tuner
        must seed the parse knob from the width the base WILL use, not
        the table default (a 'grow' from the default would silently
        shrink an explicitly wider pool)."""
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=400, d=4)
        parser = create_parser(corpus + "?engine=python", 0, 1, "libsvm",
                               threaded=True, parse_workers=5,
                               block_cache=str(tmp_path / "bc"),
                               chunk_bytes=2048)
        it = DeviceIter(parser, num_col=4, batch_size=64, layout="dense",
                        autotune=True)
        try:
            assert it._knob_parse_workers == 5
        finally:
            it.close()

    def test_resilience_sensor_monotonic_across_reset(self, tmp_path):
        """pipeline_restarts is a per-epoch budget counter (reset()
        zeroes it); the tuner's sensor must read the monotonic lifetime
        tally or a new epoch's early restarts clamp away under the
        previous epoch's count and never trigger the cooldown."""
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=300, d=4)
        parser = create_parser(corpus, 0, 1, "libsvm", threaded=True)
        it = DeviceIter(parser, num_col=4, batch_size=64, layout="dense",
                        autotune=True)
        try:
            for _ in it:
                pass
            it.pipeline_restarts = 2      # as _maybe_restart would
            it._faults_lifetime += 2
            m1 = it._autotune_mark_now()
            it.reset()                    # zeroes the per-epoch budget
            assert it.pipeline_restarts == 0
            m2 = it._autotune_mark_now()
            assert m2["res"] >= m1["res"]  # never rewinds
        finally:
            it.close()

    def test_autotune_epoch_boundary_only_by_default(self, tmp_path):
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=600, d=4)
        parser = create_parser(corpus, 0, 1, "libsvm", threaded=True)
        it = DeviceIter(parser, num_col=4, batch_size=64, layout="dense",
                        autotune=True)
        try:
            for _ in it:
                pass
            assert it.stats()["autotune"]["steps"] == 0  # no mid-epoch
            it.reset()  # first boundary only takes the mark
            for _ in it:
                pass
            it.reset()
            assert it.stats()["autotune"]["steps"] >= 1
        finally:
            it.close()


# ---------------- load-pass + service-worker parse tiers ----------------

class TestParseTierTuner:
    def test_decide_grow_shrink_hold(self, monkeypatch):
        t = autotune.ParseTierTuner(start=2)
        assert t.decide(0.9) == 3          # saturated -> grow
        assert t.decide(0.1) == 2          # idle -> shrink
        assert t.decide(0.5) == 2          # in band -> hold
        assert t.decide(None) == 2         # no measurement -> hold
        assert t.decide(0.9, workers=6) == 6  # at cap (env max 6)
        assert [h["rationale"] for h in t.history]
        snap = t.snapshot()
        assert snap["bounds"] == [1, 6]

    def test_basic_row_iter_load_pass_self_tunes(self, tmp_path):
        corpus = _write_libsvm(tmp_path / "c.libsvm", n=2500, d=6)
        it = create_row_block_iter(
            corpus + "?engine=python", parse_workers=2, chunk_bytes=512,
            autotune=True, silent=True)
        assert it.autotune is not None and it.autotune["enabled"]
        assert it.autotune["history"], "load pass made no tier decisions"

    def test_service_worker_self_tunes_between_parts(self, tmp_path):
        from dmlc_tpu.service import LocalFleet, ServiceParser

        corpus = _write_libsvm(tmp_path / "c.libsvm", n=800, d=5)
        fleet = LocalFleet(corpus, 2, num_workers=1,
                           parser={"format": "libsvm",
                                   "chunk_bytes": 4096},
                           autotune=True)
        client = None
        try:
            client = ServiceParser(fleet.address)
            blocks = 0
            while client.next_block() is not None:
                blocks += 1
            assert blocks > 0
            state = fleet.workers[0].autotune_state()
            assert state is not None and state["enabled"]
            assert state["history"], "worker made no tier decisions"
        finally:
            if client is not None:
                client.close()
            fleet.close()

    def test_worker_skips_retune_on_failed_part(self):
        """A failed part measures the failure (workers idle behind a
        dying stream), not the tier: no decision may come from it."""
        from dmlc_tpu.service import LocalFleet

        fleet = LocalFleet("/nonexistent/missing.libsvm", 1,
                           num_workers=1, parser={"format": "libsvm"},
                           autotune=True)
        try:
            worker = fleet.workers[0]
            deadline = time.time() + 10.0
            while time.time() < deadline:
                store = worker._store.get(("default", 0))
                if store is not None and store.complete:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("part 0 never completed")
            assert store.error is not None  # the parse did fail
            state = worker.autotune_state()
            assert state is not None and state["history"] == []
        finally:
            fleet.close()

    def test_worker_autotune_off_by_default(self, tmp_path):
        from dmlc_tpu.service import LocalFleet

        corpus = _write_libsvm(tmp_path / "c.libsvm", n=100, d=4)
        fleet = LocalFleet(corpus, 1, num_workers=1,
                           parser={"format": "libsvm"})
        try:
            assert fleet.workers[0].autotune_state() is None
        finally:
            fleet.close()


# ---------------- lint gate (satellite 5) ----------------

class TestKnobLintGate:
    def _scan(self):
        sys.path.insert(0, os.path.join(REPO, "bin"))
        try:
            import lint_metrics
        finally:
            sys.path.pop(0)
        return lint_metrics.scan_source

    def test_flags_adhoc_tunable_env_reads(self):
        scan = self._scan()
        bad = (
            'w = int(os.environ.get("DMLC_TPU_PARSE_WORKERS", "2") or 2)\n'
            'p = os.environ.get("DMLC_TPU_PREFETCH", "2")\n'
            'c = os.environ["DMLC_TPU_CONVERT_AHEAD"]\n'
            'a = os.environ.get("DMLC_TPU_AUTOTUNE_MAX_PREFETCH")\n'
            'g = int(os.getenv("DMLC_TPU_SNAPSHOT_READ_WORKERS", "2"))\n'
            '# os.environ.get("DMLC_TPU_PARSE_WORKERS") in comment: ok\n'
            's = os.environ.get("DMLC_TPU_TRANSFER_SAMPLE", "32")\n'
        )
        offenders = scan(bad)
        assert [ln for ln, _ in offenders] == [1, 2, 3, 4, 5]

    def test_knob_table_module_is_sanctioned(self):
        scan = self._scan()
        text = 'raw = os.environ.get("DMLC_TPU_PARSE_WORKERS", "")\n'
        assert scan(text, knob_gate=False) == []
        assert scan(text) != []
