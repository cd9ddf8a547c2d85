"""Unified tiered-store manager suite (ISSUE 11).

The contracts docs/store.md promises:

- every publish of a store-managed format (``DMLCCHK1`` / ``DMLCBC01`` /
  ``DMLCSN01``) lands in the manifest with tier, bytes, and signature
  hash, staged via a process-unique ``.tmp`` and atomically renamed —
  two concurrent writers of the same signature converge on one valid
  artifact with no torn manifest;
- orphaned ``.tmp`` files from crashed writers are garbage-collected at
  store open, age-gated so a live writer is never raced;
- under ``DMLC_TPU_STORE_BUDGET_BYTES`` the store never exceeds the
  budget while an unpinned candidate remains: eviction order is
  cheapest-to-rebuild first (snapshot, then block cache, then chunk
  cache), LRU within a tier, pinned artifacts exempt;
- eviction surfaces to readers as the existing vanished-cache path —
  the pipeline rebuilds transparently, byte-identical, with exact
  ``store_evictions`` / ``store_rebuilds_after_eviction`` counters;
- ``make lint-store`` fails direct ``os.replace`` / hand-allocated
  ``.tmp`` publishes outside ``dmlc_tpu/store/``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dmlc_tpu.data import create_parser
from dmlc_tpu.io.block_cache import (
    BlockCacheWriter,
    open_block_cache,
)
from dmlc_tpu.io.resilience import counters_delta, counters_snapshot
from dmlc_tpu.io.snapshot import SnapshotWriter, open_snapshot
from dmlc_tpu.store import manager as store_mgr
from dmlc_tpu.store import (
    reset_stores,
    store_counters,
    store_for,
    tier_for_magic,
)
from dmlc_tpu.utils import telemetry
from dmlc_tpu.utils.check import DMLCError
from dmlc_tpu.utils.knobs import store_budget_bytes, store_gc_age_seconds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_stores():
    """Each test's tmp dir gets a fresh store open (GC/adoption/budget
    run at open) and no budget leaks across tests."""
    reset_stores()
    yield
    reset_stores()


def _mk_block_cache(path, tag="x", blocks=4, rows=64):
    w = BlockCacheWriter(str(path), signature={"tag": tag})
    for i in range(blocks):
        w.add_block({"offset": np.arange(rows + 1, dtype=np.int64),
                     "label": np.full(rows, float(i), np.float32),
                     "index": np.arange(rows, dtype=np.uint32),
                     "value": np.full(rows, 0.5, np.float32)},
                    rows=rows, num_col=2)
    w.finish()
    return str(path)


def _mk_snapshot(path, tag="s", batches=2, rows=64):
    w = SnapshotWriter(str(path), signature={"tag": tag},
                       geometry={"batch_size": rows})
    for i in range(batches):
        w.add_batch("dense_packed",
                    (np.full((rows, 4), float(i), np.float32),), rows=rows)
    w.finish()
    return str(path)


def _entry(store, name):
    for e in store.entries():
        if e["path"] == name:
            return e
    return None


# ---------------- publish / manifest ----------------

class TestPublish:
    def test_publish_records_manifest_entry(self, tmp_path):
        path = _mk_block_cache(tmp_path / "c.bc")
        assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))
        store = store_for(path)
        e = _entry(store, "c.bc")
        assert e is not None
        assert e["tier"] == "block_cache"
        assert e["bytes"] == os.path.getsize(path)
        assert e["sig"] and not e["evicted"] and not e["pinned"]
        # the journal is plain JSONL: every line decodes
        manifest = os.path.join(tmp_path, store_mgr.STORE_DIRNAME,
                                store_mgr.MANIFEST_NAME)
        for line in open(manifest).read().splitlines():
            json.loads(line)
        # the registry gauge carries this root's live bytes per tier
        g = telemetry.REGISTRY.gauge(telemetry.STORE_BYTES_METRIC,
                                     root=store.root, tier="block_cache")
        assert int(g.value) == os.path.getsize(path)

    def test_tiers_and_magics(self, tmp_path):
        assert tier_for_magic(b"DMLCSN01") == "snapshot"
        assert tier_for_magic(b"DMLCBC01") == "block_cache"
        assert tier_for_magic(b"DMLCCHK1") == "chunk_cache"
        with pytest.raises(DMLCError):
            tier_for_magic(b"NOPE0000")
        snap = _mk_snapshot(tmp_path / "s.snap")
        assert _entry(store_for(snap), "s.snap")["tier"] == "snapshot"

    def test_stage_paths_are_process_unique(self, tmp_path):
        store = store_for(str(tmp_path / "c.bc"))
        a = store.stage_path(str(tmp_path / "c.bc"))
        b = store.stage_path(str(tmp_path / "c.bc"))
        assert a != b and a.endswith(".tmp") and str(os.getpid()) in a

    def test_interleaved_writers_same_path_converge(self, tmp_path):
        """Two in-process writers racing one path: distinct staging
        files, last publish wins, the artifact is valid either way."""
        path = str(tmp_path / "c.bc")
        w1 = BlockCacheWriter(path, signature={"s": 1})
        w2 = BlockCacheWriter(path, signature={"s": 1})
        assert w1.tmp_path != w2.tmp_path
        blk = {"offset": np.array([0, 1], np.int64),
               "label": np.array([1.0], np.float32)}
        w1.add_block(blk, rows=1, num_col=1)
        w2.add_block(blk, rows=1, num_col=1)
        w1.finish()
        w2.finish()
        r = open_block_cache(path, signature={"s": 1})
        assert r is not None and r.num_blocks == 1
        r.load_segments(0)  # crc verifies: no torn bytes
        r.close()
        assert len([e for e in store_for(path).entries()
                    if not e["evicted"]]) == 1

    def test_concurrent_process_publish_no_torn_manifest(self, tmp_path):
        """ISSUE 11 satellite: two PROCESSES publishing the same
        block-cache signature converge to one valid artifact and a
        manifest with no torn lines."""
        path = str(tmp_path / "c.bc")
        code = (
            "import sys, os\n"
            "sys.path.insert(0, os.environ['REPO'])\n"
            "import numpy as np\n"
            "from dmlc_tpu.io.block_cache import BlockCacheWriter\n"
            "w = BlockCacheWriter(os.environ['CACHE'],"
            " signature={'s': 1})\n"
            "for i in range(50):\n"
            "    w.add_block({'offset': np.arange(65, dtype=np.int64),\n"
            "                 'label': np.full(64, float(i),"
            " np.float32)}, rows=64, num_col=1)\n"
            "w.finish()\n"
        )
        env = dict(os.environ, REPO=REPO, CACHE=path, JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                                  stderr=subprocess.PIPE, text=True)
                 for _ in range(2)]
        for p in procs:
            _, err = p.communicate(timeout=120)
            assert p.returncode == 0, err
        r = open_block_cache(path, signature={"s": 1})
        assert r is not None and r.num_blocks == 50
        for i in range(r.num_blocks):
            r.load_segments(i)  # every crc verifies
        r.close()
        store = store_for(path)
        manifest = os.path.join(store.root, store_mgr.STORE_DIRNAME,
                                store_mgr.MANIFEST_NAME)
        for line in open(manifest).read().splitlines():
            json.loads(line)  # flock'd appends: nothing torn
        assert len([e for e in store.entries()
                    if not e["evicted"]]) == 1
        assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))

    def test_adopts_pre_store_artifacts(self, tmp_path):
        """Artifacts published by pre-store builds come under management
        (budget-counted, evictable) at store open via magic sniff."""
        path = _mk_block_cache(tmp_path / "old.bc")
        import shutil
        shutil.rmtree(tmp_path / store_mgr.STORE_DIRNAME)
        reset_stores()
        store = store_for(path)
        e = _entry(store, "old.bc")
        assert e is not None and e["tier"] == "block_cache"
        assert store.total_bytes() == os.path.getsize(path)

    def test_torn_manifest_tail_is_skipped(self, tmp_path):
        path = _mk_block_cache(tmp_path / "c.bc")
        store = store_for(path)
        manifest = os.path.join(store.root, store_mgr.STORE_DIRNAME,
                                store_mgr.MANIFEST_NAME)
        with open(manifest, "a") as f:
            f.write('{"op": "pub')  # crashed mid-append
        reset_stores()
        assert _entry(store_for(path), "c.bc") is not None

    def test_manifest_compacts(self, tmp_path, monkeypatch):
        monkeypatch.setattr(store_mgr, "COMPACT_LINES", 16)
        path = _mk_block_cache(tmp_path / "c.bc")
        store = store_for(path)
        for _ in range(40):
            store.pin(path)
            store.drop(path)
        assert _entry(store, "c.bc") is not None  # replay compacts
        manifest = os.path.join(store.root, store_mgr.STORE_DIRNAME,
                                store_mgr.MANIFEST_NAME)
        lines = open(manifest).read().splitlines()
        assert len(lines) <= 16
        e = _entry(store, "c.bc")
        assert not e["pinned"] and e["bytes"] == os.path.getsize(path)

    def test_pin_drop_steady_state_bounds_journal(self, tmp_path,
                                                  monkeypatch):
        """A warm steady state (pin/drop every epoch, no publishes, no
        replays) must not grow the sidecar without bound: the append
        path itself triggers compaction past COMPACT_BYTES."""
        monkeypatch.setattr(store_mgr, "COMPACT_LINES", 8)
        monkeypatch.setattr(store_mgr, "COMPACT_BYTES", 512)
        path = _mk_block_cache(tmp_path / "c.bc")
        store = store_for(path)
        manifest = os.path.join(store.root, store_mgr.STORE_DIRNAME,
                                store_mgr.MANIFEST_NAME)
        for _ in range(100):  # only pins/drops: no replay-causing ops
            store.pin(path)
            store.drop(path)
        assert os.path.getsize(manifest) <= 2 * 512
        e = _entry(store, "c.bc")
        assert e is not None and not e["pinned"]

    def test_missing_probe_never_creates_state(self, tmp_path):
        """An existence probe of an artifact in a directory the store
        never managed must stay a bare stat — no sidecar, no directory
        scan (the path may sit beside a huge read-only dataset)."""
        virgin = tmp_path / "data"
        virgin.mkdir()
        assert open_block_cache(str(virgin / "nope.bc")) is None
        assert open_snapshot(str(virgin / "nope.snap")) is None
        assert not (virgin / store_mgr.STORE_DIRNAME).exists()


# ---------------- orphaned .tmp GC ----------------

class TestOrphanGC:
    def test_stale_tmp_collected_fresh_kept(self, tmp_path):
        """ISSUE 11 satellite regression: a writer killed mid-publish
        used to leak its ``.tmp`` forever; store open now collects
        dead-writer staging files, age-gated so a concurrent writer
        (alive or on another host of a shared fs) is never raced."""
        p = subprocess.Popen([sys.executable, "-c", "pass"])
        p.wait(timeout=60)
        dead = p.pid  # reaped: guaranteed not alive
        stale = tmp_path / f"c.bc.{dead}.1.tmp"
        stale.write_bytes(b"half-written")
        old = 2 * store_gc_age_seconds()
        os.utime(stale, (os.path.getmtime(stale) - old,) * 2)
        fresh = tmp_path / f"c.bc.{dead}.2.tmp"
        fresh.write_bytes(b"live writer")  # young: age gate keeps it
        reset_stores()
        store_for(str(tmp_path / "c.bc"))
        assert not stale.exists()
        assert fresh.exists()

    def test_live_pid_staging_never_collected(self, tmp_path):
        """A staging file whose embedded pid is ALIVE is never GC'd,
        however stale its mtime — a cold pass stalled behind retry
        backoff must not lose its in-flight publish."""
        mine = tmp_path / f"c.bc.{os.getpid()}.1.tmp"
        mine.write_bytes(b"stalled but alive")
        old = 10 * store_gc_age_seconds()
        os.utime(mine, (os.path.getmtime(mine) - old,) * 2)
        reset_stores()
        store_for(str(tmp_path / "c.bc"))
        assert mine.exists()

    def test_gc_age_env_validated(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_STORE_GC_AGE_SECONDS", "junk")
        with pytest.raises(DMLCError):
            store_gc_age_seconds()


# ---------------- budget / eviction ----------------

class TestBudget:
    def test_budget_knob_validation(self, monkeypatch):
        assert store_budget_bytes() is None
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1048576")
        assert store_budget_bytes() == 1048576
        for bad in ("garbage", "0", "-5"):
            monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", bad)
            with pytest.raises(DMLCError):
                store_budget_bytes()

    def test_eviction_cost_order_snapshot_first(self, tmp_path,
                                                monkeypatch):
        bc_a = _mk_block_cache(tmp_path / "a.bc", tag="a")
        snap = _mk_snapshot(tmp_path / "s.snap")
        bc_b = _mk_block_cache(tmp_path / "b.bc", tag="b")
        store = store_for(bc_b)
        base = counters_snapshot()
        total = store.total_bytes()
        # squeeze by ONE byte: a single eviction of the cheapest tier
        # suffices, so the block caches must be untouched even though
        # a.bc is the LRU artifact overall
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES",
                           str(total - 1))
        reset_stores()
        store = store_for(bc_b)  # open-time enforcement
        assert not os.path.exists(snap), "snapshot tier evicts first"
        assert os.path.exists(bc_a) and os.path.exists(bc_b)
        d = counters_delta(base)
        assert d["store_evictions"] == 1
        assert store.total_bytes() <= total - 1

    def test_eviction_reaches_decision_ledger(self, tmp_path,
                                              monkeypatch):
        """ISSUE 19: every budget eviction is one audit-ledger event
        carrying the squeeze that fired it (docs/observability.md
        Decision ledger)."""
        telemetry.reset_decisions()
        snap = _mk_snapshot(tmp_path / "s.snap")
        bc = _mk_block_cache(tmp_path / "a.bc")
        store = store_for(bc)
        total = store.total_bytes()
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES",
                           str(total - 1))
        reset_stores()
        store_for(bc)  # open-time enforcement: one eviction
        assert not os.path.exists(snap)
        events = telemetry.decisions_snapshot("store")
        assert len(events) == 1
        ev = events[0]
        assert ev["action"] == "evict"
        assert ev["trigger"]["budget_bytes"] == total - 1
        assert ev["trigger"]["tier"] == "snapshot"
        assert ev["trigger"]["bytes"] > 0
        assert "s.snap" in ev["outcome"]
        assert telemetry.decision_counts()["store.evict"] == 1
        telemetry.reset_decisions()

    def test_lru_within_tier(self, tmp_path, monkeypatch):
        s_old = _mk_snapshot(tmp_path / "old.snap", tag="o")
        s_new = _mk_snapshot(tmp_path / "new.snap", tag="n")
        store = store_for(s_old)
        # touch the OLD one (a pin is a use): the LRU clock advances
        store.pin(s_old)
        store.drop(s_old)
        total = store.total_bytes()
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", str(total - 1))
        reset_stores()
        store_for(s_old)  # open-time enforcement: one eviction needed
        assert os.path.exists(s_old), "recently-used snapshot kept"
        assert not os.path.exists(s_new), "LRU victim within the tier"

    def test_pinned_artifact_survives_squeeze(self, tmp_path,
                                              monkeypatch):
        """ISSUE 11 satellite: the pinned artifact survives a budget
        squeeze that evicts everything else evictable."""
        pinned = _mk_snapshot(tmp_path / "pinned.snap", tag="p")
        loose = _mk_snapshot(tmp_path / "loose.snap", tag="l")
        store = store_for(pinned)
        store.pin(pinned)
        try:
            monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1")
            _mk_block_cache(tmp_path / "t.bc")
            assert os.path.exists(pinned), "pinned snapshot survives"
            assert not os.path.exists(loose)
        finally:
            store.drop(pinned)

    def test_dead_pid_pins_are_ignored(self, tmp_path, monkeypatch):
        snap = _mk_snapshot(tmp_path / "s.snap")
        code = (
            "import sys, os\n"
            "sys.path.insert(0, os.environ['REPO'])\n"
            "from dmlc_tpu.store import store_for\n"
            "store_for(os.environ['ART']).pin(os.environ['ART'])\n"
        )
        env = dict(os.environ, REPO=REPO, ART=snap, JAX_PLATFORMS="cpu")
        subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       timeout=60)
        # the pinning process is dead: its journaled pin must not wedge
        # the budget
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1")
        _mk_block_cache(tmp_path / "t.bc")
        assert not os.path.exists(snap)

    def test_soak_never_exceeds_budget(self, tmp_path, monkeypatch):
        """ISSUE 11 acceptance: a long-lived publisher under a small
        budget never exceeds it (while an unpinned candidate remains) —
        the volume cannot fill."""
        probe = _mk_snapshot(tmp_path / "probe.snap", tag="probe")
        store = store_for(probe)
        budget = 4 * os.path.getsize(probe)
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", str(budget))
        for i in range(12):
            if i % 3 == 2:
                _mk_block_cache(tmp_path / f"b{i}.bc", tag=str(i))
            else:
                _mk_snapshot(tmp_path / f"s{i}.snap", tag=str(i))
            assert store.total_bytes() <= budget
        d = store_counters()
        assert d["store_evictions"] >= 1


# ---------------- eviction heals via rebuild ----------------

class TestEvictionHeals:
    N = 600

    def _corpus(self, tmp_path):
        path = tmp_path / "c.libsvm"
        with open(path, "w") as f:
            for i in range(self.N):
                f.write(f"{i} 0:{i}.0 1:{i}.5\n")
        return str(path)

    @staticmethod
    def _rows(parser):
        out = []
        while (b := parser.next_block()) is not None:
            for i in range(len(b)):
                s, e = int(b.offset[i]), int(b.offset[i + 1])
                out.append((float(b.label[i]),
                            tuple(b.index[s:e].tolist()),
                            tuple(np.asarray(b.value[s:e]).tolist())))
        return out

    def test_evicted_block_cache_rebuilds_byte_identical(self, tmp_path,
                                                         monkeypatch):
        corpus = self._corpus(tmp_path)
        cache = str(tmp_path / "c.bc")
        p = create_parser(corpus, 0, 1, "libsvm", threaded=False,
                          chunk_bytes=4096, block_cache=cache)
        reference = self._rows(p)
        p.close()  # reader pin released: the cache is now evictable
        store = store_for(cache)
        base = counters_snapshot()
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1")
        _mk_snapshot(tmp_path / "t.snap")  # triggers the squeeze
        assert not os.path.exists(cache), "unpinned cache evicted"
        monkeypatch.delenv("DMLC_TPU_STORE_BUDGET_BYTES")
        # the vanished-cache path heals: fresh pipeline re-parses,
        # republished, byte-identical — and the store attributes the
        # rebuild to the eviction
        p2 = create_parser(corpus, 0, 1, "libsvm", threaded=False,
                           chunk_bytes=4096, block_cache=cache)
        assert p2.cache_state == "cold"
        assert self._rows(p2) == reference
        p2.close()
        assert os.path.exists(cache), "healing pass republished"
        d = counters_delta(base)
        assert d["store_evictions"] == 1
        assert d["store_rebuilds_after_eviction"] == 1
        # and the rebuilt cache serves warm again
        p3 = create_parser(corpus, 0, 1, "libsvm", threaded=False,
                           chunk_bytes=4096, block_cache=cache)
        assert self._rows(p3) == reference
        assert p3.cache_state == "warm"
        p3.close()

    def test_warm_serve_pinned_through_mid_epoch_squeeze(self, tmp_path,
                                                         monkeypatch):
        """ISSUE 11 satellite: a warm epoch's cache is pinned by its
        reader — a mid-epoch budget squeeze evicts the unpinned decoy,
        never the serving tier, and the stream completes
        byte-identical."""
        corpus = self._corpus(tmp_path)
        cache = str(tmp_path / "c.bc")
        p = create_parser(corpus, 0, 1, "libsvm", threaded=False,
                          chunk_bytes=4096, block_cache=cache)
        reference = self._rows(p)
        p.close()
        decoy = _mk_block_cache(tmp_path / "decoy.bc", tag="decoy")
        p2 = create_parser(corpus, 0, 1, "libsvm", threaded=False,
                           chunk_bytes=4096, block_cache=cache)
        assert p2.cache_state == "warm"
        got = [p2.next_block()]  # mid-epoch: the reader pin is live
        base = counters_snapshot()
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1")
        _mk_snapshot(tmp_path / "t.snap")  # the squeeze
        assert os.path.exists(cache), "serving cache pinned: survives"
        assert not os.path.exists(decoy), "unpinned decoy evicted"
        while (b := p2.next_block()) is not None:
            got.append(b)
        rows = []
        for b in got:
            for i in range(len(b)):
                s, e = int(b.offset[i]), int(b.offset[i + 1])
                rows.append((float(b.label[i]),
                             tuple(b.index[s:e].tolist()),
                             tuple(np.asarray(b.value[s:e]).tolist())))
        assert rows == reference
        p2.close()
        assert counters_delta(base)["store_evictions"] >= 1

    def test_evicted_chunk_cache_rebuilds(self, tmp_path, monkeypatch):
        lines = [f"row-{i}".encode() for i in range(400)]
        src = tmp_path / "data.txt"
        src.write_bytes(b"\n".join(lines) + b"\n")
        from dmlc_tpu.io import create_input_split

        cache = tmp_path / "chunks.cache"
        uri = f"{src}#{cache}"
        split = create_input_split(uri, 0, 1, "text")
        assert [bytes(r) for r in split.iter_records()] == lines
        split.close()  # pin released
        store = store_for(str(cache))
        assert _entry(store, cache.name)["tier"] == "chunk_cache"
        base = counters_snapshot()
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1")
        _mk_snapshot(tmp_path / "t.snap")
        assert not cache.exists(), "unpinned chunk cache evicted"
        monkeypatch.delenv("DMLC_TPU_STORE_BUDGET_BYTES")
        split2 = create_input_split(uri, 0, 1, "text")
        assert [bytes(r) for r in split2.iter_records()] == lines
        split2.close()
        assert cache.exists(), "rebuilt from source"
        d = counters_delta(base)
        assert d["store_rebuilds_after_eviction"] == 1

    def test_evicted_snapshot_miss_counts_rebuild(self, tmp_path,
                                                  monkeypatch):
        snap = _mk_snapshot(tmp_path / "s.snap")
        base = counters_snapshot()
        monkeypatch.setenv("DMLC_TPU_STORE_BUDGET_BYTES", "1")
        _mk_block_cache(tmp_path / "t.bc")
        assert not os.path.exists(snap)
        monkeypatch.delenv("DMLC_TPU_STORE_BUDGET_BYTES")
        assert open_snapshot(snap) is None
        d = counters_delta(base)
        assert d["store_evictions"] == 1
        assert d["store_rebuilds_after_eviction"] == 1
        # one eviction credits exactly one rebuild
        assert open_snapshot(snap) is None
        assert counters_delta(base)["store_rebuilds_after_eviction"] == 1

    def test_invalidation_is_not_an_eviction(self, tmp_path):
        """A signature-mismatch drop (deliberate invalidation) must not
        count store_rebuilds_after_eviction on the rebuild open."""
        path = _mk_block_cache(tmp_path / "c.bc", tag="old")
        base = counters_snapshot()
        assert open_block_cache(path, signature={"tag": "new"}) is None
        assert not os.path.exists(path)
        assert open_block_cache(path, signature={"tag": "new"}) is None
        d = counters_delta(base)
        assert d["cache_invalidations"] == 1
        assert d["store_rebuilds_after_eviction"] == 0


# ---------------- chunk-cache pin semantics ----------------

class TestChunkCachePins:
    def test_live_split_pins_its_cache(self, tmp_path):
        lines = [f"r{i}".encode() for i in range(50)]
        src = tmp_path / "d.txt"
        src.write_bytes(b"\n".join(lines) + b"\n")
        from dmlc_tpu.io import create_input_split

        cache = str(tmp_path / "c.cache")
        split = create_input_split(f"{src}#{cache}", 0, 1, "text")
        while split.next_record() is not None:
            pass
        split.before_first()  # cached mode now: pin held
        e = _entry(store_for(cache), "c.cache")
        assert e is not None and e["pinned"]
        split.close()
        e = _entry(store_for(cache), "c.cache")
        assert e is not None and not e["pinned"]


# ---------------- telemetry surfaces ----------------

class TestTelemetry:
    def test_store_counters_shape(self, tmp_path):
        before = store_counters()
        _mk_block_cache(tmp_path / "c.bc")
        after = store_counters()
        assert set(after) == {"store_bytes", "store_evictions",
                              "store_rebuilds_after_eviction"}
        assert after["store_bytes"] >= before["store_bytes"] + 1

    def test_pod_snapshot_carries_store(self, tmp_path):
        _mk_block_cache(tmp_path / "c.bc")
        snap = telemetry.pod_snapshot()
        assert set(snap["store"]) == {"store_bytes", "store_evictions",
                                      "store_rebuilds_after_eviction"}
        assert snap["store"]["store_bytes"] >= 1

    def test_device_iter_stats_store_section(self, tmp_path):
        import jax  # noqa: F401 - DeviceIter needs a backend

        from dmlc_tpu.data.device import DeviceIter

        path = tmp_path / "c.libsvm"
        with open(path, "w") as f:
            for i in range(64):
                f.write(f"{i % 2} 0:{i}.0 1:1.5\n")
        cache = str(tmp_path / "c.bc")
        parser = create_parser(str(path), 0, 1, "libsvm", threaded=False,
                               block_cache=cache)
        it = DeviceIter(parser, num_col=2, batch_size=16, layout="dense")
        try:
            for _ in it:
                pass
            stats = it.stats()
            assert set(stats["store"]) == {
                "store_bytes", "store_evictions",
                "store_rebuilds_after_eviction"}
            assert stats["store"]["store_bytes"] >= os.path.getsize(cache)
        finally:
            it.close()


# ---------------- the lint gate ----------------

class TestLintStoreGate:
    @pytest.fixture()
    def scan(self):
        sys.path.insert(0, os.path.join(REPO, "bin"))
        try:
            import lint_store
        finally:
            sys.path.pop(0)
        return lint_store.scan_source

    def test_flags_direct_publish(self, scan):
        bad = "os.replace(tmp, final)\ntmp = path + '.tmp'\n"
        assert len(scan(bad)) == 2

    def test_skips_comments(self, scan):
        assert scan("# os.replace(tmp, final)\n") == []

    def test_repo_is_clean(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "lint_store.py"),
             REPO],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr


class TestClaims:
    """Single-claim cold builds (docs/service.md parse-once): the store
    journals a fleet-wide build claim per artifact path, dissolved by
    the path's publish, an explicit release, or the claimant dying."""

    def test_claim_idempotent_same_owner_denied_other(self, tmp_path):
        path = str(tmp_path / "c.bc")
        store = store_for(path)
        assert store.claim(path, "w1") is True
        assert store.claim(path, "w1") is True
        assert store.claim(path, "w2") is False
        assert store.claimant(path) == "w1"

    def test_publish_dissolves_claim(self, tmp_path):
        path = tmp_path / "c.bc"
        store = store_for(str(path))
        assert store.claim(str(path), "builder") is True
        _mk_block_cache(path)
        assert store.claimant(str(path)) is None
        # the artifact is live; a newcomer may claim a rebuild
        assert store.claim(str(path), "other") is True

    def test_release_dissolves_claim(self, tmp_path):
        path = str(tmp_path / "c.bc")
        store = store_for(path)
        assert store.claim(path, "w1") is True
        store.release(path, "w1")
        assert store.claimant(path) is None
        # releasing an unheld claim is a no-op
        store.release(path, "w1")
        assert store.claim(path, "w2") is True
        # a non-holder's release does not steal the claim
        store.release(path, "w1")
        assert store.claimant(path) == "w2"

    def test_claim_survives_store_reopen(self, tmp_path):
        path = str(tmp_path / "c.bc")
        store_for(path).claim(path, "w1")
        reset_stores()
        fresh = store_for(path)
        assert fresh.claimant(path) == "w1"
        assert fresh.claim(path, "w2") is False

    def test_dead_claimant_is_dropped_on_replay(self, tmp_path):
        path = str(tmp_path / "c.bc")
        store = store_for(path)
        assert store.claim(path, "gone") is True
        manifest = os.path.join(str(tmp_path), ".dmlc_store",
                                store_mgr.MANIFEST_NAME)
        lines = []
        with open(manifest) as fh:
            for line in fh:
                ev = json.loads(line)
                if ev.get("op") == "claim":
                    # forge a claimant pid that cannot be alive
                    ev["pid"] = 2 ** 22 + 1
                lines.append(json.dumps(ev) + "\n")
        with open(manifest, "w") as fh:
            fh.writelines(lines)
        reset_stores()
        fresh = store_for(path)
        assert fresh.claimant(path) is None
        assert fresh.claim(path, "w2") is True


# ---------------- D17: a directory made again is a new store ----------------

def test_open_close_open_in_one_process(tmp_path):
    """A tier's directory removed and made again (two runs of a cell in
    one process; a checkpoint directory emptied at start): the process's
    cached store journalled into a sidecar that was gone, and the second
    publish failed with ``FileNotFoundError`` (ROADMAP D17). What outlived
    the tier's ``close()`` was the registry's entry, not a descriptor."""
    import shutil

    root = tmp_path / "tier"
    path = str(root / "a.bc")
    for run in range(3):
        root.mkdir()
        store = store_for(path)
        tmp = store.stage_path(path)
        with open(tmp, "wb") as f:
            f.write(b"DMLCBC01" + bytes([run]) * 32)
        store.publish_file(tmp, path, "block_cache")
        assert [e["path"] for e in store.entries()] == ["a.bc"]
        assert store is store_for(path)     # cached while the sidecar stands
        fds = os.listdir("/proc/self/fd")
        shutil.rmtree(root)
        assert len(os.listdir("/proc/self/fd")) == len(fds)   # none was held


def test_the_lock_file_is_closed_after_every_acquisition(tmp_path):
    from dmlc_tpu.store import AppendJournal

    journal = AppendJournal(str(tmp_path / "j.jsonl"))
    before = len(os.listdir("/proc/self/fd"))
    for i in range(5):
        journal.append({"n": i}, sync=True)
        with journal.locked():
            with journal.locked():      # reentrant: one descriptor, once
                pass
    assert len(os.listdir("/proc/self/fd")) == before
    assert [e["n"] for e in journal.read_events()] == list(range(5))
