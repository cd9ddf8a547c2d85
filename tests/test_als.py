"""ALX-style sharded ALS (models/als.py) — the pod-scale training proof.

Covers the tentpole acceptance criteria end to end:

* the alternation converges on hand-built low-rank batches (exact
  per-row solves: loss drops orders of magnitude in a few epochs);
* ELL pad slots (index = num_items, the pinned-zero sink row) are
  mathematically inert — same model state with or without them;
* the 8-virtual-device sharded trajectory matches single-device;
* mid-train checkpoint/restore replays the loss trajectory
  BYTE-identically on both feeding paths — the warm pod-sharded block
  cache (seekable ``kind='source'`` epoch-plan states) and the
  multi-tenant data service (deterministic count-based replay);
* two tenants on one fleet drain with fleet-wide parse-once and zero
  giveups;
* ``examples/train_als.py --dryrun`` passes as a real subprocess.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter
from dmlc_tpu.models import AlsLearner, AlsParams
from dmlc_tpu.models._loop import host_scalar
from dmlc_tpu.ops.sparse import EllBatch
from dmlc_tpu.parallel import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------- hand-built batches ----------------

class FakeIter:
    """Deterministic in-memory DeviceIter stand-in."""

    def __init__(self, batches):
        self.batches = batches

    def __iter__(self):
        return iter(self.batches)

    def reset(self):
        pass

    def close(self):
        pass


def _lowrank_batches(num_users=32, num_items=16, rank=3, per_row=12,
                     batch=8, seed=0):
    """Noise-free low-rank ratings in EllBatches: label = user id."""
    rng = np.random.default_rng(seed)
    gt_u = rng.normal(size=(num_users, rank)).astype(np.float32)
    gt_v = rng.normal(size=(num_items, rank)).astype(np.float32)
    batches = []
    for start in range(0, num_users, batch):
        uids = np.arange(start, start + batch)
        idx = np.stack([rng.choice(num_items, size=per_row, replace=False)
                        for _ in uids]).astype(np.int32)
        vals = np.einsum("bf,bkf->bk", gt_u[uids], gt_v[idx])
        batches.append(EllBatch(
            indices=jnp.asarray(idx),
            values=jnp.asarray(vals.astype(np.float32)),
            label=jnp.asarray(uids.astype(np.float32)),
            weight=jnp.ones(batch, dtype=jnp.float32)))
    return batches


def test_als_converges_on_lowrank_ratings():
    # per_row (observations/user) >= 2x factors, so each per-row solve is
    # overdetermined and the alternation recovers the factorization
    it = FakeIter(_lowrank_batches(rank=3, per_row=12))
    model = AlsLearner(num_users=32, num_items=16, num_factors=3,
                       reg=1e-3, seed=0)
    # ALS is non-convex: about one random start in eight stalls near
    # 0.13-0.19 on this problem. The claim under test is the alternation,
    # not the luck of a jax.random stream (which changes between JAX
    # releases), so the start comes from numpy's stable generator.
    start = 0.1 * np.random.default_rng(0).normal(size=(17, 3))
    start[-1] = 0.0  # the ELL pad sink row
    model.params = AlsParams(users=model.params.users,
                             items=jnp.asarray(start.astype(np.float32)))
    first, n = model.fit_epoch(it)
    assert n == 4
    for _ in range(14):
        last, _ = model.fit_epoch(it)
    assert last < 1e-3 < first, f"no convergence: {first} -> {last}"
    assert model.eval_loss(it) < 1e-3
    # the ELL pad sink row stays pinned to zero through every item solve
    assert float(jnp.abs(model.params.items[-1]).max()) == 0.0


def test_als_pad_slots_inert():
    """Widening every row with pad slots (index = num_items, rating 0)
    must not change the model: pad gathers read the zero sink row, pad
    scatters land in it and are re-zeroed by finalize_items. (Float
    summation order shifts with the wider K, so the pin is allclose,
    not bit-equality.)"""
    (b,) = _lowrank_batches(num_users=8, num_items=16, rank=3, per_row=12,
                            batch=8)
    num_items = 16
    pad = np.full((8, 4), num_items, dtype=np.int32)
    b_padded = EllBatch(
        indices=jnp.concatenate([b.indices, jnp.asarray(pad)], axis=1),
        values=jnp.concatenate(
            [b.values, jnp.zeros((8, 4), dtype=jnp.float32)], axis=1),
        label=b.label, weight=b.weight)

    m1 = AlsLearner(8, num_items, num_factors=3, reg=1e-3, seed=0)
    m2 = AlsLearner(8, num_items, num_factors=3, reg=1e-3, seed=0)
    l1 = host_scalar(m1.step(b))
    l2 = host_scalar(m2.step(b_padded))
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m1.params.users),
                               np.asarray(m2.params.users),
                               rtol=1e-4, atol=1e-5)
    m1.finalize_items()
    m2.finalize_items()
    np.testing.assert_allclose(np.asarray(m1.params.items),
                               np.asarray(m2.params.items),
                               rtol=1e-3, atol=1e-4)
    assert float(jnp.abs(m1.params.items[-1]).max()) == 0.0
    assert float(jnp.abs(m2.params.items[-1]).max()) == 0.0


def test_als_state_dict_roundtrip():
    it = FakeIter(_lowrank_batches())
    model = AlsLearner(32, 16, num_factors=3, reg=1e-3, seed=0)
    model.fit_epoch(it)
    state = model.state_dict()
    other = AlsLearner(32, 16, num_factors=3, reg=1e-3, seed=7)
    other.load_state_dict(state)
    for k in ("users", "items", "gram", "rhs"):
        np.testing.assert_array_equal(state[k], other.state_dict()[k])


# ---------------- corpus-fed paths ----------------

def _ratings_corpus(path, num_users, num_items, per_row, rank=4, seed=0):
    """libsvm encoding: label = user/row id, features = item:rating."""
    rng = np.random.default_rng(seed)
    gt_u = rng.normal(size=(num_users, rank)).astype(np.float32)
    gt_v = rng.normal(size=(num_items, rank)).astype(np.float32)
    with open(path, "w") as f:
        for uid in range(num_users):
            items = rng.choice(num_items, size=per_row, replace=False)
            ratings = gt_u[uid] @ gt_v[items].T
            feats = " ".join(f"{j}:{r:.6f}" for j, r in zip(items, ratings))
            f.write(f"{uid} {feats}\n")


CFG = {"users": 128, "items": 24, "factors": 2, "per_row": 8,
       "batch": 16, "reg": 0.05}


def _build(path, cache_dir, mesh, chunk_bytes=1 << 10):
    model = AlsLearner(CFG["users"], CFG["items"],
                       num_factors=CFG["factors"], reg=CFG["reg"],
                       seed=0, mesh=mesh)
    parser = create_parser(path, 0, 1, "libsvm", block_cache=cache_dir,
                           shuffle_seed=0, pod_sharding=True,
                           chunk_bytes=chunk_bytes)
    it = DeviceIter(parser, num_col=model.device_num_col(),
                    batch_size=CFG["batch"], layout="ell",
                    max_nnz=CFG["per_row"], mesh=mesh,
                    shardings=model.batch_shardings(),
                    drop_remainder=True)
    return model, it


def test_als_sharded_trajectory_matches_single(tmp_path):
    path = str(tmp_path / "ratings.libsvm")
    _ratings_corpus(path, CFG["users"], CFG["items"], CFG["per_row"])

    def run(mesh):
        model = AlsLearner(CFG["users"], CFG["items"],
                           num_factors=CFG["factors"], reg=CFG["reg"],
                           seed=0, mesh=mesh)
        parser = create_parser(path, 0, 1, "libsvm", threaded=False)
        it = DeviceIter(parser, num_col=model.device_num_col(),
                        batch_size=CFG["batch"], layout="ell",
                        max_nnz=CFG["per_row"], mesh=mesh,
                        shardings=(model.batch_shardings()
                                   if mesh else None),
                        drop_remainder=True)
        losses = [model.fit_epoch(it)[0] for _ in range(3)]
        it.close()
        return losses, model.params

    losses_1, params_1 = run(None)
    losses_8, params_8 = run(make_mesh({"data": 8}))
    np.testing.assert_allclose(losses_8, losses_1, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(params_8.users),
                               np.asarray(params_1.users),
                               rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(params_8.items),
                               np.asarray(params_1.items),
                               rtol=2e-3, atol=1e-5)


def test_als_checkpoint_restore_byte_identical_warm_cache(tmp_path):
    """Run A: warm pod-sharded-cache epoch, per-step losses recorded,
    (model, iterator) checkpointed mid-epoch. Run B: fresh objects
    restore and replay the tail — the float32 loss stream must match
    byte for byte."""
    path = str(tmp_path / "ratings.libsvm")
    _ratings_corpus(path, CFG["users"], CFG["items"], CFG["per_row"])
    cache = str(tmp_path / "cache")
    restore_at = 3  # annotations begin after the first block boundary

    model, it = _build(path, cache, mesh=None)
    model.fit_epoch(it)  # epoch 0: cold pass, publishes the block cache
    losses_a, ckpt, n = [], None, 0
    for batch in it:
        losses_a.append(np.float32(host_scalar(model.step(batch))))
        n += 1
        if ckpt is None and n == restore_at:
            ckpt = (model.state_dict(), it.state_dict())
    it.reset()
    it.close()
    assert len(losses_a) == CFG["users"] // CFG["batch"]
    # a seekable mid-epoch position in the PERMUTED warm stream — not a
    # count-based epoch-0 replay, which diverges on multi-block caches
    assert ckpt is not None and ckpt[1]["kind"] == "source", ckpt[1]

    model2, it2 = _build(path, cache, mesh=None)
    model2.load_state_dict(ckpt[0])
    it2.load_state(ckpt[1])
    losses_b = [np.float32(host_scalar(model2.step(b))) for b in it2]
    it2.close()
    tail = np.asarray(losses_a[restore_at:])
    replay = np.asarray(losses_b)
    assert tail.tobytes() == replay.tobytes(), (tail[:4], replay[:4])


def test_als_service_fed_two_tenants_parse_once(tmp_path):
    """The factorization job trains FED BY THE SERVICE beside a second
    tenant: fleet-wide parse-once (each part parsed at most once across
    both tenants and every epoch), zero giveups, and a mid-train
    checkpoint replayed byte-identically on this feeding path too."""
    from dmlc_tpu.io import resilience
    from dmlc_tpu.service import LocalFleet, ServiceParser

    path = str(tmp_path / "ratings.libsvm")
    _ratings_corpus(path, CFG["users"], CFG["items"], CFG["per_row"])
    pcfg = {"format": "libsvm"}
    num_parts = 2
    restore_at = 2
    base = resilience.counters_snapshot()
    fleet = LocalFleet(None, 0, num_workers=2, parser=pcfg,
                       share_dir=str(tmp_path / "share"))
    try:
        fleet.register_job("als", path, num_parts, parser=pcfg)

        def train_pass(model, record=None, restore=None):
            sp = ServiceParser(fleet.address, job="als")
            it = DeviceIter(sp, num_col=model.device_num_col(),
                            batch_size=CFG["batch"], layout="ell",
                            max_nnz=CFG["per_row"], drop_remainder=True)
            try:
                if restore is not None:
                    it.load_state(restore)
                losses, ckpt, n = [], None, 0
                for batch in it:
                    losses.append(np.float32(host_scalar(model.step(batch))))
                    n += 1
                    if record is not None and ckpt is None and n == record:
                        ckpt = (model.state_dict(), it.state_dict())
                model.finalize_items()
            finally:
                it.close()
            return losses, ckpt

        model = AlsLearner(CFG["users"], CFG["items"],
                           num_factors=CFG["factors"], reg=CFG["reg"],
                           seed=0)
        train_pass(model)  # epoch 0: the workers parse each part once
        # the second tenant registers AFTER the parse: its entire drain
        # must resolve to shared artifacts, adding zero parses
        fleet.register_job("tenant-b", path, num_parts, parser=pcfg)
        tb = ServiceParser(fleet.address, job="tenant-b")
        tenant_blocks = 0
        while tb.next_block() is not None:
            tenant_blocks += 1
        tb.close()
        assert tenant_blocks > 0

        losses_a, ckpt = train_pass(model, record=restore_at)
        assert ckpt is not None
        model2 = AlsLearner(CFG["users"], CFG["items"],
                            num_factors=CFG["factors"], reg=CFG["reg"],
                            seed=0)
        model2.load_state_dict(ckpt[0])
        losses_b, _ = train_pass(model2, restore=ckpt[1])
        tail = np.asarray(losses_a[restore_at:])
        replay = np.asarray(losses_b)
        assert tail.tobytes() == replay.tobytes(), (tail[:4], replay[:4])
    finally:
        fleet.close()
    res = resilience.counters_delta(base)
    assert res.get("service_giveups", 0) == 0, res
    parsed = res.get("service_parts_parsed", 0)
    assert 0 < parsed <= num_parts, (
        f"fleet-wide parse-once violated: {parsed} parses of "
        f"{num_parts} parts across two tenants and three epochs")
    assert res.get("service_parts_shared", 0) >= num_parts, res


def test_als_sink_row_is_device_num_col():
    model = AlsLearner(16, 10, num_factors=2)
    assert model.device_num_col() == 10
    assert model.params.items.shape == (11, 2)
    from dmlc_tpu.utils.check import DMLCError

    with pytest.raises(DMLCError):
        AlsLearner(0, 10)


def test_train_als_example_dryrun():
    """examples/train_als.py --dryrun is the tier-1 smoke of the whole
    stack: local warm-cache path, byte-identical mid-train restore on
    both feeding paths, two-tenant service leg."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "train_als.py"),
         "--dryrun"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout, proc.stdout[-2000:]
    assert "checkpoint/restore byte-identical" in proc.stdout
