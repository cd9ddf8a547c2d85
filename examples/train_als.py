"""End-to-end ALX-style matrix factorization on the warm ingest stack.

The pod-scale training proof (ROADMAP item 1): sharded alternating least
squares (arXiv:2112.02194's recipe, models/als.py) trained entirely from
the existing ingest machinery — no new wire types, no side channel:

 1. the ratings corpus is plain libsvm (label = user/row id, features =
    ``item:rating`` pairs), parsed by the normal native parser;
 2. the parser runs behind the pod-sharded warm block cache
    (``block_cache=`` + ``pod_sharding=True``): epoch 0 parses text once
    and publishes blocks, every later epoch is a warm columnar read, and
    on a real pod each host draws a DISJOINT set of user rows — which is
    exactly what ALS's row scatters need;
 3. batches flow through DeviceIter in ELL layout with sharded placement
    over the mesh data axis; the jitted step (donated params/opt_state
    buffers) solves the user rows and accumulates the item-side normal
    equations, which :meth:`AlsLearner.finalize_items` solves per epoch;
 4. the same model also trains FED BY THE MULTI-TENANT SERVICE: the
    factorization job registers on a LocalFleet beside a second tenant,
    both draining the same corpus with fleet-wide parse-once sharing and
    zero giveups — CSR wire + QoS + tracker bootstrap under one workload.

Run:
    python examples/train_als.py            # full run (local + service path)
    python examples/train_als.py --dryrun   # tier-1 smoke: tiny corpus, 2
                                            # factor dims, byte-identical
                                            # mid-train checkpoint/restore
                                            # on both feeding paths

Multi-host: launch through `bin/dmlc-submit --cluster tpu-pod ...`;
``pod_sharding=True`` resolves each host's disjoint row shard from the
same DMLC_TASK_ID/DMLC_NUM_WORKER contract the launcher exports.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def synthesize(path: str, num_users: int, num_items: int, per_row: int,
               rank: int = 4, seed: int = 0) -> None:
    """Low-rank ratings corpus: one libsvm row per user, label = user id."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gt_u = rng.normal(size=(num_users, rank)).astype(np.float32)
    gt_v = rng.normal(size=(num_items, rank)).astype(np.float32)
    with open(path, "w") as f:
        for uid in range(num_users):
            items = rng.choice(num_items, size=per_row, replace=False)
            ratings = gt_u[uid] @ gt_v[items].T
            feats = " ".join(f"{j}:{r:.6f}" for j, r in zip(items, ratings))
            f.write(f"{uid} {feats}\n")


def _build(path, cache_dir, cfg, mesh):
    """(model, DeviceIter) over the pod-sharded warm block cache."""
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import AlsLearner

    model = AlsLearner(cfg["users"], cfg["items"],
                       num_factors=cfg["factors"], reg=cfg["reg"],
                       seed=0, mesh=mesh)
    # blocks smaller than one batch: pod_sharding deals many blocks per
    # host, and every batch crosses a block boundary so mid-epoch
    # checkpoints carry a seekable epoch-plan source state (kind='source')
    # instead of falling back to count-based replay
    parser = create_parser(path, 0, 1, "libsvm", block_cache=cache_dir,
                           shuffle_seed=0, pod_sharding=True,
                           chunk_bytes=cfg["chunk_bytes"])
    it = DeviceIter(parser, num_col=model.device_num_col(),
                    batch_size=cfg["batch"], layout="ell",
                    max_nnz=cfg["per_row"], mesh=mesh,
                    shardings=model.batch_shardings(), drop_remainder=True)
    return model, it


def restore_check(path, cache_dir, cfg, mesh) -> int:
    """Mid-train checkpoint/restore must replay the loss trajectory
    BYTE-identically: run A records a warm epoch's per-step losses and
    checkpoints (model, iterator) mid-epoch; run B restores into fresh
    objects and replays the tail. Returns the number of compared steps."""
    import numpy as np

    from dmlc_tpu.models._loop import host_scalar

    model, it = _build(path, cache_dir, cfg, mesh)
    model.fit_epoch(it)  # epoch 0: cold pass, publishes the block cache
    losses_a, ckpt, n = [], None, 0
    for batch in it:
        losses_a.append(np.float32(host_scalar(model.step(batch))))
        n += 1
        if ckpt is None and n == cfg["restore_at"]:
            ckpt = (model.state_dict(), it.state_dict())
    it.reset()
    it.close()
    assert ckpt is not None, "corpus too small for the restore point"
    # the whole point: a seekable mid-epoch position in the PERMUTED warm
    # stream, not a count-based epoch-0 replay
    assert ckpt[1]["kind"] == "source", ckpt[1]

    model2, it2 = _build(path, cache_dir, cfg, mesh)
    model2.load_state_dict(ckpt[0])
    it2.load_state(ckpt[1])
    losses_b = [np.float32(host_scalar(model2.step(b))) for b in it2]
    it2.close()
    tail = np.asarray(losses_a[cfg["restore_at"]:])
    replay = np.asarray(losses_b)
    assert tail.tobytes() == replay.tobytes(), (
        f"restore diverged: {tail[:4]} vs {replay[:4]}")
    return len(replay)


def service_leg(path, cfg, mesh) -> dict:
    """Train the SAME model service-fed, beside a second tenant.

    The factorization job and the tenant share one fleet: epoch 0 parses
    each part once on the workers (parse-once), the tenant's drain and
    every later ALS epoch resolve to shared artifacts, and nothing gives
    up. Also replays a mid-train checkpoint byte-identically on this
    feeding path (count-based replay — service blocks carry no seekable
    source annotation, so the restore deterministically re-pulls and
    drops the prefix)."""
    import numpy as np

    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.io import resilience
    from dmlc_tpu.models import AlsLearner
    from dmlc_tpu.models._loop import host_scalar
    from dmlc_tpu.service import LocalFleet, ServiceParser

    pcfg = {"format": "libsvm"}
    num_parts = 2
    base = resilience.counters_snapshot()
    with tempfile.TemporaryDirectory(prefix="dmlc-als-share-") as share:
        fleet = LocalFleet(None, 0, num_workers=2, parser=pcfg,
                           share_dir=share)
        try:
            fleet.register_job("als", path, num_parts, parser=pcfg)

            def train_pass(model, record=None, restore=None):
                sp = ServiceParser(fleet.address, job="als")
                it = DeviceIter(sp, num_col=model.device_num_col(),
                                batch_size=cfg["batch"], layout="ell",
                                max_nnz=cfg["per_row"], mesh=mesh,
                                shardings=model.batch_shardings(),
                                drop_remainder=True)
                try:
                    if restore is not None:
                        it.load_state(restore)
                    losses, ckpt, n = [], None, 0
                    for batch in it:
                        loss = np.float32(host_scalar(model.step(batch)))
                        losses.append(loss)
                        n += 1
                        if (record is not None and ckpt is None
                                and n == record):
                            ckpt = (model.state_dict(), it.state_dict())
                    model.finalize_items()
                finally:
                    it.close()
                return losses, ckpt

            model = AlsLearner(cfg["users"], cfg["items"],
                               num_factors=cfg["factors"], reg=cfg["reg"],
                               seed=0, mesh=mesh)
            train_pass(model)  # epoch 0: workers parse each part once
            # second tenant joins AFTER the parse: its whole drain must
            # resolve to the shared artifacts (fleet-wide parse-once)
            fleet.register_job("tenant-b", path, num_parts, parser=pcfg)
            tb = ServiceParser(fleet.address, job="tenant-b")
            tenant_blocks = 0
            while tb.next_block() is not None:
                tenant_blocks += 1
            tb.close()
            # warm epoch with a mid-train checkpoint ...
            losses_a, ckpt = train_pass(model, record=cfg["restore_at"])
            # ... replayed byte-identically from fresh objects
            model2 = AlsLearner(cfg["users"], cfg["items"],
                                num_factors=cfg["factors"], reg=cfg["reg"],
                                seed=0, mesh=mesh)
            model2.load_state_dict(ckpt[0])
            losses_b, _ = train_pass(model2, restore=ckpt[1])
            tail = np.asarray(losses_a[cfg["restore_at"]:])
            replay = np.asarray(losses_b)
            assert tail.tobytes() == replay.tobytes(), (
                f"service-fed restore diverged: {tail[:4]} vs {replay[:4]}")
        finally:
            fleet.close()
    res = resilience.counters_delta(base)
    assert res.get("service_giveups", 0) == 0, res
    parsed = res.get("service_parts_parsed", 0)
    shared = res.get("service_parts_shared", 0)
    assert parsed <= num_parts, (
        f"parse-once violated: {parsed} parses of {num_parts} parts")
    return {"tenant_blocks": tenant_blocks, "parts_parsed": parsed,
            "parts_shared": shared, "service_loss": float(losses_a[-1])}


def main() -> None:
    from dmlc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    from dmlc_tpu.parallel import init_from_env, make_mesh

    init_from_env()  # no-op single-process; joins the pod under dmlc-submit

    dryrun = "--dryrun" in sys.argv
    ndev = len(jax.devices())
    if dryrun:
        cfg = {"users": 128, "items": 24, "factors": 2, "per_row": 8,
               "batch": 16, "reg": 0.05, "epochs": 3, "restore_at": 3,
               "chunk_bytes": 1 << 10}
    else:
        cfg = {"users": 4096, "items": 512, "factors": 16, "per_row": 32,
               "batch": 512, "reg": 0.05, "epochs": 4, "restore_at": 2,
               "chunk_bytes": 64 << 10}
    # global batch must divide over the mesh; user count must divide into
    # whole batches so drop_remainder loses nothing
    cfg["batch"] = max(cfg["batch"], ndev)
    cfg["users"] -= cfg["users"] % cfg["batch"]

    mesh = make_mesh()
    workdir = tempfile.mkdtemp(prefix="dmlc-als-")
    path = os.path.join(workdir, "ratings.libsvm")
    synthesize(path, cfg["users"], cfg["items"], cfg["per_row"])

    # ---- local path: pod-sharded warm block cache ----
    cache_dir = os.path.join(workdir, "cache")
    model, it = _build(path, cache_dir, cfg, mesh)

    def log(epoch, loss, nb, secs):
        st = it.stats()
        print(f"epoch {epoch}: loss={loss:.5f} batches={nb} {secs:.2f}s "
              f"cache={st.get('cache_state')} "
              f"input_wait={st.get('input_wait_seconds', 0.0):.2f}s",
              flush=True)

    model.fit(it, epochs=cfg["epochs"], log_fn=log)
    print(f"eval mse (local path): {model.eval_loss(it):.6f}", flush=True)
    it.close()

    # ---- mid-train checkpoint/restore byte-identity, warm cache ----
    steps = restore_check(path, cache_dir, cfg, mesh)
    print(f"checkpoint/restore byte-identical over {steps} steps", flush=True)

    # ---- service path: ALS job + second tenant on one fleet ----
    svc = service_leg(path, cfg, mesh)
    print(f"service-fed: loss={svc['service_loss']:.5f} "
          f"tenant_blocks={svc['tenant_blocks']} "
          f"parts parsed={svc['parts_parsed']} shared={svc['parts_shared']} "
          f"giveups=0", flush=True)
    print("OK", flush=True)


if __name__ == "__main__":
    main()
