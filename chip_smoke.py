#!/usr/bin/env python3
"""First light on the chip: the main path once, end to end, checked.

    python chip_smoke.py          # no arguments, one process

corpus on disk -> ``create_parser`` (native engine) -> ``DeviceIter``
(convert pool, staging ring, packed put) -> HBM -> ``TrainLoopMixin.fit``,
cold and then warm from the block cache and the snapshot tier, plus the
two Pallas kernels inside the programs that use them, device-side decode,
ALS at a published width and — where four devices are visible — the same
on a ``make_mesh()`` mesh. Every phase checks its result against a host
reference; the first failed check or exception ends the run non-zero.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", ...}}`` only when every phase
ran on a TPU and passed. Without a TPU (``JAX_PLATFORMS=cpu``, or no
accelerator found) or without the native parse engine it exits non-zero
and prints no result. Corpora are made from seeds under ``.chip_smoke/``;
the compile cache goes where ``dmlc_tpu.utils.compile_cache`` says.

The phase functions take their sizes as arguments so that
``tests/test_chip_smoke.py`` can run each one small on the CPU backend.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

WORK = os.path.join(REPO, ".chip_smoke")
HIGGS_COLS = 28


def log(msg: str) -> None:
    print(msg, flush=True)


def _check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {msg}")


# ---------------------------------------------------------------------------
# bookkeeping: compile seconds and cache traffic, per phase
# ---------------------------------------------------------------------------

class _CompileMeter:
    """Sums JAX's own backend-compile durations (on a persistent-cache hit
    the event carries the retrieval time instead) and counts cache hits
    and misses."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


_METER = None


def _meter() -> _CompileMeter:
    global _METER
    if _METER is None:
        _METER = _CompileMeter()
    return _METER


def run_phase(name: str, fn, *args, **kwargs):
    """Run one phase; print its wall time, compile time and the device's
    peak memory so far. Exceptions propagate: a failed phase ends the run."""
    import jax

    meter = _meter()
    c0, t0 = meter.seconds, time.monotonic()
    out = fn(*args, **kwargs)
    wall = time.monotonic() - t0
    stats = jax.devices()[0].memory_stats() or {}
    log(f"{name}: PASS wall={wall:.2f}s compile={meter.seconds - c0:.2f}s "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")
    return out


# ---------------------------------------------------------------------------
# corpora, from seeds
# ---------------------------------------------------------------------------

def _publish(path: str, write) -> str:
    if not os.path.exists(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path + ".tmp", "w") as f:
            write(f)
        os.replace(path + ".tmp", path)
    return path


def dense_corpus(work: str, rows: int, cols: int, seed: int = 42) -> str:
    """HIGGS-like libsvm text: every column present, ``%.6f`` values,
    with labels from a planted linear separator so that a learner's loss
    has somewhere to fall."""
    import numpy as np

    def write(f):
        rng = np.random.default_rng(seed)
        w_true = rng.standard_normal(cols)
        fmt = " ".join(f"{j}:%.6f" for j in range(cols))
        for start in range(0, rows, 2000):
            n = min(2000, rows - start)
            vals = rng.standard_normal((n, cols)).astype(np.float32)
            labels = (vals @ w_true + 0.5 * rng.standard_normal(n) > 0)
            f.write("".join(f"{int(lb)} {fmt % tuple(r)}\n"
                            for lb, r in zip(labels, vals.tolist())))

    return _publish(os.path.join(
        work, f"dense_{rows}x{cols}_s{seed}.libsvm"), write)


def sparse_corpus(work: str, rows: int, num_col: int, nnz: int,
                  seed: int, label: str = "binary",
                  row_id_stride: int = 1, num_rank: int = 4) -> str:
    """Sparse libsvm text, ``nnz`` distinct sorted columns per row.
    ``label='binary'``: 0/1 from a planted separator over the nonzeros;
    ``label='row_id'``: the row's id (``row * row_id_stride`` — the
    ``models/als.py`` encoding), values a low-rank rating."""
    import numpy as np

    def write(f):
        rng = np.random.default_rng(seed)
        w_true = rng.standard_normal(num_col)
        gt_v = rng.standard_normal((num_col, num_rank)).astype(np.float32)
        for start in range(0, rows, 1024):
            n = min(1024, rows - start)
            # distinct columns per row without a per-row rng.choice: a
            # random offset plus a random strictly increasing stride walk
            steps = rng.integers(1, max(2, num_col // nnz), size=(n, nnz))
            cols = (np.cumsum(steps, axis=1)
                    + rng.integers(0, num_col, size=(n, 1))) % num_col
            cols.sort(axis=1)
            if label == "row_id":
                gt_u = rng.standard_normal((n, num_rank)).astype(np.float32)
                vals = np.einsum("nr,nkr->nk", gt_u, gt_v[cols])
                labels = (np.arange(start, start + n) * row_id_stride)
            else:
                vals = rng.standard_normal((n, nnz)).astype(np.float32)
                labels = ((vals * w_true[cols]).sum(axis=1) > 0).astype(int)
            f.write("".join(
                f"{int(lb)} "
                + " ".join(f"{c}:{v:.5f}" for c, v in zip(cr, vr)) + "\n"
                for lb, cr, vr in zip(labels, cols.tolist(), vals.tolist())))

    return _publish(os.path.join(
        work, f"sparse_{label}_{rows}x{num_col}k{nnz}_s{seed}"
              f"_x{row_id_stride}.libsvm"), write)


def _remove(*paths: str) -> None:
    for p in paths:
        for q in (p, p + ".tmp"):
            try:
                os.remove(q)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# shared checks
# ---------------------------------------------------------------------------

def _bit_sum(a) -> int:
    """Order-independent, exact: the float32 bit patterns summed mod 2^32."""
    import numpy as np

    a = np.ascontiguousarray(a, dtype=np.float32)
    return int(a.view(np.uint32).sum(dtype=np.uint64) % (1 << 32))


def _fold_batch(acc, batch):
    """(rows, feature bit-sum, label bit-sum) folded over one ``(x, y, w)``
    batch, on device. Pad rows are zero-weight, zero-valued: they add 0."""
    import jax
    import jax.numpy as jnp

    x, y, w = batch

    def bits(a):
        return jax.lax.bitcast_convert_type(
            a.astype(jnp.float32), jnp.uint32).sum(dtype=jnp.uint32)

    return (acc[0] + (w > 0).sum(dtype=jnp.int32),
            acc[1] + bits(x), acc[2] + bits(y))


def _leaf_devices(tree) -> set:
    import jax

    return {d for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


class _Audited:
    """Sits between a ``DeviceIter`` and ``fit_epoch``: folds every
    delivered batch into on-device checksums, notes where its arrays live,
    hands it on and keeps NO reference — so the consumer drops each batch
    as soon as its step is dispatched, which is when a staging buffer that
    was recycled too early would show up as a wrong sum."""

    def __init__(self, it):
        import jax

        self.it = it
        self._fold = jax.jit(_fold_batch)
        self.devices = set()
        self.min_shard_devices = None  # fewest devices any array spanned
        self.begin()

    def begin(self):
        import jax.numpy as jnp

        self.acc = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.uint32),
                    jnp.zeros((), jnp.uint32))
        self.batches = 0

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        batch = next(self.it)
        self.acc = self._fold(self.acc, batch)
        self.devices |= _leaf_devices(batch)
        for leaf in jax.tree_util.tree_leaves(batch):
            n = len({s.device for s in leaf.addressable_shards})
            self.min_shard_devices = min(n, self.min_shard_devices or n)
        self.batches += 1
        return batch

    def reset(self):
        self.it.reset()

    def totals(self):
        return tuple(int(v) for v in self.acc)


def _host_parse_dense(path: str, cols: int):
    """The reference: a single-thread host parse through the registry
    parser (not the streaming reader the pipeline uses)."""
    import numpy as np

    from dmlc_tpu.data import create_parser

    parser = create_parser(path, 0, 1, "libsvm", threaded=False)
    xs, ys = [], []
    for block in parser:
        n = len(block)
        _check(len(block.index) == n * cols, "reference parse: ragged rows")
        xs.append(np.asarray(block.value, np.float32).reshape(n, cols))
        ys.append(np.asarray(block.label, np.float32))
    parser.close()
    return np.concatenate(xs), np.concatenate(ys)


def _numpy_sgd_epoch(X, y, w, b, batch: int, lr: float):
    """One epoch of float32 logistic SGD in corpus order; returns the new
    (w, b) and the per-step losses — ``LinearLearner``'s arithmetic."""
    import numpy as np

    losses = []
    for s in range(0, len(X), batch):
        xb, yb = X[s:s + batch], y[s:s + batch]
        m = xb @ w + b
        per = np.maximum(m, 0) - m * yb + np.log1p(np.exp(-np.abs(m)))
        losses.append(float(per.mean(dtype=np.float32)))
        g = ((1.0 / (1.0 + np.exp(-m)) - yb) / len(xb)).astype(np.float32)
        w = w - np.float32(lr) * (xb.T @ g)
        b = b - np.float32(lr) * g.sum(dtype=np.float32)
    return w, b, losses


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _assert_on_default_devices(devices, what: str) -> None:
    import jax

    want = set(jax.devices())
    _check(devices and devices <= want,
           f"{what}: arrays on {devices}, expected a subset of {want}")
    _check(all(d.platform == jax.default_backend() for d in devices),
           f"{what}: arrays not on the {jax.default_backend()} backend")


def _check_placement(tree, shardings, what: str) -> None:
    import jax

    for leaf, sh in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(shardings)):
        _check(leaf.sharding.is_equivalent_to(sh, leaf.ndim),
               f"{what}: placed as {leaf.sharding}, _shardings() says {sh}")


# ---------------------------------------------------------------------------
# phase 0 - device and engine
# ---------------------------------------------------------------------------

def phase0_device_and_engine() -> None:
    import jax

    from dmlc_tpu import native

    dev = jax.devices()[0]
    why = []
    if dev.platform != "tpu":
        why.append(
            f"no TPU: jax.devices()[0].platform is {dev.platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}) and this "
            f"program only passes on the chip")
    if not native.available():
        why.append(
            "native parse engine unavailable (no g++, a failed build, or "
            "DMLC_TPU_NO_NATIVE set): the numpy parsers would pass as a "
            "slow success")
    if why:
        raise SystemExit("chip_smoke: FAIL " + "; ".join(why))


# ---------------------------------------------------------------------------
# phase 1 - the flagship at the bench's size
# ---------------------------------------------------------------------------

def phase1_flagship(work: str, rows: int = 198_000, batch: int = 16384,
                    lr: float = 0.1, loss_tol: float = 2e-4,
                    mesh=None) -> list:
    """HIGGS-like corpus -> default ``create_parser`` -> default dense
    ``DeviceIter`` -> ``LinearLearner.fit``: a cold epoch, a block-cache
    warm epoch and (single device) a snapshot warm epoch. Returns the
    per-step losses of the whole run."""
    import numpy as np

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import LinearLearner
    from dmlc_tpu.models._loop import host_scalar
    from dmlc_tpu.utils.timer import format_stage_table

    path = dense_corpus(work, rows, HIGGS_COLS)
    X, y = _host_parse_dense(path, HIGGS_COLS)
    _check(len(X) == rows, f"reference parse saw {len(X)} rows, not {rows}")
    want = (rows, _bit_sum(X), _bit_sum(y))

    model = LinearLearner(num_col=HIGGS_COLS, learning_rate=lr, mesh=mesh)
    cache, snap = path + ".blockcache", path + ".snapshot"
    _remove(cache, snap)

    def pipeline(**tiers):
        parser = create_parser(path, **tiers)
        it = DeviceIter(parser, num_col=model.device_num_col(),
                        batch_size=batch, layout="dense", mesh=mesh,
                        shardings=model.batch_shardings())
        return _Audited(it)

    # the snapshot store serves single-put batches only (DeviceIter refuses
    # it on a mesh), so the mesh run has two tiers and the chip run three
    first = pipeline(block_cache=cache) if mesh is not None else \
        pipeline(block_cache=cache, snapshot=snap)
    plan = [("cold", first, {"cache_state": "cold"}),
            ("block-cache warm", pipeline(block_cache=cache),
             {"cache_state": "warm"})]
    if mesh is None:
        plan.append(("snapshot warm", first, {"snapshot_state": "warm"}))

    w_ref = np.zeros(HIGGS_COLS, np.float32)
    b_ref = np.float32(0.0)
    losses = []
    try:
        for name, feed, expect in plan:
            feed.begin()
            step_losses = []
            # TrainLoopMixin.fit_epoch, with the per-step losses kept (as
            # device scalars: still no host sync inside the epoch)
            for b in feed:
                step_losses.append(model.step(b))
            stats = feed.it.stats()
            feed.reset()
            got = feed.totals()
            _check(got[0] == want[0],
                   f"{name}: {got[0]} rows delivered, corpus has {want[0]}")
            _check(got[1:] == want[1:],
                   f"{name}: on-device checksums {got[1:]} != host parse "
                   f"{want[1:]} (features, labels)")
            for key, val in expect.items():
                _check(stats[key] == val,
                       f"{name}: stats()[{key!r}] is {stats[key]!r}, "
                       f"expected {val!r}")
            w_ref, b_ref, ref = _numpy_sgd_epoch(X, y, w_ref, b_ref,
                                                 batch, lr)
            step_losses = [host_scalar(v) for v in step_losses]
            dev = max(abs(a - r) for a, r in zip(step_losses, ref))
            _check(len(step_losses) == len(ref) and dev <= loss_tol,
                   f"{name}: loss deviates from the float32 numpy SGD "
                   f"reference by {dev:.3e} > {loss_tol:.0e}")
            losses += step_losses
            log(f"  epoch {name}: {feed.batches} batches, {got[0]} rows, "
                f"checksums ok, mean loss "
                f"{sum(step_losses) / len(step_losses):.6f} (reference "
                f"{sum(ref) / len(ref):.6f}, max step deviation {dev:.2e} "
                f"<= {loss_tol:.0e}), cache={stats['cache_state']} "
                f"snapshot={stats['snapshot_state']} "
                f"ring={stats['staging_ring']}")
            log(format_stage_table(stats["stages"], stats["wall_seconds"]))
        _check(losses[-1] < 0.9 * losses[0],
               f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
        for _, feed, _ in plan:
            _assert_on_default_devices(feed.devices, "phase 1 batches")
            if mesh is not None:
                # every array of every batch: one shard on each device of
                # the mesh (not all of them on device 0)
                _check(feed.min_shard_devices == mesh.devices.size,
                       f"a batch array had shards on only "
                       f"{feed.min_shard_devices} distinct devices, mesh "
                       f"has {mesh.devices.size}")
        if mesh is not None:
            _check_placement(model.params, model._shardings()[0],
                             "linear params")
    finally:
        for _, feed, _ in plan:
            feed.it.close()
        _remove(cache, snap)
    log(f"  loss {losses[0]:.6f} -> {losses[-1]:.6f} over {len(losses)} "
        f"steps")
    return losses


# ---------------------------------------------------------------------------
# phase 2 - the ELL kernel inside a real train step
# ---------------------------------------------------------------------------

def _step_lowering(model, batch_size: int, max_nnz: int) -> str:
    """StableHLO text of the learner's jitted step at this geometry."""
    import jax
    import jax.numpy as jnp

    from dmlc_tpu.ops.sparse import EllBatch

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    batch = EllBatch(
        jax.ShapeDtypeStruct((batch_size, max_nnz), jnp.int32),
        jax.ShapeDtypeStruct((batch_size, max_nnz), jnp.float32),
        jax.ShapeDtypeStruct((batch_size,), jnp.float32),
        jax.ShapeDtypeStruct((batch_size,), jnp.float32))
    lowered = model._step.lower(spec(model.params), spec(model.opt_state),
                                batch)
    lowered.compile()
    return lowered.as_text()


def phase2_ell_kernel(work: str, weight_dim: int = 4096, max_nnz: int = 64,
                      batch: int = 8192, steps: int = 3,
                      also=((2048, 64), (1024, 48)),
                      tol: float = 1e-5) -> None:
    """``LinearLearner(layout='ell')`` at the top of ``pallas_band``: real
    steps from a sparse corpus through ``DeviceIter(layout='ell')``, the
    step's reported loss against the XLA-gather loss on the same batch."""
    import jax

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import LinearLearner
    from dmlc_tpu.models import linear as _linear
    from dmlc_tpu.models._loop import host_scalar
    from dmlc_tpu.ops.pallas_sparse import pallas_band

    _check(pallas_band(batch, weight_dim),
           f"B={batch}, D={weight_dim} is outside pallas_band")
    model = LinearLearner(num_col=weight_dim - 1, layout="ell",
                          learning_rate=0.1)
    _check(model.weight_dim == weight_dim, "weight width")
    text = _step_lowering(model, batch, max_nnz)
    if _on_tpu():
        _check("tpu_custom_call" in text,
               "the compiled ELL step holds no Mosaic custom call: the "
               "auto-route did not take the Pallas kernel")
    for d, k in also:
        other = LinearLearner(num_col=d - 1, layout="ell")
        has = "tpu_custom_call" in _step_lowering(other, batch, k)
        _check(has or not _on_tpu(), f"D={d}/K={k}: no Mosaic custom call")
        log(f"  compiled step D={d} K={k} B={batch}: mosaic={has}")

    @jax.jit
    def xla_loss(params, b):
        margin = _linear._margin_ell(params, b, use_auto=False)
        return _linear._loss_from_margin(margin, b.label, b.weight,
                                         "logistic", 0.0, params)

    path = sparse_corpus(work, batch * steps, weight_dim - 1, max_nnz,
                         seed=7)
    it = DeviceIter(create_parser(path), num_col=model.device_num_col(),
                    batch_size=batch, layout="ell", max_nnz=max_nnz)
    devices, losses = set(), []
    try:
        for b in it:
            ref = host_scalar(xla_loss(model.params, b))  # before donation
            loss = host_scalar(model.step(b))
            _check(abs(loss - ref) <= tol,
                   f"step {len(losses)}: kernel-route loss {loss:.7f} vs "
                   f"XLA-gather loss {ref:.7f}")
            devices |= _leaf_devices(b)
            losses.append(loss)
    finally:
        it.close()
    _check(len(losses) == steps, f"{len(losses)} steps, expected {steps}")
    _check(losses[-1] < losses[0], f"ELL loss did not fall: {losses}")
    _assert_on_default_devices(devices, "phase 2 batches")
    log(f"  D={weight_dim} K={max_nnz} B={batch}: mosaic="
        f"{'tpu_custom_call' in text}, {steps} steps, loss "
        f"{losses[0]:.6f} -> {losses[-1]:.6f}, equal to the XLA route "
        f"within {tol:.0e}")


# ---------------------------------------------------------------------------
# phase 3 - device-side decode
# ---------------------------------------------------------------------------

def _decode_case(work: str, name: str, path: str, num_col: int, batch: int,
                 x_dtype: str, want_route: str) -> None:
    import jax
    import numpy as np

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.io.snapshot import SnapshotReader
    from dmlc_tpu.ops import device_decode as dd

    snap = os.path.join(work, f"decode_{name}.snapshot")
    _remove(snap)

    def epoch(device_decode: bool):
        it = DeviceIter(create_parser(path, snapshot=snap), num_col=num_col,
                        batch_size=batch, layout="dense", x_dtype=x_dtype,
                        device_decode=device_decode)
        try:
            out = [[np.asarray(leaf) for leaf in
                    jax.tree_util.tree_leaves(b)] for b in it]
            return out, it.stats()
        finally:
            it.close()

    try:
        _, cold = epoch(False)  # publishes the snapshot
        _check(cold["snapshot_state"] == "cold", f"{name}: not a cold pass")
        host, hstats = epoch(False)
        dev, dstats = epoch(True)
        _check(hstats["snapshot_state"] == dstats["snapshot_state"] == "warm",
               f"{name}: warm epochs did not serve from the snapshot")
        _check(len(host) == len(dev) > 0, f"{name}: batch counts differ")
        for i, (hb, db) in enumerate(zip(host, dev)):
            for h, d in zip(hb, db):
                _check(h.dtype == d.dtype and h.shape == d.shape
                       and h.tobytes() == d.tobytes(),
                       f"{name}: batch {i} differs between host decode "
                       f"and device decode")
        routes = dstats["device_decode_routes"]
        other = "xla" if want_route == "pallas" else "pallas"
        _check(routes[want_route] == len(dev) and routes[other] == 0,
               f"{name}: decode routes {routes}, expected every batch on "
               f"{want_route!r}")
        reader = SnapshotReader(snap)
        try:
            _, span, layout = reader.batch_span(0)
            nbytes = int(span.nbytes)
        finally:
            reader.close()
        text = dd._decode_span_jit.lower(
            jax.ShapeDtypeStruct((nbytes,), np.uint8), layout,
            use_pallas=want_route == "pallas").as_text()
        mosaic = "tpu_custom_call" in text
        if _on_tpu():
            _check(mosaic == (want_route == "pallas"),
                   f"{name}: route {want_route!r} but Mosaic custom call "
                   f"present={mosaic}")
        shapes = [(seg[1], seg[4]) for seg in layout]
        log(f"  {name}: {len(dev)} batches byte-identical, route="
            f"{want_route} mosaic={mosaic} span={nbytes}B segments={shapes}")
    finally:
        _remove(snap)


def phase3_device_decode(work: str, narrow_batch: int = 4096,
                         wide_batch: int = 1024, wide_cols: int = 4096,
                         higgs_rows: int = 40_000,
                         higgs_batch: int = 16384) -> None:
    """Snapshot-warm epochs with ``device_decode=True`` against the host
    decode, byte for byte, over geometries ``pallas_decode_eligible``
    accepts (narrow and wide, f32 packed and bf16) and one it rejects."""
    from dmlc_tpu.ops import device_decode as dd

    pallas = "pallas" if dd._on_tpu_backend() else "xla"  # the route's gate
    # f32 batches are packed [B, num_col + 2]; bf16 ones ship x [B, num_col]
    for name, cols, batch, x_dtype, pad in (
            ("f32-narrow", 128, narrow_batch, "float32", 2),
            ("f32-wide", wide_cols, wide_batch, "float32", 2),
            ("bf16-narrow", 128, narrow_batch, "bfloat16", 0),
            ("bf16-wide", wide_cols, wide_batch, "bfloat16", 0)):
        num_col = cols - pad
        path = sparse_corpus(work, 2 * batch, num_col, 32, seed=11)
        _decode_case(work, name, path, num_col, batch, x_dtype, pallas)
    path = dense_corpus(work, higgs_rows, HIGGS_COLS, seed=43)
    _decode_case(work, "higgs-30", path, HIGGS_COLS, higgs_batch,
                 "float32", "xla")


# ---------------------------------------------------------------------------
# phase 4 - ALS at a published width
# ---------------------------------------------------------------------------

ML20M_USERS, ML20M_ITEMS = 138_493, 26_744


def phase4_als(work: str, num_users: int = ML20M_USERS,
               num_items: int = ML20M_ITEMS, factors: int = 128,
               max_nnz: int = 64, batch: int = 512, steps: int = 4,
               mesh=None, solve_tol: float = 2e-3) -> list:
    """``AlsLearner`` at MovieLens-20M's shape with the ALX paper's 128
    factors: ``steps`` user batches, the item solve, then the same batches
    again. Returns both passes' per-step losses."""
    import numpy as np

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import AlsLearner
    from dmlc_tpu.models._loop import host_scalar

    users = num_users - num_users % batch  # a whole number of batches
    log(f"  users={users} (of {num_users}) items={num_items} "
        f"factors={factors} K={max_nnz} batch={batch} steps={steps}")
    reg = 0.1
    model = AlsLearner(users, num_items, num_factors=factors, reg=reg,
                       seed=0, mesh=mesh)
    # ids spread over the whole table, so scatters land across all of it
    stride = max(1, users // (batch * steps))
    path = sparse_corpus(work, batch * steps, num_items, max_nnz, seed=5,
                         label="row_id", row_id_stride=stride)
    it = DeviceIter(create_parser(path), num_col=model.device_num_col(),
                    batch_size=batch, layout="ell", max_nnz=max_nnz,
                    mesh=mesh, shardings=model.batch_shardings(),
                    drop_remainder=True)
    items0 = np.asarray(model.params.items, np.float64)
    try:
        first = next(it)
        devices = _leaf_devices(first)
        idx, vals, uid = (np.asarray(a) for a in
                          (first.indices, first.values, first.label))
        losses = [model.step(first)]
        # the first batch against a float64 host solve: every solved row,
        # and the loss the step reported for them
        v = items0[idx]                                     # [B, K, F]
        a = np.einsum("bkf,bkg->bfg", v, v) + reg * np.eye(factors)
        rhs = np.einsum("bkf,bk->bf", v, vals.astype(np.float64))
        u_ref = np.linalg.solve(a, rhs[..., None])[..., 0]
        u_dev = np.asarray(model.params.users)[uid.astype(np.int64)]
        err = np.abs(u_dev - u_ref).max() / np.abs(u_ref).max()
        _check(err <= solve_tol,
               f"first batch: solved rows deviate from the float64 "
               f"reference by {err:.2e} > {solve_tol:.0e}")
        loss_ref = float(((np.einsum("bkf,bf->bk", v, u_ref) - vals) ** 2)
                         .mean())
        loss_err = abs(host_scalar(losses[0]) - loss_ref) / loss_ref
        _check(loss_err <= solve_tol,
               f"first step: loss {host_scalar(losses[0]):.6f} vs float64 "
               f"{loss_ref:.6f} ({loss_err:.2e} > {solve_tol:.0e})")
        losses += [model.step(b) for b in it]
        it.reset()
        model.finalize_items()
        second = [model.step(b) for b in it]
        it.reset()
        model.finalize_items()
        losses = [host_scalar(v) for v in losses]
        second = [host_scalar(v) for v in second]
    finally:
        it.close()
    _check(len(losses) == len(second) == steps,
           f"{len(losses)} and {len(second)} steps, expected {steps}")
    _check(all(np.isfinite(losses + second)), f"loss not finite: {losses} "
                                              f"{second}")
    m1, m2 = sum(losses) / steps, sum(second) / steps
    _check(m2 < m1, f"second pass not lower: {m1:.6f} -> {m2:.6f}")
    _check(float(np.abs(np.asarray(model.params.items[-1])).max()) == 0.0,
           "the item table's pad sink row moved off zero")
    _assert_on_default_devices(devices, "phase 4 batches")
    if mesh is not None:
        _check(len(devices) == mesh.devices.size,
               f"als batch on {len(devices)} devices, mesh has "
               f"{mesh.devices.size}")
        _, params_sh, opt_sh = model._rep_shardings()
        _check_placement(model.params, params_sh, "als params")
        _check_placement(model.opt_state, opt_sh, "als normal equations")
    log(f"  pass 1 mean loss {m1:.6f}, pass 2 {m2:.6f} (after "
        f"finalize_items); first batch against float64: solved rows "
        f"{err:.1e}, loss {loss_err:.1e} (relative, <= {solve_tol:.0e})")
    return losses + second


# ---------------------------------------------------------------------------
# phase 5 - four devices
# ---------------------------------------------------------------------------

def phase5_mesh(work: str, single_linear: list, single_als: list,
                flagship_kw=None, als_kw=None, traj_steps: int = 20,
                tol: float = 1e-4) -> None:
    """Phases 1 and 4 again on ``make_mesh()`` with the learners' own
    ``batch_shardings()``: batch shards on distinct devices, parameters
    where ``_shardings()`` says (both checked inside the phases), and the
    trajectories equal to the one-device runs."""
    from dmlc_tpu.parallel import make_mesh

    mesh = make_mesh()
    log(f"  mesh {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")

    mesh_linear = phase1_flagship(work, mesh=mesh, **(flagship_kw or {}))
    k = min(traj_steps, len(mesh_linear), len(single_linear))
    dev = max(abs(a - b) for a, b in zip(mesh_linear[:k], single_linear[:k]))
    _check(dev <= tol, f"linear: {k}-step mesh trajectory deviates from "
                       f"the one-device run by {dev:.2e} > {tol:.0e}")
    log(f"  linear: {k}-step trajectory equals one device within "
        f"{dev:.2e} (<= {tol:.0e})")

    mesh_als = phase4_als(work, mesh=mesh, **(als_kw or {}))
    dev = max(abs(a - b) / max(abs(b), 1e-6)
              for a, b in zip(mesh_als, single_als))
    _check(len(mesh_als) == len(single_als) and dev <= 1e-2,
           f"als: mesh losses deviate from one device by {dev:.2e}")
    log(f"  als: {len(mesh_als)} step losses equal one device within "
        f"{dev:.2e} (relative, <= 1e-02)")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def _versions() -> str:
    from importlib import metadata

    out = []
    for pkg in ("jax", "jaxlib", "libtpu", "numpy", "optax"):
        try:
            out.append(f"{pkg}={metadata.version(pkg)}")
        except metadata.PackageNotFoundError:
            out.append(f"{pkg}=absent")
    return " ".join(out)


def _compile_totals(cache_dir: str, meter: _CompileMeter) -> None:
    """Print this run's compile total beside the previous run's in this
    checkout (cold, then warm), and record it for the next one."""
    record = os.path.join(WORK, "compile_totals.json")
    try:
        with open(record) as f:
            runs = json.load(f)
    except (OSError, ValueError):
        runs = []
    this = {"compile_seconds": round(meter.seconds, 2),
            "cache_hits": meter.hits, "cache_misses": meter.misses,
            "cache_dir": cache_dir}
    log(f"compile total this run: {this['compile_seconds']}s "
        f"(persistent cache hits={meter.hits} misses={meter.misses})")
    for prev in runs[-1:]:
        log(f"compile total previous run in this checkout: "
            f"{prev['compile_seconds']}s (hits={prev['cache_hits']} "
            f"misses={prev['cache_misses']})")
    os.makedirs(WORK, exist_ok=True)
    with open(record, "w") as f:
        json.dump(runs + [this], f)


def main() -> int:
    from dmlc_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax

    from dmlc_tpu import native

    meter = _meter()
    devs = jax.devices()
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)}")
    log(_versions())
    log(f"engine={'native' if native.available() else 'numpy'} "
        f"compile_cache={cache_dir}")

    run_phase("phase 0 device and engine", phase0_device_and_engine)
    linear = run_phase("phase 1 flagship", phase1_flagship, WORK)
    run_phase("phase 2 ell kernel in a train step", phase2_ell_kernel, WORK)
    run_phase("phase 3 device-side decode", phase3_device_decode, WORK)
    als = run_phase("phase 4 als at MovieLens-20M width", phase4_als, WORK)
    if len(devs) >= 4:
        run_phase("phase 5 mesh", phase5_mesh, WORK, linear, als)
    else:
        log(f"phase 5 mesh: not run ({len(devs)} device visible, needs 4)")
    _compile_totals(cache_dir, meter)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
