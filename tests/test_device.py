"""JAX shim tests: sparse layouts, device pipeline, sharded linear learner.

Runs on the 8-device virtual CPU mesh (conftest.py), per SURVEY.md §4(d).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu.data import create_parser
from dmlc_tpu.data.device import DeviceIter, rebatch_blocks
from dmlc_tpu.data.row_block import RowBlock
from dmlc_tpu.models import LinearLearner
from dmlc_tpu.ops import (
    block_to_bcoo, block_to_dense, block_to_ell, ell_matvec, segment_csr_matvec,
)
from dmlc_tpu.parallel import data_sharding, make_mesh


def _block():
    return RowBlock(
        offset=[0, 2, 3, 6],
        label=[1.0, 0.0, 1.0],
        index=np.array([0, 3, 1, 0, 2, 4], dtype=np.uint64),
        value=np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0], dtype=np.float32),
        weight=np.array([1.0, 0.5, 2.0], dtype=np.float32),
    )


def test_devices_are_8():
    assert len(jax.devices()) == 8


# ---------------- layouts ----------------

def test_block_to_ell_matches_dense():
    blk = _block()
    ncol = 5
    ell = block_to_ell(blk, ncol)
    assert ell.indices.shape == (3, 3)  # max row nnz = 3
    dense = blk.to_dense(ncol)
    w = np.arange(1, ncol + 1, dtype=np.float32)
    want = dense @ w
    wp = jnp.concatenate([jnp.asarray(w), jnp.zeros(1)])  # +pad sink
    got = ell_matvec(wp, ell._replace(
        indices=jnp.asarray(ell.indices), values=jnp.asarray(ell.values)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_block_to_ell_pad_and_truncate():
    blk = _block()
    ell = block_to_ell(blk, 5, max_nnz=2, pad_rows_to=6)
    assert ell.indices.shape == (6, 2)
    assert ell.weight[3:].sum() == 0.0       # padded rows carry zero weight
    assert (ell.indices[3:] == 5).all()      # pad index = num_col
    # truncation kept the first 2 entries of row 2
    np.testing.assert_array_equal(ell.indices[2], [0, 2])


@pytest.mark.parametrize("max_nnz", [None, 3, 6, 7, 16])
@pytest.mark.parametrize("with_values,with_fields", [(True, True),
                                                     (False, False)])
def test_block_to_ell_is_a_row_loop_whether_or_not_a_row_is_cut(
        max_nnz, with_values, with_fields):
    """``block_to_ell`` (one masked assignment a plane; of a row that is
    cut, its first K entries) against a plain loop over rows and slots;
    rows of 0 to 7 entries, so K = 3 and 6 cut rows and K = 7, 16 and the
    block's own maximum do not."""
    rng = np.random.default_rng(5)
    lens = rng.integers(0, 8, 200)
    lens[:4] = [0, 7, 0, 7]
    offset = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    nnz = int(offset[-1])
    blk = RowBlock(
        offset=offset, label=rng.random(200).astype(np.float32),
        index=rng.integers(0, 1000, nnz).astype(np.uint64),
        value=rng.random(nnz).astype(np.float32) if with_values else None,
        weight=rng.random(200).astype(np.float32),
        field=rng.integers(0, 300, nnz).astype(np.uint64)
        if with_fields else None)
    ell = block_to_ell(blk, 1000, max_nnz=max_nnz, pad_rows_to=208,
                       fields=with_fields)
    k = 7 if max_nnz is None else max_nnz
    want_i = np.full((208, k), 1000, np.int32)
    want_v = np.zeros((208, k), np.float32)
    want_f = np.zeros((208, k), np.uint16)
    for r in range(200):
        for slot, j in enumerate(range(offset[r], offset[r + 1])):
            if slot < k:
                want_i[r, slot] = blk.index[j]
                want_v[r, slot] = blk.value[j] if with_values else 1.0
                if with_fields:
                    want_f[r, slot] = blk.field[j]
    np.testing.assert_array_equal(ell.indices, want_i)
    np.testing.assert_array_equal(ell.values, want_v)
    assert ell.indices.dtype == np.int32 and ell.values.dtype == np.float32
    if with_fields:
        np.testing.assert_array_equal(ell.fields, want_f)
        assert ell.fields.dtype == np.uint16
    else:
        assert ell.fields is None
    np.testing.assert_array_equal(ell.label[:200], blk.label)
    assert ell.weight[200:].sum() == 0.0


def test_block_to_dense_pad():
    x, y, w = block_to_dense(_block(), 5, pad_rows_to=4)
    assert x.shape == (4, 5)
    assert y[3] == 0 and w[3] == 0
    assert x[0, 3] == 2.0


def test_block_to_bcoo():
    bc = block_to_bcoo(_block(), 5)
    np.testing.assert_allclose(np.asarray(bc.todense()), _block().to_dense(5))


def test_segment_csr_matvec():
    blk = _block()
    w = jnp.arange(1.0, 6.0)
    rows = np.repeat(np.arange(3), np.diff(blk.offset))
    got = segment_csr_matvec(
        w, jnp.asarray(blk.index.astype(np.int32)), jnp.asarray(blk.value),
        jnp.asarray(rows), 3)
    want = blk.to_dense(5) @ np.arange(1.0, 6.0, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


# ---------------- rebatching ----------------

def test_rebatch_blocks_fixed_size():
    blocks = [_block() for _ in range(5)]  # 15 rows total
    out = list(rebatch_blocks(iter(blocks), 4))
    assert [len(b) for b in out] == [4, 4, 4, 3]
    # labels preserved in order
    labels = np.concatenate([b.label for b in out])
    np.testing.assert_array_equal(labels, np.tile([1, 0, 1], 5))
    out2 = list(rebatch_blocks(iter(blocks), 4, drop_remainder=True))
    assert [len(b) for b in out2] == [4, 4, 4]


# ---------------- device iter ----------------

def _libsvm_corpus(tmp_path, n=64, d=6):
    rng = np.random.default_rng(0)
    lines = []
    for i in range(n):
        nnz = rng.integers(1, d)
        idx = sorted(rng.choice(d, size=nnz, replace=False))
        feats = " ".join(f"{j}:{rng.normal():.4f}" for j in idx)
        lines.append(f"{i % 2} {feats}")
    p = tmp_path / "train.libsvm"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


@pytest.mark.parametrize("layout", ["dense", "ell", "bcoo"])
def test_device_iter_shapes_and_epochs(tmp_path, layout):
    uri = _libsvm_corpus(tmp_path)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout=layout, max_nnz=6)
    batches = list(it)
    assert len(batches) == 4
    if layout == "dense":
        x, y, w = batches[0]
        assert x.shape == (16, 6) and isinstance(x, jax.Array)
    elif layout == "bcoo":
        mat, y, w = batches[0]
        assert mat.shape == (16, 6) and isinstance(mat.data, jax.Array)
        assert y.shape == (16,) and w.shape == (16,)
        # BCOO batch densifies to the same matrix as the dense layout
        dense_it = DeviceIter(
            create_parser(uri, 0, 1, "libsvm", threaded=False),
            num_col=6, batch_size=16, layout="dense")
        dx, dy, dw = next(iter(dense_it))
        np.testing.assert_allclose(np.asarray(mat.todense()), np.asarray(dx),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(y), np.asarray(dy))
        dense_it.close()
    else:
        assert batches[0].indices.shape[0] == 16
    it.reset()
    batches2 = list(it)
    assert len(batches2) == 4
    if layout == "dense":
        np.testing.assert_allclose(np.asarray(batches[0][0]),
                                   np.asarray(batches2[0][0]))
    assert it.stats()["bytes_to_device"] > 0
    it.close()


def test_device_iter_sharded_over_mesh(tmp_path):
    mesh = make_mesh({"data": 8})
    uri = _libsvm_corpus(tmp_path)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=32, layout="dense", mesh=mesh)
    x, y, w = next(iter(it))
    assert x.shape == (32, 6)
    assert x.sharding.spec == data_sharding(mesh, ndim=2).spec
    # each device holds 4 rows
    assert x.addressable_shards[0].data.shape == (4, 6)
    it.close()


# ---------------- linear learner ----------------

def _separable_corpus(tmp_path, n=256, d=8):
    rng = np.random.default_rng(1)
    w_true = rng.normal(size=d)
    lines = []
    for _ in range(n):
        x = rng.normal(size=d)
        y = int(x @ w_true > 0)
        feats = " ".join(f"{j}:{x[j]:.5f}" for j in range(d))
        lines.append(f"{y} {feats}")
    p = tmp_path / "sep.libsvm"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


@pytest.mark.parametrize("layout", ["dense", "ell"])
def test_linear_learner_learns(tmp_path, layout):
    uri = _separable_corpus(tmp_path)
    model = LinearLearner(num_col=8, objective="logistic", layout=layout,
                          learning_rate=0.5)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=64,
                    layout=layout, max_nnz=8)
    model.fit(it, epochs=15)
    acc = model.accuracy(it)
    assert acc > 0.9, f"layout={layout} acc={acc}"
    it.close()


def test_linear_learner_sharded_dp_matches_single(tmp_path):
    uri = _separable_corpus(tmp_path)
    mesh = make_mesh({"data": 8})

    def run(mesh_arg):
        model = LinearLearner(num_col=8, layout="dense", learning_rate=0.5,
                              mesh=mesh_arg)
        parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
        it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=64,
                        layout="dense", mesh=mesh_arg, drop_remainder=True)
        model.fit(it, epochs=3)
        it.close()
        return np.asarray(model.params.weight)

    w_single = run(None)
    w_sharded = run(mesh)
    # data-parallel grads psum to the same update as single-device
    np.testing.assert_allclose(w_sharded, w_single, rtol=1e-4, atol=1e-5)


def test_linear_learner_dp_tp_mesh(tmp_path):
    # 4-way data x 2-way model sharding on the dense path
    uri = _separable_corpus(tmp_path)
    mesh = make_mesh({"data": 4, "model": 2})
    model = LinearLearner(num_col=8, layout="dense", learning_rate=0.5,
                          mesh=mesh, model_axis="model")
    assert model.weight_dim == 10  # 8+1 rounded up to the model axis
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=64,
                    layout="dense", mesh=mesh, drop_remainder=True,
                    shardings=model.batch_shardings())
    model.fit(it, epochs=3)
    acc = model.accuracy(it)
    assert acc > 0.8
    it.close()


# ---------------- cached split + http fs + pallas ----------------

def test_cached_input_split(tmp_path):
    from dmlc_tpu.io import create_input_split

    p = tmp_path / "data.txt"
    lines = [f"row-{i}".encode() for i in range(200)]
    p.write_bytes(b"\n".join(lines) + b"\n")
    cache = tmp_path / "chunks.cache"
    uri = f"{p}#{cache}"
    split = create_input_split(uri, 0, 1, "text")
    first = [bytes(r) for r in split.iter_records()]
    assert first == lines
    assert cache.exists()
    split.before_first()
    second = [bytes(r) for r in split.iter_records()]
    assert second == lines
    split.close()
    # second open reads only from cache — delete the source to prove it
    p.unlink()
    split2 = create_input_split(uri, 0, 1, "text")
    assert [bytes(r) for r in split2.iter_records()] == lines
    split2.close()


def test_cached_split_partition_qualified(tmp_path):
    from dmlc_tpu.io import create_input_split

    p = tmp_path / "d.txt"
    p.write_bytes(b"\n".join(f"r{i}".encode() for i in range(100)) + b"\n")
    cache = tmp_path / "c"
    got = []
    for part in range(2):
        s = create_input_split(f"{p}#{cache}", part, 2, "text")
        got.extend(bytes(r) for r in s.iter_records())
        s.close()
    assert got == [f"r{i}".encode() for i in range(100)]
    assert (tmp_path / "c.split2.part0").exists()
    assert (tmp_path / "c.split2.part1").exists()


def test_http_filesystem_range_reads(tmp_path):
    import functools
    import http.server
    import threading

    from dmlc_tpu.io import create_input_split, open_stream

    lines = [f"line-{i}".encode() for i in range(500)]
    (tmp_path / "serve.txt").write_bytes(b"\n".join(lines) + b"\n")
    handler = functools.partial(
        http.server.SimpleHTTPRequestHandler, directory=str(tmp_path))
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{port}/serve.txt"
        with open_stream(url) as f:
            head = f.read(16)
            assert head == b"\n".join(lines)[:16]
            f.seek(7)
            assert f.read(6) == (b"\n".join(lines))[7:13]
        # full input-split over http with byte-range partitioning
        got = []
        for part in range(3):
            s = create_input_split(url, part, 3, "text", threaded=False)
            got.extend(bytes(r) for r in s.iter_records())
            s.close()
        assert got == lines
    finally:
        server.shutdown()


def test_cloud_protocol_slots():
    import os

    from dmlc_tpu.io import get_filesystem
    from dmlc_tpu.io.gcs_filesys import GcsFileSystem
    from dmlc_tpu.io.s3_filesys import S3FileSystem

    # gs/s3/hdfs/azure are all real clients now (azure exceeds the
    # reference, whose own client is a stub — azure_filesys.h:22-31)
    from dmlc_tpu.io.azure_filesys import AzureFileSystem
    from dmlc_tpu.io.hdfs_filesys import HdfsFileSystem

    assert isinstance(get_filesystem("gs://b/x"), GcsFileSystem)
    assert isinstance(get_filesystem("s3://b/x"), S3FileSystem)
    assert isinstance(get_filesystem("hdfs://nn/x"), HdfsFileSystem)
    os.environ.setdefault("AZURE_STORAGE_ACCOUNT", "a")
    os.environ.setdefault("AZURE_STORAGE_ACCESS_KEY", "az==")
    try:
        assert isinstance(get_filesystem("azure://c/x"), AzureFileSystem)
    finally:
        for var in ("AZURE_STORAGE_ACCOUNT", "AZURE_STORAGE_ACCESS_KEY"):
            if os.environ.get(var) in ("a", "az=="):
                del os.environ[var]


def test_pallas_ell_matvec_matches_xla():
    from dmlc_tpu.ops.pallas_sparse import ell_matvec_pallas
    from dmlc_tpu.ops import ell_matvec

    rng = np.random.default_rng(0)
    B, K, D = 256, 16, 640
    indices = rng.integers(0, D, size=(B, K)).astype(np.int32)
    values = rng.normal(size=(B, K)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    from dmlc_tpu.ops.sparse import EllBatch

    ell = EllBatch(jnp.asarray(indices), jnp.asarray(values),
                   jnp.zeros(B), jnp.ones(B))
    want = ell_matvec(w, ell)
    got = ell_matvec_pallas(w, ell.indices, ell.values,
                            block_b=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # K large enough that r2's unrolled lowering used to blow up (K=64):
    # the grid-K kernel must stay numerically identical (its IR is O(1)
    # in K — k is a grid dimension, so there is nothing to blow up)
    K2 = 64
    idx2 = rng.integers(0, D, size=(B, K2)).astype(np.int32)
    val2 = rng.normal(size=(B, K2)).astype(np.float32)
    ell2 = EllBatch(jnp.asarray(idx2), jnp.asarray(val2),
                    jnp.zeros(B), jnp.ones(B))
    want2 = ell_matvec(w, ell2)
    got2 = ell_matvec_pallas(w, ell2.indices, ell2.values,
                             block_b=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("D,K", [(512, 32), (1024, 48), (2048, 64)])
def test_pallas_ell_matvec_candidate_band_parity(D, K):
    """Interpret-mode parity at EXACTLY the auto-router candidate band
    (bench_sparse_tpu.py hashed_512/1k/2k shapes): when the hardware A/B
    runs, the only open question should be SPEED — numerical identity at these widths is pre-established
    here, so a winning band can be gated in without a correctness
    escort."""
    from dmlc_tpu.ops import ell_matvec
    from dmlc_tpu.ops.pallas_sparse import ell_matvec_pallas
    from dmlc_tpu.ops.sparse import EllBatch

    rng = np.random.default_rng(D)
    B = 256
    idx = rng.integers(0, D, size=(B, K)).astype(np.int32)
    val = rng.normal(size=(B, K)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    ell = EllBatch(jnp.asarray(idx), jnp.asarray(val),
                   jnp.zeros(B), jnp.ones(B))
    want = ell_matvec(w, ell)
    got = ell_matvec_pallas(w, ell.indices, ell.values,
                            block_b=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,block_b", [(1, 32), (7, 64), (96, 32)])
def test_pallas_ell_matvec_interpret_edge_widths(K, block_b):
    """Interpret-mode parity OFF the candidate band: K=1 (degenerate
    single-slot rows), K=7 (non-power-of-2), K=96 (wider than any bench
    shape), at small block_b tiles — the grid-K kernel must be exact at
    widths the auto-router never picks, so a future band change can't
    silently step onto untested math."""
    from dmlc_tpu.ops import ell_matvec
    from dmlc_tpu.ops.pallas_sparse import ell_matvec_pallas
    from dmlc_tpu.ops.sparse import EllBatch

    rng = np.random.default_rng(K)
    B, D = 128, 384
    idx = rng.integers(0, D, size=(B, K)).astype(np.int32)
    val = rng.normal(size=(B, K)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    ell = EllBatch(jnp.asarray(idx), jnp.asarray(val),
                   jnp.zeros(B), jnp.ones(B))
    want = ell_matvec(w, ell)
    got = ell_matvec_pallas(w, ell.indices, ell.values,
                            block_b=block_b, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_pallas_ell_matvec_interpret_duplicate_and_padded_slots():
    """ELL rows routinely repeat a column (hash collisions) or pad the
    tail with value 0.0 — the kernel's gather+multiply must accumulate
    duplicates and ignore padding exactly like the XLA reference."""
    from dmlc_tpu.ops import ell_matvec
    from dmlc_tpu.ops.pallas_sparse import ell_matvec_pallas
    from dmlc_tpu.ops.sparse import EllBatch

    rng = np.random.default_rng(42)
    B, K, D = 64, 8, 256
    idx = rng.integers(0, D, size=(B, K)).astype(np.int32)
    idx[:, 1] = idx[:, 0]          # every row: one duplicated column
    val = rng.normal(size=(B, K)).astype(np.float32)
    val[:, K // 2:] = 0.0          # and a zero-padded tail
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    ell = EllBatch(jnp.asarray(idx), jnp.asarray(val),
                   jnp.zeros(B), jnp.ones(B))
    want = ell_matvec(w, ell)
    got = ell_matvec_pallas(w, ell.indices, ell.values,
                            block_b=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pallas_tile_pick_lane_aligned():
    """Compiled-mode tiles must be multiples of 128 (Mosaic lane minimum,
    advisor r3): _pick_block_b returns only {256, 128, 0}, and the raw
    kernel entry refuses loudly when no valid tile exists instead of
    failing to lower on hardware."""
    from dmlc_tpu.ops.pallas_sparse import (
        _pick_block_b, ell_matvec_pallas,
    )

    assert _pick_block_b(8192, 640) == 256
    assert _pick_block_b(8192, 1 << 20) == 0       # slab beyond VMEM budget
    assert _pick_block_b(384, 640) == 128          # 384 % 256 != 0
    assert _pick_block_b(200, 640) == 0            # no lane-aligned divisor

    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 64, size=(200, 4)).astype(np.int32))
    val = jnp.asarray(rng.normal(size=(200, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=64).astype(np.float32))
    with pytest.raises(ValueError, match="lane-aligned"):
        ell_matvec_pallas(w, idx, val)  # compiled-mode pick: B=200 invalid


def test_ell_tile_budget_counts_the_whole_kernel_footprint():
    """_valid_block_b budgets the kernel's whole scoped-VMEM footprint —
    slab scratch, its loaded value, a compare temporary, the lane-padded
    iota column, double-buffered blocks — not the slab alone. D=8192 at
    bb=128 is the case the slab-only rule accepted (a 4 MiB slab) and the
    compiler refused (16.5 MiB against a 16 MiB limit)."""
    from dmlc_tpu.ops.pallas_sparse import (
        SCOPED_VMEM_BYTES, _kernel_vmem_bytes, _pick_block_b, _valid_block_b,
    )

    B = 8192
    for D in (28, 512, 1024, 2048, 4096, 4480, 6144, 7936, 8192, 1 << 20):
        for bb in (128, 256):
            if _valid_block_b(B, D, bb):
                slab = D * bb * 4
                assert 3 * slab + D * 128 * 4 < SCOPED_VMEM_BYTES, (D, bb)
                assert _kernel_vmem_bytes(D, bb) < SCOPED_VMEM_BYTES
    assert _valid_block_b(B, 4096, 256)       # the top of pallas_band fits
    assert not _valid_block_b(B, 8192, 128)   # a 4 MiB slab, 16.5 MiB total
    assert _pick_block_b(B, 4480) == 128      # 256 compiles to 16.34 MiB
    assert _pick_block_b(B, 8192) == 0
    assert _pick_block_b(B, 4096, vmem_budget=9 << 20) == 128


def test_softmax_learner_sharded():
    """Multinomial softmax on a 2D mesh (dp x tp), end-to-end data pipeline."""
    import jax.numpy as jnp

    from dmlc_tpu.models.linear import LinearLearner
    from dmlc_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 4, "model": 2})
    model = LinearLearner(num_col=8, objective="softmax", num_class=3,
                          mesh=mesh, model_axis="model", learning_rate=0.5)
    rng = np.random.default_rng(1)
    n = 64
    X = rng.normal(size=(n, model.device_num_col())).astype(np.float32)
    X[:, 8:] = 0
    w_true = rng.normal(size=(8, 3))
    y = (X[:, :8] @ w_true).argmax(-1).astype(np.float32)
    ones = np.ones(n, np.float32)
    batch = (jnp.asarray(X), jnp.asarray(y), jnp.asarray(ones))
    first = float(model.step(batch))
    for _ in range(40):
        loss = float(model.step(batch))
    assert loss < first
    pred = np.asarray(model.predict(batch)).argmax(-1)
    assert (pred == y).mean() > 0.9


def test_softmax_config_validation():
    from dmlc_tpu.models.linear import LinearLearner
    from dmlc_tpu.utils.check import DMLCError

    with pytest.raises(DMLCError):
        LinearLearner(num_col=4, objective="softmax")  # num_class missing
    with pytest.raises(DMLCError):
        LinearLearner(num_col=4, num_class=3)  # non-softmax multi-class
    # softmax over the ELL layout is supported (2D table ELL gather)
    m = LinearLearner(num_col=4, objective="softmax", num_class=3,
                      layout="ell")
    assert m.params.weight.shape == (5, 3)  # +1 padding-sink row


# ---------------- bcoo natural-block mode ----------------

def _binary_libfm_corpus(tmp_path, n=200):
    lines = []
    for i in range(n):
        feats = " ".join(f"{j}:{(i * 7 + j) % 50}:1" for j in range(4))
        lines.append(f"{i % 2} {feats}")
    p = tmp_path / "bin.libfm"
    p.write_text("\n".join(lines) + "\n")
    return str(p) + "?format=libfm"


def test_bcoo_elide_unit_values(tmp_path):
    """Binary corpora: value array elided from transfer, synthesized ones."""
    uri = _binary_libfm_corpus(tmp_path)

    def totals(elide):
        parser = create_parser(uri, 0, 1, "libfm", threaded=False)
        # buckets off for byte-exact accounting: the elided-vs-not delta
        # must equal exactly 4 B/nnz of REAL data (bucketing composes with
        # elision — OOB pad slots synthesize masked ones — but would pad
        # both sides' coord bytes and obscure the arithmetic)
        it = DeviceIter(parser, num_col=50, batch_size=None, layout="bcoo",
                        elide_unit_values=elide, nnz_bucket=0, row_bucket=0)
        rows, s, bytes_ = 0, 0.0, 0
        for mat, y, w in it:
            rows += mat.shape[0]
            s += float(mat.todense().sum())
        bytes_ = it.stats()["bytes_to_device"]
        it.close()
        return rows, s, bytes_

    rows_e, sum_e, bytes_e = totals(True)
    rows_f, sum_f, bytes_f = totals(False)
    assert rows_e == rows_f == 200
    assert sum_e == sum_f == 200 * 4  # all values are 1
    # elision drops exactly the float32 value array (4 B/nnz) from transfer
    assert bytes_f - bytes_e == 200 * 4 * 4


def test_bcoo_natural_resume_skips_without_transfer(tmp_path):
    """load_state in natural-block mode must not re-transfer skipped blocks."""
    uri = _binary_libfm_corpus(tmp_path, n=400)

    def make_iter():
        parser = create_parser(uri, 0, 1, "libfm", threaded=False,
                               chunk_bytes=2048)  # force several blocks
        return DeviceIter(parser, num_col=50, batch_size=None, layout="bcoo")

    it = make_iter()
    full = [(np.asarray(m.todense()), np.asarray(y)) for m, y, _ in it]
    full_bytes = it.stats()["bytes_to_device"]
    assert len(full) >= 3
    state_after = 2
    it.close()

    it2 = make_iter()
    for _ in range(state_after):
        next(it2)
    state = it2.state_dict()
    it2.close()

    it3 = make_iter()
    it3.load_state(state)
    rest = [(np.asarray(m.todense()), np.asarray(y)) for m, y, _ in it3]
    # the skipped prefix was never re-transferred: the resumed epoch moves
    # strictly fewer bytes than a full one (prefetch of the NEEDED suffix
    # during load_state is fine and expected)
    assert it3.stats()["bytes_to_device"] < full_bytes
    assert len(rest) == len(full) - state_after
    for (xa, ya), (xb, yb) in zip(rest, full[state_after:]):
        np.testing.assert_allclose(xa, xb)
        np.testing.assert_allclose(ya, yb)
    it3.close()


# ---------------- byte-exact resume ----------------

def _resume_corpus(tmp_path, n=600):
    rng = np.random.default_rng(4)
    lines = []
    for i in range(n):
        feats = " ".join(f"{j}:{rng.normal():.5f}" for j in range(6))
        lines.append(f"{i % 2} {feats}")
    p = tmp_path / "resume.libsvm"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


@pytest.mark.parametrize("threaded", [False, True])
def test_device_iter_byte_exact_resume(tmp_path, threaded):
    """Mid-epoch DeviceIter restore seeks the split (O(1) in position)
    instead of replaying the epoch prefix."""
    uri = _resume_corpus(tmp_path)
    full_bytes = __import__("os").path.getsize(uri)

    def make():
        # force the Python parser chain (annotations) + several small chunks
        p = create_parser(uri + "?engine=python", 0, 1, "libsvm",
                          threaded=threaded, chunk_bytes=4096)
        return DeviceIter(p, num_col=6, batch_size=64, layout="dense"), p

    it, _ = make()
    full = [(np.asarray(x), np.asarray(y)) for x, y, w in it]
    it.close()
    assert len(full) >= 6

    it2, _ = make()
    for _ in range(4):
        next(it2)
    state = it2.state_dict()
    it2.close()
    assert state["kind"] == "source", state  # byte-exact, not count replay

    it3, p3 = make()
    it3.load_state(state)
    rest = [(np.asarray(x), np.asarray(y)) for x, y, w in it3]
    # the resumed stream matches the unresumed one exactly
    assert len(rest) == len(full) - 4
    for (xa, ya), (xb, yb) in zip(rest, full[4:]):
        np.testing.assert_allclose(xa, xb)
        np.testing.assert_allclose(ya, yb)
    # and the prefix was SOUGHT past, not re-read: the parser consumed
    # well under the full corpus to serve the remainder
    assert p3.bytes_read < full_bytes * 0.8, (p3.bytes_read, full_bytes)
    it3.close()


def test_threaded_parser_byte_exact_resume(tmp_path):
    """ThreadedParser checkpoints ride block annotations: restore seeks."""
    uri = _resume_corpus(tmp_path)
    full_bytes = __import__("os").path.getsize(uri)

    def make():
        return create_parser(uri + "?engine=python", 0, 1, "libsvm",
                             threaded=True, chunk_bytes=4096)

    p = make()
    full = []
    while (b := p.next_block()) is not None:
        full.append(np.asarray(b.label))
    p.close()
    assert len(full) >= 6

    p2 = make()
    for _ in range(3):
        p2.next_block()
    state = p2.state_dict()
    p2.close()
    assert state["kind"] == "split", state

    p3 = make()
    p3.load_state(state)
    rest = []
    while (b := p3.next_block()) is not None:
        rest.append(np.asarray(b.label))
    assert len(rest) == len(full) - 3
    for a, b_ in zip(rest, full[3:]):
        np.testing.assert_array_equal(a, b_)
    assert p3.bytes_read < full_bytes * 0.8
    p3.close()


def test_resume_after_epoch_reset_not_stale(tmp_path):
    """Checkpoint taken right after an epoch reset (before any pull) must
    restore to the epoch START — not a stale end-of-epoch position."""
    uri = _resume_corpus(tmp_path, n=200)

    def make():
        return create_parser(uri + "?engine=python", 0, 1, "libsvm",
                             threaded=True, chunk_bytes=4096)

    p = make()
    full = 0
    while p.next_block() is not None:
        full += 1
    p.before_first()
    state = p.state_dict()  # epoch start, nothing pulled yet
    p.close()
    p2 = make()
    p2.load_state(state)
    again = 0
    while p2.next_block() is not None:
        again += 1
    p2.close()
    assert again == full  # the whole epoch, not a skipped-to-EOF stream


def test_count_resume_then_byte_exact_recheckpoint(tmp_path):
    """A count-based restore must keep annotation/batch pairing aligned so
    a LATER checkpoint from the restored iterator is still byte-exact."""
    uri = _resume_corpus(tmp_path, n=600)

    def make():
        # one huge chunk -> early batches carry no block-boundary
        # annotation -> first checkpoint is count-based
        p = create_parser(uri + "?engine=python", 0, 1, "libsvm",
                          threaded=False, chunk_bytes=1 << 20)
        return DeviceIter(p, num_col=6, batch_size=64, layout="dense")

    it = make()
    full = [(np.asarray(x), np.asarray(y)) for x, y, w in it]
    it.close()

    it2 = make()
    next(it2)
    next(it2)
    st1 = it2.state_dict()
    it2.close()
    assert st1["kind"] == "batches", st1  # no boundary crossed yet

    it3 = make()
    it3.load_state(st1)
    got3 = [(np.asarray(x), np.asarray(y)) for x, y, w in it3]
    assert len(got3) == len(full) - 2
    for (xa, ya), (xb, yb) in zip(got3, full[2:]):
        np.testing.assert_allclose(xa, xb)

    # resume again, consume past the block boundary, re-checkpoint: the
    # annotation stream must still be aligned with deliveries
    it4 = make()
    it4.load_state(st1)
    for _ in range(len(full) - 3):
        next(it4)
    st2 = it4.state_dict()
    want_tail = [np.asarray(next(it4)[1])]
    it4.close()
    it5 = make()
    it5.load_state(st2)
    tail = [np.asarray(y) for x, y, w in it5]
    it5.close()
    assert len(tail) == 1
    np.testing.assert_allclose(tail[0], want_tail[0])


def test_checkpoint_in_second_epoch_after_reset(tmp_path):
    """reset() mid-epoch must not leak stale annotations into the next
    epoch's checkpoints (producer joined before state clears)."""
    uri = _resume_corpus(tmp_path, n=400)

    def make():
        p = create_parser(uri + "?engine=python", 0, 1, "libsvm",
                          threaded=True, chunk_bytes=4096)
        return DeviceIter(p, num_col=6, batch_size=64, layout="dense")

    it = make()
    full = [np.asarray(y) for x, y, w in it]
    # interrupt epoch 2 mid-flight, reset, then checkpoint in epoch 3
    it.reset()
    next(it)
    next(it)
    it.reset()
    for _ in range(3):
        next(it)
    state = it.state_dict()
    it.close()

    it2 = make()
    it2.load_state(state)
    rest = [np.asarray(y) for x, y, w in it2]
    it2.close()
    assert len(rest) == len(full) - 3
    for a, b in zip(rest, full[3:]):
        np.testing.assert_allclose(a, b)


# ---------------- bf16 ingest ----------------

@pytest.mark.parametrize("threaded", [False, True])
def test_device_iter_bf16_dense(tmp_path, threaded):
    """x_dtype='bfloat16': half the transfer bytes, values equal to the
    f32 pipeline within bf16 rounding — native repack and python fallback."""
    import ml_dtypes

    uri = _libsvm_corpus(tmp_path, n=64, d=6)

    def run(x_dtype):
        parser = create_parser(uri, 0, 1, "libsvm", threaded=threaded)
        it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense",
                        x_dtype=x_dtype)
        out = [(np.asarray(x), np.asarray(y)) for x, y, w in it]
        bytes_ = it.stats()["bytes_to_device"]
        it.close()
        return out, bytes_

    f32, bytes_f32 = run("float32")
    bf16, bytes_bf16 = run("bfloat16")
    assert len(bf16) == len(f32) == 4
    for (xb, yb), (xf, yf) in zip(bf16, f32):
        assert xb.dtype == np.dtype(ml_dtypes.bfloat16)
        np.testing.assert_allclose(
            np.asarray(xb, dtype=np.float32), xf, rtol=1 / 128)
        np.testing.assert_array_equal(yb, yf)  # labels stay f32
    # x shrinks by exactly half; labels/weights stay f32
    n_x_f32 = sum(x.size * 4 for x, _ in f32)
    assert bytes_f32 - bytes_bf16 == n_x_f32 // 2, (bytes_bf16, bytes_f32)


def test_native_bf16_repack_matches_f32(tmp_path):
    """The C++ repack's round-to-nearest-even conversion A/B'd directly."""
    from dmlc_tpu import native

    if not native.available():
        pytest.skip("native core unavailable")
    import ml_dtypes

    path = tmp_path / "bf.libsvm"
    rng = np.random.default_rng(12)
    special = ["nan", "-nan", "inf", "-inf", "-0.0", "3.4e38", "1e-40"]
    with open(path, "w") as f:
        for i in range(500):
            feats = " ".join(f"{j}:{rng.normal():.6f}" for j in range(8))
            f.write(f"{i % 2} {feats}\n")
        # special values: NaN payloads must not round into Inf etc.
        for i in range(len(special)):
            feats = " ".join(
                f"{j}:{special[(i + j) % len(special)]}" for j in range(8))
            f.write(f"1 {feats}\n")
    from dmlc_tpu.data.native_parser import NativeStreamParser

    def collect(dtype):
        p = NativeStreamParser(str(path), {}, 0, 1, "libsvm")
        assert p.set_emit_dense(8, batch_rows=64, dtype=dtype)
        xs = []
        while (b := p.next_block()) is not None:
            xs.append(np.asarray(b.x))
        p.close()
        return np.concatenate(xs)

    x32 = collect("float32")
    x16 = collect("bfloat16")
    assert x16.dtype == np.dtype(ml_dtypes.bfloat16)
    assert x16.shape == x32.shape
    # C++ rne conversion must equal numpy/ml_dtypes' own rne cast exactly
    np.testing.assert_array_equal(
        x16.view(np.uint16), x32.astype(ml_dtypes.bfloat16).view(np.uint16))


# ---------------- factorization machine ----------------

def _xor_corpus(tmp_path, n=512):
    """Labels depend on a feature INTERACTION (x0 XOR x1) — linearly
    inseparable, learnable only through the second-order term."""
    rng = np.random.default_rng(3)
    lines = []
    for _ in range(n):
        a, b = int(rng.integers(0, 2)), int(rng.integers(0, 2))
        y = a ^ b
        noise = " ".join(f"{j}:{rng.normal() * 0.01:.5f}" for j in range(2, 6))
        lines.append(f"{y} 0:{2 * a - 1} 1:{2 * b - 1} {noise}")
    p = tmp_path / "xor.libsvm"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


@pytest.mark.parametrize("layout", ["dense", "ell", "bcoo"])
def test_fm_learns_interactions(tmp_path, layout):
    from dmlc_tpu.models.fm import FMLearner

    uri = _xor_corpus(tmp_path)
    model = FMLearner(num_col=6, num_factors=4, layout=layout,
                      learning_rate=0.1, seed=1)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=64,
                    layout=layout, max_nnz=6, drop_remainder=True,
                    nnz_bucket=256, row_bucket=32)
    model.fit(it, epochs=40)
    acc = model.accuracy(it)
    it.close()
    assert acc > 0.9, f"layout={layout} acc={acc}"

    # a LINEAR model cannot express XOR: it stays near chance
    lin = LinearLearner(num_col=6, layout="dense", learning_rate=0.1)
    parser2 = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it2 = DeviceIter(parser2, num_col=lin.device_num_col(), batch_size=64,
                     layout="dense", drop_remainder=True)
    lin.fit(it2, epochs=40)
    lin_acc = lin.accuracy(it2)
    it2.close()
    assert lin_acc < 0.75, lin_acc


def test_fm_sharded_dp_matches_single(tmp_path):
    from dmlc_tpu.models.fm import FMLearner

    uri = _xor_corpus(tmp_path, n=256)
    mesh = make_mesh({"data": 8})

    def run(mesh_arg):
        model = FMLearner(num_col=6, num_factors=4, layout="dense",
                          learning_rate=0.1, seed=2, mesh=mesh_arg)
        parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
        it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=64,
                        layout="dense", mesh=mesh_arg, drop_remainder=True)
        model.fit(it, epochs=3)
        it.close()
        return np.asarray(model.params.v)

    v_single = run(None)
    v_sharded = run(mesh)
    # (under a mesh the tables are laid by rows, padded to a multiple of
    # the shards: seven rows on eight devices, and the padding row inert)
    assert v_sharded.shape == (8, 4) and not v_sharded[7:].any()
    np.testing.assert_allclose(v_sharded[:7], v_single, rtol=1e-4, atol=1e-5)


def test_fm_libfm_format_end_to_end(tmp_path):
    """The libfm FORMAT feeding the FM MODEL — the pairing the reference's
    libfm parser exists for (libfm_parser.h)."""
    from dmlc_tpu.models.fm import FMLearner

    rng = np.random.default_rng(5)
    lines = []
    for _ in range(400):
        a, b = int(rng.integers(0, 2)), int(rng.integers(0, 2))
        y = a ^ b
        # field:index:value tokens (fields 0/1)
        lines.append(f"{y} 0:{a}:1 1:{2 + b}:1")
    p = tmp_path / "fm.libfm"
    p.write_text("\n".join(lines) + "\n")

    model = FMLearner(num_col=4, num_factors=4, layout="ell",
                      learning_rate=0.15, seed=3)
    parser = create_parser(str(p) + "?format=libfm", 0, 1, "auto",
                           threaded=False)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=50,
                    layout="ell", max_nnz=2, drop_remainder=True)
    model.fit(it, epochs=60)
    acc = model.accuracy(it)
    it.close()
    assert acc > 0.9, acc


@pytest.mark.parametrize("trace_env", [None, "1"])
def test_device_iter_trace_annotation_path(tmp_path, monkeypatch, trace_env):
    """SURVEY §5.1: every transfer runs inside a
    jax.profiler.TraceAnnotation (``dmlc_tpu:dispatch``) with nothing set
    — the retired ``DMLC_TPU_TRACE=1`` switch changes nothing — and the
    wrapper is a behavioral no-op on the delivered batches (it only tags
    them for a Perfetto trace)."""
    from dmlc_tpu.utils import telemetry

    monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
    if trace_env is not None:
        monkeypatch.setenv("DMLC_TPU_TRACE", trace_env)
    uri = _libsvm_corpus(tmp_path, n=48)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense")
    assert not hasattr(it, "_trace") and it._trace_export is None
    before = telemetry.span_counts().get("dispatch", 0)
    batches = list(it)
    it.close()
    assert telemetry.span_counts().get("dispatch", 0) - before == 3
    assert len(batches) == 3
    x, y, w = batches[0]
    assert x.shape == (16, 6) and isinstance(x, jax.Array)


def test_sync_min_single_process():
    from dmlc_tpu.parallel import sync_min

    assert sync_min(7) == 7  # 1-process: identity, no collective needed


def test_bcoo_shape_bucketing_quantizes_and_preserves_math(tmp_path):
    """nnz/row bucketing: batch shapes repeat (a novel shape per batch
    forces a fresh transfer plan) and the padding is a mathematical
    no-op: out-of-bounds coords (masked by every BCOO op), zero-weight
    rows."""
    uri = _binary_libfm_corpus(tmp_path, n=400)

    def run(nnz_bucket, row_bucket):
        parser = create_parser(uri, 0, 1, "libfm", threaded=False,
                               chunk_bytes=2048)  # several natural blocks
        it = DeviceIter(parser, num_col=50, batch_size=None, layout="bcoo",
                        nnz_bucket=nnz_bucket, row_bucket=row_bucket)
        shapes, mats, ys, ws = set(), [], [], []
        for mat, y, w in it:
            shapes.add((mat.nse, mat.shape[0]))
            mats.append(np.asarray(mat.todense()))
            ys.append(np.asarray(y))
            ws.append(np.asarray(w))
        it.close()
        return shapes, mats, ys, ws

    shapes_b, mats_b, ys_b, ws_b = run(256, 64)
    shapes_e, mats_e, ys_e, ws_e = run(0, 0)
    assert len(mats_b) == len(mats_e) >= 3
    # bucketed: every nnz a multiple of 256, rows of 64 -> shapes repeat
    assert all(n % 256 == 0 and r % 64 == 0 for n, r in shapes_b)
    assert len(shapes_b) < len(mats_b) or len(shapes_b) == 1
    for mb, me, yb, ye, wb, we in zip(mats_b, mats_e, ys_b, ys_e, ws_b, ws_e):
        rows = me.shape[0]
        np.testing.assert_array_equal(mb[:rows], me)
        assert mb[rows:].sum() == 0  # padded rows are empty
        np.testing.assert_array_equal(yb[:rows], ye)
        assert (wb[rows:] == 0).all()  # padded rows carry zero weight
        # the padded slab changes no matvec result
        v = np.arange(50, dtype=np.float32)
        np.testing.assert_allclose(mb @ v[: mb.shape[1]],
                                   np.concatenate([me @ v[: me.shape[1]],
                                                   np.zeros(mb.shape[0] - rows,
                                                            np.float32)]),
                                   rtol=1e-6)


def test_bcoo_fixed_batch_tail_closes_shape_set(tmp_path):
    """Fixed-batch BCOO: the final partial batch pads its nse UP into the
    set already emitted by full batches, so the epoch's device-shape set is
    closed — no novel transfer shape (a fresh transfer plan) and no
    downstream jit recompile on the last batch of every epoch."""
    uri = _libsvm_corpus(tmp_path, n=72)  # 4 full batches of 16 + tail of 8

    def epoch_shapes(it):
        shapes = []
        for mat, y, w in it:
            shapes.append((mat.nse, mat.shape[0]))
        return shapes

    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=16, layout="bcoo",
                    nnz_bucket=16)
    ep1 = epoch_shapes(it)
    it.reset()
    ep2 = epoch_shapes(it)
    it.close()
    assert len(ep1) == len(ep2) == 5
    # rows always padded to batch_size
    assert all(r == 16 for _, r in ep1)
    # the tail's shape is one a full batch already used...
    assert ep1[-1] in ep1[:-1]
    # ...so the distinct-shape set over 2 epochs equals the full batches'
    assert set(ep1) | set(ep2) == set(ep1[:-1])


def test_bcoo_derived_nnz_bucket_capped(tmp_path):
    """ADVICE r4 #4: the derived batch_size*max_nnz bucket is capped — the
    bucket is the worst-case per-batch pad, and an uncapped ceiling product
    makes host->HBM pad bytes unbounded for sparse-below-max corpora."""
    uri = _libsvm_corpus(tmp_path, n=8)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=8192, layout="bcoo",
                    max_nnz=1000)
    assert it.nnz_bucket == 512 * 1024
    it.close()
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    small = DeviceIter(parser, num_col=6, batch_size=16, layout="bcoo",
                       max_nnz=6)
    assert small.nnz_bucket == 96  # under the cap: one exact shape
    small.close()


def test_ell_matvec_auto_routing_guards():
    """Off the TPU backend the auto route stays on the XLA gather even for
    an in-band shape, 2D (multinomial) weight tables never route to the
    kernel, and an explicit pallas opt-in with a 2D table refuses loudly —
    the kernel is a [D]-table matvec only."""
    from dmlc_tpu.ops.pallas_sparse import ell_matvec_auto, ell_matvec_pallas
    from dmlc_tpu.ops.sparse import EllBatch, ell_matvec

    rng = np.random.default_rng(0)
    B, K, D, C = 256, 4, 64, 3
    idx = jnp.asarray(rng.integers(0, D, size=(B, K)).astype(np.int32))
    val = jnp.asarray(rng.normal(size=(B, K)).astype(np.float32))
    batch = EllBatch(idx, val, None, None)
    w2 = jnp.asarray(rng.normal(size=(D, C)).astype(np.float32))
    got = ell_matvec_auto(w2, batch)          # default: XLA gather
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ell_matvec(w2, batch)), rtol=1e-6)
    assert got.shape == (B, C)
    with pytest.raises(ValueError, match=r"\[D\] table"):
        ell_matvec_pallas(w2, idx, val, interpret=True)


def test_softmax_learner_ell_layout(tmp_path):
    """Multinomial softmax over the ELL sparse layout (2D weight table
    through the ELL gather)."""
    rng = np.random.default_rng(5)
    d, n, C = 6, 300, 3
    centers = rng.normal(size=(C, d)) * 2
    lines = []
    for _ in range(n):
        c = int(rng.integers(0, C))
        x = centers[c] + rng.normal(size=d) * 0.3
        feats = " ".join(f"{j}:{x[j]:.5f}" for j in range(d))
        lines.append(f"{c} {feats}")
    p = tmp_path / "multi.libsvm"
    p.write_text("\n".join(lines) + "\n")

    model = LinearLearner(num_col=d, objective="softmax", num_class=C,
                          layout="ell", learning_rate=0.5)
    parser = create_parser(str(p), 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=50,
                    layout="ell", max_nnz=d)
    model.fit(it, epochs=10)
    acc = model.accuracy(it)
    assert acc > 0.85, acc
    it.close()


def test_pallas_ell_matvec_grad_matches_xla():
    """value_and_grad through the pallas forward (custom_vjp: XLA backward)
    must match grads of the pure-XLA gather — this is the training-path
    configuration (single-device TPU, 1D table) that routes to the kernel."""
    from dmlc_tpu.ops.pallas_sparse import _ell_matvec_pallas_ad
    from dmlc_tpu.ops.sparse import EllBatch, ell_matvec

    rng = np.random.default_rng(11)
    B, K, D = 256, 7, 96
    idx = jnp.asarray(rng.integers(0, D, size=(B, K)).astype(np.int32))
    val = jnp.asarray(rng.normal(size=(B, K)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    g = jnp.asarray(rng.normal(size=B).astype(np.float32))  # loss weights

    def loss_pallas(w_, v_):
        return jnp.sum(_ell_matvec_pallas_ad(w_, idx, v_, True) * g)

    def loss_xla(w_, v_):
        return jnp.sum(ell_matvec(w_, EllBatch(idx, v_, None, None)) * g)

    (lp, (dwp, dvp)) = jax.value_and_grad(loss_pallas, argnums=(0, 1))(w, val)
    (lx, (dwx, dvx)) = jax.value_and_grad(loss_xla, argnums=(0, 1))(w, val)
    np.testing.assert_allclose(float(lp), float(lx), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dwp), np.asarray(dwx),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dvp), np.asarray(dvx),
                               rtol=1e-4, atol=1e-5)


def test_linear_learner_fit_through_pallas_routed_margin(tmp_path, monkeypatch):
    """End-to-end fit() with the margin forced onto the pallas kernel
    (interpret mode): exercises jit(value_and_grad(custom_vjp(pallas)))
    — the exact single-device-TPU training path the auto-router selects."""
    import dmlc_tpu.ops.pallas_sparse as ps
    import dmlc_tpu.models.linear as lin

    real_kernel = ps.ell_matvec_pallas

    def forced_interpret(w, i, v, **kw):
        kw["interpret"] = True  # CPU backend: interpret is the only mode
        return real_kernel(w, i, v, **kw)

    monkeypatch.setattr(ps, "ell_matvec_pallas", forced_interpret)
    calls = {"n": 0}
    real_auto = ps.ell_matvec_auto

    def forced_auto(w, batch, use_pallas=None):
        calls["n"] += 1
        return real_auto(w, batch, use_pallas=True)

    monkeypatch.setattr(ps, "ell_matvec_auto", forced_auto)

    uri = _separable_corpus(tmp_path, n=512)
    model = lin.LinearLearner(num_col=8, objective="logistic", layout="ell",
                              learning_rate=0.5)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=256,
                    layout="ell", max_nnz=8, drop_remainder=True)
    model.fit(it, epochs=8)
    acc = model.accuracy(it)
    it.close()
    assert calls["n"] > 0, "margin never reached the routed kernel"
    assert acc > 0.9, acc


@pytest.mark.parametrize("batch_size", [64, None])
def test_linear_learner_bcoo_layout(tmp_path, batch_size):
    """Training straight off BCOO batches (fixed-size and natural-block):
    the libfm->BCOO ingestion path ends in a learner, not just a transfer."""
    uri = _separable_corpus(tmp_path, n=256)
    model = LinearLearner(num_col=8, objective="logistic", layout="bcoo",
                          learning_rate=0.5)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=False,
                           chunk_bytes=4096)
    it = DeviceIter(parser, num_col=model.device_num_col(),
                    batch_size=batch_size, layout="bcoo",
                    nnz_bucket=256, row_bucket=32)
    model.fit(it, epochs=12)
    acc = model.accuracy(it)
    it.close()
    assert acc > 0.9, f"batch_size={batch_size} acc={acc}"


# ---------------- packed dense batches ----------------

def test_packed_pipeline_equals_split(tmp_path):
    """pack_aux pipeline (one [B, D+2] put per batch, PackedDenseBatch)
    must deliver identical x/y/w to the split-array pipeline, including
    the zero-weight padded tail."""
    from dmlc_tpu.data.device import PackedDenseBatch

    uri = _libsvm_corpus(tmp_path, n=70, d=6)  # 70 % 16 != 0 -> padded tail

    def run(pack):
        parser = create_parser(uri, 0, 1, "libsvm", threaded=True)
        it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense",
                        pack_aux=pack)
        out = []
        for batch in it:
            if pack:
                assert isinstance(batch, PackedDenseBatch)
                assert batch.packed.shape == (16, 8)
            x, y, w = batch
            out.append((np.asarray(x), np.asarray(y), np.asarray(w)))
        it.close()
        return out

    a, b = run(True), run(False)
    assert len(a) == len(b) == 5
    for (xa, ya, wa), (xb, yb, wb) in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
        np.testing.assert_array_equal(wa, wb)
    # tail pad rows are weight-0 (masked by any weighted consumer)
    assert (a[-1][2][70 % 16:] == 0).all()


def test_learner_step_packed_equals_tuple(tmp_path):
    """A jitted train step consumes PackedDenseBatch via pytree flattening
    with the slices fused into the step graph — losses must match the
    tuple-batch path exactly."""
    from dmlc_tpu.models.linear import LinearLearner

    uri = _libsvm_corpus(tmp_path, n=64, d=6)

    def losses(pack):
        model = LinearLearner(num_col=5, learning_rate=0.3)
        parser = create_parser(uri, 0, 1, "libsvm", threaded=True)
        it = DeviceIter(parser, num_col=model.device_num_col(),
                        batch_size=16, layout="dense", pack_aux=pack)
        out = [float(model.step(b)) for b in it]
        it.close()
        return out

    np.testing.assert_allclose(losses(True), losses(False), rtol=1e-6)


def test_packed_drop_remainder(tmp_path):
    """drop_remainder must drop the partial packed tail, same as the
    split-array path (review r5 finding)."""
    uri = _libsvm_corpus(tmp_path, n=70, d=6)

    def count(pack):
        parser = create_parser(uri, 0, 1, "libsvm", threaded=True)
        it = DeviceIter(parser, num_col=6, batch_size=16, layout="dense",
                        pack_aux=pack, drop_remainder=True)
        n = sum(1 for _ in it)
        it.close()
        return n

    assert count(True) == count(False) == 70 // 16


# ------- stage attribution + convert/dispatch overlap (ISSUE 1 tentpole) -------

@pytest.mark.parametrize("layout", ["dense", "ell"])
def test_device_iter_stage_attribution_partitions_wall(tmp_path, layout):
    """stats()['stages'] exposes the five named stages, every value is
    non-negative, and their sum never exceeds consumer wall (the
    attribution is a PARTITION of wall, never a double count — overlap
    shows up in stage_busy, which may exceed wall, not in stages)."""
    uri = _libsvm_corpus(tmp_path, n=256)
    parser = create_parser(uri, 0, 1, "libsvm", threaded=True)
    it = DeviceIter(parser, num_col=6, batch_size=32, layout=layout,
                    max_nnz=6, convert_workers=2, transfer_sample=2)
    n = sum(1 for _ in it)
    s = it.stats()
    it.close()
    assert n == 8
    assert set(s["stages"]) == {"read", "cache_read", "snapshot_read",
                                "parse", "convert", "dispatch",
                                "device_decode", "transfer"}
    assert s["cache_state"] is None  # no block cache armed on this source
    assert all(v >= 0.0 for v in s["stages"].values())
    assert s["wall_seconds"] > 0.0
    total = sum(s["stages"].values())
    assert total <= s["wall_seconds"] * 1.02 + 1e-6, (total, s)
    # the transfer sideband actually sampled (every 2nd of 8 batches)
    assert s["transfer_samples"] >= 3
    # raw busy counters ride along for the overlap diagnosis
    assert set(s["stage_busy"]) >= {"read", "parse", "convert", "dispatch"}
    assert s["convert_workers"] == 2


def test_device_iter_attribution_names_supply_cost(tmp_path):
    """A pipeline bottlenecked on upstream supply must attribute the
    consumer's wait to the supply stages (read/parse), not leave it
    unaccounted."""
    from dmlc_tpu.data.parsers import Parser as _Parser

    class SlowSource(_Parser):
        """Hands out a few blocks with a deliberate per-block delay."""

        def __init__(self):
            self.i = 0

        def before_first(self):
            self.i = 0

        def next_block(self):
            import time as _time

            if self.i >= 4:
                return None
            self.i += 1
            _time.sleep(0.05)
            rng = np.random.default_rng(self.i)
            vals = rng.normal(size=(8, 4)).astype(np.float32)
            idx = np.tile(np.arange(4, dtype=np.uint64), 8)
            return RowBlock(
                offset=np.arange(0, 33, 4, dtype=np.int64),
                label=np.zeros(8, np.float32), index=idx,
                value=vals.reshape(-1))

    # the process's first put of this shape is not the pipeline's cost
    jax.block_until_ready(jax.device_put(np.zeros((8, 6), np.float32)))
    it = DeviceIter(SlowSource(), num_col=4, batch_size=8, layout="dense",
                    convert_workers=2)
    assert sum(1 for _ in it) == 4
    s = it.stats()
    it.close()
    # the source sleeps 4 x 0.05 s and every one is booked: the slow
    # source exposes no read/parse split, so under 'parse'
    slept = 4 * 0.05
    assert s["stage_busy"]["parse"] >= slept, s
    # ... and what of them the consumer waited through is attributed to
    # that stage. Held to the seconds slept, not to the wall: the wall
    # holds the machine's load (a put that takes 0.15 s on a busy host
    # runs on the consumer's thread while the source sleeps on, so those
    # sleeps are no wait of the consumer's, and the share of the wall
    # that is 'parse' falls under a half with nothing wrong). Only the
    # consumer's own measured work can overlap a sleep
    own = sum(s["stages"][k] for k in ("dispatch", "device_decode",
                                       "transfer"))
    assert s["stages"]["parse"] >= 0.5 * max(slept - own, 0.05), s


def test_device_iter_resume_and_reset_with_convert_pool(tmp_path):
    """state_dict()/load_state() round-trips and reset() restarts cleanly
    with the conversion-worker pool active (out-of-order convert must not
    desync the delivery order or the resume annotations)."""
    uri = _resume_corpus(tmp_path)

    def make():
        p = create_parser(uri + "?engine=python", 0, 1, "libsvm",
                          threaded=True, chunk_bytes=4096)
        return DeviceIter(p, num_col=6, batch_size=64, layout="dense",
                          convert_workers=3, convert_ahead=4)

    it = make()
    full = [np.asarray(b[0]) for b in it]
    assert len(full) >= 6
    # epoch reset with the pool: same batches again, in order
    it.reset()
    again = [np.asarray(b[0]) for b in it]
    assert len(again) == len(full)
    for a, b in zip(full, again):
        np.testing.assert_allclose(a, b)
    it.close()

    it2 = make()
    for _ in range(3):
        next(it2)
    state = it2.state_dict()
    it2.close()
    assert state["kind"] == "source", state  # byte-exact through the pool

    it3 = make()
    it3.load_state(state)
    rest = [np.asarray(b[0]) for b in it3]
    assert len(rest) == len(full) - 3
    for a, b in zip(rest, full[3:]):
        np.testing.assert_allclose(a, b)
    it3.close()


def test_staging_ring_reuses_buffers(tmp_path):
    """Dropped batches free their staging slots (weakref-gated), so a
    consume-and-discard epoch runs on a bounded ring instead of one fresh
    allocation per batch; batches still in use keep their slots pinned."""
    uri = _libsvm_corpus(tmp_path, n=512)
    parser = create_parser(uri + "?engine=python", 0, 1, "libsvm",
                           threaded=False)
    it = DeviceIter(parser, num_col=6, batch_size=32, layout="dense",
                    convert_workers=2)
    kept = []
    for i, batch in enumerate(it):
        if i < 2:
            kept.append(batch)  # pin two batches: their slots must not free
    s = it.stats()
    ring = s["staging_ring"]
    it.close()
    assert ring is not None
    # 16 batches through a ring whose depth stays well under batch count
    assert ring["depth"] <= 2 + 4 + 2 + 2  # prefetch+ahead+workers+slack
    assert ring["hits"] > 0, ring  # buffers actually recycled
    assert len(kept) == 2  # the pinned handles stayed valid to the end


def test_ell_matvec_auto_band_predicate():
    """The routing band is exactly lane-aligned D in [512, 4096]
    (SPARSE_TPU_r05.json): inside routes pallas, outside routes gather."""
    from dmlc_tpu.ops.pallas_sparse import pallas_band

    B = 8192
    # the four measured win shapes (and the D=1024 anomaly, kept in-band
    # pending the grid leg's tile-vs-shape attribution)
    for D in (512, 1024, 2048, 4096):
        assert pallas_band(B, D), D
    # outside: dense-in-sparse, off-alignment, beyond band, high-D
    for D in (28, 384, 520, 4224, 8192, 1 << 20):
        assert not pallas_band(B, D), D
    # B must be lane-aligned for a valid tile
    assert not pallas_band(200, 2048)
    assert pallas_band(256, 2048)
    # 2D (multinomial) tables never route to the kernel
    assert not pallas_band(B, 2048, weights_ndim=2)


def test_ell_matvec_auto_routes_band_on_tpu(monkeypatch):
    """With the TPU gate forced open (interpret-mode kernel), the auto
    route hits the pallas kernel exactly in-band and the gather elsewhere
    — the models/linear.py default path end to end."""
    import dmlc_tpu.ops.pallas_sparse as ps
    from dmlc_tpu.ops.sparse import EllBatch, ell_matvec

    monkeypatch.setattr(ps, "_on_tpu_backend", lambda: True)
    real_kernel = ps.ell_matvec_pallas
    calls = {"n": 0}

    def forced_interpret(w, i, v, **kw):
        calls["n"] += 1
        kw["interpret"] = True  # CPU backend: interpret is the only mode
        return real_kernel(w, i, v, **kw)

    monkeypatch.setattr(ps, "ell_matvec_pallas", forced_interpret)

    rng = np.random.default_rng(7)
    B, K = 256, 4
    for D, expect_pallas in ((512, True), (28, False)):
        idx = jnp.asarray(rng.integers(0, D, size=(B, K)).astype(np.int32))
        val = jnp.asarray(rng.normal(size=(B, K)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=D).astype(np.float32))
        before = calls["n"]
        got = ps.ell_matvec_auto(w, EllBatch(idx, val, None, None))
        assert (calls["n"] > before) == expect_pallas, D
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(ell_matvec(w, EllBatch(idx, val, None, None))),
            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("convert_workers", [0, 3])
def test_stats_ell_counts_real_non_zeros_and_slots_shipped(tmp_path,
                                                          convert_workers):
    """``stats()["ell"]`` (PR 49): the non-zeros convert kept and the slots
    it shipped, ``batch_size * max_nnz`` a batch the short tail included;
    what is left of the quotient is the share of slots a learner's step is
    told are padding. A row longer than ``max_nnz`` keeps that many."""
    rng = np.random.default_rng(5)
    lengths = rng.integers(0, 9, 100)           # max_nnz 6 cuts 7 and 8
    path = tmp_path / "ragged.libsvm"
    path.write_text("".join(
        "%d %s\n" % (i % 2, " ".join(
            "%d:1" % c for c in sorted(rng.choice(50, n, replace=False))))
        for i, n in enumerate(lengths)))
    it = DeviceIter(create_parser(str(path), 0, 1, "libsvm"), num_col=50,
                    batch_size=32, layout="ell", max_nnz=6,
                    convert_workers=convert_workers)
    assert it.stats()["ell"] == {"nnz": 0, "slots": 0}
    real = sum(int((np.asarray(b.values) != 0).sum()) for b in it)
    kept = int(np.minimum(lengths, 6).sum())
    assert real == kept
    assert it.stats()["ell"] == {"nnz": kept, "slots": 4 * 32 * 6}
    assert it.stats()["ell_truncated_slots"] == int(lengths.sum()) - kept
    assert it.stats()["bcoo"]["slots"] == 0
    it.close()
