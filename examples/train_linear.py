"""End-to-end example: libsvm file -> sharded logistic regression on TPU.

The SURVEY.md §7 minimum slice: InputSplit shard -> native parse ->
RowBlocks -> async host->HBM batches -> jitted SGD with data-parallel psum
over the device mesh.

Run (single host, any JAX backend):
    python examples/train_linear.py [path.libsvm] [num_col]

Without a path it generates a small separable synthetic dataset.
``DMLC_EXAMPLE_LAYOUT`` picks the device layout: ``dense`` (default,
sharded over the mesh), or single-device ``ell`` / ``bcoo`` — the same
model trains on all three.
Multi-host: launch through `bin/dmlc-submit --cluster tpu-pod ...`; each
process reads its own partition (process_index/process_count) and the psum
runs over ICI.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def synthesize(path: str, n: int = 4096, d: int = 28) -> None:
    import numpy as np

    rng = np.random.default_rng(0)
    w = rng.normal(size=d)
    with open(path, "w") as f:
        for _ in range(n):
            x = rng.normal(size=d)
            y = int(x @ w + rng.normal() * 0.1 > 0)
            feats = " ".join(f"{j}:{x[j]:.6f}" for j in range(d))
            f.write(f"{y} {feats}\n")


def main() -> None:
    from dmlc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import LinearLearner
    from dmlc_tpu.parallel import init_from_env, make_mesh, host_shard_info

    init_from_env()  # no-op single-process; joins the pod under dmlc-submit

    if len(sys.argv) > 1:
        path = sys.argv[1]
        if len(sys.argv) > 2:
            num_col = int(sys.argv[2])
        else:
            # one host-only pass to discover the feature count
            scan = create_parser(path, 0, 1, "libsvm", threaded=False)
            num_col = max((int(b.index.max()) + 1 for b in scan if len(b.index)),
                          default=1)
            scan.close()
            print(f"inferred num_col={num_col}")
    else:
        path = "/tmp/dmlc_tpu_example.libsvm"
        num_col = 28
        # enough rows for several full global batches on any device count
        synthesize(path, n=4096 * max(1, len(jax.devices())), d=num_col)

    layout = os.environ.get("DMLC_EXAMPLE_LAYOUT", "dense")
    # sparse layouts run single-device; dense shards over the mesh
    mesh = make_mesh() if layout == "dense" else None
    part, nparts = host_shard_info()
    model = LinearLearner(num_col=num_col, objective="logistic",
                          layout=layout, learning_rate=0.3, mesh=mesh)
    parser = create_parser(path, part, nparts, "libsvm")
    batch = 1024 * (len(jax.devices()) if mesh is not None else 1)
    it = DeviceIter(parser, num_col=model.device_num_col(), batch_size=batch,
                    layout=layout, mesh=mesh, drop_remainder=True,
                    max_nnz=num_col,
                    shardings=model.batch_shardings() if mesh else None)

    def log(epoch, loss, nb, secs):
        print(f"epoch {epoch}: loss={loss:.4f} batches={nb} {secs:.2f}s "
              f"stall={it.stall_seconds:.2f}s")

    model.fit(it, epochs=5, log_fn=log)
    print(f"train accuracy: {model.accuracy(it):.3f}")
    it.close()


if __name__ == "__main__":
    main()
