"""Compile the configuration's step at full size for a described ``v5e:2x2``, with no chip:

    JAX_PLATFORMS=cpu python3 -m cellbench.tools.aot_compile kdd12_fm [4]

Nothing runs; what the chip's compiler refuses (memory, layouts) shows
here at no chip time. Prints the compiler's memory analysis.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(config_name: str, chips: int = 1) -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
        SingleDeviceSharding

    from cellbench.run import HERE, load_json
    from dmlc_tpu.models import fm as fm_mod
    from dmlc_tpu.ops.sparse import EllBatch
    import numpy as np

    cfg = load_json(HERE, "configs", config_name + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    rows, f = cfg["num_features"] + 1, cfg["num_factors"]
    b, k = cfg["batch_size"], cfg["max_nnz"]
    if chips == 1:
        rep = vec = row = SingleDeviceSharding(topo.devices[0])
        mesh = None
    else:
        mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
        rep = NamedSharding(mesh, P())
        vec, row = NamedSharding(mesh, P("data")), \
            NamedSharding(mesh, P("data", None))
    sds = lambda shape, dt, sh: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    params = fm_mod.FMParams(w0=sds((), jnp.float32, rep),
                             w=sds((rows,), jnp.float32, rep),
                             v=sds((rows, f), jnp.float32, rep))
    opt = optax.adam(cfg["learning_rate"])
    opt_state = jax.eval_shape(opt.init, params)
    opt_state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype, rep), opt_state)
    batch = EllBatch(indices=sds((b, k), jnp.int32, row),
                     values=sds((b, k), jnp.float32, row),
                     label=sds((b,), jnp.float32, vec),
                     weight=sds((b,), jnp.float32, vec))

    def loss_fn(p, bt):
        margin = fm_mod._margin_ell(p, bt)
        per = optax.sigmoid_binary_cross_entropy(margin, bt.label)
        return (per * bt.weight).sum() / jnp.maximum(bt.weight.sum(), 1.0)

    def step(p, s, bt):
        loss, g = jax.value_and_grad(loss_fn)(p, bt)
        up, s = opt.update(g, s, p)
        p = optax.apply_updates(p, up)
        p = p._replace(w=p.w.at[-1].set(0.0), v=p.v.at[-1].set(0.0))
        return p, s, loss

    for name, fn, args, kw in (
            ("step", step, (params, opt_state, batch),
             dict(donate_argnums=(0, 1))),):
        t = time.time()
        compiled = jax.jit(fn, **kw).lower(*args).compile()
        print(f"{name}: compiled for {chips} chip(s) in "
              f"{time.time() - t:.1f} s")
        print(compiled.memory_analysis())
        if name == "step" and chips > 1:
            text = compiled.as_text()
            print("collectives:", sorted({ln.split("=")[1].split("(")[0].strip().split(" ")[-1]
                                          for ln in text.splitlines()
                                          if "all-reduce" in ln and "=" in ln})[:8])


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1)
