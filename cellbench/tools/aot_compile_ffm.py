"""Compile the field-aware FM's step at full size for a described
``v5e:2x2``, with no chip (``aot_compile.py`` builds the FM's step by hand
and serves no other learner):

    JAX_PLATFORMS=cpu python3 -m cellbench.tools.aot_compile_ffm kdd12_ffm

Nothing runs; what the chip's compiler refuses (memory, layouts, the
kernel's tiles) shows here at no chip time. The gradient's scatter is put
on the kernel route, as on the chip. Prints the compiler's memory analysis
and every instruction of the optimised step whose result is of the
table's size, with its scope: a copy or transpose among them is a pass
over 2.4 GB that the step need not make.
"""

from __future__ import annotations

import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(config_name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from cellbench.run import HERE, load_json
    from dmlc_tpu.models import FFMLearner
    from dmlc_tpu.ops import grad_scatter as gs
    from dmlc_tpu.ops.sparse import EllBatch

    cfg = load_json(HERE, "configs", config_name + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    gs._on_tpu_backend = lambda: True        # the chip's route, compiled here
    # the learner at a toy size, for its step function; the shapes compiled
    # are the configuration's
    model = FFMLearner(num_col=7, num_fields=cfg["num_fields"],
                       num_factors=cfg["num_factors"],
                       learning_rate=cfg["learning_rate"], l2=cfg["l2"])
    step_fn, options = model._step._jit_args
    rows = cfg["num_features"] + 1
    width = cfg["num_fields"] * cfg["num_factors"]
    b, k = cfg["batch_size"], cfg["max_nnz"]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    table = sds((rows, width), jnp.float32)
    params = type(model.params)(w=table)
    opt_state = jax.tree_util.tree_map(
        lambda x: sds((rows, width) if x.ndim == 2 else x.shape, x.dtype),
        model.opt_state)
    batch = EllBatch(indices=sds((b, k), jnp.int32),
                     values=sds((b, k), jnp.float32),
                     label=sds((b,), jnp.float32),
                     weight=sds((b,), jnp.float32),
                     fields=sds((b, k), jnp.uint8))
    t = time.time()
    compiled = jax.jit(step_fn, **options).lower(
        params, opt_state, batch).compile()
    print(f"step: compiled for one chip in {time.time() - t:.1f} s")
    print(compiled.memory_analysis())
    big = re.compile(rf"f32\[({rows},{width}|{width},{rows})\]")
    for line in compiled.as_text().splitlines():
        head = line.split(" = ", 1)
        if len(head) == 2 and big.search(head[1].split("(")[0]):
            op = re.search(r'op_name="([^"]*)"', line)
            print(" ", head[0].strip(), "=", head[1].split("(")[0],
                  "|", op[1] if op else "")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "kdd12_ffm")
