"""Compile the dealt field-aware FM's step and its start at full size for
the four chips of a described ``v5e:2x2``, with no chip (PR 32):

    JAX_PLATFORMS=cpu python3 -m cellbench.tools.aot_compile_ffm_ps4 [kdd12_ffm_ps4]

Nothing runs. The learner is built at a toy size on four virtual CPU
devices for its functions, then handed the described mesh and the
configuration's sizes; the kernels' routes are the chip's. Prints the
compiler's memory analysis a chip, the collectives of the optimised step
with their scopes, and every instruction whose result is of the whole
table's size on one device: there must be none, in the step or the start.
"""

from __future__ import annotations

import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(config_name: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from cellbench.run import HERE, load_json
    from dmlc_tpu.models import FFMLearner
    from dmlc_tpu.ops import grad_scatter as gs
    from dmlc_tpu.parallel.mesh import make_mesh

    cfg = load_json(HERE, "configs", config_name + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    gs._on_tpu_backend = lambda: True        # the chip's routes, compiled here
    model = FFMLearner(num_col=63, num_fields=cfg["num_fields"],
                       num_factors=cfg["num_factors"],
                       learning_rate=cfg["learning_rate"], l2=cfg["l2"],
                       mesh=make_mesh(devices=jax.devices()[:4]))
    # the described chips and the configuration's rows in the toy's place
    model.num_col = cfg["num_features"]
    model.weight_dim = cfg["num_features"] + 1
    model._deal_over(make_mesh(devices=topo.devices[:4]))
    deal = model.deal
    params_sh, opt_sh, batch_sh, _ = model._shardings
    step_fn, options = model._build_step()._jit_args
    width = cfg["num_fields"] * cfg["num_factors"]
    b, k = cfg["batch_size"], cfg["max_nnz"]
    sds = jax.ShapeDtypeStruct
    table = sds((deal.padded_rows, width), jnp.float32,
                sharding=params_sh.w)
    params = type(model.params)(w=table)
    opt_state = jax.tree_util.tree_map(
        lambda x, sh: sds(table.shape if x.ndim == 2 and x.shape[1] == width
                          else x.shape, x.dtype, sharding=sh),
        model.opt_state, opt_sh)
    shapes = dict(indices=((b, k), jnp.int32), values=((b, k), jnp.float32),
                  label=((b,), jnp.float32), weight=((b,), jnp.float32),
                  fields=((b, k), jnp.uint8))
    batch = type(batch_sh)(**{
        name: sds(*shapes[name], sharding=getattr(batch_sh, name))
        for name in shapes})
    whole = re.compile(
        rf"f32\[({model.weight_dim}|{deal.padded_rows}),{width}\]|"
        rf"f32\[{width},({model.weight_dim}|{deal.padded_rows})\]")
    bad = 0
    key = sds((2,), jnp.uint32, sharding=opt_sh[-1])
    for name, lowered in (
            ("start", model._start_fn().lower(key)),
            ("step", jax.jit(step_fn, **options).lower(
                params, opt_state, batch))):
        t = time.time()
        compiled = lowered.compile()
        print(f"{name}: compiled for four chips in {time.time() - t:.1f} s")
        print(compiled.memory_analysis())
        for line in compiled.as_text().splitlines():
            head = line.split(" = ", 1)
            if len(head) != 2:
                continue
            what = head[1].split("(")[0]
            op = re.search(r'op_name="([^"]*)"', line)
            if whole.search(what):
                bad += 1
                print("  WHOLE TABLE:", head[0].strip(), "=", what)
            if re.search(r" (all-gather|all-reduce|reduce-scatter|all-to-all|"
                         r"collective-permute)(-start)?\(|tpu_custom_call",
                         line):
                print(" ", head[0].strip(), "=", head[1].split(" ")[0], "|",
                      op[1] if op else "")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "kdd12_ffm_ps4"))
