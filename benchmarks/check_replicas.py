"""Run one cell of the benchmark and then ask whether the learner's
replicated state is still the same bits on every chip:

    chiprun --chips 4 -- python3 benchmarks/check_replicas.py \
        --workload kdd12_fm_dp4_bcache --seed 2700000401 --seconds 20

Since PR 27 the chips of a mesh no longer all-reduce the gradient: each
builds it from the all-gathered batch rows (``collective="rows"`` in
``grad_scatter_route``) or, since PR 31, updates its replica in place from
them (``table_update_route{route="fused"}``), so nothing but identical
arithmetic keeps the replicas of ``w``, ``v`` and Adam's moments together.
The cell runs through
``cellbench.run`` untouched (its result line comes first); the learner it
built is then compared leaf by leaf on the device: the elementwise maximum
and minimum over the mesh axis of every leaf's bit pattern must agree. One
JSON line last; exit code 1 if a replica differs.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def differing_elements(x, mesh, axis: str) -> int:
    """Elements of the replicated ``x`` whose bit pattern is not the same
    on every device of ``axis`` (each device contributes its own copy)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def local(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.int32)
        differ = jax.lax.pmax(bits, axis) != jax.lax.pmin(bits, axis)
        return jnp.sum(differ, dtype=jnp.int32)[None]

    counts = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(),
                                   out_specs=P(axis), check_vma=False))(x)
    return int(counts.max())


def main(argv) -> int:
    from cellbench import run
    from cellbench.learners import fm

    built = []

    class Recorded(fm.Adapter):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            built.append(self)

    fm.Adapter = Recorded
    rc = run.main(argv)
    if rc or not built or built[0].mesh is None:
        print(json.dumps({"replicas": "not checked: the cell built no "
                          "learner under a mesh", "rc": rc}))
        return rc or 1
    import jax

    from dmlc_tpu.utils import telemetry

    learner = built[0].learner
    adam = learner.opt_state[0]
    leaves = {"w0": learner.params.w0, "w": learner.params.w,
              "v": learner.params.v, "mu_w": adam.mu.w, "mu_v": adam.mu.v,
              "nu_w": adam.nu.w, "nu_v": adam.nu.v}
    differ = {k: differing_elements(x, learner.mesh, learner.data_axis)
              for k, x in leaves.items()}
    print(json.dumps({
        "replicas_bit_identical": not any(differ.values()),
        "differing_elements": differ,
        "elements": {k: int(x.size) for k, x in leaves.items()},
        "devices": jax.device_count(), "adam_steps": int(adam.count),
        **{name: [ln for ln in telemetry.render_prometheus().splitlines()
                  if ln.startswith(f"dmlc_tpu_{name}_total")]
           for name in ("grad_scatter_route", "table_update_route")}}),
        flush=True)
    return 1 if any(differ.values()) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
