"""Device milliseconds per execution of the jitted step in the operations
of one named scope of the learner (``jax.named_scope`` in
``dmlc_tpu/models``), from the device plane of the profiler trace; mean
over chips.

A trace names an operation by its HLO instruction (``fusion.3``), which
XLA renumbers whenever the step changes; the learner's ``hlo_scopes()``
maps instruction names to ``op_name`` paths, which hold the scope. (The
trace's own ``tf_op`` stat is whatever the executable was compiled with,
and the persistent compilation cache may have served an older build's.)
An operation belongs to the metric if its ``op_name`` contains one of
``params["include"]`` and none of ``params["exclude"]``: the gradient's
scatter, which is written nowhere, is the transpose of the gather and
reads ``transpose(jvp(fm_gather))``. Collectives are
left to ``allreduce_exposed_ms``. The time is the union of those
operations' intervals inside the counted executions (the rule of
``step_device_ms``), so nested events are not counted twice."""

import glob
import json
import os

from cellbench import trace_reduce as T
from cellbench.readers import _program as P


def belongs(op_name: str, params: dict) -> bool:
    return (any(s in op_name for s in params["include"])
            and not any(s in op_name for s in params.get("exclude", [])))


def scope_metrics() -> dict:
    """``{metric: its file}`` of the metric files that this reader reads."""
    out = {}
    for path in sorted(glob.glob(os.path.join(P.HERE, "metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("reader") == "scope_device_ms":
            out[os.path.basename(path)[:-len(".json")]] = spec
    return out


def step_ops(ctx):
    """``{"executions", "scopes": {instruction: op_name}, "chips":
    [(operations but collectives, merged counted executions)]}`` of this
    run's trace, once; ``None`` where there is no trace, no counted
    execution, no ``hlo_scopes()`` on the learner (a parent commit), or an
    operation of a counted execution that ``hlo_scopes()`` does not hold:
    the names then come from another program than the one that ran, and
    no metric is better than a wrong one."""
    if "step_ops" in P._cache:
        return P._cache["step_ops"]
    path = P.find_trace(ctx)
    scopes_of = getattr(getattr(ctx.adapter, "learner", None),
                        "hlo_scopes", None)
    out = None
    if path and scopes_of:
        P.counters_at_first_read()   # before hlo_scopes() compiles
        scopes, n, chips = scopes_of(), 0, []
        for dev in P.loaded(path)["devices"].values():
            _, execs = P.step_executions(
                dev, ctx.adapter.config["step_module"])
            if execs:
                n += len(execs)
                chips.append(([(name.split(" ")[0], a, b)
                               for name, a, b in dev["ops"]
                               if not T.COLLECTIVE.match(name)],
                              T.merge(execs)))
        # device ns inside the counted executions, by instruction
        inside_ns: dict = {}
        for ops, inside in chips:
            for ins, a, b in ops:
                ns = T.length(P.within([(a, b)], inside))
                if ns:
                    inside_ns[ins] = inside_ns.get(ins, 0.0) + ns
        unknown = sorted(set(inside_ns) - set(scopes))
        if unknown and scopes:
            P.log(f"hlo_scopes() does not hold {len(unknown)} of the "
                  f"{len(inside_ns)} operations the step ran ("
                  + ", ".join(unknown[:8]) + "): no scope metric")
        elif n and scopes:
            out = {"executions": n, "scopes": scopes, "chips": chips}
            claimed = scope_metrics().values()
            rest = sorted(((ns, ins) for ins, ns in inside_ns.items()
                           if not any(belongs(scopes[ins], spec)
                                      for spec in claimed)), reverse=True)
            names = {ins for _, ins in rest}
            union = sum(T.length(P.within(
                [(a, b) for ins, a, b in ops if ins in names], inside))
                for ops, inside in chips)
            P.log(f"step operations that no scope metric counts: "
                  f"{union / n * 1e-6:.3f} ms a step in {len(rest)}, the "
                  "largest: " + ", ".join(
                      f"{ins} {ns / n * 1e-6:.3f} "
                      f"({scopes[ins] or 'no op_name'})"
                      for ns, ins in rest[:8]))
    P._cache["step_ops"] = out
    return out


def read(ctx, params):
    found = step_ops(ctx)
    if found is None:
        return None
    scopes = found["scopes"]
    ns = sum(T.length(P.within([(a, b) for ins, a, b in ops
                                if belongs(scopes.get(ins, ""), params)],
                               inside))
             for ops, inside in found["chips"])
    return ns / found["executions"] * 1e-6
