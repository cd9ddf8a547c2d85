"""The share of the traced window during which the device sat idle under
no stage span of the program (``dmlc_tpu:<name>``, ``telemetry.span``), in
percent; mean over chips. It polices the tracing itself: host work added
outside any span, in the program or around it, shows here, in the unit of
the throughput it costs. ``params["containers"]`` names the spans that
only wrap other stages (``next``, ``first_batch``): idle time whose
innermost span is one of them counts as uncovered, so work added inside
the iterator but outside a stage span shows too. The window, not the idle
time, is the denominator: a well-fed cell idles 12-18 ms in 10 s, and a
share of that measured the harness's own 2 ms hand-off (PERF.md section
6, PR 24). The idle seconds by program span go on an earlier line.
Nothing is read from a trace without a program span (a parent commit) or
without a device operation."""

from cellbench.readers import _program as P

NO_SPAN = "(no host span)"


def read(ctx, params):
    path = P.find_trace(ctx)
    trace = P.loaded(path) if path else None
    by_span = P.idle_by_span(trace) if trace else None
    if not by_span:
        return None
    edges = [t for d in trace["devices"].values() for _, a, b in d["ops"]
             for t in (a, b)]
    window = (max(edges) - min(edges)) * 1e-9
    uncovered = sum(v for k, v in by_span.items()
                    if k == NO_SPAN or k in params.get("containers", ()))
    P.log("idle seconds by program span: " + ", ".join(
        f"{k} {v:.6f}" for k, v in by_span.items()))
    P.log(f"idle {sum(by_span.values()):.6f} s of a window of {window:.6f} s;"
          f" under no stage span {uncovered:.6f} s")
    return 100.0 * uncovered / window
