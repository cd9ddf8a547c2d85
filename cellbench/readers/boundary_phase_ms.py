"""Milliseconds of one phase of the epoch boundary (``params["phase"]``:
``first_put`` or ``withheld``, see ``_boundary``), from the program's own
span ring: the median over the window's boundaries. Every boundary's own
numbers, the clock's tie to the profiler and the device's idle by phase go
on earlier lines. No value where the program labels no batch (a parent
commit) or the window holds no whole boundary."""

import statistics

from cellbench.readers import _boundary as B


def read(ctx, params):
    found = B.boundaries(ctx)
    if not found:
        return None
    B.log_idle_by_phase(ctx)
    return statistics.median(B.phase_ms(b)[params["phase"]] for b in found)
