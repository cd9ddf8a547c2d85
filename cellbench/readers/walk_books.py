"""One count of the walk's books (PR 50): what the update's kernel walked
for the last batch the window stepped, by the learner's own count
(``learner.walk_books()``: one sort of the batch's ids and the walk's own
arithmetic over its chunks, outside any step; on a table dealt by rows the
mean over the chips). ``params["what"]`` names the count: ``pairs`` (the
(block, chunk) pairs the walk meets), ``tile_products`` (the one-hot
products they are contracted over). No value where the learner keeps no
books (a parent commit) or its step takes no kernel.

The log line gives the whole count, what reading it cost on the host (the
first reading compiles its function), and, where the trace has them, the
two kernels' device time over the pairs: nanoseconds a pair from the cell's
own trace and the cell's own batch."""

import json
import time

from cellbench.readers import _program as P
from cellbench.readers import scope_device_ms


def books(ctx) -> dict:
    """``learner.walk_books()``, once a run; ``{}`` where there is none."""
    if "walk_books" in P._cache:
        return P._cache["walk_books"]
    count = getattr(getattr(ctx.adapter, "learner", None), "walk_books",
                    None)
    found = {}
    if count:
        P.counters_at_first_read()      # before the count compiles
        t0 = time.perf_counter()
        found = count()
        t1 = time.perf_counter()
        again = count()
        t2 = time.perf_counter()
        if found:
            per_pair = []
            for kernel in ("walk_gather_kernel", "walk_update_kernel"):
                # (what the scope's own metric file asks the reader for)
                ms = scope_device_ms.read(ctx, {"include": [kernel]})
                if ms and found.get("pairs"):
                    per_pair.append(f"{kernel} {ms:.3f} ms = "
                                    f"{ms * 1e6 / found['pairs']:.1f} ns a "
                                    "pair")
            P.log("walk books of the last batch stepped: "
                  + json.dumps(found) + f"; read in {t1 - t0:.3f} s (with "
                  f"its compile), again in {t2 - t1:.4f} s"
                  + ("" if again == found else " AND NOT THE SAME")
                  + "".join("; " + s for s in per_pair))
    P._cache["walk_books"] = found
    return found


def read(ctx, params):
    value = books(ctx).get(params["what"])
    return None if value is None else float(value)
