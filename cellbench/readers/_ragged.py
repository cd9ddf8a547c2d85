"""What the readers of a ragged (``bcoo``) cell share (PR 37): the real
non-zeros and the slots shipped in the window, from
``DeviceIter.stats()["bcoo"]``. ``None`` where the program keeps no such
books (a parent commit): a reader then leaves its metric out."""

from __future__ import annotations


def window(ctx):
    """``(non-zeros, slots shipped, steps)`` of the window: the books
    between its two ``stats()`` and the steps it dispatched (the books are
    kept where a batch's shape is planned, a few batches ahead of the
    steps at both ends alike), or ``None``."""
    a, b = ctx.stats_start, ctx.stats_end
    if not a or not b or "bcoo" not in a or "bcoo" not in b:
        return None
    slots = b["bcoo"]["slots"] - a["bcoo"]["slots"]
    if ctx.steps_dispatched <= 0 or slots <= 0:
        return None
    return b["bcoo"]["nnz"] - a["bcoo"]["nnz"], slots, ctx.steps_dispatched


def sizes(ctx):
    """The arguments of ``cellbench/costs_fm_ragged.py``'s functions for
    this run: table rows (the ids and the padding row), factors, rows a
    batch and the mean real non-zeros a step; ``None`` with no books."""
    seen = window(ctx)
    if seen is None:
        return None
    c = ctx.adapter.config
    return (c["num_features"] + c["first_id"] + 1, c["num_factors"],
            c["batch_size"], seen[0] / seen[2])
