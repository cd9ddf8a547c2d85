"""Slots shipped to the device in the window that are no non-zero (the
padding of a ragged batch's slot count to its bucket), as a percentage of
the slots shipped. From ``DeviceIter.stats()["bcoo"]``."""

from cellbench.readers import _ragged


def read(ctx, params):
    seen = _ragged.window(ctx)
    if seen is None:
        return None
    nnz, slots, _ = seen
    return 100.0 * (slots - nnz) / slots
