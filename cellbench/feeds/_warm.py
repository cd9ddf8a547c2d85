"""What the warm feeds share: one cold pass that builds the tier."""

from __future__ import annotations


def build_pass(device_iter) -> int:
    """Drain one epoch so that the tier behind ``device_iter`` is written
    and published, then reset for the first warm epoch."""
    import jax

    n, last = 0, None
    for batch in device_iter:
        n, last = n + 1, batch
    if last is not None:
        jax.block_until_ready(last)
    device_iter.reset()
    return n

