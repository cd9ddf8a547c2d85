"""Operations and bytes the algorithms need, from their shapes alone, and
the table of device peaks. Kept with the benchmark so that no PR that
claims a gain can change the yardstick."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def device_peaks(device_kind: str) -> dict:
    """Peaks of one chip, by ``jax.devices()[0].device_kind``. A device
    that is not in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def fm_adam_step_min_bytes(num_features: int, num_factors: int,
                           batch_size: int, max_nnz: int) -> int:
    """HBM bytes one exact dense-Adam step of an ELL factorization machine
    has to move, whatever the program does.

    Exact Adam decays every coordinate's two moments on every step, so the
    parameter and both moments are each read once and written once: six
    float32 passes over ``(num_features + 1) * (num_factors + 1) + 1``
    coordinates (the +1 row is the ELL padding sink, the +1 scalar the
    bias). The batch is read once (int32 index and float32 value per slot,
    label and weight per row). The touched rows are gathered for the margin
    and their gradient rows scattered back: ``2 * batch * max_nnz *
    (num_factors + 1)`` float32. A materialised dense gradient is the
    program's choice and is not counted, so the share of the roofline can
    only rise towards 100% as the step improves. A lazy per-row decay of
    the moments would need fewer bytes than this; it is not exact Adam.
    """
    coords = (num_features + 1) * (num_factors + 1) + 1
    tables = 6 * 4 * coords
    batch = batch_size * max_nnz * 8 + batch_size * 8
    rows = 2 * batch_size * max_nnz * (num_factors + 1) * 4
    return tables + batch + rows
