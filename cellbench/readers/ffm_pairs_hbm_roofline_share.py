"""``op_hbm_roofline_share`` for the field-aware FM's pair terms: the least
time the chip's memory system could take for the named kernels of the step
together (the bytes from the function of ``cellbench/costs_ffm_criteo.py``
that ``params["bytes"]`` names, called with the configuration's sizes
``params["sizes"]``, over the peak HBM bandwidth in ``peaks.json``) as a
percentage of those kernels' measured device time a step. The kernels are
found by their HLO instruction names (``params["op"]``, one pattern for
both: a Pallas kernel keeps the name its ``pallas_call`` gave it); their
time is the union of their intervals inside the counted executions of the
step. The reading is ``op_hbm_roofline_share``'s own, which is bound to
``costs_ffm.py``: while it runs here, that name is this configuration's
cost module. No value with no trace or no such operation (the pair terms
on the plain ``jax.numpy`` route, a parent commit). Bound: HBM bytes."""

from unittest import mock

from cellbench import costs_ffm_criteo
from cellbench.readers import op_hbm_roofline_share as _op


def read(ctx, params):
    with mock.patch.object(_op, "costs_ffm", costs_ffm_criteo):
        return _op.read(ctx, params)
