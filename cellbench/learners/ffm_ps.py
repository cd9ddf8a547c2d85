"""Adapter between the harness and ``dmlc_tpu.models.FFMLearner`` with its
table dealt by rows over a mesh (PR 32, configuration ``kdd12_ffm_ps4``).

The mathematics is ``learners/ffm.py``'s and so are the comparison and the
controls; three things differ. The reference's start is drawn row by row
(``reference/ffm_start_blocks.py``): the whole draw of
``ffm_adagrad.initial_rows`` is 9.6 GB on one device at this size. The
program's table is never whole anywhere, so its rows are read by id
through the program's own deal (``RowDeal.take``). And the count of bytes
behind the step's roofline is one chip's (``costs_ffm_ps.py``).
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from cellbench.learners import ffm as _ffm
from cellbench.learners.ffm import compare  # noqa: F401 - the harness reads it
from cellbench.reference import ffm_adagrad, ffm_start_blocks
# at import, not in Adapter: a program that cannot deal a table (the
# parent of PR 32) fails here, at once, before the corpus and the reference
from dmlc_tpu.parallel.mesh import RowDeal


def _blockwise_start():
    """``learners/ffm.py`` asks ``ffm_adagrad`` for the start by name;
    while one of its functions runs here, that name draws in blocks."""
    return mock.patch.object(ffm_adagrad, "initial_rows",
                             ffm_start_blocks.initial_rows)


def reference_digest(config: dict, seed: int, corpus_path: str, **how):
    with _blockwise_start():
        return _ffm.reference_digest(config, seed, corpus_path, **how)


def control_numbers(config: dict, seed: int, corpus_path: str,
                    ref: dict) -> dict:
    with _blockwise_start():
        return _ffm.control_numbers(config, seed, corpus_path, ref)


class Adapter(_ffm.Adapter):
    def __init__(self, config: dict, seed: int, mesh=None):
        if mesh is None or config["mesh"] != dict(mesh.shape):
            raise ValueError("ffm_ps adapter: the configuration deals its "
                             f"table over the mesh {config['mesh']}")
        super().__init__(config, seed, mesh=mesh)
        deal = self.learner.deal
        if not isinstance(deal, RowDeal) or (
                deal.shards, deal.local_rows, deal.padded_rows) != (
                config["shards"], config["shard_rows"],
                config["padded_rows"]):
            raise ValueError(f"ffm_ps adapter: the learner's deal {deal} is "
                             "not the configuration's")

    def step_min_bytes(self) -> int:
        from cellbench.costs_ffm_ps import ffm_ps_chip_step_min_bytes

        c = self.config
        return ffm_ps_chip_step_min_bytes(
            c["num_fields"], c["num_factors"], c["batch_size"],
            c["max_nnz"], c["shards"])

    # ---- readings for the comparison: rows by id, through the deal ----
    def _take(self, table, ids):
        import jax.numpy as jnp

        return self.learner.deal.take(self.mesh, table,
                                      jnp.asarray(ids, jnp.int32))

    def update_norms(self, reference: dict) -> list:
        import jax.numpy as jnp

        moved = self._take(self.learner.params.w,
                           reference["all_touched_ids"]) - jnp.asarray(
                               reference["w_start_touched"])
        return [float(jnp.sqrt(jnp.sum(jnp.square(moved))))]

    def rows(self, ids: np.ndarray) -> dict:
        return {"w": np.asarray(self._take(self.learner.params.w, ids)),
                "g": np.asarray(self._take(self.learner.accumulators, ids))}
