"""Parallelism: mesh/sharding helpers, collectives, distributed bootstrap.

The reference's distributed story is rank bootstrap + input sharding
(SURVEY.md §2.3-2.4); its TPU-native equivalent is a
``jax.sharding.Mesh`` + XLA collectives over ICI, with ``jax.distributed``
as the DCN control plane bootstrapped from the same ``DMLC_*`` env contract
the tracker exports.
"""

from dmlc_tpu.parallel.mesh import (
    RowDeal, RowRanges, make_mesh, data_sharding, replicated,
    local_batch_to_global, host_shard_info,
)
from dmlc_tpu.parallel.distributed import (
    EnvContract, init_from_env, pod_identity, sync_min,
)

__all__ = [
    "make_mesh", "data_sharding", "replicated", "local_batch_to_global",
    "host_shard_info", "init_from_env", "EnvContract", "pod_identity",
    "sync_min", "RowDeal", "RowRanges",
]
