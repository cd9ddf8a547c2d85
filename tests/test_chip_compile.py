"""The main path's kernel compiled at the benchmark's real widths for a
described TPU v5e, with no chip: what Mosaic refuses (tile alignment,
VMEM, SMEM) shows here at no chip time. Nothing runs. The topology is
described inside a fixture, in the test's own process, and every such test
lives in this one file (only one process may hold the TPU's library)."""

import jax
import jax.numpy as jnp
import pytest

from dmlc_tpu.ops import grad_scatter as gs

ROWS, FACTORS = 54_686_453, 8          # kdd12_fm: W + 1 rows, libFM's 8


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots", [65_536 * 16, 16_384 * 16],
                         ids=["one_chip_batch", "one_shard_of_four"])
def test_grad_scatter_kernel_compiles_at_the_cells_shape(one_chip, slots):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = 3 * 16
    compiled = jax.jit(lambda b, i, p: gs.grad_scatter_pallas(
        b, i, p, num_rows=ROWS, num_factors=FACTORS)).lower(
        sds((2, slots // gs.CHUNK_SLOTS + 1), jnp.int32),
        sds((1, slots), jnp.int32), sds((rows, slots), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # lane-major outputs and nothing else of the table's size: no zero
    # fill, no re-layout
    assert "f32[8,54686453]" in text and "f32[54686453]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)
