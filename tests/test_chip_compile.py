"""The main path's kernel compiled at the benchmark's real widths for a
described TPU v5e, with no chip: what Mosaic refuses (tile alignment,
VMEM, SMEM) shows here at no chip time. Nothing runs. The topology is
described inside a fixture, in the test's own process, and every such test
lives in this one file (only one process may hold the TPU's library)."""

import jax
import jax.numpy as jnp
import pytest

from dmlc_tpu.ops import grad_scatter as gs

# W + 1 rows and the tables after the id axis: kdd12_fm's linear column and
# libFM's 8 factors; kdd12_ffm's one table of 11 fields x 4 factors
SHAPES = {"fm": (54_686_453, ((), (8,))), "ffm": (13_671_614, ((44,),))}


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


@pytest.mark.parametrize("learner,slots", [
    ("fm", 65_536 * 16), ("fm", 16_384 * 16), ("ffm", 65_536 * 16)],
    ids=["one_chip_batch", "one_shard_of_four", "ffm_one_chip_batch"])
def test_grad_scatter_kernel_compiles_at_the_cells_shape(one_chip, learner,
                                                         slots):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    num_rows, trailing = SHAPES[learner]
    width = sum(t[0] if t else 1 for t in trailing)
    rows = 3 * (-(-width // 16) * 16)
    compiled = jax.jit(lambda b, i, p: gs.grad_scatter_pallas(
        b, i, p, num_rows=num_rows, trailing=trailing)).lower(
        sds((2, slots // gs.CHUNK_SLOTS + 1), jnp.int32),
        sds((1, slots), jnp.int32), sds((rows, slots), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # lane-major outputs and nothing else of the table's size: no zero
    # fill, no re-layout
    for tail in trailing:
        assert f"f32[{','.join(map(str, tail + (num_rows,)))}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)
