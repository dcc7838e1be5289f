"""The chunk scan's gather-sum kernel (ops/aggregate.py ``_gather_sum``)
compiled for a v5e that is described, not attached: what the chip's
compiler would refuse (an unaligned slice, more VMEM than the kernel
may use) is refused here, at the cells' widths, without chip time.
Nothing runs; the topology is described inside a fixture (the TPU
library belongs to one process, and the workers all collect this
file)."""

import os

import pytest

import jax
import jax.numpy as jnp

import roc_tpu.ops.aggregate as A


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows, F, dtype, weights, seg", [
    (65_537, 256, "bfloat16", "bfloat16", 131_072),  # Reddit, packed
    (65_537, 41, "bfloat16", "bfloat16", 131_072),   # Reddit's last op
    (56_449, 128, "bfloat16", None, 106_496),        # arxiv backward
    (65_537, 256, "float32", "float32", 8_192),      # the most held
])
def test_kernel_compiles_for_the_chip(one_chip, rows, F, dtype, weights,
                                      seg):
    assert A.gather_sum_form(rows, F, dtype) == "fused"

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def f(table, idx, w):
        # a [seg, 8] chunk, as the sectioned tables hold it
        return A._gather_sum_call(A._vmem_words(table), idx.T,
                                  None if w is None else w.T, F,
                                  table.dtype, interpret=False)

    compiled = jax.jit(f).lower(
        S((rows, F), dtype), S((seg, 8), jnp.int32),
        None if weights is None else S((seg, 8), weights)).compile()
    assert "tpu_custom_call" in compiled.as_text()
