"""Main-path kernels compiled for a described TPU v5e (no chip needed).

Interpret mode cannot see what the TPU compiler refuses: block shapes off
its tiling, scratch over the fast-memory limit.  These tests compile the
kernels at real widths against a ``v5e:2x2`` topology description and check
that the program holds the Mosaic custom call.  The topology is described
inside a fixture, never at import, so only the test process that runs this
file loads the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_decode_attention


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("H,KV,T,num_blocks", [
    pytest.param(20, 20, 64, 513, id="20-20"),  # qwen1.5-4b: MHA, 20 x 128
    pytest.param(32, 4, 64, 513, id="32-4"),  # GQA, 8 query heads a KV head
    # the benchmark's served shape: a 400-block pool, tables 399 wide
    pytest.param(20, 20, 399, 400, id="20-20-served"),
])
def test_paged_decode_attention_compiles_for_v5e(one_chip, H, KV, T,
                                                 num_blocks):
    B, D, block_size = 8, 128, 16
    dt = jnp.bfloat16

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(paged_decode_attention).lower(
        arg((B, H, D), dt),
        arg((num_blocks, KV, block_size, D), dt),
        arg((num_blocks, KV, block_size, D), dt),
        arg((B, T), jnp.int32),
        arg((B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
