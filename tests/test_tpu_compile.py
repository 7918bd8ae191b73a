"""Main-path kernels compiled for a described TPU v5e (no chip needed).

Interpret mode cannot see what the TPU compiler refuses: block shapes off
its tiling, scratch over the fast-memory limit.  These tests compile the
kernels at real widths against a ``v5e:2x2`` topology description and check
that the program holds the Mosaic custom call.  The topology is described
inside a fixture, never at import, so only the test process that runs this
file loads the TPU compiler library.
"""

import os
import re

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


# "%name = shape opcode(operands), ..." of one compiled HLO instruction
_DEF = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*?)\)(?:,|$)")


def _pool_traffic(hlo: str, pool_dims: str) -> list:
    """Pool-sized results of copies, slices, write-backs and rematerialised
    values in compiled HLO text.  A dynamic-update-slice whose update is
    not pool-sized is the in-place write of new rows, and is not listed."""
    shapes, hits = {}, []
    for line in hlo.splitlines():
        m = _DEF.match(line)
        if not m:
            continue
        name, shape, opcode, operands = m.groups()
        shapes[name] = shape
        if pool_dims not in shape:
            continue
        ops = re.findall(r"%([\w.\-]+)", operands)
        update = shapes.get(ops[1], "") if len(ops) > 1 else ""
        if opcode == "dynamic-update-slice" and update and (
                pool_dims not in update):
            continue
        kind = opcode if opcode != "fusion" else name
        if "remat" in name or any(w in kind for w in ("copy", "slice")):
            hits.append(f"{name} = {shape} {opcode}")
    return hits


@pytest.mark.parametrize("C", [16, 1])
def test_paged_tick_keeps_pool_in_place_for_v5e(one_chip, C):
    """The tick at qwen1.5-4b's widths with the served 400-block pool (two
    scanned layers keep the compile short): the stacked pool is carried
    through the micro-steps and the layers, so no copy, slice or write-back
    of a whole pool, stacked or one layer's, is left in the program."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import ModelOptions, abstract_params
    from repro.serve.paged_model import init_paged_state, make_paged_tick

    B, num_blocks, bs = 8, 400, 16
    cfg = dataclasses.replace(get_config("qwen1.5-4b"), num_layers=2)

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    state = place(jax.eval_shape(lambda: init_paged_state(
        cfg, B, num_blocks, bs, jnp.bfloat16)))
    args = (place(abstract_params(cfg, jnp.bfloat16)), state,
            place(jax.ShapeDtypeStruct((B, num_blocks - 1), jnp.int32)),
            place(jax.ShapeDtypeStruct((B, C), jnp.int32)),
            place(jax.ShapeDtypeStruct((B,), jnp.int32)),
            place(jax.ShapeDtypeStruct((B,), jnp.bool_)))
    tick = make_paged_tick(cfg, ModelOptions(compute_dtype="bfloat16"),
                           attn_impl="kernel")
    compiled = tick.lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    pool = state["main"][0]["k"]
    assert pool.shape == (2, num_blocks, 20, bs, 128)
    assert _pool_traffic(hlo, f"{num_blocks},20,{bs},128]") == []
    # nothing pool-sized is held besides the donated state
    one_layer = pool.size // 2 * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer
