"""Compile a serve cell's tick for a described TPU v5e and print its memory.

  JAX_PLATFORMS=cpu PYTHONPATH=src python bench/aot.py --arch qwen1.5-4b \
      --num-blocks 512 600 700

No chip is needed: the TPU compiler runs against ``v5e:2x2`` described by
``jax.experimental.topologies``, on one of its devices.  For each pool size
it prints the bytes ``memory_analysis`` gives for the prefill-width tick
(C = chunk) and the decode tick (C = 1).  The pool of a serve config is the
largest for which the prefill tick, with the weights, fits the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def tick_memory(arch: str, num_blocks: int, block_size: int, max_active: int,
                chunk: int) -> dict:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.models import ModelOptions, abstract_params
    from repro.serve.paged_model import init_paged_state, make_paged_tick

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = get_config(arch)
    opts = ModelOptions(compute_dtype="bfloat16")

    def place(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    params = place(abstract_params(cfg, jnp.bfloat16))
    state = place(jax.eval_shape(lambda: init_paged_state(
        cfg, max_active, num_blocks, block_size, jnp.bfloat16)))
    T = num_blocks - 1
    out = {}
    for C in (chunk, 1):
        args = (params, state,
                jax.ShapeDtypeStruct((max_active, T), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((max_active, C), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((max_active,), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((max_active,), jnp.bool_, sharding=one))
        tick = make_paged_tick(cfg, opts, attn_impl="kernel")
        m = tick.lower(*args).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        out[f"C={C}"] = {"argument": m.argument_size_in_bytes,
                         "output": m.output_size_in_bytes,
                         "temp": m.temp_size_in_bytes,
                         "alias": m.alias_size_in_bytes, "total": total,
                         "total_gib": round(total / 2**30, 3)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--num-blocks", type=int, nargs="+", required=True)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-active", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    for n in args.num_blocks:
        print(json.dumps({"num_blocks": n, **tick_memory(
            args.arch, n, args.block_size, args.max_active, args.chunk)}),
            flush=True)


if __name__ == "__main__":
    sys.exit(main())
