"""Serving launcher: batched requests through the paged serving engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b \\
      --requests 8 --max-active 8 --num-blocks 320 --block-size 16 \\
      --max-new 32
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve \\
      --arch qwen1.5-4b --smoke --num-blocks 64 --block-size 4

Weights are random from ``--seed``, built on the device in the compute
dtype (bfloat16 on a TPU).  KV lives in a pool of ``--num-blocks`` blocks
of ``--block-size`` tokens shared by at most ``--max-active`` sequences;
attention reads it through the paged Pallas kernel, compiled on a TPU and
interpreted elsewhere.  ``--smoke`` serves the reduced config.
``--platform`` submits a serving Job through the cloud-native control
plane instead (replicated servers behind a router).
"""

from __future__ import annotations

import argparse
import time

import jax

from ..configs.base import ArchConfig
from ..models import ModelOptions
from ..serve import PagedServeEngine


def serving_options() -> ModelOptions:
    """bfloat16 compute on an accelerator; float32 on the CPU, where the
    tests compare paths token for token."""
    return ModelOptions(compute_dtype="float32" if jax.default_backend() == "cpu"
                        else "bfloat16")


def build_engine(cfg: ArchConfig, params, opts: ModelOptions, *,
                 num_blocks: int, block_size: int, max_active: int,
                 prefill_chunk: int, attn_impl: str = "kernel"
                 ) -> PagedServeEngine:
    """The paged engine as served: Pallas attention compiled on a TPU and
    in interpret mode on any other backend."""
    return PagedServeEngine(cfg, params, num_blocks=num_blocks,
                            block_size=block_size, max_active=max_active,
                            prefill_chunk=prefill_chunk, opts=opts,
                            attn_impl=attn_impl,
                            interpret=jax.default_backend() != "tpu")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=320)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-active", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--platform", action="store_true")
    args = ap.parse_args()

    if args.platform:
        from ..platform import Platform

        arch = args.arch
        if args.smoke:
            from ..configs import reduced_config

            arch = reduced_config(args.arch)
        p = Platform(num_nodes=4)
        try:
            p.submit("serve", {"app": {"type": "serve", "arch": arch,
                                       "replicas": 2}})
            assert p.wait_submitted("serve", 60)
            assert p.wait_full_health("serve", 120)
            print("serving job healthy:",
                  [(x.spec["peId"], x.status.get("phase")) for x in p.pods("serve")])
            time.sleep(2)
        finally:
            p.delete_job("serve")
            p.wait_terminated("serve", 30)
            p.shutdown()
        return

    from ..configs import get_config, reduced_config
    from ..models import init_serving_params
    from ..serve import Request
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.smoke else get_config(args.arch)
    opts = serving_options()
    print(f"loading {cfg.name}: {cfg.param_count()/1e6:.0f}M params")
    params = init_serving_params(jax.random.key(args.seed), cfg, opts.dtype)
    engine = build_engine(cfg, params, opts, num_blocks=args.num_blocks,
                          block_size=args.block_size,
                          max_active=args.max_active,
                          prefill_chunk=args.prefill_chunk)
    for rid in range(args.requests):
        prompt = [1 + rid % 13, 7, (rid * 31) % cfg.vocab_size]
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=args.max_new))
    t0 = time.time()
    done = engine.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"{len(done)} requests, {toks} tokens, {dt:.1f}s "
          f"({toks/max(dt,1e-9):.1f} tok/s, {jax.devices()[0].device_kind})")


if __name__ == "__main__":
    main()
