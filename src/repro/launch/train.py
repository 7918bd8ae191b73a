"""Training launcher: run a (possibly sharded) training job directly, or
submit it to the platform.

Direct mode (``train_direct``) executes real steps on the available
devices — with XLA_FLAGS=--xla_force_host_platform_device_count=4 for
multi-device CPU runs, or on a four-chip TPU host with ``--mesh 2,2``
(data, model):

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \\
      --steps 20 --batch 8 --seq 64
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \\
      --mesh 2,2 --steps 10

Platform mode (--platform) submits a Job CRD and drives it through the
cloud-native control plane (checkpointing, recovery, elasticity):

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \\
      --platform --steps 40
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default=None, help="e.g. 2,2,2 for pod,data,model")
    ap.add_argument("--platform", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()

    if args.platform:
        from ..platform import Platform

        arch = args.arch
        if args.smoke:
            from ..configs import reduced_config

            arch = reduced_config(args.arch)
        p = Platform(num_nodes=4)
        try:
            p.submit("train", {
                "app": {"type": "train", "arch": arch, "data_parallel": 2,
                        "steps": args.steps, "batch_per_shard": max(args.batch // 2, 1),
                        "seq_len": args.seq, "lr": args.lr},
                "consistentRegion": {"name": "dp",
                                     "interval": max(args.steps // 4, 1)},
            })
            assert p.wait_full_health("train", 120)
            last = -1
            while True:
                ms = p.metrics("train")
                steps = [m.get("step", 0) for m in ms.values()]
                if steps and max(steps) > last:
                    last = max(steps)
                    losses = [m["loss"] for m in ms.values() if "loss" in m]
                    print(f"step {last:4d} loss {min(losses):.4f}")
                if steps and max(steps) >= args.steps:
                    break
                time.sleep(0.5)
        finally:
            p.delete_job("train")
            p.wait_terminated("train", 30)
            p.shutdown()
        return

    from ..configs import get_config, reduced_config
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.smoke else get_config(args.arch)
    mesh_shape = (tuple(int(x) for x in args.mesh.split(","))
                  if args.mesh else None)
    train_direct(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                 accum=args.accum, remat=not args.smoke,
                 mesh_shape=mesh_shape)


@dataclass
class TrainRun:
    state: dict  # train state after the last step
    history: list  # per step: loss, grad_norm, seconds
    compiled: object  # the compiled step (``as_text()``, memory analysis)


def train_direct(cfg, *, steps: int, batch: int, seq: int, accum: int = 1,
                 remat: bool = True, mesh_shape=None,
                 seed: int = 0) -> TrainRun:
    """Run ``steps`` train steps in this process.  Unsharded on the default
    device, or over a mesh of ``mesh_shape`` (axes named from the right of
    ``(pod, data, model)``, so ``(2, 2)`` is ``(data, model)``)."""
    import jax

    from ..data import StreamSource
    from ..models import ModelOptions
    from ..sharding.ctx import activation_rules
    from ..train import (TrainConfig, batch_sharding, init_train_state,
                         make_train_step, train_state_specs)

    opts = ModelOptions(compute_dtype="float32" if jax.default_backend() == "cpu"
                        else "bfloat16")
    tcfg = TrainConfig(accum_steps=accum, remat=remat)
    src = StreamSource(vocab_size=cfg.vocab_size, batch=batch, seq_len=seq,
                       seed=seed)
    state = init_train_state(jax.random.key(seed), cfg, tcfg)

    if mesh_shape:
        axes = ("pod", "data", "model")[-len(mesh_shape):]
        mesh = jax.make_mesh(mesh_shape, axes)
        specs = train_state_specs(state, mesh)
        state = jax.device_put(state, specs)
        bspecs = batch_sharding(mesh, src.batch_at(0))
        step = jax.jit(make_train_step(cfg, tcfg, opts, mesh=mesh,
                                       act_rules=activation_rules()),
                       in_shardings=(specs, bspecs), donate_argnums=0)

        def place(b):
            return jax.device_put(b, bspecs)
    else:
        step = jax.jit(make_train_step(cfg, tcfg, opts), donate_argnums=0)

        def place(b):
            return b

    compiled = step.lower(state, place(src.batch_at(0))).compile()
    history = []
    for i in range(steps):
        b = place(src.batch_at(i))
        t0 = time.perf_counter()
        state, metrics = compiled(state, b)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        gnorm = float(metrics["grad_norm"])
        print(f"step {i:4d} loss {loss:9.4f} gnorm {gnorm:8.3f} ({dt:.2f}s)")
        history.append({"loss": loss, "grad_norm": gnorm, "seconds": dt})
    return TrainRun(state, history, compiled)


if __name__ == "__main__":
    main()
