"""Training step construction: pjit-ready, remat'd, optionally compressed.

``make_train_step`` builds a pure (state, batch) -> (state, metrics) function
suitable for jax.jit with in/out shardings from ``train_state_specs``.

Variants:
- baseline: global loss over the ('pod','data')-sharded batch; XLA inserts
  the gradient reduce automatically (paper-faithful: let the platform own
  communication).
- grad accumulation: lax.scan over microbatches.
- compressed cross-pod sync: partial-manual shard_map over the 'pod' axis,
  per-pod grads combined with int8+EF all-gather (see train/compress.py) —
  the beyond-paper collective-term optimization (§Perf).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..models import ModelOptions, init_params, loss_fn
from ..sharding.ctx import auto_axes, use_rules
from ..sharding.specs import PARAM_RULES, param_specs
from .compress import compressed_mean_over_axis, init_ef_state
from .optim import OptimizerConfig, adamw_update, clip_by_global_norm, init_opt_state


@dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = OptimizerConfig()
    accum_steps: int = 1
    compress_pod_grads: bool = False
    num_pods: int = 1
    remat: bool = True


def init_train_state(key, cfg: ArchConfig, tcfg: TrainConfig = TrainConfig()) -> dict:
    params = init_params(key, cfg)
    state = {
        "params": params,
        "opt": init_opt_state(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if tcfg.compress_pod_grads:
        state["ef"] = init_ef_state(params, tcfg.num_pods)
    return state


def abstract_train_state(cfg: ArchConfig, tcfg: TrainConfig = TrainConfig()):
    return jax.eval_shape(lambda: init_train_state(jax.random.key(0), cfg, tcfg))


def train_state_specs(state, mesh: Mesh, rules: dict = PARAM_RULES):
    """NamedShardings for a (possibly abstract) train state."""
    mesh = auto_axes(mesh)
    p_specs = param_specs(state["params"], mesh, rules)
    specs = {
        "params": p_specs,
        "opt": {"m": p_specs, "v": p_specs},
        "step": NamedSharding(mesh, P()),
    }
    if "ef" in state:
        # EF buffers: leading pod dim + the parameter's own sharding —
        # without the param-dim sharding every device would hold a full
        # per-pod gradient replica (measured: 50x memory-term blowup)
        from ..sharding.specs import fit_spec, logical_to_spec, param_logical_axes

        logical = param_logical_axes(state["params"])

        def ef_spec(leaf, ax):
            spec = logical_to_spec(ax, rules)
            spec = fit_spec(spec, leaf.shape[1:], mesh)
            return NamedSharding(mesh, P(*(("pod",) + tuple(spec))))

        specs["ef"] = jax.tree.map(
            ef_spec, state["ef"], logical,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))
    return specs


def batch_sharding(mesh: Mesh, batch, data_axes: tuple = ("pod", "data")):
    """Batch leaves split over whichever of ``data_axes`` the mesh has."""
    mesh = auto_axes(mesh)
    axes = tuple(a for a in data_axes if a in mesh.axis_names)
    return jax.tree.map(lambda _: NamedSharding(mesh, P(axes)), batch)


def _grads_and_metrics(params, batch, cfg, opts, remat, accum_steps):
    def lf(p, b):
        return loss_fn(p, cfg, b, opts, remat=remat)

    if accum_steps <= 1:
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params, batch)
        return grads, loss, metrics

    B = batch["tokens"].shape[0]
    assert B % accum_steps == 0
    micro = jax.tree.map(
        lambda x: x.reshape((accum_steps, B // accum_steps) + x.shape[1:]), batch)

    def body(carry, mb):
        acc, loss_acc = carry
        (loss, _metrics), grads = jax.value_and_grad(lf, has_aux=True)(params, mb)
        acc = jax.tree.map(jnp.add, acc, grads)
        return (acc, loss_acc + loss), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gsum, loss_sum), _ = jax.lax.scan(body, (zero, jnp.zeros((), jnp.float32)), micro)
    grads = jax.tree.map(lambda g: g / accum_steps, gsum)
    loss = loss_sum / accum_steps
    return grads, loss, {"ce_loss": loss, "aux_loss": jnp.zeros(()), "tokens": jnp.zeros(())}


def make_train_step(cfg: ArchConfig, tcfg: TrainConfig = TrainConfig(),
                    opts: ModelOptions = ModelOptions(),
                    mesh: Optional[Mesh] = None,
                    act_rules: Optional[dict] = None):
    """Returns step(state, batch) -> (state, metrics)."""
    ocfg = tcfg.optimizer
    if mesh is not None:
        mesh = auto_axes(mesh)

    def apply_update(state, grads, loss, metrics):
        grads, gnorm = clip_by_global_norm(grads, ocfg.clip_norm)
        new_params, new_opt = adamw_update(ocfg, state["params"], grads,
                                           state["opt"], state["step"])
        new_state = dict(state)
        new_state.update(params=new_params, opt=new_opt, step=state["step"] + 1)
        out_metrics = {"loss": loss, "grad_norm": gnorm, **metrics}
        return new_state, out_metrics

    if not tcfg.compress_pod_grads:
        def step(state, batch):
            ctx = use_rules(mesh, act_rules) if (mesh is not None and act_rules) else None
            if ctx is not None:
                with ctx:
                    grads, loss, metrics = _grads_and_metrics(
                        state["params"], batch, cfg, opts, tcfg.remat, tcfg.accum_steps)
            else:
                grads, loss, metrics = _grads_and_metrics(
                    state["params"], batch, cfg, opts, tcfg.remat, tcfg.accum_steps)
            return apply_update(state, grads, loss, metrics)

        return step

    # --- compressed cross-pod variant -------------------------------------
    # Pure-pjit formulation (partial-manual shard_map lowering is fragile):
    # gradients are computed per pod-group via vmap over a pod-sharded
    # leading dim; EF + int8 quantization are elementwise on that dim (stay
    # pod-local); only the final dequant-mean crosses pods — and its
    # all-gather operand is the int8 tensor, which is the wire saving.
    assert mesh is not None and "pod" in mesh.axis_names
    npods = mesh.shape["pod"]
    inner_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
    _prules = {k: v for k, v in PARAM_RULES.items()
               if (v in mesh.axis_names if isinstance(v, str) else True)}

    def step(state, batch):
        params = state["params"]
        micro = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x.reshape((npods, x.shape[0] // npods) + x.shape[1:]),
                NamedSharding(mesh, P("pod", inner_axes))),
            batch)

        def lf(p, b):
            return loss_fn(p, cfg, b, opts, remat=tcfg.remat)

        def gfn(b):
            (loss, _metrics), g = jax.value_and_grad(lf, has_aux=True)(params, b)
            return g, loss

        grads_g, losses = jax.vmap(gfn)(micro)  # (npods, ...) pod-sharded
        # pin grads_g to pod+param sharding (mirrors the EF buffers)
        from ..sharding.specs import fit_spec, logical_to_spec, param_logical_axes
        from .compress import ef_quantize_mean

        logical = param_logical_axes(params)
        prules = _prules

        def pin(leaf, ax):
            spec = fit_spec(logical_to_spec(ax, prules), leaf.shape[1:], mesh)
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, P(*(("pod",) + tuple(spec)))))

        grads_g = jax.tree.map(
            pin, grads_g, logical,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))
        mean_grads, new_ef = ef_quantize_mean(grads_g, state["ef"])
        loss = jnp.mean(losses)
        metrics = {"ce_loss": loss, "aux_loss": jnp.zeros(()),
                   "tokens": jnp.zeros(())}
        new_state, out_metrics = apply_update(state, mean_grads, loss, metrics)
        new_state["ef"] = new_ef
        return new_state, out_metrics

    return step
