"""Unified decoder LM covering all 10 assigned architectures.

The layer stack is segmented for ``lax.scan``: a (possibly empty) unrolled
prefix (e.g. deepseek-moe's dense first layer), a scanned main body of
repeating pattern groups (e.g. Griffin's (rglru, rglru, local)), and an
unrolled tail for remainder layers.  Parameters for the main body are
stacked with a leading group dimension so the whole model compiles to one
program per distinct layer shape — essential to keep dry-run compile times
sane at 48 layers and to bound HLO size at scale.

Entry points:
- ``init_params``      — real parameter construction (smoke tests);
- ``forward``          — full-sequence logits (+ MoE aux loss): train/prefill;
- ``init_cache``       — decode cache/state pytree (abstract or concrete);
- ``decode_step``      — one-token serving step.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..sharding.ctx import shard
from . import recurrent as rec
from .layers import (
    apply_rope,
    blockwise_causal_attention,
    decode_attention,
    dense_init,
    init_mlp,
    init_rmsnorm,
    local_band_attention,
    mlp_apply,
    rmsnorm,
    rope_table,
)
from .moe import init_moe, moe_apply


@dataclass(frozen=True)
class ModelOptions:
    """Implementation knobs that do not change semantics (perf levers)."""

    q_chunk: int = 512
    kv_chunk: int = 1024
    tree_attention: bool = False  # binary-tree causal decomposition (§Perf)
    mlstm_chunk: int = 128
    compute_dtype: str = "bfloat16"
    moe_impl: Optional[str] = None  # override MoECfg.impl
    attn_recurrence: Optional[object] = None  # Pallas hooks (TPU path)
    mlstm_recurrence: Optional[object] = None

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)


# ----------------------------------------------------------- stack segmenting


@dataclass(frozen=True)
class LayerSpec:
    kind: str  # attn | local | rglru | mlstm | slstm
    use_moe: bool
    d_ff: int  # MLP width for this layer (0 = no MLP sub-block)


def layer_specs(cfg: ArchConfig) -> list:
    specs = []
    for i, kind in enumerate(cfg.layer_kinds):
        use_moe = cfg.moe is not None and i >= cfg.first_dense and kind in ("attn", "local")
        if use_moe:
            ff = 0
        elif cfg.moe is not None and i < cfg.first_dense:
            ff = cfg.first_dense_ff or cfg.d_ff
        elif kind in ("mlstm", "slstm"):
            ff = 0
        else:
            ff = cfg.d_ff
        specs.append(LayerSpec(kind, use_moe, ff))
    return specs


@dataclass(frozen=True)
class StackPlan:
    prefix: tuple  # tuple[LayerSpec]
    pattern: tuple  # tuple[LayerSpec] — one period
    num_groups: int
    tail: tuple  # tuple[LayerSpec]


def stack_plan(cfg: ArchConfig) -> StackPlan:
    specs = layer_specs(cfg)
    p = len(cfg.block_pattern)
    prefix = tuple(specs[: cfg.first_dense])
    rest = specs[cfg.first_dense:]
    # the rest must be periodic with period p (by construction of layer_kinds
    # when first_dense is a multiple of the pattern — enforce by assertion)
    num_groups = len(rest) // p
    pattern = tuple(rest[:p]) if num_groups else ()
    for g in range(num_groups):
        assert tuple(rest[g * p: (g + 1) * p]) == pattern, "stack not periodic"
    tail = tuple(rest[num_groups * p:])
    return StackPlan(prefix, pattern, num_groups, tail)


# ------------------------------------------------------------------- params


def _init_layer(key, cfg: ArchConfig, spec: LayerSpec) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p: dict = {"norm1": init_rmsnorm(d)}
    if spec.kind in ("attn", "local"):
        hd, H, KV = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
        # fan-in is the contracted width: d for q/k/v, H*hd for the output
        attn = {
            "wq": dense_init(ks[0], (d, H, hd), fan_in=d),
            "wk": dense_init(ks[1], (d, KV, hd), fan_in=d),
            "wv": dense_init(ks[2], (d, KV, hd), fan_in=d),
            "wo": dense_init(ks[3], (H, hd, d), scale=1.0 / max(cfg.num_layers, 1) ** 0.5,
                             fan_in=H * hd),
        }
        if cfg.qkv_bias:
            attn["bq"] = jnp.zeros((H, hd), jnp.float32)
            attn["bk"] = jnp.zeros((KV, hd), jnp.float32)
            attn["bv"] = jnp.zeros((KV, hd), jnp.float32)
        if cfg.qk_norm:
            attn["q_norm"] = init_rmsnorm(hd)
            attn["k_norm"] = init_rmsnorm(hd)
        p["attn"] = attn
    elif spec.kind == "rglru":
        p["rglru"] = rec.init_rglru(ks[0], d, cfg.d_rnn or d, cfg.conv_width)
    elif spec.kind == "mlstm":
        p["mlstm"] = rec.init_mlstm(ks[0], d, cfg.num_heads, cfg.conv_width)
    elif spec.kind == "slstm":
        p["slstm"] = rec.init_slstm(ks[0], d, cfg.num_heads)
    else:
        raise ValueError(spec.kind)
    if spec.use_moe:
        p["norm2"] = init_rmsnorm(d)
        p["moe"] = init_moe(ks[4], d, cfg.moe)
    elif spec.d_ff > 0:
        p["norm2"] = init_rmsnorm(d)
        p["mlp"] = init_mlp(ks[4], d, spec.d_ff, cfg.gated_mlp,
                            out_scale=1.0 / max(cfg.num_layers, 1) ** 0.5)
    return p


def init_params(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    """Random parameters from ``key``, stored in ``dtype``.  The main
    groups are built stacked (``vmap`` over group keys), so no per-group
    copy is ever stacked; ``init_serving_params`` runs this under ``jit``."""
    plan = stack_plan(cfg)
    ks = jax.random.split(key, 8)
    params: dict = {
        "embed": {"table": dense_init(ks[0], (cfg.padded_vocab, cfg.d_model)) * cfg.d_model ** 0.5},
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": dense_init(ks[1], (cfg.d_model, cfg.padded_vocab))}
    if cfg.frontend:
        params["frontend"] = {"w": dense_init(ks[2], (cfg.frontend_dim, cfg.d_model))}
    params["prefix"] = [
        _init_layer(k, cfg, s)
        for k, s in zip(jax.random.split(ks[3], max(len(plan.prefix), 1)), plan.prefix)
    ]
    if plan.num_groups:
        def init_group(k):
            kk = jax.random.split(k, len(plan.pattern))
            return [_init_layer(kk[i], cfg, s) for i, s in enumerate(plan.pattern)]

        group_keys = jax.random.split(ks[4], plan.num_groups)
        params["main"] = jax.vmap(init_group)(group_keys)
    else:
        params["main"] = []
    params["tail"] = [
        _init_layer(k, cfg, s)
        for k, s in zip(jax.random.split(ks[5], max(len(plan.tail), 1)), plan.tail)
    ]
    return jax.tree.map(lambda x: x.astype(dtype), params)


def init_serving_params(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    """``init_params`` compiled as one program that writes ``dtype``
    weights: no float32 copy of the model is ever held on the device."""
    return jax.jit(init_params, static_argnums=(1, 2))(key, cfg, dtype)


def abstract_params(cfg: ArchConfig, dtype=jnp.float32) -> dict:
    """Parameter ShapeDtypeStructs without allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg, dtype))


# ------------------------------------------------------------------ forward


def _attention_block(aparams, cfg: ArchConfig, x, sin, cos, kind: str,
                     opts: ModelOptions, return_kv: bool = False):
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, aparams["wq"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    k = jnp.einsum("bsd,dhe->bshe", x, aparams["wk"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("bsd,dhe->bshe", x, aparams["wv"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    if "bq" in aparams:
        q = q + aparams["bq"].astype(dt)
        k = k + aparams["bk"].astype(dt)
        v = v + aparams["bv"].astype(dt)
    if "q_norm" in aparams:
        q = rmsnorm(q, aparams["q_norm"]["scale"], cfg.norm_eps)
        k = rmsnorm(k, aparams["k_norm"]["scale"], cfg.norm_eps)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    # seq stays unsharded inside attention (under SP the residual stream is
    # seq-sharded; attention needs the full sequence per shard)
    q = shard(q, ("batch", None, "heads", None))
    kv_compact = (k, v)
    if cfg.q_groups > 1:
        # expand KV heads to the flattened H layout (kv head j -> query heads
        # j*G..j*G+G-1); flattened heads shard cleanly over the tensor axis
        k = jnp.repeat(k, cfg.q_groups, axis=2)
        v = jnp.repeat(v, cfg.q_groups, axis=2)
    k = shard(k, ("batch", None, "heads", None))
    v = shard(v, ("batch", None, "heads", None))
    if kind == "local":
        out = local_band_attention(q, k, v, window=cfg.window)
    else:
        out = blockwise_causal_attention(
            q, k, v, q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk,
            skip_masked_blocks=opts.tree_attention)
    out = shard(out, ("batch", None, "heads", None))
    # output in compute dtype: the TP partial-sum all-reduce rides on this
    # tensor, and bf16 wire bytes are half of f32 (per-shard accumulation
    # stays f32 inside the MXU)
    proj = jnp.einsum("bshe,hed->bsd", out, aparams["wo"].astype(dt),
                      preferred_element_type=dt).astype(dt)
    if return_kv:
        return proj, kv_compact  # un-expanded (B,S,KV,hd) for the cache
    return proj


def _pack_kv_cache(k, v, kind: str, cfg: ArchConfig, max_len: int):
    """Pack full-sequence K/V into the decode cache layout.

    Global attention: zero-padded (B, max_len, KV, hd) buffer.
    Local attention: ring buffer of size window, slot = position % window.
    """
    B, S = k.shape[:2]
    if kind == "local":
        w = min(cfg.window, max_len)
        n = min(S, w)
        pos = S - n + jnp.arange(n)
        slots = pos % w
        buf_k = jnp.zeros((B, w) + k.shape[2:], k.dtype).at[:, slots].set(k[:, S - n:])
        buf_v = jnp.zeros((B, w) + v.shape[2:], v.dtype).at[:, slots].set(v[:, S - n:])
        return {"k": buf_k, "v": buf_v}
    pad = max_len - S
    assert pad >= 0, (S, max_len)
    buf_k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    buf_v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": buf_k, "v": buf_v}


def _apply_layer_seq(lparams, cfg: ArchConfig, spec: LayerSpec, x, sin, cos,
                     opts: ModelOptions, want_state: bool = False,
                     max_len: int = 0):
    """One layer over a full sequence.  Returns (x, aux_loss[, state])."""
    aux = jnp.zeros((), jnp.float32)
    state = None
    h = rmsnorm(x, lparams["norm1"]["scale"], cfg.norm_eps)
    if spec.kind in ("attn", "local"):
        if want_state:
            mix, (kk, vv) = _attention_block(lparams["attn"], cfg, h, sin, cos,
                                             spec.kind, opts, return_kv=True)
            state = _pack_kv_cache(kk, vv, spec.kind, cfg, max_len)
        else:
            mix = _attention_block(lparams["attn"], cfg, h, sin, cos, spec.kind, opts)
    elif spec.kind == "rglru":
        r = rec.rglru_seq(lparams["rglru"], h, return_state=want_state)
        mix, state = r if want_state else (r, None)
    elif spec.kind == "mlstm":
        r = rec.mlstm_seq(lparams["mlstm"], h, cfg.num_heads,
                          chunk=opts.mlstm_chunk,
                          recurrence=opts.mlstm_recurrence,
                          return_state=want_state)
        mix, state = r if want_state else (r, None)
    elif spec.kind == "slstm":
        r = rec.slstm_seq(lparams["slstm"], h, cfg.num_heads,
                          return_state=want_state)
        mix, state = r if want_state else (r, None)
    else:
        raise ValueError(spec.kind)
    x = x + mix
    x = shard(x, ("batch", "seq", "embed"))
    if spec.use_moe:
        h2 = rmsnorm(x, lparams["norm2"]["scale"], cfg.norm_eps)
        m = cfg.moe if opts.moe_impl is None else cfg.moe.__class__(
            **{**cfg.moe.__dict__, "impl": opts.moe_impl})
        out, aux = moe_apply(lparams["moe"], h2, m, cfg.act)
        x = x + out
    elif spec.d_ff > 0:
        h2 = rmsnorm(x, lparams["norm2"]["scale"], cfg.norm_eps)
        x = x + mlp_apply(lparams["mlp"], h2, cfg.act, cfg.gated_mlp)
    x = shard(x, ("batch", "seq", "embed"))
    if want_state:
        return x, aux, state
    return x, aux


def embed_inputs(params, cfg: ArchConfig, tokens, frontend_embeds, dtype):
    """tokens (B,S_tok) int32; frontend_embeds (B,F,frontend_dim) or None."""
    table = params["embed"]["table"]
    x = table.astype(dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if cfg.frontend:
        fe = jnp.einsum("bfe,ed->bfd", frontend_embeds.astype(dtype),
                        params["frontend"]["w"].astype(dtype))
        x = jnp.concatenate([fe, x], axis=1)
    return x


def forward(params, cfg: ArchConfig, tokens, frontend_embeds=None,
            opts: ModelOptions = ModelOptions(), remat: bool = False):
    """Full-sequence forward.  Returns (logits (B,S,V) f32, aux_loss)."""
    plan = stack_plan(cfg)
    dt = opts.dtype
    x = embed_inputs(params, cfg, tokens, frontend_embeds, dt)
    x = shard(x, ("batch", "seq", "embed"))
    B, S = x.shape[:2]
    positions = jnp.arange(S)[None, :]
    sin, cos = rope_table(positions, cfg.head_dim, cfg.rope_theta)
    aux_total = jnp.zeros((), jnp.float32)

    def run_layer(lp, spec, x):
        return _apply_layer_seq(lp, cfg, spec, x, sin, cos, opts)

    for lp, spec in zip(params["prefix"], plan.prefix):
        x, aux = run_layer(lp, spec, x)
        aux_total = aux_total + aux

    if plan.num_groups:
        def group_body(carry, group_params):
            x, aux_total = carry
            for i, spec in enumerate(plan.pattern):
                x, aux = run_layer(group_params[i], spec, x)
                aux_total = aux_total + aux
            return (x, aux_total), None

        body = group_body
        if remat:
            body = jax.checkpoint(
                group_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["main"])

    for lp, spec in zip(params["tail"], plan.tail):
        x, aux = run_layer(lp, spec, x)
        aux_total = aux_total + aux

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["head"]["w"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt),
                        preferred_element_type=jnp.float32)
    logits = shard(logits, ("batch", None, "vocab"))  # vocab wins under SP
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    logits = _mask_padded_vocab(logits, cfg)
    return logits, aux_total


def _mask_padded_vocab(logits, cfg: ArchConfig):
    """Padded vocab columns are masked to -inf: function-preserving padding."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    col = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(col, logits, -1e30)


def forward_with_cache(params, cfg: ArchConfig, tokens, frontend_embeds=None,
                       max_len: int = 0, opts: ModelOptions = ModelOptions()):
    """Prefill: full-sequence forward that also builds the decode cache.

    Returns (logits (B,S,V) f32, cache) with cache['len'] set to the full
    sequence length (frontend prefix included).
    """
    plan = stack_plan(cfg)
    dt = opts.dtype
    x = embed_inputs(params, cfg, tokens, frontend_embeds, dt)
    x = shard(x, ("batch", "seq", "embed"))
    B, S = x.shape[:2]
    max_len = max(max_len, S)
    positions = jnp.arange(S)[None, :]
    sin, cos = rope_table(positions, cfg.head_dim, cfg.rope_theta)
    aux_total = jnp.zeros((), jnp.float32)
    cache = {"prefix": [], "tail": [], "main": [],
             "len": jnp.full((B,), S, jnp.int32)}

    for lp, spec in zip(params["prefix"], plan.prefix):
        x, aux, st = _apply_layer_seq(lp, cfg, spec, x, sin, cos, opts,
                                      want_state=True, max_len=max_len)
        aux_total = aux_total + aux
        cache["prefix"].append(st)

    if plan.num_groups:
        def group_body(carry, group_params):
            x, aux_total = carry
            states = []
            for i, spec in enumerate(plan.pattern):
                x, aux, st = _apply_layer_seq(group_params[i], cfg, spec, x,
                                              sin, cos, opts, want_state=True,
                                              max_len=max_len)
                aux_total = aux_total + aux
                states.append(st)
            return (x, aux_total), states

        (x, aux_total), main_states = jax.lax.scan(group_body, (x, aux_total),
                                                   params["main"])
        cache["main"] = main_states

    for lp, spec in zip(params["tail"], plan.tail):
        x, aux, st = _apply_layer_seq(lp, cfg, spec, x, sin, cos, opts,
                                      want_state=True, max_len=max_len)
        aux_total = aux_total + aux
        cache["tail"].append(st)

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["head"]["w"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt),
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return _mask_padded_vocab(logits, cfg), cache


# -------------------------------------------------------------------- decode


def _init_layer_state(cfg: ArchConfig, spec: LayerSpec, batch: int,
                      max_len: int, dtype):
    if spec.kind == "attn":
        return {
            "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        }
    if spec.kind == "local":
        w = min(cfg.window, max_len)
        return {
            "k": jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dtype),
        }
    if spec.kind == "rglru":
        return rec.rglru_init_state(batch, cfg.d_rnn or cfg.d_model, cfg.conv_width, dtype)
    if spec.kind == "mlstm":
        return rec.mlstm_init_state(batch, cfg.d_model, cfg.num_heads, cfg.conv_width, dtype)
    if spec.kind == "slstm":
        return rec.slstm_init_state(batch, cfg.d_model)
    raise ValueError(spec.kind)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    plan = stack_plan(cfg)
    cache = {
        "prefix": [_init_layer_state(cfg, s, batch, max_len, dtype) for s in plan.prefix],
        "tail": [_init_layer_state(cfg, s, batch, max_len, dtype) for s in plan.tail],
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if plan.num_groups:
        one = [_init_layer_state(cfg, s, batch, max_len, dtype) for s in plan.pattern]
        cache["main"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (plan.num_groups,) + x.shape).copy(), one)
    else:
        cache["main"] = []
    return cache


def _decode_layer(lparams, cfg: ArchConfig, spec: LayerSpec, state, x, sin, cos,
                  lengths, opts: ModelOptions):
    """One layer, one token.  x (B,d).  Returns (x, new_state)."""
    dt = x.dtype
    h = rmsnorm(x, lparams["norm1"]["scale"], cfg.norm_eps)
    if spec.kind in ("attn", "local"):
        ap = lparams["attn"]
        q = jnp.einsum("bd,dhe->bhe", h, ap["wq"].astype(dt))
        k = jnp.einsum("bd,dhe->bhe", h, ap["wk"].astype(dt))
        v = jnp.einsum("bd,dhe->bhe", h, ap["wv"].astype(dt))
        if "bq" in ap:
            q, k, v = q + ap["bq"].astype(dt), k + ap["bk"].astype(dt), v + ap["bv"].astype(dt)
        if "q_norm" in ap:
            q = rmsnorm(q, ap["q_norm"]["scale"], cfg.norm_eps)
            k = rmsnorm(k, ap["k_norm"]["scale"], cfg.norm_eps)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        Smax = state["k"].shape[1]
        # local: ring buffer; global: clamp (dry-run decodes the final slot)
        slot = lengths % Smax if spec.kind == "local" else jnp.minimum(lengths, Smax - 1)
        bidx = jnp.arange(x.shape[0])
        new_k = state["k"].at[bidx, slot].set(k)
        new_v = state["v"].at[bidx, slot].set(v)
        window = cfg.window if spec.kind == "local" else 0
        out = decode_attention(q, new_k, new_v, lengths + 1, window=window)
        mix = jnp.einsum("bhe,hed->bd", out, ap["wo"].astype(dt))
        new_state = {"k": new_k, "v": new_v}
    elif spec.kind == "rglru":
        mix, new_state = rec.rglru_step(lparams["rglru"], h, state)
    elif spec.kind == "mlstm":
        mix, new_state = rec.mlstm_step(lparams["mlstm"], h, state, cfg.num_heads)
    elif spec.kind == "slstm":
        mix, new_state = rec.slstm_step(lparams["slstm"], h, state, cfg.num_heads)
    else:
        raise ValueError(spec.kind)
    x = x + mix
    if spec.use_moe:
        h2 = rmsnorm(x, lparams["norm2"]["scale"], cfg.norm_eps)
        out, _ = moe_apply(lparams["moe"], h2[:, None, :], cfg.moe, cfg.act)
        x = x + out[:, 0]
    elif spec.d_ff > 0:
        h2 = rmsnorm(x, lparams["norm2"]["scale"], cfg.norm_eps)
        x = x + mlp_apply(lparams["mlp"], h2, cfg.act, cfg.gated_mlp)
    return x, new_state


def decode_step(params, cfg: ArchConfig, cache, tokens,
                opts: ModelOptions = ModelOptions()):
    """One serving step: tokens (B,) int32 -> (logits (B,V) f32, new cache).

    ``cache['len']`` (B,) is the number of tokens already in context.
    """
    plan = stack_plan(cfg)
    dt = opts.dtype
    lengths = cache["len"]
    x = params["embed"]["table"].astype(dt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    x = shard(x, ("batch", "embed"))
    sin, cos = rope_table(lengths, cfg.head_dim, cfg.rope_theta)
    new_cache = {"len": lengths + 1, "prefix": [], "tail": [], "main": cache["main"]}

    for lp, spec, st in zip(params["prefix"], plan.prefix, cache["prefix"]):
        x, ns = _decode_layer(lp, cfg, spec, st, x, sin, cos, lengths, opts)
        new_cache["prefix"].append(ns)

    if plan.num_groups:
        def group_body(x, scanned):
            group_params, group_state = scanned
            new_states = []
            for i, spec in enumerate(plan.pattern):
                x, ns = _decode_layer(group_params[i], cfg, spec, group_state[i],
                                      x, sin, cos, lengths, opts)
                new_states.append(ns)
            return x, new_states

        x, new_main = jax.lax.scan(group_body, x, (params["main"], cache["main"]))
        new_cache["main"] = new_main

    for lp, spec, st in zip(params["tail"], plan.tail, cache["tail"]):
        x, ns = _decode_layer(lp, cfg, spec, st, x, sin, cos, lengths, opts)
        new_cache["tail"].append(ns)

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings else params["head"]["w"])
    logits = jnp.einsum("bd,dv->bv", x, head.astype(dt),
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return _mask_padded_vocab(logits, cfg), new_cache


# --------------------------------------------------------------------- loss


def loss_fn(params, cfg: ArchConfig, batch: dict,
            opts: ModelOptions = ModelOptions(), remat: bool = True):
    """batch: tokens (B,S), labels (B,S) (-1 = masked), optional frontend."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("frontend_embeds"), opts, remat=remat)
    labels = batch["labels"]
    if cfg.frontend:
        # frontend prefix positions carry no labels
        logits = logits[:, cfg.frontend_len:]
    mask = (labels >= 0).astype(jnp.float32)
    labels_c = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    total = loss + aux_w * aux
    return total, {"ce_loss": loss, "aux_loss": aux,
                   "tokens": jnp.sum(mask)}
