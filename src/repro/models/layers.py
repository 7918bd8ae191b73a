"""Shared model layers: norms, RoPE, blockwise attention, MLPs.

Conventions
-----------
- Parameters are plain nested dicts of ``jnp.ndarray`` (fp32 master copies).
- Compute is bf16 with fp32 accumulation (``preferred_element_type``).
- Attention is *blockwise* (online-softmax over KV chunks) so the XLA path
  never materializes an S×S score matrix — the same memory shape the Pallas
  flash kernel targets on TPU.  ``repro.kernels`` provides the TPU kernels;
  these functions are the reference/XLA path used by the CPU dry-run.
- Head layout: flattened H everywhere in full-sequence attention (GQA KV
  heads are pre-expanded by the caller, kv head j -> q heads j*G..j*G+G-1);
  decode keeps the compact (KV, G) grouping since the cache stays compact.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..sharding.ctx import shard

DEFAULT_COMPUTE_DTYPE = jnp.bfloat16
NEG_INF = -1e30


# --------------------------------------------------------------------- norms


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def init_rmsnorm(d: int) -> dict:
    # Stored as deltas from 1.0 (gemma convention); init 0 == unit scale.
    return {"scale": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------- rope


def rope_table(positions: jax.Array, head_dim: int, theta: float):
    """positions (...,) int32 -> (sin, cos) each (..., head_dim/2) float32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x (..., H, D); sin/cos (..., D/2) — broadcast over the head axis."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[..., None, :].astype(jnp.float32)
    cos = cos[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention


def _online_update(m, l, acc, scores, v_blk):
    """One online-softmax accumulation step.

    scores: (..., q, k) f32 (already masked); v_blk: (..., k, D) with batch
    dims broadcastable against the score batch dims.
    m, l: (..., q) f32; acc: (..., q, D) f32.
    """
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("...qk,...kd->...qd", p, v_blk.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def blockwise_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    causal: bool = True,
    skip_masked_blocks: bool = False,
) -> jax.Array:
    """Online-softmax blockwise attention.  q (B,S,H,D); k,v (B,S,H,D)
    (GQA KV heads pre-expanded to H by the caller — flattened head layout
    shards cleanly over the tensor axis, unlike a (KV, G) factorization).

    The baseline computes every (q, kv) block pair and masks — the paper-
    faithful naive data plane (2x causal FLOP waste).
    ``skip_masked_blocks=True`` switches to ``tree_causal_attention`` which
    performs only the causal work (beyond-paper optimization, §Perf).
    """
    if skip_masked_blocks and causal:
        return tree_causal_attention(q, k, v, chunk=q_chunk)
    B, S, H, D = q.shape
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    assert S % q_chunk == 0 and S % kv_chunk == 0, (S, q_chunk, kv_chunk)
    nq, nk = S // q_chunk, S // kv_chunk
    scale = 1.0 / math.sqrt(D)

    # Pin batch/head sharding on scanned operands and carries: without these
    # the scan-cotangent accumulation in backward loses the batch sharding
    # and XLA all-gathers K/V to the *global* batch inside the loop
    # (measured: 62% of all collective bytes on qwen3 train_4k).
    blk_ax = (None, "batch", "heads", None, None)
    qs = shard(q.reshape(B, nq, q_chunk, H, D).transpose(1, 0, 3, 2, 4), blk_ax)
    ks = shard(k.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 3, 2, 4), blk_ax)
    vs = shard(v.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 3, 2, 4), blk_ax)
    q_starts = jnp.arange(nq) * q_chunk
    k_starts = jnp.arange(nk) * kv_chunk
    carry_ax = ("batch", "heads", None)

    def q_body(_, xq):
        q_blk, q0 = xq  # (B,H,qc,D), scalar
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, D), jnp.float32)

        # checkpoint the chunk body: backward recomputes scores from
        # (q_blk, k_blk) instead of stashing exp-scores for every chunk —
        # the flash-attention memory trade, applied to the XLA path
        @jax.checkpoint
        def kv_body(carry, xk):
            m, l, acc = carry
            k_blk, v_blk, k0 = xk
            k_blk = shard(k_blk, ("batch", "heads", None, None))
            v_blk = shard(v_blk, ("batch", "heads", None, None))
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                qp = q0 + jnp.arange(q_chunk)
                kp = k0 + jnp.arange(kv_chunk)
                mask = qp[:, None] >= kp[None, :]
                s = jnp.where(mask, s, NEG_INF)
            m, l, acc = _online_update(m, l, acc, s, v_blk)
            m = shard(m, carry_ax)
            l = shard(l, carry_ax)
            acc = shard(acc, carry_ax + (None,))
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), (ks, vs, k_starts))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_body, None, (qs, q_starts))
    # outs: (nq,B,H,qc,D) -> (B,S,H,D)
    return outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, D)


def tree_causal_attention(q, k, v, *, chunk: int = 512) -> jax.Array:
    """Binary-tree causal decomposition: exactly the causal FLOPs.

    Causal attention over S decomposes into masked diagonal blocks of size
    ``chunk`` plus log2(S/chunk) levels of *unmasked* block-dense cross
    attention (the top half of every span attends the bottom half).  Score
    FLOPs = S*chunk + S^2/2 vs ~S^2 for masked-blockwise — the beyond-paper
    compute-term optimization recorded in EXPERIMENTS.md §Perf.  Partial
    (m, l, acc) statistics from all levels merge via online softmax: exact.
    """
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    c = min(chunk, S)
    assert S % c == 0
    nc = S // c

    ax = ("batch", None, None, "heads", None)
    qs = shard(q.reshape(B, nc, c, H, D), ax)
    ks = shard(k.reshape(B, nc, c, H, D), ax)
    vs = shard(v.reshape(B, nc, c, H, D), ax)

    # --- diagonal blocks (masked causal within each chunk)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qs, ks,
                   preferred_element_type=jnp.float32) * scale
    dmask = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    s = jnp.where(dmask, s, NEG_INF)
    m = jnp.full((B, nc, H, c), NEG_INF, jnp.float32)
    l = jnp.zeros((B, nc, H, c), jnp.float32)
    acc = jnp.zeros((B, nc, H, c, D), jnp.float32)
    v_diag = vs.transpose(0, 1, 3, 2, 4)  # (B,nc,H,c,D)
    m, l, acc = _online_update(m, l, acc, s, v_diag)

    # --- tree levels: unmasked cross attention, top half -> bottom half
    span = 2
    while span <= nc:
        nspans = nc // span
        half = span // 2
        sb = half * c  # bottom keys per span
        q_top = qs.reshape(B, nspans, span, c, H, D)[:, :, half:]
        k_bot = ks.reshape(B, nspans, span, c, H, D)[:, :, :half].reshape(B, nspans, sb, H, D)
        v_bot = vs.reshape(B, nspans, span, c, H, D)[:, :, :half].reshape(B, nspans, sb, H, D)
        s = jnp.einsum("bntqhd,bnkhd->bnthqk", q_top, k_bot,
                       preferred_element_type=jnp.float32) * scale  # (B,ns,half,H,c,sb)
        m_s = m.reshape(B, nspans, span, H, c)
        l_s = l.reshape(B, nspans, span, H, c)
        a_s = acc.reshape(B, nspans, span, H, c, D)
        v_b = v_bot.transpose(0, 1, 3, 2, 4)[:, :, None]  # (B,ns,1,H,sb,D)
        m_top, l_top, a_top = _online_update(
            m_s[:, :, half:], l_s[:, :, half:], a_s[:, :, half:], s, v_b)
        m = jnp.concatenate([m_s[:, :, :half], m_top], axis=2).reshape(B, nc, H, c)
        l = jnp.concatenate([l_s[:, :, :half], l_top], axis=2).reshape(B, nc, H, c)
        acc = jnp.concatenate([a_s[:, :, :half], a_top], axis=2).reshape(B, nc, H, c, D)
        span *= 2

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 1, 3, 2, 4).reshape(B, S, H, D).astype(q.dtype)


def local_band_attention(q, k, v, *, window: int) -> jax.Array:
    """Sliding-window causal attention with O(S*window) compute.

    q,k,v (B,S,H,D) (KV pre-expanded).  Chunk size == window: each query
    chunk attends its own chunk (causal mask) plus the previous chunk (band
    mask) — the standard band decomposition for Griffin/Mistral local attn.
    """
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    c = min(window, S)
    assert S % c == 0, (S, c)
    nc = S // c

    ax = ("batch", None, None, "heads", None)
    qs = shard(q.reshape(B, nc, c, H, D), ax)
    ks = shard(k.reshape(B, nc, c, H, D), ax)
    vs = shard(v.reshape(B, nc, c, H, D), ax)
    kcat = jnp.concatenate([jnp.roll(ks, 1, axis=1), ks], axis=2)  # (B,nc,2c,H,D)
    vcat = jnp.concatenate([jnp.roll(vs, 1, axis=1), vs], axis=2)

    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qs, kcat,
                   preferred_element_type=jnp.float32) * scale  # (B,nc,H,c,2c)
    a = jnp.arange(c)
    b = jnp.arange(2 * c)
    rel = (a[:, None] + c) - b[None, :]  # qpos - kpos in the 2c concat frame
    base = (rel >= 0) & (rel < window)  # (c, 2c)
    mask = jnp.broadcast_to(base[None], (nc, c, 2 * c))
    first = jnp.broadcast_to((b >= c)[None, None, :], (1, c, 2 * c))
    mask = jnp.where((jnp.arange(nc) == 0)[:, None, None], mask & first, mask)
    s = jnp.where(mask[None, :, None, :, :], s, NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - mx)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bnhqk,bnkhd->bnhqd", p, vcat.astype(jnp.float32),
                     preferred_element_type=jnp.float32)  # (B,nc,H,c,D)
    return out.transpose(0, 1, 3, 2, 4).reshape(B, S, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0) -> jax.Array:
    """Single-token attention against a cache.

    q (B,H,D); caches (B,Smax,KV,D); lengths (B,) = #valid positions.
    ``window`` > 0 marks a ring-buffer cache (local attention): all Smax
    slots are valid once the ring has wrapped.
    """
    B, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    Smax = k_cache.shape[1]
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(Smax)
    if window:
        # ring buffer: slot p holds a token iff p < length (not yet wrapped)
        # or always (wrapped).  lengths counts total tokens written.
        valid = (pos[None, :] < lengths[:, None]) | (lengths[:, None] >= Smax)
    else:
        valid = pos[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - mx)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(q.dtype)


# ----------------------------------------------------------------------- mlp


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[name]


def mlp_apply(params: dict, x: jax.Array, act: str, gated: bool) -> jax.Array:
    dt = x.dtype
    if gated:
        g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(dt),
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dt),
                       preferred_element_type=jnp.float32)
        h = (act_fn(act)(g) * u).astype(dt)
    else:
        u = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dt),
                       preferred_element_type=jnp.float32)
        h = act_fn(act)(u).astype(dt)
    # bf16 output: halves the TP all-reduce wire bytes (see lm.py note)
    return jnp.einsum("...f,fd->...d", h, params["w_down"].astype(dt),
                      preferred_element_type=dt).astype(dt)


def init_mlp(key, d: int, d_ff: int, gated: bool, out_scale: float = 1.0) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(k1, (d, d_ff)),
        "w_down": dense_init(k2, (d_ff, d), scale=out_scale),
    }
    if gated:
        p["w_gate"] = dense_init(k3, (d, d_ff))
    return p


def dense_init(key, shape, scale: float = 1.0, fan_in: int = 0) -> jax.Array:
    """Normal(0, scale^2 / fan_in); ``fan_in`` defaults to ``shape[-2]``,
    the contracted dim of a matrix or a stack of matrices."""
    fan_in = fan_in or max(shape[-2] if len(shape) >= 2 else 1, 1)
    std = scale / math.sqrt(fan_in)
    return jax.random.normal(key, shape, jnp.float32) * std
