"""Pure-jnp oracles for every Pallas kernel.

Deliberately the *simplest correct* implementations (quadratic attention
with explicit masks, step-by-step sequential recurrences) — no blocking, no
online softmax — so kernel bugs cannot hide in shared structure.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def causal_attention_ref(q, k, v, *, window: int = 0):
    """q (B,S,H,D); k,v (B,S,KV,D).  Plain masked softmax attention.
    window > 0: sliding-window (local) causal attention."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    kf = jnp.repeat(k, G, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=2).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(D)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = j <= i
    if window:
        mask = mask & (j > i - window)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """q (B,H,D); caches (B,Smax,KV,D); lengths (B,)."""
    B, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    kf = jnp.repeat(k_cache, G, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v_cache, G, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kf) / math.sqrt(D)
    valid = jnp.arange(k_cache.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, vf).astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths):
    """q (B,H,D); pools (N,KV,bs,D); block_tables (B,T); lengths (B,).

    Gathers each sequence's blocks into a contiguous cache and defers to
    the dense oracle — the simplest statement of what paging must equal.
    """
    B = q.shape[0]
    _, KV, bs, D = k_pool.shape
    kc = k_pool[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, -1, KV, D)
    vc = v_pool[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, -1, KV, D)
    return decode_attention_ref(q, kc, vc, lengths)


def rglru_scan_ref(log_a, b):
    """h_t = exp(log_a_t) * h_{t-1} + b_t, sequential.  (B,S,C) f32."""

    def step(h, xs):
        la, bb = xs
        h = jnp.exp(la) * h + bb
        return h, h

    B, S, C = log_a.shape
    h0 = jnp.zeros((B, C), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (log_a.transpose(1, 0, 2), b.transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2)


def mlstm_ref(q, k, v, i_pre, f_pre):
    """Fully sequential stabilized mLSTM.  q,k,v (B,S,H,dk); gates (B,S,H).

    C_t = f C_{t-1} + i k v^T;  h_t = (q C_t) / max(|q n_t|, exp(-m_t)).
    """
    B, S, H, dk = q.shape
    scale = 1.0 / math.sqrt(dk)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))
    log_i = i_pre.astype(jnp.float32)

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, li, lf = xs  # (B,H,dk) ... (B,H)
        m_next = jnp.maximum(lf + m, li)
        f_sc = jnp.exp(lf + m - m_next)
        i_sc = jnp.exp(li - m_next)
        C = f_sc[..., None, None] * C + i_sc[..., None, None] * (
            kt[..., :, None] * vt[..., None, :])
        n = f_sc[..., None] * n + i_sc[..., None] * kt
        num = jnp.einsum("bhd,bhde->bhe", qt, C)
        den = jnp.einsum("bhd,bhd->bh", qt, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_next))[..., None]
        return (C, n, m_next), h

    C0 = jnp.zeros((B, H, dk, dk), jnp.float32)
    n0 = jnp.zeros((B, H, dk), jnp.float32)
    m0 = jnp.zeros((B, H), jnp.float32)
    xs = (qf.transpose(1, 0, 2, 3), kf.transpose(1, 0, 2, 3),
          vf.transpose(1, 0, 2, 3), log_i.transpose(1, 0, 2),
          log_f.transpose(1, 0, 2))
    _, hs = jax.lax.scan(step, (C0, n0, m0), xs)
    return hs.transpose(1, 0, 2, 3)  # (B,S,H,dk) f32


def rmsnorm_ref(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
