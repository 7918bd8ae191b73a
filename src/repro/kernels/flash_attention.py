"""Causal GQA flash attention (forward) — Pallas TPU kernel.

TPU-native design (not a CUDA port): the KV axis is the innermost
*sequential* grid dimension; the online-softmax state (m, l, acc) lives in
VMEM scratch that persists across KV grid steps; blocks are MXU-shaped
((block_q, head_dim) x (head_dim, block_k) matmuls with 128-aligned tiles);
fully-masked causal blocks are skipped with ``pl.when`` (grid-step cost
only, no MXU work).  GQA is handled by block-indexing the compact KV array
with ``h // group`` — no KV expansion in memory.

Layouts: q (B, S, H, D); k, v (B, S, KV, D); out (B, S, H, D).
Grid: (B, H, S/block_q, S/block_k), KV innermost (sequential accumulate).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, num_k: int,
                  causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the diagonal
    needed = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ik == num_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        out = acc_ref[...] / l[:, None]
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_ref[:, 0] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "interpret", "return_lse"))
def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    causal: bool = True, interpret: bool = False,
                    return_lse: bool = False):
    """q (B,S,H,D); k,v (B,S,KV,D) -> (B,S,H,D) [, lse (B,S,H) f32]."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, num_k=nk, causal=causal)
    grid = (B, H, nq, nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, D), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, iq, ik: (b, ik, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, iq, ik: (b, ik, h // G, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1, D), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, h, iq, ik: (b, iq, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
            jax.ShapeDtypeStruct((B, S, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    if return_lse:
        return out, lse
    return out


# ------------------------------------------------------------- backward


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, scale: float, block_q: int,
                         block_k: int, num_k: int, causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    @pl.when(ik == num_k - 1)
    def _finalize():
        dq_ref[0, :, 0, :] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                          block_q: int, block_k: int, num_q: int, causal: bool):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = (not causal) or (iq * block_q + block_q - 1 >= ik * block_k)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(iq == num_q - 1)
    def _finalize():
        dk_ref[0, :, 0, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, :, 0, :] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "interpret"))
def flash_attention_bwd(q, k, v, out, lse, do, *, block_q: int = 128,
                        block_k: int = 128, causal: bool = True,
                        interpret: bool = False):
    """Backward kernels.  Returns (dq (B,S,H,D), dk, dv (B,S,KV,D)).

    GQA: per-q-head dK/dV partials are produced by the kernel and group-
    summed outside (keeps the kernel free of cross-head accumulation).
    ``delta`` = rowsum(dO ∘ O) is precomputed (the standard two-pass split).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(D)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # (B,S,H)

    qspec = pl.BlockSpec((1, block_q, 1, D), lambda b, h, iq, ik: (b, iq, h, 0))
    kspec = pl.BlockSpec((1, block_k, 1, D), lambda b, h, iq, ik: (b, ik, h // G, 0))
    rowspec = pl.BlockSpec((1, block_q, 1), lambda b, h, iq, ik: (b, iq, h))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, num_k=nk, causal=causal),
        grid=(B, H, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dkv grid: kv blocks outer, q blocks inner (sequential accumulate)
    qspec2 = pl.BlockSpec((1, block_q, 1, D), lambda b, h, ik, iq: (b, iq, h, 0))
    kspec2 = pl.BlockSpec((1, block_k, 1, D), lambda b, h, ik, iq: (b, ik, h // G, 0))
    outk2 = pl.BlockSpec((1, block_k, 1, D), lambda b, h, ik, iq: (b, ik, h, 0))
    rowspec2 = pl.BlockSpec((1, block_q, 1), lambda b, h, ik, iq: (b, iq, h))
    dk_ph, dv_ph = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, num_q=nq, causal=causal),
        grid=(B, H, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[outk2, outk2],
        out_shape=[jax.ShapeDtypeStruct((B, S, H, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk = dk_ph.reshape(B, S, KV, G, D).sum(axis=3).astype(k.dtype)
    dv = dv_ph.reshape(B, S, KV, G, D).sum(axis=3).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_train(q, k, v, block_q=128, block_k=128, causal=True,
                          interpret=False):
    """Differentiable flash attention (fwd + bwd Pallas kernels)."""
    return flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                           causal=causal, interpret=interpret)


def _fa_fwd(q, k, v, block_q, block_k, causal, interpret):
    out, lse = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                               causal=causal, interpret=interpret,
                               return_lse=True)
    return out, (q, k, v, out, lse)


def _fa_bwd(block_q, block_k, causal, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, do, block_q=block_q,
                                     block_k=block_k, causal=causal,
                                     interpret=interpret)
    return dq, dk, dv


flash_attention_train.defvjp(_fa_fwd, _fa_bwd)
