"""Chunkwise-parallel mLSTM — Pallas TPU kernel.

The xLSTM matrix-memory recurrence in its chunkwise form: the (dk x dk)
state C, the normalizer n, and the stabilizer m live in VMEM scratch and
are carried across the innermost sequential grid axis (chunks); within a
chunk the math is MXU-shaped (two (c x dk) matmuls plus a (c x c) masked
intra-chunk product) — quadratic only inside the chunk, linear across the
sequence.  Mirrors ``repro.models.recurrent.mlstm_chunk_recurrence``; the
oracle is the fully sequential ``ref.mlstm_ref``.

Layouts: q,k,v (BH, S, dk) f32 (batch*heads flattened by the wrapper);
         log_i, log_f (BH, S).  Grid (BH, S/chunk), chunks innermost.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, o_ref,
                  C_ref, n_ref, m_ref, *, chunk: int, scale: float):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (c, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li = li_ref[0, :]  # (c,)
    lf = lf_ref[0, :]
    m_prev = m_ref[0, 0]
    C_prev = C_ref[...]
    n_prev = n_ref[0, :]

    csum = jnp.cumsum(lf)  # decay from chunk start to position i
    total = csum[chunk - 1]
    # intra-chunk log weights D[i,j] = csum_i - csum_j + li_j (j <= i)
    D = csum[:, None] - csum[None, :] + li[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    D = jnp.where(row >= col, D, NEG_INF)
    g = csum + m_prev  # inter-chunk contribution magnitude per position
    m_i = jnp.maximum(jnp.max(D, axis=1), g)  # (c,)
    w_intra = jnp.exp(D - m_i[:, None])
    s_qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    W = s_qk * w_intra
    inter = jnp.exp(g - m_i)  # (c,)
    num = jax.lax.dot_general(W, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    num = num + inter[:, None] * jax.lax.dot_general(
        q, C_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    den = jnp.sum(W, axis=1) + inter * jnp.einsum("cd,d->c", q, n_prev)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_i))[:, None]
    o_ref[0] = h.astype(o_ref.dtype)

    # carry update to the chunk end
    dec = total - csum + li  # weight of k_j v_j at chunk end
    m_next = jnp.maximum(m_prev + total, jnp.max(dec))
    w_new = jnp.exp(dec - m_next)  # (c,)
    kw = k * w_new[:, None]
    C_ref[...] = jnp.exp(m_prev + total - m_next) * C_prev + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_ref[0, :] = jnp.exp(m_prev + total - m_next) * n_prev + jnp.sum(kw, axis=0)
    m_ref[0, 0] = m_next


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk(q, k, v, i_pre, f_pre, *, chunk: int = 64,
                interpret: bool = False):
    """q,k,v (B,S,H,dk); i_pre,f_pre (B,S,H) -> h (B,S,H,dk) f32."""
    B, S, H, dk = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    scale = 1.0 / math.sqrt(dk)
    BH = B * H

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(BH, S, dk).astype(jnp.float32)

    qf, kf, vf = to_bh(q), to_bh(k), to_bh(v)
    li = i_pre.transpose(0, 2, 1).reshape(BH, S).astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_pre.astype(jnp.float32)).transpose(0, 2, 1).reshape(BH, S)

    kernel = functools.partial(_mlstm_kernel, chunk=chunk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(BH, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk), lambda b, ic: (b, ic)),
            pl.BlockSpec((1, chunk), lambda b, ic: (b, ic)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dk), lambda b, ic: (b, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dk), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((dk, dk), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, li, lf)
    return out.reshape(B, H, S, dk).transpose(0, 2, 1, 3)
