"""GQA decode attention (split-K over the cache) — Pallas TPU kernel.

Decode is memory-bound: one query token per sequence reads the whole KV
cache.  The kernel streams the cache in ``block_k`` slices along the
innermost sequential grid axis (split-K / flash-decoding), keeping online
(m, l, acc) per query-head group in VMEM scratch.  All G query heads of one
KV head are processed together as the matmul M-dimension — the natural MXU
mapping for GQA decode (the q "matrix" is (G, D), the cache block (D, bk)).

Valid-length masking uses the per-sequence ``lengths`` vector, delivered to
SMEM (scalar memory) rather than VMEM: it is control data, not tensor data.

Layouts: q (B, KV, G, D); caches (B, Smax, KV, D); lengths (B, 1) int32.
Grid: (B, KV, Smax/block_k), cache axis innermost (sequential).

``paged_decode_attention`` is the same split-K online-softmax kernel over a
*paged* cache: K/V live in a block pool ``(num_blocks, KV, block_size, D)``
and each sequence names its blocks through a block table delivered as a
scalar-prefetch operand (SMEM, like ``lengths``).  The K/V BlockSpec index
maps read the table, so the gather happens in the DMA engine block by
block — the paged layout is never materialized as a contiguous cache
on-device.  A pool block's last two dims, ``(block_size, D)``, are whole
array dims, the tiling the TPU compiler requires of a block; one grid step
carries every KV head of a block and loops over them.  Block 0 of the
pool is the engine's scratch block; table rows of inactive sequences point
at it, which is safe because ``lengths`` masks their output anyway.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale: float, block_k: int, num_k: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b, 0]
    needed = ik * block_k < length

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # (G, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
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
        out = acc_ref[...] / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, block_k: int = 512,
                     interpret: bool = False):
    """q (B,H,D); caches (B,Smax,KV,D); lengths (B,) -> (B,H,D)."""
    B, H, D = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    block_k = min(block_k, Smax)
    if Smax % block_k:
        # arbitrary cache lengths: pad the cache axis up to the next
        # block_k multiple instead of crashing the caller — the padded
        # positions sit beyond every ``lengths`` entry, so the in-kernel
        # valid-length mask already ignores them
        pad = block_k - Smax % block_k
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Smax += pad
    nk = Smax // block_k
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KV, G, D)
    len2d = lengths.reshape(B, 1).astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               num_k=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, whole array
            pl.BlockSpec((1, 1, G, D), lambda b, j, ik: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, j, ik: (b, ik, j, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, j, ik: (b, ik, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, j, ik: (b, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(len2d, qg, k_cache, v_cache)
    return out.reshape(B, H, D)


# ------------------------------------------------------------------- paged


def _paged_decode_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale: float,
                         block_size: int, num_t: int, num_kv: int):
    # the dense kernel's online softmax, one pool block of every KV head
    # per grid step (a loop over heads inside).  tab_ref / len_ref are the
    # scalar-prefetch operands (SMEM).
    b = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(it * block_size < length)
    def _compute():
        G = q_ref.shape[2]
        pos = it * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_size), 1)
        valid = pos < length

        def head(h, carry):
            q = q_ref[0, h].astype(jnp.float32)  # (G, D)
            k = k_ref[0, h].astype(jnp.float32)  # (block_size, D)
            v = v_ref[0, h].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)
            m_prev = m_ref[h]  # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + pv
            return carry

        jax.lax.fori_loop(0, num_kv, head, 0)

    @pl.when(it == num_t - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           interpret: bool = False):
    """Decode attention over a paged KV cache.

    q (B,H,D); pools (num_blocks, KV, block_size, D); block_tables (B,T)
    int32 (physical block of each sequence's t-th logical block); lengths
    (B,) -> (B,H,D).  Split-K runs over the T logical blocks; each grid
    step DMAs one pool block — all KV heads, ``(KV, block_size, D)`` —
    selected by the prefetched table, so no contiguous (B, Smax, KV, D)
    cache ever exists on-device.  Steps past a sequence's last block
    re-select that block, so they issue no DMA and skip their compute.
    """
    B, H, D = q.shape
    _, KV, block_size, _ = k_pool.shape
    T = block_tables.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KV, G, D)
    tab = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def kv_block(b, it, tab, lens):
        last = jnp.maximum(lens[b] - 1, 0) // block_size
        return (tab[b, jnp.minimum(it, last)], 0, 0, 0)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               block_size=block_size, num_t=T, num_kv=KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block tables + lengths land in SMEM
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, it, tab, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, KV, block_size, D), kv_block),
            pl.BlockSpec((1, KV, block_size, D), kv_block),
        ],
        out_specs=pl.BlockSpec((1, KV, G, D),
                               lambda b, it, tab, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, D), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tab, lens, qg, k_pool, v_pool)
    return out.reshape(B, H, D)
