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

``paged_decode_attention`` is the same online softmax over a *paged*
cache: K/V live in a block pool ``(num_blocks, KV, block_size, D)`` that
stays in HBM, or in a stack of layers' pools ``(L, num_blocks, KV,
block_size, D)`` read in place at one layer, and each sequence names its
blocks through a block table delivered, with ``lengths`` and the layer, as
scalar-prefetch operands (SMEM).  The grid has one step per sequence, and
a step walks only that sequence's live blocks, ``cdiv(length,
block_size)`` of them: a loop with a trip count set by the data, so blocks
past the length cost nothing.  The blocks come in waves of ``P`` pool
blocks, each copied by its own DMA, picked by the table, into one slot of
a double buffer ``(2, KV, P, block_size, D)`` (one for K, one for V); the
next wave, or the next non-empty sequence's first, is in flight while one
is computed.  ``P`` follows from shapes alone (``blocks_per_wave``: about
1 MiB of K+V a wave, in whole 128-lane rows of scores).  A wave is
computed for all KV heads at once, as dots batched over heads (scores
``(KV, G, P * block_size)``, running max and sum ``(KV, G, 1)``,
accumulator ``(KV, G, D)``, all float32); positions
past the length are dropped by a select, so stale or dead data in a slot
never reaches the result.  A sequence of length 0 issues no DMA and gives
zeros.  Block 0 of the pool is the engine's scratch block; table entries
past a sequence's last block point at it and are never read.
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

# K+V bytes one DMA wave aims to carry: enough to keep the DMA engine
# streaming between waves, and with two slots a small share of the scoped
# VMEM budget
_WAVE_BYTES = 1 << 20
_LANES = 128


def blocks_per_wave(num_kv: int, block_size: int, head_dim: int,
                    itemsize: int, table_width: int) -> int:
    """Pool blocks one DMA wave of the paged kernel copies, from shapes.

    About ``_WAVE_BYTES`` of K+V a wave, in whole lane rows of scores (a
    multiple of ``128 // block_size`` blocks), and no more than a table row.
    """
    block_bytes = 2 * num_kv * block_size * head_dim * itemsize  # K and V
    row = max(1, _LANES // block_size)
    p = max(row, _WAVE_BYTES // block_bytes // row * row)
    return min(p, table_width)


def _paged_decode_kernel(tab_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sems, slot_ref, m_ref, l_ref,
                         acc_ref, *, scale: float, wave: int):
    # one grid step per row; the stacked pools stay in HBM and the row's
    # live blocks of layer ``layer_ref[0]`` come in waves of ``wave``
    # blocks, double-buffered: wave w+1 (or the next non-empty row's first
    # wave) is in flight while wave w is computed.  tab_ref / len_ref /
    # layer_ref are the scalar-prefetch operands (SMEM); slot_ref (SMEM)
    # carries the buffer slot across grid steps.
    b = pl.program_id(0)
    B, T = tab_ref.shape
    bs = k_buf.shape[3]
    span = wave * bs
    layer = layer_ref[0]

    def live_blocks(r):
        return jnp.minimum(pl.cdiv(len_ref[r], bs), T)

    def each_copy(r, w, slot, fn):
        # one DMA per live block of the wave: blocks past the row's last
        # issue none
        def one(j, _):
            blk = tab_ref[r, w * wave + j]
            fn(pltpu.make_async_copy(k_hbm.at[layer, blk],
                                     k_buf.at[slot, :, j], sems.at[0, slot]))
            fn(pltpu.make_async_copy(v_hbm.at[layer, blk],
                                     v_buf.at[slot, :, j], sems.at[1, slot]))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(wave, live_blocks(r) - w * wave),
                          one, 0)

    def start_first_wave_after(r, slot):
        # of the first row past r with a live block, if any
        n = jax.lax.while_loop(
            lambda n: (n < B) & (len_ref[jnp.minimum(n, B - 1)] <= 0),
            lambda n: n + 1, r + 1)

        @pl.when(n < B)
        def _():
            each_copy(n, 0, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        start_first_wave_after(-1, 0)

    nw = pl.cdiv(live_blocks(b), wave)
    length = len_ref[b]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    KV, G, D = acc_ref.shape
    q = q_ref[0]  # (KV, G, D)

    def body(w, _):
        slot = slot_ref[0]
        slot_ref[0] = 1 - slot

        @pl.when(w + 1 < nw)
        def _():
            each_copy(b, w + 1, 1 - slot, lambda c: c.start())

        @pl.when(w + 1 == nw)
        def _():
            start_first_wave_after(b, 1 - slot)

        each_copy(b, w, slot, lambda c: c.wait())
        k = k_buf[slot].reshape(KV, span, D)
        v = v_buf[slot].reshape(KV, span, D)
        pos = w * span + jax.lax.broadcasted_iota(jnp.int32, (KV, G, span), 2)
        s = jax.lax.dot_general(
            q.astype(jnp.promote_types(q.dtype, k.dtype)), k,
            (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32)
        # a slot past the row's last block holds stale or dead data: the
        # select drops it whatever it holds, NaN included
        s = jnp.where(pos < length, s * scale, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        m_ref[...] = m_new
        vpos = w * span + jax.lax.broadcasted_iota(jnp.int32, (KV, span, D), 1)
        v = jnp.where(vpos < length, v.astype(jnp.float32), 0.0)
        pv = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        return 0

    jax.lax.fori_loop(0, nw, body, 0)
    # a row of length 0 ran no wave: acc and l are 0 and it writes zeros
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           layer=None, *, interpret: bool = False):
    """Decode attention over a paged KV cache.

    q (B,H,D); pools (num_blocks, KV, block_size, D), or a stack of
    layers' pools (L, num_blocks, KV, block_size, D) of which ``layer``
    (an int32 scalar, traced or not) is read in place; block_tables (B,T)
    int32 (physical block of each sequence's t-th logical block); lengths
    (B,) -> (B,H,D).  One grid step per sequence walks its live blocks only,
    ``blocks_per_wave`` of them per double-buffered DMA wave, all KV heads
    at once; no contiguous (B, Smax, KV, D) cache ever exists on-device.
    A sequence of length 0 reads nothing and gives zeros.
    """
    if k_pool.ndim == 4:  # one layer's pool: a stack of one
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    B, H, D = q.shape
    _, _, KV, block_size, _ = k_pool.shape
    T = block_tables.shape[1]
    G = H // KV
    wave = blocks_per_wave(KV, block_size, D, k_pool.dtype.itemsize, T)
    qg = q.reshape(B, KV, G, D)
    tab = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    kernel = functools.partial(_paged_decode_kernel,
                               scale=1.0 / math.sqrt(D), wave=wave)
    row = pl.BlockSpec((1, KV, G, D), lambda b, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block tables, lengths, layer land in SMEM
        grid=(B,),
        in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, KV, wave, block_size, D), k_pool.dtype),
            pltpu.VMEM((2, KV, wave, block_size, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K or V, slot)
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the next row's first wave is started by this row's last
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(tab, lens, lyr, qg, k_pool, v_pool)
    return out.reshape(B, H, D)
