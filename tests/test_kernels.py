"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    decode_attention,
    flash_attention,
    mlstm_chunk,
    paged_decode_attention,
    ref,
    rglru_scan,
    rmsnorm,
)
from repro.kernels.decode_attention import blocks_per_wave

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 64),   # MHA
    (2, 256, 8, 2, 64),   # GQA
    (1, 256, 4, 1, 128),  # MQA
    (2, 128, 4, 4, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, D, dtype):
    ks = jax.random.split(jax.random.key(hash((B, S, H, KV, D)) % 2**31), 3)
    q = rand(ks[0], (B, S, H, D), dtype)
    k = rand(ks[1], (B, S, KV, D), dtype)
    v = rand(ks[2], (B, S, KV, D), dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.causal_attention_ref(q, k, v)
    np.testing.assert_allclose(out.astype(np.float32), want.astype(np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("block_q,block_k", [(32, 128), (128, 32), (64, 64)])
def test_flash_attention_block_shapes(block_q, block_k):
    ks = jax.random.split(jax.random.key(0), 3)
    q = rand(ks[0], (2, 256, 4, 64), jnp.float32)
    k = rand(ks[1], (2, 256, 2, 64), jnp.float32)
    v = rand(ks[2], (2, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, block_q=block_q, block_k=block_k, interpret=True)
    want = ref.causal_attention_ref(q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,KV,D,Smax", [
    (2, 8, 2, 64, 512),
    (3, 4, 1, 128, 1024),
    (1, 4, 4, 64, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, KV, D, Smax, dtype):
    ks = jax.random.split(jax.random.key(hash((B, H, KV, D)) % 2**31), 3)
    q = rand(ks[0], (B, H, D), dtype)
    kc = rand(ks[1], (B, Smax, KV, D), dtype)
    vc = rand(ks[2], (B, Smax, KV, D), dtype)
    lengths = jnp.asarray([(Smax // (i + 1)) for i in range(B)], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_k=128, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(out.astype(np.float32), want.astype(np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,H,KV,D,Smax,block_k", [
    (2, 4, 2, 64, 384, 256),   # Smax % block_k != 0
    (1, 4, 4, 64, 100, 128),   # Smax < block_k after clamping (100 % 100 == 0
                               # never hits; 100 stays unpadded)
    (2, 8, 2, 64, 260, 128),   # remainder of 4
])
def test_decode_attention_unaligned_cache(B, H, KV, D, Smax, block_k):
    """Regression: cache lengths that aren't block_k multiples must pad,
    not assert (the serve engine sizes caches by prompt, not by kernel)."""
    ks = jax.random.split(jax.random.key(11), 3)
    q = rand(ks[0], (B, H, D), jnp.float32)
    kc = rand(ks[1], (B, Smax, KV, D), jnp.float32)
    vc = rand(ks[2], (B, Smax, KV, D), jnp.float32)
    lengths = jnp.asarray([Smax - 7 * i for i in range(B)], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_k=block_k, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def _ragged(P, bs, T, B):
    # the original sweep's lengths: the longest row fills the table
    return [max(1, (T * bs) // (i + 1) - 3) for i in range(B)]


# lengths are drawn from the derived wave size P, the block size and T
@pytest.mark.parametrize("B,H,KV,D,bs,T,lens", [
    pytest.param(2, 8, 2, 64, 16, 8, _ragged, id="2-8-2-64-16-8"),  # GQA
    pytest.param(3, 4, 1, 128, 32, 4, _ragged, id="3-4-1-128-32-4"),  # MQA
    pytest.param(1, 4, 4, 64, 8, 16, _ragged, id="1-4-4-64-8-16"),  # MHA
    pytest.param(3, 8, 8, 128, 16, 24,
                 lambda P, bs, T, B: [T * bs, 0, T * bs - 5],
                 id="len0-between-full-rows"),
    pytest.param(3, 8, 8, 128, 16, 40,
                 lambda P, bs, T, B: [3 * bs, P * bs, 2 * P * bs],
                 id="block-and-wave-boundaries"),
    pytest.param(3, 32, 8, 128, 16, 40,
                 lambda P, bs, T, B: [(P + 3) * bs - 1, (2 * P + 1) * bs, 5],
                 id="gqa-blocks-not-multiple-of-wave"),
    pytest.param(2, 8, 8, 128, 16, 51,
                 lambda P, bs, T, B: [T * bs, T * bs - 7],
                 id="table-not-multiple-of-wave"),
    pytest.param(3, 8, 8, 128, 16, 72,
                 lambda P, bs, T, B: [T * bs, 40 * bs + 3, 1],
                 id="table-of-72-blocks"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_parity(B, H, KV, D, bs, T, lens, dtype):
    """Paged kernel vs gather-then-dense oracle, with shuffled non-identity
    block tables and ragged lengths (some rows pointing at scratch)."""
    num_blocks = B * T + 1  # + scratch block 0
    P = blocks_per_wave(KV, bs, D, jnp.dtype(dtype).itemsize, T)
    ks = jax.random.split(jax.random.key(hash((B, H, KV, D, bs)) % 2**31), 4)
    q = rand(ks[0], (B, H, D), dtype)
    k_pool = rand(ks[1], (num_blocks, KV, bs, D), dtype)
    v_pool = rand(ks[2], (num_blocks, KV, bs, D), dtype)
    perm = jax.random.permutation(ks[3], num_blocks - 1) + 1
    tables = perm.reshape(B, T).astype(jnp.int32)
    lengths = jnp.asarray(lens(P, bs, T, B), jnp.int32)
    # unused trailing table entries point at scratch, as the engine leaves them
    used = -(-lengths // bs)  # ceil-div: blocks actually referenced
    tables = jnp.where(jnp.arange(T)[None, :] < used[:, None], tables, 0)
    out = paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, lengths)
    # a row of length 0 attends nothing: zeros, where the oracle has NaN
    live = lengths > 0
    want = jnp.where(live[:, None, None], want, 0)
    np.testing.assert_allclose(out.astype(np.float32), want.astype(np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_decode_attention_never_reads_dead_blocks():
    """Every pool block no row references, and the tail of each row's last
    block past its length, hold NaN: the output still equals the oracle on
    clean data, and a row of length 0 gives zeros."""
    B, H, KV, D, bs, T = 4, 8, 8, 128, 16, 40
    dtype = jnp.bfloat16
    P = blocks_per_wave(KV, bs, D, jnp.dtype(dtype).itemsize, T)
    assert T > 2 * P  # several waves, both buffer slots
    num_blocks = B * T + 1
    ks = jax.random.split(jax.random.key(21), 4)
    q = rand(ks[0], (B, H, D), dtype)
    k_pool = rand(ks[1], (num_blocks, KV, bs, D), dtype)
    v_pool = rand(ks[2], (num_blocks, KV, bs, D), dtype)
    tables = (jax.random.permutation(ks[3], num_blocks - 1) + 1).reshape(B, T)
    lengths = jnp.asarray([T * bs - 3, 0, (P + 1) * bs + 5, 7], jnp.int32)
    used = -(-lengths // bs)
    live_blk = jnp.arange(T)[None, :] < used[:, None]
    tables = jnp.where(live_blk, tables, 0).astype(jnp.int32)
    want = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, lengths)

    # per pool block and position: is it a live token of some row?
    pos = jnp.arange(T)[:, None] * bs + jnp.arange(bs)[None, :]  # (T, bs)
    token_live = live_blk[:, :, None] & (pos[None] < lengths[:, None, None])
    keep = jnp.zeros((num_blocks, bs), bool).at[tables].max(token_live)
    dead = ~keep[:, None, :, None]
    k_bad = jnp.where(dead, jnp.nan, k_pool).astype(dtype)
    v_bad = jnp.where(dead, jnp.nan, v_pool).astype(dtype)
    out = paged_decode_attention(q, k_bad, v_bad, tables, lengths,
                                 interpret=True)
    out = np.asarray(out.astype(np.float32))
    assert not np.isnan(out).any()
    np.testing.assert_array_equal(out[1], 0)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(out[live],
                               np.asarray(want.astype(np.float32))[live],
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_matches_dense_same_tokens():
    """The same logical cache gives identical attention whether stored
    contiguously (dense kernel) or scattered across pool blocks (paged)."""
    B, H, KV, D, bs, T = 2, 4, 2, 64, 16, 4
    num_blocks = B * T + 1
    ks = jax.random.split(jax.random.key(12), 4)
    q = rand(ks[0], (B, H, D), jnp.float32)
    k_pool = rand(ks[1], (num_blocks, KV, bs, D), jnp.float32)
    v_pool = rand(ks[2], (num_blocks, KV, bs, D), jnp.float32)
    tables = (jnp.arange(B * T, dtype=jnp.int32).reshape(B, T) + 1)
    lengths = jnp.asarray([T * bs, T * bs - 5], jnp.int32)
    kc = k_pool[tables].transpose(0, 1, 3, 2, 4).reshape(B, T * bs, KV, D)
    vc = v_pool[tables].transpose(0, 1, 3, 2, 4).reshape(B, T * bs, KV, D)
    dense = decode_attention(q, kc, vc, lengths, block_k=bs, interpret=True)
    paged = paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                   interpret=True)
    np.testing.assert_allclose(paged, dense, atol=2e-5, rtol=2e-5)


def _stacked_pools(L, dtype, key):
    """A stack of L layers' pools with shuffled tables: ragged rows, a row
    of length 0, and one that crosses a wave boundary."""
    B, H, KV, D, bs, T = 4, 8, 4, 128, 16, 24
    num_blocks = B * T + 1
    P = blocks_per_wave(KV, bs, D, jnp.dtype(dtype).itemsize, T)
    ks = jax.random.split(key, 4)
    q = rand(ks[0], (B, H, D), dtype)
    k = rand(ks[1], (L, num_blocks, KV, bs, D), dtype)
    v = rand(ks[2], (L, num_blocks, KV, bs, D), dtype)
    tables = (jax.random.permutation(ks[3], num_blocks - 1) + 1).reshape(B, T)
    lengths = jnp.asarray([T * bs - 3, 0, (P + 1) * bs + 5, 7], jnp.int32)
    used = -(-lengths // bs)
    tables = jnp.where(jnp.arange(T)[None, :] < used[:, None], tables, 0)
    return q, k, v, tables.astype(jnp.int32), lengths


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_stacked_pool(layer, dtype):
    """A stack of layers' pools read in place at one layer (the index a
    traced scalar) equals the oracle on that layer's pool, and the one-pool
    entry point on the same pool bit for bit."""
    q, k, v, tables, lengths = _stacked_pools(5, dtype, jax.random.key(31))
    out = paged_decode_attention(q, k, v, tables, lengths, jnp.int32(layer),
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, k[layer], v[layer], tables,
                                          lengths)
    want = jnp.where((lengths > 0)[:, None, None], want, 0)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    one = paged_decode_attention(q, k[layer], v[layer], tables, lengths,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(out.astype(np.float32)),
                                  np.asarray(one.astype(np.float32)))


def test_paged_decode_attention_stacked_ignores_other_layers():
    """Every block of every layer but the one read holds NaN: nothing of
    them reaches the result."""
    dtype, layer = jnp.bfloat16, 1
    q, k, v, tables, lengths = _stacked_pools(3, dtype, jax.random.key(32))
    other = (jnp.arange(3) != layer)[:, None, None, None, None]
    k_bad = jnp.where(other, jnp.nan, k).astype(dtype)
    v_bad = jnp.where(other, jnp.nan, v).astype(dtype)
    out = paged_decode_attention(q, k_bad, v_bad, tables, lengths, layer,
                                 interpret=True)
    out = np.asarray(out.astype(np.float32))
    assert not np.isnan(out).any()
    want = ref.paged_decode_attention_ref(q, k[layer], v[layer], tables,
                                          lengths)
    live = np.asarray(lengths) > 0
    np.testing.assert_array_equal(out[~live], 0)
    np.testing.assert_allclose(out[live],
                               np.asarray(want.astype(np.float32))[live],
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,S,C,bt", [(2, 128, 128, 16), (4, 64, 256, 8),
                                      (1, 256, 128, 64)])
def test_rglru_scan_sweep(B, S, C, bt):
    ks = jax.random.split(jax.random.key(1), 2)
    log_a = -jnp.abs(jax.random.normal(ks[0], (B, S, C))) * 0.2
    b = jax.random.normal(ks[1], (B, S, C))
    out = rglru_scan(log_a, b, block_b=min(2, B), block_c=128, block_t=bt,
                     interpret=True)
    want = ref.rglru_scan_ref(log_a, b)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,S,H,dk,chunk", [
    (1, 64, 2, 32, 16), (2, 128, 2, 64, 32), (1, 128, 4, 32, 64),
])
def test_mlstm_chunk_sweep(B, S, H, dk, chunk):
    ks = jax.random.split(jax.random.key(2), 5)
    q = rand(ks[0], (B, S, H, dk), jnp.float32)
    k = rand(ks[1], (B, S, H, dk), jnp.float32)
    v = rand(ks[2], (B, S, H, dk), jnp.float32)
    i_pre = jax.random.normal(ks[3], (B, S, H)) - 2.0
    f_pre = jax.random.normal(ks[4], (B, S, H)) + 3.0
    out = mlstm_chunk(q, k, v, i_pre, f_pre, chunk=chunk, interpret=True)
    want = ref.mlstm_ref(q, k, v, i_pre, f_pre)
    np.testing.assert_allclose(out, want, atol=5e-5, rtol=5e-4)


def test_mlstm_kernel_matches_model_recurrence():
    """The kernel and the model's XLA chunk recurrence agree with each other
    (both already match the sequential oracle)."""
    from repro.models.recurrent import mlstm_chunk_recurrence

    ks = jax.random.split(jax.random.key(3), 5)
    B, S, H, dk = 2, 128, 2, 32
    q = rand(ks[0], (B, S, H, dk), jnp.float32)
    k = rand(ks[1], (B, S, H, dk), jnp.float32)
    v = rand(ks[2], (B, S, H, dk), jnp.float32)
    i_pre = jax.random.normal(ks[3], (B, S, H)) - 2.0
    f_pre = jax.random.normal(ks[4], (B, S, H)) + 3.0
    a = mlstm_chunk(q, k, v, i_pre, f_pre, chunk=32, interpret=True)
    b = mlstm_chunk_recurrence(q, k, v, i_pre, f_pre, chunk=32)
    np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("shape", [(7, 128), (2, 33, 256), (1, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    ks = jax.random.split(jax.random.key(4), 2)
    x = rand(ks[0], shape, dtype)
    scale = jax.random.normal(ks[1], (shape[-1],)) * 0.1
    out = rmsnorm(x, scale, block_rows=16, interpret=True)
    want = ref.rmsnorm_ref(x, scale)
    np.testing.assert_allclose(out.astype(np.float32), want.astype(np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 32), (2, 256, 4, 2, 64), (1, 256, 4, 1, 64),
])
def test_flash_attention_backward_kernels(B, S, H, KV, D):
    """Custom-VJP flash attention (fwd + dq/dkv Pallas kernels) vs autodiff
    through the oracle."""
    from repro.kernels.flash_attention import flash_attention_train

    ks = jax.random.split(jax.random.key(B * S + H), 3)
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, KV, D), jnp.float32)
    v = rand(ks[2], (B, S, KV, D), jnp.float32)
    w = jnp.sin(jnp.arange(B * S * H * D, dtype=jnp.float32).reshape(B, S, H, D))

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention_train(q, k, v, 64, 64, True, True) * w)

    def loss_ref(q, k, v):
        return jnp.sum(ref.causal_attention_ref(q, k, v) * w)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)
