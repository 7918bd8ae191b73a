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


@pytest.mark.parametrize("B,H,KV,D,bs,T", [
    (2, 8, 2, 64, 16, 8),   # GQA
    (3, 4, 1, 128, 32, 4),  # MQA
    (1, 4, 4, 64, 8, 16),   # MHA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_parity(B, H, KV, D, bs, T, dtype):
    """Paged kernel vs gather-then-dense oracle, with shuffled non-identity
    block tables and ragged lengths (some rows pointing at scratch)."""
    num_blocks = B * T + 1  # + scratch block 0
    ks = jax.random.split(jax.random.key(hash((B, H, KV, D, bs)) % 2**31), 4)
    q = rand(ks[0], (B, H, D), dtype)
    k_pool = rand(ks[1], (num_blocks, KV, bs, D), dtype)
    v_pool = rand(ks[2], (num_blocks, KV, bs, D), dtype)
    perm = jax.random.permutation(ks[3], num_blocks - 1) + 1
    tables = perm.reshape(B, T).astype(jnp.int32)
    lengths = jnp.asarray([max(1, (T * bs) // (i + 1) - 3) for i in range(B)],
                          jnp.int32)
    # unused trailing table entries point at scratch, as the engine leaves them
    used = -(-lengths // bs)  # ceil-div: blocks actually referenced
    tables = jnp.where(jnp.arange(T)[None, :] < used[:, None], tables, 0)
    out = paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(out.astype(np.float32), want.astype(np.float32),
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
