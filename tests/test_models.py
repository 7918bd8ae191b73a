"""Per-arch smoke tests (reduced configs, CPU) + attention/MoE equivalences."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import ARCH_IDS, get_config, reduced_config, SHAPES, shape_applicable
from repro.kernels import ref
from repro.models import (
    ModelOptions,
    decode_step,
    forward,
    forward_with_cache,
    init_cache,
    init_params,
    init_serving_params,
    loss_fn,
)
from repro.models.layers import (
    blockwise_causal_attention,
    local_band_attention,
    tree_causal_attention,
)

OPTS = ModelOptions(compute_dtype="float32")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    """Reduced same-family config: one forward + one grad step, shapes + no NaN."""
    cfg = reduced_config(arch)
    params = init_params(jax.random.key(0), cfg)
    B, S = 2, 64
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    fe = (jax.random.normal(jax.random.key(2), (B, cfg.frontend_len, cfg.frontend_dim))
          if cfg.frontend else None)
    logits, aux = forward(params, cfg, toks, fe, OPTS)
    exp_s = S + (cfg.frontend_len if cfg.frontend else 0)
    assert logits.shape == (B, exp_s, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())
    batch = {"tokens": toks, "labels": toks}
    if fe is not None:
        batch["frontend_embeds"] = fe
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(p, cfg, batch, OPTS, remat=False), has_aux=True)(params)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ["qwen3-14b", "recurrentgemma-9b",
                                  "xlstm-125m", "gemma-2b"])
def test_prefill_decode_equivalence(arch):
    """decode_step from a prefilled cache == full forward logits."""
    cfg = reduced_config(arch)
    params = init_params(jax.random.key(0), cfg)
    B, S = 2, 128
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, toks, None, OPTS)
    n0 = 64
    pre, cache = forward_with_cache(params, cfg, toks[:, :n0], None,
                                    max_len=S, opts=OPTS)
    errs = [float(jnp.max(jnp.abs(pre[:, -1] - full[:, n0 - 1])))]
    step = jax.jit(lambda c, t: decode_step(params, cfg, c, t, OPTS))
    for t in range(n0, S):
        lg, cache = step(cache, toks[:, t])
        errs.append(float(jnp.max(jnp.abs(lg - full[:, t]))))
    assert max(errs) < 5e-3, errs


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "recurrentgemma-9b",
                                  "xlstm-125m"])
def test_serving_params_match_init_params(arch):
    """The jitted bf16 init builds init_params' tree, shapes and values,
    rounded to bf16."""
    cfg = reduced_config(arch)
    want = init_params(jax.random.key(3), cfg)
    got = init_serving_params(jax.random.key(3), cfg, jnp.bfloat16)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-2, atol=1e-6)


def test_tree_attention_exact():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 4, 32))
    v = jax.random.normal(ks[2], (2, 256, 4, 32))
    a = blockwise_causal_attention(q, k, v, q_chunk=64, kv_chunk=64)
    b = tree_causal_attention(q, k, v, chunk=64)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    want = ref.causal_attention_ref(q, k, v)
    np.testing.assert_allclose(a, want, atol=1e-5, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([64, 128, 256]), st.sampled_from([1, 2, 4]),
       st.sampled_from([32, 64]), st.integers(0, 2 ** 31 - 1))
def test_blockwise_attention_matches_oracle(S, H, D, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (1, S, H, D))
    k = jax.random.normal(ks[1], (1, S, H, D))
    v = jax.random.normal(ks[2], (1, S, H, D))
    out = blockwise_causal_attention(q, k, v, q_chunk=64, kv_chunk=64)
    want = ref.causal_attention_ref(q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_local_band_matches_windowed_oracle():
    ks = jax.random.split(jax.random.key(1), 3)
    S, w = 256, 64
    q = jax.random.normal(ks[0], (2, S, 2, 32))
    k = jax.random.normal(ks[1], (2, S, 2, 32))
    v = jax.random.normal(ks[2], (2, S, 2, 32))
    out = local_band_attention(q, k, v, window=w)
    want = ref.causal_attention_ref(q, k, v, window=w)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_moe_einsum_vs_sort_dispatch():
    """Both dispatch implementations share capacity/drop semantics."""
    from repro.models.moe import init_moe, moe_apply
    from repro.configs.base import MoECfg

    m = MoECfg(num_experts=8, num_shared=2, top_k=2, d_expert=32,
               group_size=64, capacity_factor=2.0)
    d = 64
    params = init_moe(jax.random.key(0), d, m)
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    out_e, aux_e = moe_apply(params, x, m, "silu")
    m_sort = MoECfg(**{**m.__dict__, "impl": "sort"})
    out_s, aux_s = moe_apply(params, x, m_sort, "silu")
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_s),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(float(aux_e), float(aux_s), rtol=1e-5)


def test_param_counts_close_to_published():
    """Param counts from configs should be in the right ballpark (the
    published sizes are approximate — embeddings/details vary)."""
    expected = {  # billions, generous bands
        "qwen3-14b": (12, 17), "yi-6b": (5, 7), "gemma-2b": (2, 3.2),
        "deepseek-moe-16b": (14, 19), "qwen2-moe-a2.7b": (12, 16),
        "recurrentgemma-9b": (7.5, 11), "musicgen-large": (1.5, 2.8),
        "qwen1.5-4b": (3, 5), "internvl2-26b": (18, 24),
        "xlstm-125m": (0.10, 0.22),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count() / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo}, {hi}]"


def test_shape_applicability_matrix():
    """40 cells: full-attention archs skip long_500k only."""
    total = runnable = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            total += 1
            ok, why = shape_applicable(cfg, shape)
            runnable += ok
            if shape.name == "long_500k":
                assert ok == cfg.sub_quadratic
            else:
                assert ok
    assert total == 40 and runnable == 32  # 8 archs skip long_500k; 2 run it
