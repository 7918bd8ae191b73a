"""Serving engine: continuous batching over a tiny model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import ModelOptions, init_params
from repro.serve import PagedServeEngine, Request, ServeEngine


def test_continuous_batching_greedy():
    cfg = reduced_config("gemma-2b")
    params = init_params(jax.random.key(0), cfg)
    opts = ModelOptions(compute_dtype="float32")
    eng = ServeEngine(cfg, params, num_slots=2, max_len=64, opts=opts)
    for rid in range(4):  # more requests than slots -> queueing
        eng.submit(Request(rid=rid, prompt=[1 + rid, 2, 3], max_new_tokens=5))
    done = eng.run_until_drained(max_ticks=200)
    assert len(done) == 4
    for req in done:
        assert len(req.generated) == 5
        assert all(0 <= t < cfg.padded_vocab for t in req.generated)


def test_batched_decode_matches_single():
    """A request decoded alongside others equals the same request alone."""
    cfg = reduced_config("qwen3-14b")
    params = init_params(jax.random.key(0), cfg)
    opts = ModelOptions(compute_dtype="float32")

    eng1 = ServeEngine(cfg, params, num_slots=1, max_len=32, opts=opts)
    eng1.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=4))
    alone = eng1.run_until_drained(max_ticks=50)[0].generated

    eng2 = ServeEngine(cfg, params, num_slots=2, max_len=32, opts=opts)
    eng2.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=4))
    eng2.submit(Request(rid=1, prompt=[9, 10], max_new_tokens=4))
    together = {r.rid: r.generated for r in eng2.run_until_drained(max_ticks=50)}
    assert together[0] == alone


# --------------------------------------------------------------------- paged


_PROMPTS = [[1, 5, 9, 2], [1, 5, 9, 2, 7, 3], [4, 4, 8], [1, 5, 9, 2, 6]]


def _requests():
    return [Request(rid=i, prompt=list(p), max_new_tokens=5)
            for i, p in enumerate(_PROMPTS)]


def _fixed_outputs(cfg, params, opts):
    eng = ServeEngine(cfg, params, num_slots=2, max_len=16, opts=opts)
    for r in _requests():
        eng.submit(r)
    return {r.rid: r.generated for r in eng.run_until_drained(max_ticks=200)}


def test_paged_engine_matches_fixed_slot():
    """Paged engine (chunked prefill + prefix reuse + CoW) reproduces the
    fixed-slot engine's greedy outputs token for token."""
    cfg = reduced_config("gemma-2b")
    params = init_params(jax.random.key(0), cfg)
    opts = ModelOptions(compute_dtype="float32")
    want = _fixed_outputs(cfg, params, opts)

    eng = PagedServeEngine(cfg, params, num_blocks=24, block_size=4,
                           max_active=3, prefill_chunk=3, opts=opts)
    for r in _requests():
        eng.submit(r)
    got = {r.rid: r.generated for r in eng.run_until_drained(max_ticks=200)}
    assert got == want
    m = eng.metrics()
    # the three shared-prefix prompts actually shared cached blocks
    assert m["prefixHitRate"] > 0
    assert m["cowCopies"] >= 1  # divergence after a shared tail block
    assert m["prefillBacklog"] == 0
    assert m["blocksFree"] == m["blocksTotal"] - m["blocksCached"]


def test_paged_engine_kernel_attention_path():
    """attn_impl='kernel' (paged Pallas kernel, interpret mode) produces the
    same tokens as the jnp gather path."""
    cfg = reduced_config("gemma-2b")
    params = init_params(jax.random.key(0), cfg)
    opts = ModelOptions(compute_dtype="float32")
    reqs = _requests()[:2]

    outs = []
    for impl in ("gather", "kernel"):
        eng = PagedServeEngine(cfg, params, num_blocks=16, block_size=4,
                               max_active=2, prefill_chunk=4, opts=opts,
                               attn_impl=impl, interpret=True)
        for r in _requests()[:2]:
            eng.submit(r)
        outs.append({r.rid: r.generated
                     for r in eng.run_until_drained(max_ticks=100)})
    assert outs[0] == outs[1]


def test_paged_admission_waits_for_blocks():
    """A pool too small for all requests at once still drains: admission
    stalls until retiring requests return blocks, and nothing leaks."""
    cfg = reduced_config("gemma-2b")
    params = init_params(jax.random.key(0), cfg)
    opts = ModelOptions(compute_dtype="float32")
    # capacity 6 blocks of 4 = 24 tokens; each request needs ~3 blocks,
    # so at most 2 of the 4 requests fit concurrently
    eng = PagedServeEngine(cfg, params, num_blocks=7, block_size=4,
                           max_active=4, prefill_chunk=4, opts=opts,
                           prefix_cache=False)
    for r in _requests():
        eng.submit(r)
    done = eng.run_until_drained(max_ticks=400)
    assert len(done) == 4
    assert eng.peak_active <= 2
    m = eng.metrics()
    assert m["blocksFree"] == m["blocksTotal"]  # all blocks returned


def test_paged_oversized_request_rejected():
    cfg = reduced_config("gemma-2b")
    params = init_params(jax.random.key(0), cfg)
    eng = PagedServeEngine(cfg, params, num_blocks=3, block_size=2,
                           opts=ModelOptions(compute_dtype="float32"))
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=[1] * 8, max_new_tokens=4))


def _hybrid_config():
    # recurrent and attention layers in the scanned pattern, and a
    # recurrent tail layer outside it
    return reduced_config("recurrentgemma-9b").with_(
        block_pattern=("rglru", "attn"), num_layers=5)


_TICK_CONFIGS = {
    "all-attention": lambda: reduced_config("qwen1.5-4b"),
    "prefix-moe": lambda: reduced_config("deepseek-moe-16b"),
    "hybrid-tail": _hybrid_config,
}


def _sliced_step(params, cfg, state, tables, tokens, adv, opts):
    """One micro-step with the layer scan as it was before the pool rode in
    its carry: each scanned layer's pool is sliced out of the stack (xs),
    written and read as one layer's pool, and stacked again (ys)."""
    from repro.models.layers import rmsnorm, rope_table
    from repro.models.lm import _mask_padded_vocab, stack_plan
    from repro.serve.paged_model import _paged_layer

    plan = stack_plan(cfg)
    lengths = state["len"]
    x = params["embed"]["table"].astype(opts.dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, opts.dtype)
    sin, cos = rope_table(lengths, cfg.head_dim, cfg.rope_theta)

    def layer(lp, spec, st, x):
        return _paged_layer(lp, cfg, spec, st, None, x, sin, cos, lengths,
                            adv, tables, opts, "gather", False)

    new = {"len": jnp.where(adv, lengths + 1, lengths), "prefix": [],
           "tail": [], "main": []}
    for lp, spec, st in zip(params["prefix"], plan.prefix, state["prefix"]):
        x, ns = layer(lp, spec, st, x)
        new["prefix"].append(ns)
    if plan.num_groups:
        def body(x, scanned):
            outs = []
            for i, spec in enumerate(plan.pattern):
                x, ns = layer(scanned[0][i], spec, scanned[1][i], x)
                outs.append(ns)
            return x, outs

        x, new["main"] = jax.lax.scan(body, x,
                                      (params["main"], state["main"]))
    for lp, spec, st in zip(params["tail"], plan.tail, state["tail"]):
        x, ns = layer(lp, spec, st, x)
        new["tail"].append(ns)
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["head"]["w"])
    logits = jnp.einsum("bd,dv->bv", x, head.astype(opts.dtype),
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return _mask_padded_vocab(logits, cfg), new


@pytest.mark.parametrize("name", list(_TICK_CONFIGS))
def test_paged_tick_matches_sliced_layers(name):
    """Over mixed prefill/decode ticks, the tick that writes and reads the
    stacked pool in place (kernel and gather paths) gives the logits and
    the state of the layer-by-layer slice-and-write-back scan."""
    from repro.serve.paged_model import init_paged_state, make_paged_tick

    cfg = _TICK_CONFIGS[name]()
    params = init_params(jax.random.key(3), cfg)
    opts = ModelOptions(compute_dtype="float32")
    B, bs, T = 3, 4, 6
    num_blocks = B * T + 1
    tables = (jax.random.permutation(jax.random.key(4), num_blocks - 1)
              + 1).reshape(B, T).astype(jnp.int32)
    # (counts, active) of each tick: prefill chunks beside decodes, a row
    # idle, a row that stops mid-tick, then decodes alone
    schedule = [([4, 2, 0], [True, True, False]),
                ([1, 4, 3], [True, True, True]),
                ([1, 1, 0], [True, True, False]),
                ([2, 0, 4], [True, False, True]),
                ([1, 1, 1], [True, True, True])]
    feeds = jax.random.randint(jax.random.key(5), (len(schedule), B, 4), 1,
                               cfg.vocab_size)
    step = jax.jit(lambda p, s, t, f, a: _sliced_step(p, cfg, s, t, f, a,
                                                      opts))
    ticks = {impl: make_paged_tick(cfg, opts, attn_impl=impl, interpret=True)
             for impl in ("gather", "kernel")}
    states = {impl: init_paged_state(cfg, B, num_blocks, bs, jnp.float32)
              for impl in ticks}
    ref_state = init_paged_state(cfg, B, num_blocks, bs, jnp.float32)
    for t, (counts, active) in enumerate(schedule):
        counts = jnp.asarray(counts, jnp.int32)
        active = jnp.asarray(active)
        C = int(counts.max())
        feed = feeds[t, :, :C]
        want = jnp.zeros((B, cfg.padded_vocab), jnp.float32)
        for i in range(C):
            logits, ref_state = step(params, ref_state, tables, feed[:, i],
                                     active & (i < counts))
            want = jnp.where((active & (i == counts - 1))[:, None], logits,
                             want)
        for impl, tick in ticks.items():
            got, states[impl] = tick(params, states[impl], tables, feed,
                                     counts, active)
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4,
                                       err_msg=f"{impl}, tick {t}")
    for impl, state in states.items():
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, atol=1e-5, rtol=1e-5, err_msg=impl), state, ref_state)
