"""Paged-cache execution of the decoder LM: mixed prefill/decode ticks.

``init_paged_state`` mirrors ``models.lm.init_cache`` but replaces every
global-attention layer's ``(B, max_len, KV, D)`` cache with a shared block
pool ``(num_blocks, KV, block_size, D)`` (the layout the paged kernel
reads block by block) — sequences address it through a
per-slot block table, so cache memory is proportional to tokens actually
held, not ``slots x max_len``.  Non-attention state (sliding-window ring
buffers, recurrent states) stays per-slot: it is O(window) / O(1) per
sequence and gains nothing from paging.

``make_paged_tick`` builds the engine's one jitted step: a ``lax.scan``
over up to ``C`` micro-steps in which every active slot advances by its
own number of tokens (``counts``).  Decoding slots advance one sampled
token (count 1); prefilling slots consume up to a whole prompt chunk —
chunked prefill interleaved with decode in a single batched program, which
replaces the fixed-slot engine's O(prompt) per-token admit/merge loop and
bounds the tail-latency impact of admission on running requests to
``C - 1`` masked micro-steps.

The scanned group's pools stay stacked ``(G, num_blocks, KV, block_size,
D)`` and ride in the layer scan's carry: each layer writes its new rows
into the stack and the kernel reads the layer from it in place, so no
pool is sliced, laid out again or copied whole inside the tick.

Block 0 of every pool is scratch: inactive rows write there and mask their
outputs, so no per-slot control flow exists inside the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.lm import (
    ModelOptions,
    _decode_layer,
    _init_layer_state,
    _mask_padded_vocab,
    stack_plan,
)
from ..models.layers import apply_rope, mlp_apply, rmsnorm, rope_table
from ..models.layers import decode_attention as decode_attention_jnp
from ..models.moe import moe_apply
from ..kernels.decode_attention import paged_decode_attention


def _is_paged(spec) -> bool:
    """Global-attention layers page through the block pool; everything
    else (local ring buffers, recurrences) keeps per-slot state."""
    return spec.kind == "attn"


def _init_entry(cfg, spec, max_active, num_blocks, block_size, dtype):
    if _is_paged(spec):
        shape = (num_blocks, cfg.num_kv_heads, block_size, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    # _init_layer_state only uses max_len to clamp the local window
    return _init_layer_state(cfg, spec, max_active, cfg.window or 1, dtype)


def init_paged_state(cfg, max_active: int, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16) -> dict:
    """Same pytree skeleton as ``init_cache`` (prefix/main/tail/len), with
    attn entries pool-shaped.  ``len`` is per-slot tokens in context."""
    plan = stack_plan(cfg)
    state = {
        "prefix": [_init_entry(cfg, s, max_active, num_blocks, block_size,
                               dtype) for s in plan.prefix],
        "tail": [_init_entry(cfg, s, max_active, num_blocks, block_size,
                             dtype) for s in plan.tail],
        "len": jnp.zeros((max_active,), jnp.int32),
    }
    if plan.num_groups:
        one = [_init_entry(cfg, s, max_active, num_blocks, block_size, dtype)
               for s in plan.pattern]
        state["main"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None],
                                       (plan.num_groups,) + x.shape).copy(),
            one)
    else:
        state["main"] = []
    return state


def all_attention(cfg) -> bool:
    """True when every layer is global attention — the precondition for
    prefix-cache reuse (recurrent/windowed state at a cut point cannot be
    reconstructed from shared KV blocks alone)."""
    plan = stack_plan(cfg)
    return all(_is_paged(s) for s in
               list(plan.prefix) + list(plan.pattern) + list(plan.tail))


def _mask_tree(new, old, adv):
    """Keep ``old`` rows where ``adv`` is False (per-slot state leaves all
    lead with the slot axis)."""
    def pick(n, o):
        a = adv.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)

    return jax.tree.map(pick, new, old)


def _write_rows(pool, lead, blk, off, rows):
    """Row ``b`` of ``rows`` (B, KV, D) into ``pool[lead + (blk[b], :,
    off[b])]``, one dynamic-update-slice a row: XLA writes each in place in
    the pool's own layout, where one scatter of all rows would have the
    pool laid out again (KV beside D) and copied whole around the write."""
    B, KV, D = rows.shape
    for b in range(B):
        upd = rows[b].reshape((1,) * (len(lead) + 1) + (KV, 1, D))
        pool = jax.lax.dynamic_update_slice(pool, upd.astype(pool.dtype),
                                            lead + (blk[b], 0, off[b], 0))
    return pool


def _paged_attn_layer(lparams, cfg, spec, pool, layer, x, sin, cos, lengths,
                      adv, tables, opts, attn_impl, interpret):
    """The attn branch of ``lm._decode_layer`` against the block pool.

    ``pool`` is one layer's ``{"k", "v"}`` pools ``(N, KV, bs, D)`` with
    ``layer`` None, or the scanned group's stacked pools ``(G, N, KV, bs,
    D)`` with ``layer`` the index into them: the new rows are written into
    the stack and the layer is read from it in place, never sliced out."""
    dt = x.dtype
    h = rmsnorm(x, lparams["norm1"]["scale"], cfg.norm_eps)
    ap = lparams["attn"]
    q = jnp.einsum("bd,dhe->bhe", h, ap["wq"].astype(dt))
    k = jnp.einsum("bd,dhe->bhe", h, ap["wk"].astype(dt))
    v = jnp.einsum("bd,dhe->bhe", h, ap["wv"].astype(dt))
    if "bq" in ap:
        q, k, v = (q + ap["bq"].astype(dt), k + ap["bk"].astype(dt),
                   v + ap["bv"].astype(dt))
    if "q_norm" in ap:
        q = rmsnorm(q, ap["q_norm"]["scale"], cfg.norm_eps)
        k = rmsnorm(k, ap["k_norm"]["scale"], cfg.norm_eps)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)

    KV, bs, D = pool["k"].shape[-3:]
    lead = () if layer is None else (layer,)
    bidx = jnp.arange(x.shape[0])
    # inactive rows write the scratch block (0, :, 0); their output is
    # ignored by the caller, so no gather/scatter is ever masked out
    blk = jnp.where(adv, tables[bidx, lengths // bs], 0)
    off = jnp.where(adv, lengths % bs, 0)
    pool = {"k": _write_rows(pool["k"], lead, blk, off, k),
            "v": _write_rows(pool["v"], lead, blk, off, v)}

    if attn_impl == "kernel":
        out = paged_decode_attention(q, pool["k"], pool["v"], tables,
                                     lengths + 1, layer, interpret=interpret)
    else:  # pure-jnp gather: XLA materializes each slot's view on gather
        B = x.shape[0]
        kc = pool["k"][lead + (tables,)].transpose(0, 1, 3, 2, 4)
        vc = pool["v"][lead + (tables,)].transpose(0, 1, 3, 2, 4)
        out = decode_attention_jnp(q, kc.reshape(B, -1, KV, D),
                                   vc.reshape(B, -1, KV, D), lengths + 1)
    mix = jnp.einsum("bhe,hed->bd", out, ap["wo"].astype(dt))
    x = x + mix
    if spec.use_moe:
        h2 = rmsnorm(x, lparams["norm2"]["scale"], cfg.norm_eps)
        out2, _ = moe_apply(lparams["moe"], h2[:, None, :], cfg.moe, cfg.act)
        x = x + out2[:, 0]
    elif spec.d_ff > 0:
        h2 = rmsnorm(x, lparams["norm2"]["scale"], cfg.norm_eps)
        x = x + mlp_apply(lparams["mlp"], h2, cfg.act, cfg.gated_mlp)
    return x, pool


def _paged_layer(lparams, cfg, spec, state, layer, x, sin, cos, lengths, adv,
                 tables, opts, attn_impl, interpret):
    if _is_paged(spec):
        return _paged_attn_layer(lparams, cfg, spec, state, layer, x, sin,
                                 cos, lengths, adv, tables, opts, attn_impl,
                                 interpret)
    x2, ns = _decode_layer(lparams, cfg, spec, state, x, sin, cos, lengths,
                           opts)
    return x2, _mask_tree(ns, state, adv)


def _paged_decode_step(params, cfg, state, tables, tokens, adv, opts,
                       attn_impl, interpret):
    """One token for every advancing slot: ``lm.decode_step`` against the
    paged state.  tokens/adv (B,); tables (B, T) int32."""
    plan = stack_plan(cfg)
    dt = opts.dtype
    lengths = state["len"]
    x = params["embed"]["table"].astype(dt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    sin, cos = rope_table(lengths, cfg.head_dim, cfg.rope_theta)
    new_state = {"len": jnp.where(adv, lengths + 1, lengths),
                 "prefix": [], "tail": [], "main": state["main"]}

    for lp, spec, st in zip(params["prefix"], plan.prefix, state["prefix"]):
        x, ns = _paged_layer(lp, cfg, spec, st, None, x, sin, cos, lengths,
                             adv, tables, opts, attn_impl, interpret)
        new_state["prefix"].append(ns)

    if plan.num_groups:
        # the stacked pools ride in the carry, so every layer writes and
        # reads them in place; per-slot state and weights are scanned
        paged = [_is_paged(s) for s in plan.pattern]
        pools = [e if p else None for p, e in zip(paged, state["main"])]
        slots = [None if p else e for p, e in zip(paged, state["main"])]

        def group_body(carry, scanned):
            x, pools = carry
            g, group_params, group_slots = scanned
            pools, new_slots = list(pools), []
            for i, spec in enumerate(plan.pattern):
                x, ns = _paged_layer(group_params[i], cfg, spec,
                                     pools[i] if paged[i] else group_slots[i],
                                     g, x, sin, cos, lengths, adv, tables,
                                     opts, attn_impl, interpret)
                if paged[i]:
                    pools[i], ns = ns, None
                new_slots.append(ns)
            return (x, pools), new_slots

        (x, pools), slots = jax.lax.scan(
            group_body, (x, pools),
            (jnp.arange(plan.num_groups, dtype=jnp.int32), params["main"],
             slots))
        new_state["main"] = [e if p else s
                             for p, e, s in zip(paged, pools, slots)]

    for lp, spec, st in zip(params["tail"], plan.tail, state["tail"]):
        x, ns = _paged_layer(lp, cfg, spec, st, None, x, sin, cos, lengths,
                             adv, tables, opts, attn_impl, interpret)
        new_state["tail"].append(ns)

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["head"]["w"])
    logits = jnp.einsum("bd,dv->bv", x, head.astype(dt),
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return _mask_padded_vocab(logits, cfg), new_state


def make_paged_tick(cfg, opts: ModelOptions = ModelOptions(), *,
                    attn_impl: str = "gather", interpret: bool = False):
    """Build the engine's jitted mixed tick.

    ``tick(params, state, tables, feed, counts, active)`` runs
    ``feed.shape[1]`` micro-steps; slot ``b`` advances through
    ``feed[b, :counts[b]]`` (masked no-op afterwards) and the returned
    logits row is the one produced by its *last* advanced token — the
    sampling point for decode slots and the first-token logits for slots
    that just finished prefill.  The state is donated: callers must adopt
    the returned state and drop the argument.
    """

    def tick(params, state, tables, feed, counts, active):
        B, C = feed.shape
        last = jnp.zeros((B, cfg.padded_vocab), jnp.float32)

        def micro(carry, i):
            state, last = carry
            adv = active & (i < counts)
            logits, state = _paged_decode_step(params, cfg, state, tables,
                                               feed[:, i], adv, opts,
                                               attn_impl, interpret)
            sel = active & (i == counts - 1)
            last = jnp.where(sel[:, None], logits, last)
            return (state, last), None

        (state, last), _ = jax.lax.scan(micro, (state, last),
                                        jnp.arange(C, dtype=jnp.int32))
        return last, state

    return jax.jit(tick, donate_argnums=1)


def make_copy_block(cfg):
    """Jitted pool-slab copy ``src -> dst`` across every paged layer — the
    device half of copy-on-write (the allocator decides *when*).  The state
    is donated, as in the tick."""
    plan = stack_plan(cfg)

    def copy_entry(spec, entry, src, dst, stacked):
        if not _is_paged(spec):
            return entry
        if stacked:  # scanned main group: leading group axis
            return {k: p.at[:, dst].set(p[:, src]) for k, p in entry.items()}
        return {k: p.at[dst].set(p[src]) for k, p in entry.items()}

    def copy(state, src, dst):
        out = {"len": state["len"]}
        out["prefix"] = [copy_entry(s, e, src, dst, False)
                         for s, e in zip(plan.prefix, state["prefix"])]
        out["tail"] = [copy_entry(s, e, src, dst, False)
                       for s, e in zip(plan.tail, state["tail"])]
        if plan.num_groups:
            out["main"] = [copy_entry(s, e, src, dst, True)
                           for s, e in zip(plan.pattern, state["main"])]
        else:
            out["main"] = []
        return out

    return jax.jit(copy, donate_argnums=0)


def make_reset_slot(cfg):
    """Jitted per-slot reset for admission: zero the slot's rows of every
    *per-slot* (non-paged) state leaf and seed its length with the number
    of prefix-cached tokens it adopts.  Paged pools need no reset — block
    contents beyond a sequence's length are masked by construction.  The
    state is donated, as in the tick."""
    plan = stack_plan(cfg)

    def reset_entry(spec, entry, slot, stacked):
        if _is_paged(spec):
            return entry

        def zero(x):
            if stacked:
                return x.at[:, slot].set(jnp.zeros_like(x[:, slot]))
            return x.at[slot].set(jnp.zeros_like(x[slot]))

        return jax.tree.map(zero, entry)

    def reset(state, slot, n_tokens):
        out = {"len": state["len"].at[slot].set(n_tokens)}
        out["prefix"] = [reset_entry(s, e, slot, False)
                         for s, e in zip(plan.prefix, state["prefix"])]
        out["tail"] = [reset_entry(s, e, slot, False)
                       for s, e in zip(plan.tail, state["tail"])]
        if plan.num_groups:
            out["main"] = [reset_entry(s, e, slot, True)
                           for s, e in zip(plan.pattern, state["main"])]
        else:
            out["main"] = []
        return out

    return jax.jit(reset, donate_argnums=0)
