"""Serving: prefill + decode steps and the continuous-batching engines.

``make_prefill_step`` / ``make_decode_step`` build the jit-able pure
functions the dry-run lowers for the inference shapes.  ``ServeEngine`` is
the fixed-slot continuous-batching driver: it pads every slot's cache to
``max_len`` and admits prompts one token at a time through full-cache
merges — kept as the baseline the serve benchmark measures against.

``PagedServeEngine`` is the production path: KV lives in fixed-size blocks
handed out by a free-list allocator (``paging.py``), so admission capacity
scales with tokens actually held instead of ``slots x max_len``; prompt
admission runs as *chunked prefill* interleaved with decode inside one
jitted mixed tick (``paged_model.py``); and committed prompt blocks are
shared across requests through a refcounted prefix cache with
copy-on-write on divergence.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models import ModelOptions, decode_step, forward_with_cache, init_cache
from ..sharding.ctx import use_rules
from .paged_model import (
    all_attention,
    init_paged_state,
    make_copy_block,
    make_paged_tick,
    make_reset_slot,
)
from .paging import BlockAllocator, OutOfBlocks, PrefixCache, SequenceBlocks


def make_prefill_step(cfg: ArchConfig, opts: ModelOptions = ModelOptions(),
                      max_len: int = 0, mesh=None, act_rules=None):
    def prefill(params, batch):
        ctx = use_rules(mesh, act_rules) if (mesh is not None and act_rules) else None
        if ctx is not None:
            with ctx:
                return forward_with_cache(params, cfg, batch["tokens"],
                                          batch.get("frontend_embeds"),
                                          max_len=max_len, opts=opts)
        return forward_with_cache(params, cfg, batch["tokens"],
                                  batch.get("frontend_embeds"),
                                  max_len=max_len, opts=opts)

    return prefill


def make_decode_step(cfg: ArchConfig, opts: ModelOptions = ModelOptions(),
                     mesh=None, act_rules=None):
    def step(params, cache, tokens):
        ctx = use_rules(mesh, act_rules) if (mesh is not None and act_rules) else None
        if ctx is not None:
            with ctx:
                return decode_step(params, cfg, cache, tokens, opts)
        return decode_step(params, cfg, cache, tokens, opts)

    return step


@dataclass
class Request:
    rid: int
    prompt: list
    max_new_tokens: int
    generated: list = field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Continuous batching over a fixed slot count (single-host driver).

    Admission prefills a request into a free slot by re-running the batched
    prefill with the slot's row swapped in (slot caches are batch rows of the
    shared cache pytree).  Greedy decoding; per-slot lengths.
    """

    def __init__(self, cfg: ArchConfig, params, num_slots: int, max_len: int,
                 opts: ModelOptions = ModelOptions()):
        self.cfg = cfg
        self.params = params
        self.opts = opts
        self.num_slots = num_slots
        self.max_len = max_len
        self.cache = init_cache(cfg, num_slots, max_len,
                                dtype=opts.dtype if opts.compute_dtype != "float32"
                                else jnp.float32)
        self.slots: list = [None] * num_slots
        self.queue: deque = deque()  # popleft() is O(1); a list's pop(0) is O(n)
        self.finished: list = []
        self._decode = jax.jit(make_decode_step(cfg, opts))
        self._next_token = jnp.zeros((num_slots,), jnp.int32)
        # slot-occupancy metrics (the serving load signal the platform's
        # metrics plane aggregates, so serving jobs can autoscale too).
        # slots_busy is maintained incrementally on admit/retire so
        # metrics() never rescans the slot list per tick.
        self.ticks = 0
        self.tokens_generated = 0
        self.slots_busy = 0
        self._busy_ticks = 0
        self.on_metrics: Optional[Callable[[dict], None]] = None

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for slot in range(self.num_slots):
            if self.slots[slot] is None and self.queue:
                req = self.queue.popleft()
                self.slots[slot] = req
                self.slots_busy += 1
                # reset the slot's cache row and feed the prompt token by token
                self.cache = _reset_slot(self.cache, slot)
                tok = self._next_token
                for t in req.prompt:
                    tok = tok.at[slot].set(t)
                    logits, self.cache = self._decode_one_slot(slot, tok)
                self._next_token = self._next_token.at[slot].set(
                    int(jnp.argmax(logits[slot])))

    def _decode_one_slot(self, slot: int, tokens):
        # mask: only this slot advances during admission; other slots' len
        # must not change.  We run the batched step but restore other rows.
        before = self.cache
        logits, after = self._decode(self.params, self.cache, tokens)
        self.cache = _merge_slot(before, after, slot)
        return logits, self.cache

    def metrics(self) -> dict:
        """Slot occupancy + queue state: the engine's scaling signals.

        ``occupancy`` is instantaneous (busy slots / slots); ``backpressure``
        is the admission queue normalized by slot count — >0 means requests
        are waiting for a slot, the cue to add replicas.
        """
        busy = self.slots_busy
        return {
            "numSlots": self.num_slots, "slotsBusy": busy,
            "occupancy": busy / self.num_slots,
            "meanOccupancy": (self._busy_ticks / (self.ticks * self.num_slots)
                              if self.ticks else 0.0),
            "queueDepth": len(self.queue),
            "backpressure": min(1.0, len(self.queue) / self.num_slots),
            "ticks": self.ticks, "tokensGenerated": self.tokens_generated,
            "finished": len(self.finished),
        }

    def step(self) -> list:
        """One engine tick: admit, decode one token for all active slots."""
        self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        self.ticks += 1
        self._busy_ticks += len(active)
        if self.on_metrics is not None:
            self.on_metrics(self.metrics())
        if not active:
            return []
        logits, self.cache = self._decode(self.params, self.cache, self._next_token)
        out = []
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for i in active:
            req = self.slots[i]
            tok = int(nxt[i])
            req.generated.append(tok)
            if len(req.generated) >= req.max_new_tokens:
                req.done = True
                self.finished.append(req)
                self.slots[i] = None
                self.slots_busy -= 1
            out.append((req.rid, tok))
        self.tokens_generated += len(out)
        self._next_token = nxt
        return out

    def run_until_drained(self, max_ticks: int = 10000) -> list:
        ticks = 0
        while (self.queue or self.slots_busy) and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.finished


def _is_stacked(path) -> bool:
    """Leaves under cache['main'] carry a leading scanned-group dim; the
    batch dim is axis 1 there, axis 0 elsewhere.  Decide by path, not by
    shape — group count can collide with the slot count."""
    from jax.tree_util import DictKey

    for p in path:
        if isinstance(p, DictKey):
            return p.key == "main"
    return False


def _reset_slot(cache, slot: int):
    def zero_row(path, x):
        if _is_stacked(path):
            return x.at[:, slot].set(jnp.zeros_like(x[:, slot]))
        if x.ndim >= 1:
            return x.at[slot].set(jnp.zeros_like(x[slot]))
        return x

    new = jax.tree_util.tree_map_with_path(zero_row, cache)
    new["len"] = cache["len"].at[slot].set(0)
    return new


def _merge_slot(before, after, slot: int):
    """Take ``after``'s row ``slot``; keep ``before`` elsewhere."""

    def merge(path, b, a):
        if _is_stacked(path):
            return b.at[:, slot].set(a[:, slot])
        if b.ndim >= 1:
            return b.at[slot].set(a[slot])
        return b

    out = jax.tree_util.tree_map_with_path(merge, before, after)
    out["len"] = before["len"].at[slot].set(after["len"][slot])
    return out


# ---------------------------------------------------------------- paged


@dataclass
class _PagedSlot:
    """One active request's engine-side bookkeeping."""

    req: Request
    seq: SequenceBlocks
    pos: int  # prompt tokens fed so far (== cached tokens at admission)
    next_token: int = 0  # next decode feed once prefill completed
    reserved: int = 0  # future block demand still counted in the reserve


class PagedServeEngine:
    """Continuous batching over a paged KV cache (single-host driver).

    Admission allocates blocks for the request's actual length — no
    ``max_len`` padding — after consulting the prefix cache for committed
    prompt blocks it can share (refcounted; copy-on-write on the first
    divergent write into a shared tail block).  Prompts prefill in chunks
    of ``prefill_chunk`` tokens *inside* the regular batched tick, so a
    long admission delays running decodes by at most ``prefill_chunk - 1``
    masked micro-steps instead of a full O(prompt) blocking loop.  Greedy
    decoding, same output semantics as ``ServeEngine``.
    """

    def __init__(self, cfg: ArchConfig, params, *, num_blocks: int,
                 block_size: int = 16, max_active: int = 8,
                 prefill_chunk: int = 8, opts: ModelOptions = ModelOptions(),
                 attn_impl: str = "gather", interpret: bool = False,
                 prefix_cache: bool = True):
        self.cfg = cfg
        self.params = params
        self.opts = opts
        self.max_active = max_active
        self.prefill_chunk = max(1, prefill_chunk)
        dtype = (opts.dtype if opts.compute_dtype != "float32"
                 else jnp.float32)
        self.alloc = BlockAllocator(num_blocks, block_size)
        # prefix sharing needs every layer's state to be reconstructable
        # from shared KV blocks — only true for pure global attention
        # (recurrent/windowed state at the cut point is not in the blocks)
        self.cache = (PrefixCache(self.alloc)
                      if prefix_cache and all_attention(cfg) else None)
        self.state = init_paged_state(cfg, max_active, num_blocks,
                                      block_size, dtype)
        self._tables = np.zeros((max_active, self.alloc.capacity), np.int32)
        self._tick = make_paged_tick(cfg, opts, attn_impl=attn_impl,
                                     interpret=interpret)
        self._copy = make_copy_block(cfg)
        self._reset = make_reset_slot(cfg)
        self.slots: list = [None] * max_active
        self.queue: deque = deque()
        self.finished: list = []
        # incremental signal counters (metrics() never rescans)
        self.ticks = 0
        self.tokens_generated = 0
        self.slots_busy = 0
        self._busy_ticks = 0
        self._reserved = 0  # future block demand of active slots
        self._prefill_backlog = 0  # prompt tokens submitted, not yet fed
        self._prompt_tokens = 0  # admitted prompt tokens (hit-rate denom)
        self._cached_tokens = 0  # admitted via prefix cache (hit-rate num)
        self.cow_copies = 0
        self.peak_active = 0
        self.on_metrics: Optional[Callable[[dict], None]] = None
        # called with (request, logits row) when a prompt completes: the
        # logits its first token is sampled from
        self.on_first_logits: Optional[Callable[[Request, np.ndarray],
                                                None]] = None

    # ----------------------------------------------------------- admission

    def submit(self, req: Request) -> None:
        need = self.alloc.blocks_for_tokens(
            len(req.prompt) + req.max_new_tokens)
        if need > self.alloc.capacity:
            raise ValueError(
                f"request {req.rid} needs {need} blocks; pool holds "
                f"{self.alloc.capacity}")
        self.queue.append(req)
        self._prefill_backlog += len(req.prompt)

    def _admit(self) -> None:
        while self.queue:
            slot = next((i for i, s in enumerate(self.slots) if s is None),
                        None)
            if slot is None:
                return
            req = self.queue[0]
            prompt = list(req.prompt)
            blocks, n, tail_shared = ([], 0, False)
            if self.cache is not None:
                blocks, n, tail_shared = self.cache.match(prompt)
            # banker's admission: reserve the request's *entire* footprint
            # (prompt + worst-case decode; a shared tail costs one extra —
            # its copy-on-write replacement) against free blocks minus the
            # outstanding reservations of already-running requests, so
            # growth during decode can never deadlock the pool
            required = (self.alloc.blocks_for_tokens(
                len(prompt) + req.max_new_tokens)
                - len(blocks) + (1 if tail_shared else 0))
            short = required + self._reserved - self.alloc.blocks_free
            if short > 0 and self.cache is not None:
                self.cache.evict(short)
            if required + self._reserved > self.alloc.blocks_free:
                for b in blocks:  # memory-aware admission control: wait
                    self.alloc.decref(b)
                return
            self.queue.popleft()
            seq = SequenceBlocks(self.alloc)
            seq.adopt(blocks, n)
            self.slots[slot] = _PagedSlot(req=req, seq=seq, pos=n,
                                          reserved=required)
            self._reserved += required
            self.slots_busy += 1
            self.peak_active = max(self.peak_active, self.slots_busy)
            self._prompt_tokens += len(prompt)
            self._cached_tokens += n
            self._prefill_backlog -= n  # cached tokens are never fed
            self._table_row(slot)
            # zero the slot's per-slot (non-paged) state, seed len with the
            # adopted prefix length
            self.state = self._reset(self.state, slot, n)

    def _table_row(self, slot: int) -> None:
        blocks = self.slots[slot].seq.blocks
        self._tables[slot, :len(blocks)] = blocks
        self._tables[slot, len(blocks):] = BlockAllocator.SCRATCH

    def _retire(self, slot: int) -> None:
        s = self.slots[slot]
        self._reserved -= s.reserved  # release any unused reservation
        s.seq.free()
        self._tables[slot, :] = BlockAllocator.SCRATCH
        self.state["len"] = self.state["len"].at[slot].set(0)
        self.slots[slot] = None
        self.slots_busy -= 1

    # ---------------------------------------------------------------- tick

    def _spend(self, s: _PagedSlot, n_blocks: int) -> None:
        take = min(s.reserved, n_blocks)
        s.reserved -= take
        self._reserved -= take

    def _grow(self, s: _PagedSlot, n_tokens: int) -> bool:
        """CoW guard + capacity for the next ``n_tokens`` writes; evicts
        cache blocks under pressure.  False => stall this slot one tick.
        Every block actually allocated drains the slot's admission-time
        reservation, keeping the banker's ledger exact."""
        seq = s.seq
        try:
            dst, src = seq.ensure_writable()
        except OutOfBlocks:
            if self.cache is None or not self.cache.evict(1):
                return False
            dst, src = seq.ensure_writable()
        if src is not None:
            self.state = self._copy(self.state, src, dst)
            self.cow_copies += 1
            self._spend(s, 1)
        try:
            self._spend(s, len(seq.ensure_capacity(n_tokens)))
        except OutOfBlocks:
            need = self.alloc.blocks_for_tokens(seq.length + n_tokens) \
                - len(seq.blocks)
            if self.cache is None or \
                    not self.cache.evict(need - self.alloc.blocks_free):
                return False
            try:
                self._spend(s, len(seq.ensure_capacity(n_tokens)))
            except OutOfBlocks:
                return False
        return True

    def step(self) -> list:
        """One engine tick: admit, then one mixed prefill/decode program."""
        self._admit()
        active_idx = [i for i, s in enumerate(self.slots) if s is not None]
        self.ticks += 1
        self._busy_ticks += len(active_idx)
        if self.on_metrics is not None:
            self.on_metrics(self.metrics())
        if not active_idx:
            return []
        prefilling = [i for i in active_idx
                      if self.slots[i].pos < len(self.slots[i].req.prompt)]
        C = self.prefill_chunk if prefilling else 1
        feed = np.zeros((self.max_active, C), np.int32)
        counts = np.zeros((self.max_active,), np.int32)
        active = np.zeros((self.max_active,), bool)
        issued: dict = {}
        for i in active_idx:
            s = self.slots[i]
            P = len(s.req.prompt)
            toks = (s.req.prompt[s.pos:s.pos + C] if s.pos < P
                    else [s.next_token])
            if not self._grow(s, len(toks)):
                continue  # pool exhausted: the slot stalls this tick
            self._table_row(i)
            feed[i, :len(toks)] = toks
            counts[i] = len(toks)
            active[i] = True
            issued[i] = len(toks)
            s.seq.length += len(toks)
        if not issued:
            return []
        logits, self.state = self._tick(
            self.params, self.state, jnp.asarray(self._tables),
            jnp.asarray(feed), jnp.asarray(counts), jnp.asarray(active))
        logits = np.asarray(logits)

        out = []
        for i, n in issued.items():
            s = self.slots[i]
            P = len(s.req.prompt)
            if s.pos < P:  # was prefilling
                s.pos += n
                self._prefill_backlog -= n
                if s.pos == P:
                    # prompt complete: sample the first token (fed next
                    # tick — same semantics as ServeEngine._admit) and
                    # publish the prompt's blocks for prefix reuse
                    s.next_token = int(np.argmax(logits[i]))
                    if self.on_first_logits is not None:
                        self.on_first_logits(s.req, logits[i])
                    if self.cache is not None:
                        self.cache.insert(s.req.prompt, s.seq.blocks, P)
            else:
                tok = int(np.argmax(logits[i]))
                s.req.generated.append(tok)
                s.next_token = tok
                out.append((s.req.rid, tok))
                self.tokens_generated += 1
                if len(s.req.generated) >= s.req.max_new_tokens:
                    s.req.done = True
                    self.finished.append(s.req)
                    self._retire(i)
        return out

    def run_until_drained(self, max_ticks: int = 10000) -> list:
        ticks = 0
        while (self.queue or self.slots_busy) and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.finished

    # ------------------------------------------------------------- signals

    def metrics(self) -> dict:
        """ServeEngine-shaped occupancy signals plus the paged engine's
        own: ``blocksFree``/``blocksCached`` (allocator + prefix-cache
        state), ``prefixHitRate`` (admitted prompt tokens served from
        cache), ``prefillBacklog`` (prompt tokens waiting to be fed) —
        the signals the platform's metrics plane rolls up per region."""
        return {
            "numSlots": self.max_active, "slotsBusy": self.slots_busy,
            "occupancy": self.slots_busy / self.max_active,
            "meanOccupancy": (self._busy_ticks
                              / (self.ticks * self.max_active)
                              if self.ticks else 0.0),
            "queueDepth": len(self.queue),
            "backpressure": min(1.0, len(self.queue) / self.max_active),
            "ticks": self.ticks, "tokensGenerated": self.tokens_generated,
            "finished": len(self.finished),
            "blocksTotal": self.alloc.capacity,
            "blocksFree": self.alloc.blocks_free,
            "blocksReserved": self._reserved,
            "blocksCached": (self.cache.blocks_cached
                             if self.cache is not None else 0),
            "prefixHitRate": (self._cached_tokens / self._prompt_tokens
                              if self._prompt_tokens else 0.0),
            "prefillBacklog": self._prefill_backlog,
            "cowCopies": self.cow_copies,
        }
