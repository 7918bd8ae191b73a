"""The load generator: deterministic by seed, the same work for every
seed, the stated distributions, shared system prompts and unshared batch
prompts."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import harness, loadgen

V = 151936


def mix(name: str) -> dict:
    return harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                          name + ".json"))


@pytest.mark.parametrize("name", ["sessions", "batch"])
def test_same_seed_same_inputs(name):
    a = loadgen.generate(mix(name), 2**33 + 5, V)
    b = loadgen.generate(mix(name), 2**33 + 5, V)
    c = loadgen.generate(mix(name), 17, V)
    assert [(i.prompt, i.max_new_tokens, i.due_s) for i in a.items] == \
        [(i.prompt, i.max_new_tokens, i.due_s) for i in b.items]
    assert [i.prompt for i in a.items] != [i.prompt for i in c.items]


@pytest.mark.parametrize("name", ["sessions", "batch"])
def test_every_seed_gets_the_same_work(name):
    """Sizes, due times and which prompts share what are the seed's
    neither; only the tokens are."""
    a = loadgen.generate(mix(name), 1, V)
    b = loadgen.generate(mix(name), 2**40 + 3, V)
    assert [(len(i.prompt), i.max_new_tokens, i.due_s, i.session)
            for i in a.items] == [(len(i.prompt), i.max_new_tokens, i.due_s,
                                   i.session) for i in b.items]

    def shared(s):  # for each prompt, the earlier prompts it shares a block with
        first = {}
        out = []
        for it in s.items:
            blocks = {tuple(it.prompt[j:j + 16])
                      for j in range(0, len(it.prompt) - 15, 16)}
            out.append(sorted({first[k] for k in blocks if k in first}))
            for k in blocks:
                first.setdefault(k, it.rid)
        return out

    assert shared(a) == shared(b)


def test_lognormal_clip_and_median():
    rng = np.random.default_rng(0)
    spec = {"median": 48, "sigma": 0.8, "min": 16, "max": 512}
    x = loadgen.lognormal_int(rng, spec, 20000)
    assert x.min() >= 16 and x.max() <= 512
    assert abs(np.median(x) - 48) <= 2
    assert (x == 16).mean() > 0.05  # the clip is hit


def test_zipf_ranks():
    rng = np.random.default_rng(0)
    d = loadgen.zipf_draws(rng, 32, 1.1, 50000)
    p = np.arange(1, 33) ** -1.1
    p /= p.sum()
    got = np.bincount(d, minlength=32) / len(d)
    assert np.allclose(got, p, atol=0.01)


def test_sessions_share_system_prompt_blocks():
    m = mix("sessions")
    s = loadgen.generate(m, 5, V)
    sp = m["sessions"]
    assert len(s.system_prompts) == sp["system_prompts"]
    assert all(len(p) == sp["system_prompt_tokens"] for p in s.system_prompts)
    n = sp["system_prompt_tokens"]
    for it in s.items:
        assert it.prompt[:n] == s.system_prompts[it.system_prompt]
        assert len(it.prompt) <= sp["max_prompt_tokens"]
    # a later turn of a session starts with the session's earlier prompt
    # unless it started a new conversation
    turns = {}
    continued = 0
    for it in s.items:
        prev = turns.get(it.session)
        if prev is not None and len(it.prompt) > len(prev.prompt):
            assert it.prompt[:len(prev.prompt)] == prev.prompt
            continued += 1
        turns[it.session] = it
    assert continued > len(s.items) // 2


def test_batch_prompts_share_no_full_block():
    s = loadgen.generate(mix("batch"), 5, V)
    assert not s.open_loop and s.backlog >= 16
    bs = 16
    seen = set()
    for it in s.items:
        for j in range(0, len(it.prompt) - bs + 1, bs):
            blk = tuple(it.prompt[j:j + bs])
            assert blk not in seen
            seen.add(blk)


def test_open_loop_rate_per_period():
    """Every period holds the stated rate's share of the arrivals, each
    uniform in it, so a window of a few periods sees the stated rate."""
    m = json.loads(json.dumps(mix("sessions")))
    m["requests"] = 4000
    arr = m["arrival"]
    due = np.array([i.due_s for i in loadgen.generate(m, 3, V).items])
    assert np.all(np.diff(due) >= 0)
    T, r = arr["period_s"], arr["rate_per_s"]
    k = np.arange(len(due) // int(np.ceil(r * T)))
    want = np.floor(r * T * (k + 1)) - np.floor(r * T * k)
    got = np.bincount((due // T).astype(int), minlength=len(k))[:len(k)]
    assert np.array_equal(got, want)
    for j in (0, 7, 300):  # a window of two whole periods anywhere
        n = np.sum((due >= j * T) & (due < (j + 2) * T))
        assert abs(n - 2 * r * T) < 1
    # within a period the arrivals are spread, not packed at one end
    phase = (due % T) / T
    assert 0.45 < phase.mean() < 0.55
