"""The one load generator: a traffic mix file in, a request schedule out.

A mix (``bench/traffic/<mix>.json``) holds parameters only:

- ``arrival``: ``{"kind": "poisson", "rate_per_s": r, "period_s": T}``
  (open loop: each request has a due time) or ``{"kind": "backlog",
  "depth": n}`` (offline: ``n`` requests are kept queued).  Poisson
  arrivals are conditioned on their count in each period of ``T``
  seconds: period ``k`` holds ``floor(r T (k + 1)) - floor(r T k)`` of
  them, each uniform in it, so any window of a few periods sees the
  stated rate;
- ``requests``: how many requests the schedule holds;
- ``prompt_tokens`` / ``output_tokens``: lognormal ``median`` and
  ``sigma``, clipped to ``[min, max]`` (with ``sessions``, prompt tokens are
  the new user text of a turn);
- ``sessions`` (optional): ``count`` sessions drawn by Zipf(``zipf``), each
  with one of ``system_prompts`` shared prompts of ``system_prompt_tokens``;
  a turn's prompt is the system prompt, the session's history (earlier user
  text and an assistant stand-in as long as that turn's output) and the new
  user text; a session whose prompt would pass ``max_prompt_tokens`` starts
  a new conversation.

Every seed gets the same work: the sizes, the session structure and the
arrival times, in their order, are drawn from the mix's ``shape_seed``;
the run seed draws every token.  The engine's work depends on lengths and
on which prompts share blocks, not on token values, so seeds differ in
what is computed and not in how much.  The program receives only the
generated token lists and lengths.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Item:
    """One request of the schedule."""

    rid: int
    prompt: list
    max_new_tokens: int
    due_s: float = 0.0  # offset from the window's start (open loop)
    session: int = -1
    system_prompt: int = -1


@dataclass
class Schedule:
    items: list
    open_loop: bool
    backlog: int = 0
    system_prompts: list = field(default_factory=list)  # warmed in set-up


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) % (1 << 63) for w in words]))


def lognormal_int(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_draws(rng: np.random.Generator, count: int, s: float,
               n: int) -> np.ndarray:
    """``n`` draws from {0..count-1} with P(k) proportional to (k+1)^-s."""
    p = np.arange(1, count + 1, dtype=np.float64) ** -s
    return rng.choice(count, size=n, p=p / p.sum())


def _due(arrival: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    rate, period = arrival["rate_per_s"], arrival["period_s"]
    due = []
    k = 0
    while len(due) < n:
        m = int(np.floor(rate * period * (k + 1))
                - np.floor(rate * period * k))
        due.extend(np.sort(k * period + period * rng.random(m)))
        k += 1
    return np.asarray(due[:n])


def generate(mix: dict, seed: int, vocab: int) -> Schedule:
    n = int(mix["requests"])
    shape = _rng(mix.get("shape_seed", 0), 1)
    run = _rng(seed, 2)
    outs = lognormal_int(shape, mix["output_tokens"], n)
    users = lognormal_int(shape, mix["prompt_tokens"], n)
    open_loop = mix["arrival"]["kind"] == "poisson"
    due = (_due(mix["arrival"], n, _rng(mix.get("shape_seed", 0), 3))
           if open_loop else np.zeros(n))

    def tokens(k: int) -> list:
        return run.integers(0, vocab, size=int(k)).tolist()

    sess = mix.get("sessions")
    items, sys_prompts = [], []
    if sess is None:
        for i in range(n):
            items.append(Item(i, tokens(users[i]), int(outs[i]),
                              float(due[i])))
    else:
        sys_prompts = [tokens(sess["system_prompt_tokens"])
                       for _ in range(sess["system_prompts"])]
        who = zipf_draws(shape, sess["count"], sess["zipf"], n)
        history: dict = {}
        for i in range(n):
            s = int(who[i])
            sp = s % sess["system_prompts"]
            h = history.get(s, [])
            head = len(sys_prompts[sp])
            if head + len(h) + users[i] > sess["max_prompt_tokens"]:
                h = []  # a new conversation
            user = tokens(users[i])
            items.append(Item(i, sys_prompts[sp] + h + user, int(outs[i]),
                              float(due[i]), s, sp))
            history[s] = h + user + tokens(outs[i])  # assistant stand-in
    backlog = 0 if open_loop else int(mix["arrival"]["depth"])
    return Schedule(items, open_loop, backlog, sys_prompts)


def max_tokens(mix: dict) -> int:
    """The longest prompt plus output a request of the mix can have."""
    sess = mix.get("sessions")
    prompt = (sess["max_prompt_tokens"] if sess
              else mix["prompt_tokens"]["max"])
    return prompt + mix["output_tokens"]["max"]
