"""Serving cells: the paged engine driven by a traffic mix.

Set-up makes the weights on the device from the seed (one jitted call, in
the served type), builds the engine with ``launch.serve.build_engine``,
warms every program the mix uses (both tick widths, slot reset and retire,
and the copy-on-write block copy where requests share prefixes) and, for
session traffic, puts the system prompts into the prefix cache.  The
window then drives ``PagedServeEngine.submit/step`` from one thread:
open-loop requests are submitted once due, a backlog is topped up to its
depth, and every step is timed on the host clock.  The window closes at
the end of the first step that ends after ``seconds``.

Open-loop requests due in the window keep the schedule running after the
close until each has its first token (at most ``drain_s``): their time to
first token counts that wait, and one still without a token then counts
as that long.  No request is submitted after that; the engine steps on
until the requests due in the window have finished (at most ``finish_s``),
so that the check compares whole answers.

The engine is watched through its public surface alone: ``submit``,
``step``, ``metrics()`` before and after each step, ``on_metrics`` (called
once a step has admitted, before its tick) and ``on_first_logits``.

After the window the device's peak memory is read, the engine is freed,
and a sample of the finished requests, drawn from the seed with the
longest among them, is compared with the configuration's float32
reference.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from bench import harness, loadgen

clock = time.perf_counter


@dataclass
class ReqRec:
    item: loadgen.Item
    due: float = 0.0  # host clock
    submit: float = 0.0
    first_token: int = -1  # sampled from the prompt's logits
    first_logits: object = None  # that logits row, as the engine gave it
    times: list = field(default_factory=list)  # emission time per token
    req: object = None


@dataclass
class Tick:
    start: float
    end: float
    prefill: bool  # fed prompt tokens (C = chunk) or decode only (C = 1)
    tokens: int  # tokens computed: prompt tokens fed and tokens decoded
    keys: float  # keys those tokens attend, summed (see Server.step)
    busy: int  # slots occupied during the tick
    queued: int = 0  # requests waiting for a slot after the tick


@dataclass
class Record:
    """What the window produced; per-layer readers take their numbers
    from here."""

    model: dict
    max_active: int
    t0: float = 0.0
    t_end: float = 0.0
    ticks: list = field(default_factory=list)
    reqs: dict = field(default_factory=dict)
    open_loop: bool = True
    hit0: tuple = (0.0, 0)  # prefixHitRate and prompt tokens admitted
    hit1: tuple = (0.0, 0)
    traced: tuple = (0, 0)  # tick indices [first, last) inside the trace
    trace: object = None  # bench.trace.Reduced of the traced window
    device_kind: str = ""

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def window_ticks(self):
        return [t for t in self.ticks if t.start >= self.t0
                and t.end <= self.t_end]


def seed_key(seed: int):
    import jax

    words = np.random.SeedSequence(seed % (1 << 63)).generate_state(2)
    return jax.random.key(int(words[0]) & 0x7FFFFFFF)


def arch_config(config: dict):
    """The program's ArchConfig for the configuration file's model dict:
    the named arch with every size the file states."""
    from repro.configs import get_config

    m = config["model"]
    arch = get_config(config["arch"]).with_(
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        head_dim=m.get("head_dim",
                       m["hidden_size"] // m["num_attention_heads"]),
        vocab_size=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=m["rms_norm_eps"],
        qkv_bias=m.get("qkv_bias", True),
        tie_embeddings=m.get("tie_word_embeddings", False))
    return arch


class Server:
    """One engine with the harness's public hooks.

    Per step it reads the engine's counters before the step, when the step
    has admitted (``on_metrics``) and after it: requests admitted (the
    queue shrinks; the engine admits from its head), prompt tokens the
    prefix cache served (``prefillBacklog`` falls at admission) and prompt
    tokens fed (it falls in the tick).  ``on_first_logits`` says which
    requests finished their prompt.  From these it keeps the summed
    position of the requests still prefilling, which gives the keys that
    the tick's prompt tokens attend, each prefilling request taken to feed
    an equal share of them (exact whenever they feed equal chunks); a
    decoded token attends its own position, read from its request."""

    def __init__(self, cell: harness.Cell, seed: int):
        import jax

        from repro.launch.serve import build_engine, serving_options

        conf, dep = cell.config, cell.config["deployment"]
        self.model = conf["model"]
        self.block_size = dep["block_size"]
        self.ref = harness.load_reference(conf["reference"])
        self.arch = arch_config(conf)
        self.opts = serving_options()
        if self.opts.compute_dtype != dep["dtype"]:
            raise SystemExit(f"the program serves {self.opts.compute_dtype}; "
                             f"the configuration states {dep['dtype']}")
        self.weights = self.ref.init_weights(
            seed_key(seed), self.model, dep["dtype"],
            conf.get("weights", {}).get("logit_std", 4.0))
        jax.block_until_ready(self.weights)
        self._check_layout()
        self.engine = build_engine(
            self.arch, self.weights, self.opts, num_blocks=dep["num_blocks"],
            block_size=dep["block_size"], max_active=dep["max_active"],
            prefill_chunk=dep["prefill_chunk"], attn_impl=dep["attn_impl"])
        self.max_active = dep["max_active"]
        self.requests: dict = {}  # rid -> Request, every one submitted
        self.waiting: deque = deque()  # submitted, not admitted yet
        self.prompt_admitted = 0
        self.prefilling = 0  # admitted requests without their first token
        self.positions = 0  # prompt tokens in the cache of those requests
        self.first: dict = {}  # rid -> (first token, logits row)
        self._mid = None
        self._completed: list = []
        self.engine.on_first_logits = self._on_first
        self.engine.on_metrics = self._on_metrics

    def _check_layout(self):
        import jax

        from repro.models import abstract_params

        want = abstract_params(self.arch)
        got_s = jax.tree.structure(self.weights)
        if jax.tree.structure(want) != got_s or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree.leaves(want), jax.tree.leaves(self.weights))):
            raise SystemExit("the reference's weight layout differs from "
                             "the program's")

    def _on_first(self, req, row):
        row = np.asarray(row[:self.model["vocab_size"]], np.float32)
        self.first[req.rid] = (int(np.argmax(row)), row)
        self._completed.append(req)

    def _on_metrics(self, m):
        self._mid = m

    def submit(self, req) -> None:
        self.engine.submit(req)
        self.requests[req.rid] = req
        self.waiting.append(req)

    def busy(self) -> bool:
        m = self.engine.metrics()
        return bool(m["queueDepth"] or m["slotsBusy"])

    def step(self) -> tuple:
        """One engine step; returns (Tick or None, emitted tokens)."""
        eng = self.engine
        pre = eng.metrics()
        self._completed = []
        t_a = clock()
        out = eng.step()
        t_b = clock()
        post, mid = eng.metrics(), self._mid
        for _ in range(pre["queueDepth"] - mid["queueDepth"]):
            self.prompt_admitted += len(self.waiting.popleft().prompt)
            self.prefilling += 1
        self.positions += pre["prefillBacklog"] - mid["prefillBacklog"]
        fed = mid["prefillBacklog"] - post["prefillBacklog"]
        keys = 0.0
        if fed:
            share = fed / self.prefilling
            keys += share * self.positions + fed * (share + 1) / 2
            self.positions += fed
        for req in self._completed:
            self.positions -= len(req.prompt)
            self.prefilling -= 1
        for rid, _tok in out:
            req = self.requests[rid]
            keys += len(req.prompt) + len(req.generated)
        if not (fed or out):
            return None, out
        return Tick(t_a, t_b, fed > 0, fed + len(out), keys,
                    mid["slotsBusy"], post["queueDepth"]), out

    def hit_counter(self) -> tuple:
        return (self.engine.metrics()["prefixHitRate"], self.prompt_admitted)

    def free(self):
        self.engine = None
        gc.collect()


def _warm(server: Server, schedule: loadgen.Schedule, seed: int) -> None:
    """Compile and run once every program the window will use."""
    from repro.serve import Request

    rng = np.random.default_rng(np.random.SeedSequence([seed % (1 << 63), 7]))
    V = server.model["vocab_size"]
    bs = server.block_size
    a = rng.integers(0, V, size=bs + bs // 2).tolist()  # 1 block + part of one
    server.submit(Request(rid=-1, prompt=a, max_new_tokens=2))
    _drain(server)
    if schedule.system_prompts:  # shared tails are copied on write
        server.submit(Request(rid=-2, prompt=a + rng.integers(
            0, V, size=4).tolist(), max_new_tokens=2))
        _drain(server)
        if server.engine.metrics()["cowCopies"] == 0:
            raise RuntimeError("warm-up made no copy-on-write")
        for i, p in enumerate(schedule.system_prompts):
            server.submit(Request(rid=-10 - i, prompt=list(p),
                                  max_new_tokens=1))
        _drain(server)


def _drain(server: Server, limit: int = 100_000) -> None:
    n = 0
    while server.busy():
        server.step()
        n += 1
        if n > limit:
            raise RuntimeError("engine does not drain")


def _submit(server: Server, rec: Record, item: loadgen.Item, due: float):
    from repro.serve import Request

    req = Request(rid=item.rid, prompt=list(item.prompt),
                  max_new_tokens=item.max_new_tokens)
    r = ReqRec(item, due, clock(), req=req)
    rec.reqs[item.rid] = r
    server.submit(req)


def _record_step(server: Server, rec: Record):
    tick, out = server.step()
    if tick is not None:
        rec.ticks.append(tick)
        for rid, _tok in out:
            if rid in rec.reqs:
                rec.reqs[rid].times.append(tick.end)
    return tick


def window(server: Server, schedule: loadgen.Schedule, seconds: float,
           drain_s: float, finish_s: float = 0.0,
           trace_dir: str | None = None) -> Record:
    """Drive the engine for ``seconds``; with ``trace_dir`` the profiler
    records the whole window, marked by the ``bench.window`` host span,
    and stops once the drain is over."""
    import jax

    rec = Record(server.model, server.max_active,
                 open_loop=schedule.open_loop)
    pending = list(schedule.items)
    pending.reverse()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
    rec.hit0 = server.hit_counter()
    t0 = clock()
    rec.t0 = t0
    deadline = t0 + seconds
    closed = False

    def feed(now):
        if schedule.open_loop:
            while pending and t0 + pending[-1].due_s <= now:
                it = pending.pop()
                with jax.profiler.TraceAnnotation("bench.submit"):
                    _submit(server, rec, it, t0 + it.due_s)
        else:
            while pending and len(server.waiting) < schedule.backlog:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    _submit(server, rec, pending.pop(), clock())

    while True:
        now = clock()
        if not closed and now >= deadline:
            closed = True
            last = rec.ticks[-1].end if rec.ticks else deadline
            rec.t_end = max(last, deadline)
            rec.hit1 = server.hit_counter()
            rec.traced = (0, len(rec.ticks))
            if trace_dir is not None:
                span.__exit__(None, None, None)
        if closed:
            if not schedule.open_loop or now >= rec.t_end + drain_s:
                break
            if all(r.times for r in rec.reqs.values()
                   if r.due <= rec.t_end):
                break
        feed(now)
        if not server.busy():
            if not pending:
                raise RuntimeError("the schedule ran out")
            if schedule.open_loop:
                wake = t0 + pending[-1].due_s
                if not closed:
                    wake = min(wake, deadline)
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(0.0, wake - clock()))
            continue
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            _record_step(server, rec)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    due = ([r for r in rec.reqs.values() if r.due <= rec.t_end]
           if schedule.open_loop else [])
    finish_by = clock() + finish_s
    while (any(not r.req.done for r in due) and server.busy()
           and clock() < finish_by):
        _record_step(server, rec)
    for rid, r in rec.reqs.items():
        r.first_token, r.first_logits = server.first.get(rid, (-1, None))
    return rec


# ------------------------------------------------------------- metrics


def end_to_end(rec: Record, drain_s: float) -> dict:
    """Host-clock metrics of the window."""
    t0, t1 = rec.t0, rec.t_end
    tokens = sum(1 for r in rec.reqs.values() for t in r.times
                 if t0 <= t <= t1)
    gaps = [(b - a) * 1e3 for r in rec.reqs.values()
            for a, b in zip(r.times, r.times[1:]) if t0 <= a and b <= t1]
    out = {"out_tok_s": tokens / rec.window_s}
    if gaps:
        out["itl_p95_ms"] = harness.percentile(gaps, 95)
    due = [r for r in rec.reqs.values() if r.due <= t1]
    if rec.open_loop and due:
        censor = t1 + drain_s
        ttft = [((r.times[0] if r.times else censor) - r.due) * 1e3
                for r in due]
        out["ttft_p50_ms"] = harness.percentile(ttft, 50)
        out["ttft_p95_ms"] = harness.percentile(ttft, 95)
        out["ttft_samples"] = len(ttft)
        out["unserved"] = sum(1 for r in due if not r.times)
    return out


# ---------------------------------------------------------- correctness


def sample(rec: Record, seed: int, max_seqs: int, min_tokens: int) -> list:
    """Finished requests, drawn from the seed; the one with the most served
    tokens always in."""
    served = [r for r in rec.reqs.values()
              if r.first_token >= 0 and r.req.done]
    if not served:
        return []
    served.sort(key=lambda r: (-len(r.req.generated), r.item.rid))
    rng = np.random.default_rng(np.random.SeedSequence([seed % (1 << 63),
                                                        11]))
    rest = [served[i] for i in rng.permutation(len(served) - 1) + 1]
    pick, n = [served[0]], len(served[0].req.generated) + 1
    for r in rest:
        if len(pick) >= max_seqs or n >= min_tokens:
            break
        pick.append(r)
        n += len(r.req.generated) + 1
    return pick


def compare(server: Server, picks: list, pad_to: int,
            modes=("f32",)) -> dict:
    """Per mode, the mean and the largest gap over every served token of
    the sampled requests, and the largest error of a prompt's logits row
    (see the reference's ``served_gaps``); and the number of served tokens
    compared."""
    agg = {m: {"max": 0.0, "sum": 0.0, "n": 0, "row": 0.0} for m in modes}
    for r in picks:
        toks = list(r.item.prompt) + [r.first_token] + list(r.req.generated)
        g = server.ref.served_gaps(server.weights, server.model, toks,
                                   len(r.item.prompt), modes, pad_to,
                                   first_logits=r.first_logits)
        for m, v in g.items():
            a = agg[m]
            a["max"] = max(a["max"], v["max"])
            a["sum"] += v["sum"]
            a["n"] += v["n"]
            a["row"] = max(a["row"], v["row_err"])
    out = {"served_tokens": agg["f32"]["n"]}
    for m, a in agg.items():
        out[m] = a["sum"] / a["n"] if a["n"] else float("inf")
        out[m + "_max"] = a["max"]
        out[m + "_row"] = a["row"] if a["n"] else float("inf")
    return out


def decide(gaps: dict, mode: str, chk: dict, compiles: int) -> tuple:
    """``correct`` and the numbers it compared, each with its limit, for
    the readings of ``mode`` in ``gaps`` (``f32``: the program's).  A
    reading is compared where the configuration's ``check`` gives it a
    limit."""
    read = {"logit_gap_mean": gaps[mode],
            "first_logits_err": gaps[mode + "_row"]}
    checks = {k: {"value": v, "limit": chk[k]} for k, v in read.items()
              if k in chk}
    checks["tokens_compared_min"] = {"value": gaps["served_tokens"],
                                     "limit": chk["min_tokens_compared"]}
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    correct = (all(c["value"] <= c["limit"] for k, c in checks.items()
                   if k in read)
               and gaps["served_tokens"] >= chk["min_tokens_compared"]
               and compiles == 0)
    return correct, checks


NO_GAPS = {"f32": float("inf"), "f32_max": float("inf"),
           "f32_row": float("inf"), "served_tokens": 0}


# ------------------------------------------------------------------ run


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        devices: list, started: float) -> dict:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    clk = harness.CompileClock()
    conf = cell.config
    chk = conf["check"]
    server = Server(cell, seed)
    schedule = loadgen.generate(cell.traffic, seed, server.model["vocab_size"])
    _warm(server, schedule, seed)
    compiles0 = clk.count
    trace_dir = None
    if trace:
        import shutil

        trace_dir = harness.scratch_dir("trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.time() - started  # the window starts now
    rec = window(server, schedule, seconds, chk["drain_s"], chk["finish_s"],
                 trace_dir)
    compiles = clk.count - compiles0
    device = harness.device_record(devices)
    rec.device_kind = device["kind"]
    server.free()
    picks = sample(rec, seed, chk["max_seqs"], chk["min_tokens"])
    gaps = (compare(server, picks, loadgen.max_tokens(cell.traffic))
            if picks else NO_GAPS)
    e2e = end_to_end(rec, chk["drain_s"])
    metrics, breakdown = {}, None
    if trace:
        from bench import trace as tr

        rec.trace = tr.reduce(tr.load(tr.find_xplane(trace_dir)))
        if rec.trace is not None:
            device["busy_s"] = rec.trace.busy_mean_s
            device["window_s"] = rec.trace.window_s
            breakdown = tr.breakdown(rec.trace)
        for m in cell.per_layer:
            v = harness.load_reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    info = {k: v for k, v in e2e.items() if k not in metrics}
    print("window: " + " ".join(f"{k}={v}" for k, v in info.items())
          + f" ticks={len(rec.window_ticks())} window_s={rec.window_s}"
          f" logit_gap_max={gaps['f32_max']}"
          f" first_logits_err={gaps['f32_row']}"
          f" compile_s={clk.seconds}", flush=True)
    correct, checks = decide(gaps, "f32", chk, compiles)
    if rec.open_loop:
        due = [r for r in rec.reqs.values() if r.due <= rec.t_end]
        attempted, failed = len(due), sum(1 for r in due if not r.times)
    else:
        attempted = sum(1 for r in rec.reqs.values() if r.submit <= rec.t_end)
        failed = 0
    return harness.result_line(correct=correct, attempted=attempted,
                               failed=failed, metrics=metrics, device=device,
                               checks=checks, breakdown=breakdown)
