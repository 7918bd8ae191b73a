"""On-chip smoke run of the system's two accelerator paths, through the
launchers' own entry points.

  python chip_smoke.py                # one chip: train phase, serve phase
  python chip_smoke.py --four-chips   # four chips: sharded vs unsharded train

Serve phase: full-width qwen1.5-4b (40 layers, bfloat16 weights, random
from ``--seed``) served by ``PagedServeEngine``, built by
``launch.serve.build_engine`` with the compiled paged Pallas kernel.  Eight
requests, prompts of 64-512 tokens drawn from the seed, two pairs sharing
a 256-token prefix (the second of a pair arrives once the first's prompt is
cached), 32 new tokens each.  Checks: every request ends with 32 in-vocab
tokens, the pairs hit the prefix cache, and the kernel path's first-token
logits match the pure-jnp ``gather`` path's for the same prompts (run
after it, so one pool exists at a time).  Before the engines, the compiled
kernel alone is held to the plain f32 reference (``kernels.ref``) on a
random pool of the served shape, at the served sequence lengths.

Train phase: three steps of ``launch.train.train_direct``, unsharded, on
qwen1.5-4b's published widths cut to 2 layers and an eighth of the
vocabulary; the losses must be finite.

``--four-chips`` runs only the sharded step on a (data=2, model=2) mesh
over four chips and the same step unsharded on one of them, two steps
each, and compares losses, gradients and parameters.

Everything runs in this one process: no phase starts a child that uses
JAX.  Nothing is caught: a phase that raises or a check that fails exits
nonzero, and so does a run that finds no TPU, before any phase starts.
The last line of output is one JSON object naming the device.

``peak_bytes_in_use`` is the device's peak since the process started, so
the smaller train phase runs first and each phase's line reads its own
peak.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.decode_attention import paged_decode_attention  # noqa: E402
from repro.kernels.ref import paged_decode_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_engine, serving_options  # noqa: E402
from repro.launch.train import train_direct  # noqa: E402
from repro.models import init_serving_params  # noqa: E402
from repro.serve import Request  # noqa: E402
from repro.train.optim import OptimizerConfig  # noqa: E402

ARCH = "qwen1.5-4b"
# serve phase
MAX_ACTIVE = 8
NUM_BLOCKS = 256  # 255 usable x 16 tokens; 1.56 GiB of bf16 K/V at 40 layers
BLOCK_SIZE = 16
PREFILL_CHUNK = 16
PROMPT_RANGE = (64, 512)
SHARED_PREFIX = 256
MAX_NEW = 32
# kernel vs gather first-token logits: max |diff| over the vocab, relative
# to the largest |logit|.  Both paths keep bf16 K/V and round attention
# output to bf16.  On a TPU v5e the sound kernel reads 0.0232; with the K/V
# heads read in reverse it reads 1.65, with each table row read one block
# late 1.34.
LOGITS_RTOL = 5e-2
# compiled kernel vs the f32 reference, same relative measure.  On a TPU
# v5e: sound 0.0019; heads reversed 1.1, table one block late 0.71, mask
# admitting the slot at ``length`` 0.25.
KERNEL_RTOL = 2e-2
# train phase: published widths, 2 layers, vocab / 8
TRAIN_LAYERS = 2
TRAIN_VOCAB_DIV = 8
TRAIN_BATCH, TRAIN_SEQ = 8, 512
# four-chip comparison (bf16 compute; sharded sums reduce in another
# order).  AdamW's first moment is a blend of the gradients, so it is
# compared in relative L2; AdamW moves a parameter by about lr_t per step
# whatever the gradient, so parameters are held to 4x the summed rates.
LOSS_ATOL = 2e-2
GRAD_NORM_RTOL = 5e-2
MOMENT_RTOL = 1e-1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


class CompileClock:
    """Seconds the backend spent compiling, summed from JAX's own event."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def device_label() -> str:
    d = jax.devices()[0]
    return f"{d.platform}/{d.device_kind} x{len(jax.devices())}"


def peak_bytes() -> list:
    """Per device, where the backend reports it (the TPU one does)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
            for d in jax.devices()]


def report(phase: str, clock: CompileClock, compile0: float, **fields):
    parts = [f"compile_s={clock.seconds - compile0:.1f}"]
    parts += [f"{k}={v}" for k, v in fields.items()]
    parts.append(f"peak_bytes_in_use={peak_bytes()}")
    print(f"[{device_label()}] {phase}: " + " ".join(parts), flush=True)


# ------------------------------------------------------------------ serve


def make_prompts(vocab: int, seed: int):
    """Eight prompts; 0/1 and 2/3 share a SHARED_PREFIX-token prefix.
    Returns (prompts, {follower rid: leader rid})."""
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_RANGE
    lengths = rng.integers(lo, hi + 1, size=MAX_ACTIVE)
    lengths[:4] = rng.integers(SHARED_PREFIX + BLOCK_SIZE, hi + 1, size=4)
    prompts = [rng.integers(0, vocab, size=n).tolist() for n in lengths]
    for leader, follower in ((0, 1), (2, 3)):
        prompts[follower][:SHARED_PREFIX] = prompts[leader][:SHARED_PREFIX]
    return prompts, {1: 0, 3: 2}


def drive(engine, prompts, followers, max_new):
    """Serve ``prompts``; a follower is submitted once its leader has
    decoded a token, i.e. once the leader's prompt is in the prefix cache.
    Returns (requests, ticks, first-token logits by rid)."""
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    first = {}
    engine.on_first_logits = lambda req, row: first.__setitem__(
        req.rid, np.array(row))
    waiting = dict(followers)
    for r in reqs:
        if r.rid not in waiting:
            engine.submit(r)
    ticks = 0
    while engine.queue or engine.slots_busy or waiting:
        for f, leader in list(waiting.items()):
            if reqs[leader].generated:
                engine.submit(reqs[f])
                del waiting[f]
        engine.step()
        ticks += 1
        check(ticks < 10_000, "serve drains")
    return reqs, ticks, first


def kernel_check(cfg, lengths, seed: int) -> float:
    """Compiled paged kernel against the f32 reference on a random bf16
    pool of the served shape: each sequence of ``lengths`` holds its own
    blocks, scattered over the pool.  Returns the largest |difference|
    relative to the largest |reference output|."""
    KV, D = cfg.num_kv_heads, cfg.head_dim
    nblk = [-(-n // BLOCK_SIZE) for n in lengths]
    T = max(nblk)
    perm = np.random.default_rng(seed).permutation(NUM_BLOCKS)
    tables = np.zeros((len(lengths), T), np.int32)
    offsets = np.cumsum([0] + nblk)
    check(offsets[-1] <= NUM_BLOCKS, "kernel check fits the pool")
    for b, n in enumerate(nblk):
        tables[b, :n] = perm[offsets[b]:offsets[b] + n]
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    pool = (NUM_BLOCKS, KV, BLOCK_SIZE, D)
    q = jax.random.normal(kq, (len(lengths), cfg.num_heads, D), jnp.bfloat16)
    k = jax.random.normal(kk, pool, jnp.bfloat16)
    v = jax.random.normal(kv, pool, jnp.bfloat16)
    tables, lens = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    got = np.asarray(paged_decode_attention(q, k, v, tables, lens)
                     .astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(paged_decode_attention_ref(
            q.astype(jnp.float32), k, v, tables, lens))
    check(bool(np.all(np.isfinite(got))), "kernel output finite")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def serve_phase(seed: int, clock: CompileClock) -> None:
    c0 = clock.seconds
    cfg = get_config(ARCH)
    opts = serving_options()
    check(opts.compute_dtype == "bfloat16", "bf16 compute on the chip")
    t0 = time.perf_counter()
    params = init_serving_params(jax.random.key(seed), cfg, opts.dtype)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    leaves = jax.tree.leaves(params)
    check(all(x.dtype == opts.dtype for x in leaves), "bf16 weights")
    check(params["main"][0]["attn"]["wq"].shape[0] == cfg.num_layers == 40,
          "all 40 layers")
    n_params = sum(x.size for x in leaves)
    prompts, followers = make_prompts(cfg.vocab_size, seed)
    kernel_rel = kernel_check(cfg, [len(p) + MAX_NEW for p in prompts], seed)
    check(kernel_rel <= KERNEL_RTOL,
          f"kernel vs reference: {kernel_rel:.3g} > {KERNEL_RTOL}")

    # kernel path: the served path
    engine = build_engine(cfg, params, opts, num_blocks=NUM_BLOCKS,
                          block_size=BLOCK_SIZE, max_active=MAX_ACTIVE,
                          prefill_chunk=PREFILL_CHUNK, attn_impl="kernel")
    t0 = time.perf_counter()
    reqs, ticks, kernel_logits = drive(engine, prompts, followers, MAX_NEW)
    serve_s = time.perf_counter() - t0
    m = engine.metrics()
    for r in reqs:
        check(r.done and len(r.generated) == MAX_NEW,
              f"request {r.rid} finished with {MAX_NEW} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.generated),
              f"request {r.rid} tokens in vocab")
    check(m["prefixHitRate"] > 0, "shared prefixes hit the cache")
    check(m["blocksFree"] == m["blocksTotal"] - m["blocksCached"],
          "every block returned or cached")
    check(len(kernel_logits) == len(prompts), "first-token logits recorded")
    del engine  # one pool at a time

    # gather path: same prompts, first token only
    engine = build_engine(cfg, params, opts, num_blocks=NUM_BLOCKS,
                          block_size=BLOCK_SIZE, max_active=MAX_ACTIVE,
                          prefill_chunk=PREFILL_CHUNK, attn_impl="gather")
    _, _, gather_logits = drive(engine, prompts, followers, 1)
    del engine
    worst = 0.0
    V = cfg.vocab_size  # padded vocab entries hold the mask value
    for rid, want in gather_logits.items():
        got, want = kernel_logits[rid][:V], want[:V]
        check(bool(np.all(np.isfinite(got))), f"request {rid} logits finite")
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        worst = max(worst, rel)
        check(rel <= LOGITS_RTOL,
              f"request {rid} kernel vs gather logits: {rel:.3g} > "
              f"{LOGITS_RTOL}")
    report("serve", clock, c0, arch=ARCH, layers=cfg.num_layers,
           params=n_params, weights_init_s=f"{init_s:.1f}",
           requests=len(reqs), prompt_tokens=sum(map(len, prompts)),
           new_tokens=sum(len(r.generated) for r in reqs), ticks=ticks,
           serve_s=f"{serve_s:.1f}", prefix_hit_rate=f"{m['prefixHitRate']:.3f}",
           kernel_rel_diff=f"{kernel_rel:.3g}", logits_rel_diff=f"{worst:.3g}",
           attn="pallas-compiled")


# ------------------------------------------------------------------ train


def train_config():
    base = get_config(ARCH)
    return base.with_(name=f"{ARCH}-{TRAIN_LAYERS}l",
                      num_layers=TRAIN_LAYERS,
                      vocab_size=base.vocab_size // TRAIN_VOCAB_DIV)


def train_phase(seed: int, clock: CompileClock) -> None:
    c0 = clock.seconds
    cfg = train_config()
    run = train_direct(cfg, steps=3, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                       seed=seed)
    for i, h in enumerate(run.history):
        check(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"]),
              f"step {i} loss finite")
    report("train", clock, c0, arch=cfg.name, params=cfg.param_count(),
           steps=len(run.history),
           losses=[round(h["loss"], 4) for h in run.history],
           step_s=[round(h["seconds"], 3) for h in run.history])


def four_chip_phase(seed: int, clock: CompileClock) -> None:
    c0 = clock.seconds
    check(len(jax.devices()) == 4, "four chips")
    cfg = train_config()
    kw = dict(steps=2, batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=seed)
    sharded = train_direct(cfg, mesh_shape=(2, 2), **kw)
    hlo = sharded.compiled.as_text()
    check("num_partitions=4" in hlo, "step partitioned over four devices")
    # the embedding table (vocab -> model, d_model -> data) enters the
    # partitioned program as a quarter of itself on every device
    quarter = f"f32[{cfg.padded_vocab // 2},{cfg.d_model // 2}]"
    entry = hlo.split("\nENTRY", 1)[1].split("\n", 1)[0]
    check(quarter in entry, f"embedding table enters as {quarter}")
    for x in jax.tree.leaves(sharded.state["params"]):
        check(len(x.sharding.device_set) == 4, "parameters on all devices")
    got, got_hist = jax.tree.map(np.asarray, sharded.state), sharded.history
    del sharded
    single = train_direct(cfg, **kw)  # on the default device
    want = jax.tree.map(np.asarray, single.state)

    for i, (a, b) in enumerate(zip(got_hist, single.history)):
        check(abs(a["loss"] - b["loss"]) <= LOSS_ATOL,
              f"step {i} loss {a['loss']} vs {b['loss']}")
        check(abs(a["grad_norm"] - b["grad_norm"])
              <= GRAD_NORM_RTOL * b["grad_norm"],
              f"step {i} grad norm {a['grad_norm']} vs {b['grad_norm']}")
    param_diff = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree.leaves(got["params"]), jax.tree.leaves(want["params"])))
    ocfg = OptimizerConfig()
    param_atol = 4 * sum(ocfg.lr * min(1.0, (t + 1) / ocfg.warmup_steps)
                         for t in range(kw["steps"]))
    check(param_diff <= param_atol,
          f"parameters differ by {param_diff:.3g} > {param_atol:.3g}")
    ma, mb = jax.tree.leaves(got["opt"]["m"]), jax.tree.leaves(want["opt"]["m"])
    moment_rel = math.sqrt(
        sum(float(np.sum((a.astype(np.float64) - b) ** 2))
            for a, b in zip(ma, mb))
        / sum(float(np.sum(b.astype(np.float64) ** 2)) for b in mb))
    check(moment_rel <= MOMENT_RTOL,
          f"first moments differ by {moment_rel:.3g} > {MOMENT_RTOL}")
    report("train (data=2, model=2) vs unsharded", clock, c0, arch=cfg.name,
           params=cfg.param_count(), steps=kw["steps"],
           losses_sharded=[round(h["loss"], 5) for h in got_hist],
           losses_unsharded=[round(h["loss"], 5) for h in single.history],
           param_max_diff=f"{param_diff:.3g}",
           moment_rel_diff=f"{moment_rel:.3g}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-unsharded train step")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {d.platform!r})")
    enable_compile_cache()
    clock = CompileClock()
    if args.four_chips:
        four_chip_phase(args.seed, clock)
    else:
        train_phase(args.seed, clock)
        serve_phase(args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
