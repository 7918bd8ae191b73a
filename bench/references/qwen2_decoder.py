"""Plain float32 reference of a Qwen2-style decoder (Qwen1.5 models).

Straight ``jax.numpy`` at ``Precision.HIGHEST``, no kernel, cache or
batching: embedding, then per layer RMSNorm, attention with biased q/k/v
projections, rotary embedding (half-split, base ``rope_theta``), causal
softmax and output projection, RMSNorm, SwiGLU MLP; final RMSNorm and an
untied head.  It imports nothing of the program under test.

One departure from the published description, kept because the weights
are made here in the served layout: an RMSNorm weight is stored as its
offset from 1 (the scale is ``1 + w``).

The weights are made here too, from a seed, in the served type, by one
jitted call.  ``init_weights`` returns the nested layout the served program
takes (``embed``, ``final_norm``, ``head``, and the layers stacked along a
leading axis under ``main``); the reference reads the same arrays.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(m: dict) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    return {"L": m["num_hidden_layers"], "d": d, "H": H,
            "KV": m["num_key_value_heads"], "D": m.get("head_dim", d // H),
            "F": m["intermediate_size"], "V": m["vocab_size"],
            "eps": m["rms_norm_eps"], "theta": m["rope_theta"]}


@partial(jax.jit, static_argnums=(1, 2, 3))
def _init(key, shape_key, dtype_name, logit_std):
    L, d, H, KV, D, F, V = shape_key
    dtype = jnp.dtype(dtype_name)
    ks = iter(jax.random.split(key, 16))

    def n(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    out = 1.0 / L ** 0.5  # residual branches shrink with depth
    layers = {
        "norm1": {"scale": n((L, d), 0.1)},
        "attn": {"wq": n((L, d, H, D), d ** -0.5),
                 "wk": n((L, d, KV, D), d ** -0.5),
                 "wv": n((L, d, KV, D), d ** -0.5),
                 "wo": n((L, H, D, d), out * (H * D) ** -0.5),
                 "bq": n((L, H, D), 0.1), "bk": n((L, KV, D), 0.1),
                 "bv": n((L, KV, D), 0.1)},
        "norm2": {"scale": n((L, d), 0.1)},
        "mlp": {"w_gate": n((L, d, F), d ** -0.5),
                "w_up": n((L, d, F), d ** -0.5),
                "w_down": n((L, F, d), out * F ** -0.5)},
    }
    return {"embed": {"table": n((V, d), 1.0)},
            "final_norm": {"scale": n((d,), 0.1)},
            "head": {"w": n((d, V), logit_std * d ** -0.5)},
            "prefix": [], "main": [layers], "tail": []}


def init_weights(key, m: dict, dtype: str, logit_std: float = 4.0) -> dict:
    """Random weights of model ``m`` (HF config keys) in ``dtype``, made on
    the device in one program.  ``V`` rows are padded to a multiple of
    128 (the served layout); padded rows are never a served token."""
    g = dims(m)
    V = -(-g["V"] // 128) * 128
    return _init(key, (g["L"], g["d"], g["H"], g["KV"], g["D"], g["F"], V),
                 jnp.dtype(dtype).name, float(logit_std))


# ------------------------------------------------------------- forward


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq  # (S, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, g, pw, pa):
    """One decoder layer over a sequence ``x`` (S, d), float32; ``pw`` and
    ``pa`` round weights and matmul operands (identity in the reference)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    a = lp["attn"]
    mm = partial(jnp.einsum, precision=HIGHEST,
                 preferred_element_type=jnp.float32)
    h = pa(_rms(x, lp["norm1"]["scale"], g["eps"]))
    q = mm("sd,dhe->she", h, pw(a["wq"])) + a["bq"].astype(jnp.float32)
    k = mm("sd,dhe->she", h, pw(a["wk"])) + a["bk"].astype(jnp.float32)
    v = mm("sd,dhe->she", h, pw(a["wv"])) + a["bv"].astype(jnp.float32)
    q, k = _rope(q, pos, g["theta"]), _rope(k, pos, g["theta"])
    rep = g["H"] // g["KV"]  # query head h reads kv head h // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = mm("qhe,khe->hqk", pa(q), pa(k)) / np.sqrt(g["D"])
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("hqk,khe->qhe", pa(p), pa(v))
    x = x + mm("qhe,hed->qd", pa(o), pw(a["wo"]))
    h2 = pa(_rms(x, lp["norm2"]["scale"], g["eps"]))
    ml = lp["mlp"]
    act = (jax.nn.silu(mm("sd,df->sf", h2, pw(ml["w_gate"])))
           * mm("sd,df->sf", h2, pw(ml["w_up"])))
    return x + mm("sf,fd->sd", pa(act), pw(ml["w_down"]))


def _quantize(w, kind: str):
    """``w`` rounded to int8 or fp8 (e4m3) with one scale per tensor."""
    w = w.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    if kind == "int8":
        return jnp.round(w / amax * 127.0) * (amax / 127.0)
    if kind == "fp8":
        return (w / amax * 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * (amax / 448.0)
    raise ValueError(f"unknown precision {kind!r}")


def _rounding(mode: str):
    """``(weights, operands)`` transforms: identity in float32 (the
    reference), or a lower precision (the control).  ``int8`` and ``fp8``
    round the weight matrices and keep every matmul operand in bfloat16;
    ``int8-all`` and ``fp8-all`` compute in that precision: weights and
    operands alike."""
    def f32(w):
        return w.astype(jnp.float32)

    if mode == "f32":
        return f32, f32

    def bf16(w):
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    kind, _, scope = mode.partition("-")
    if scope == "all":
        def low(w):
            return _quantize(w, kind)

        return low, low
    if scope:
        raise ValueError(f"unknown precision {mode!r}")
    return (lambda w: bf16(_quantize(w, kind))), bf16


@partial(jax.jit, static_argnums=(2, 3))
def logits(weights, tokens, shape_key, mode="f32"):
    """Float32 logits (S, V) for ``tokens`` (S,), layer by layer: a scan
    over the stacked layers takes one layer's weights at a time."""
    g = dict(zip(("L", "d", "H", "KV", "D", "F", "V", "eps", "theta"),
                 shape_key))
    pw, pa = _rounding(mode)
    x = pw(weights["embed"]["table"])[tokens]

    def body(x, lp):
        return _layer(x, lp, g, pw, pa), None

    x, _ = jax.lax.scan(body, x, weights["main"][0])
    x = pa(_rms(x, weights["final_norm"]["scale"], g["eps"]))
    out = jnp.einsum("sd,dv->sv", x, pw(weights["head"]["w"]),
                     precision=HIGHEST, preferred_element_type=jnp.float32)
    return out[:, :g["V"]]


def shape_key(m: dict) -> tuple:
    g = dims(m)
    return tuple(g[k] for k in ("L", "d", "H", "KV", "D", "F", "V", "eps",
                                "theta"))


def served_gaps(weights, m: dict, tokens: list, first: int,
                modes=("f32",), pad_to: int = 0, first_logits=None) -> dict:
    """Gaps of served tokens against the float32 reference.

    ``tokens`` is the prompt followed by every served token; served tokens
    start at index ``first``.  For the reference (``f32``) the gap at a
    served position is how far the served token's logit lies below the
    reference's best.  For a lower-precision mode it is the same gap for
    the token that mode puts first at that position.  Returns, per mode,
    the largest gap, the sum of the gaps, the number of positions, and
    ``row_err``: the error of the logits row at the prompt's last position
    (the program's ``first_logits`` for ``f32``, the mode's own row
    otherwise), as the norm of its difference from the reference's row
    over the norm of that row (0 for ``f32`` without ``first_logits``).
    Sequences are padded at the end to ``pad_to`` (one compiled program
    for every length) or to a multiple of 256."""
    toks = np.asarray(tokens, np.int32)
    S = len(toks) - 1  # the last served token is predicted, never fed
    pad = max(pad_to, -(-S // 256) * 256)
    feed = np.zeros(pad, np.int32)
    feed[:S] = toks[:-1]
    key = shape_key(m)
    ref = logits(weights, jnp.asarray(feed), key, "f32")
    rows = jnp.arange(first - 1, S)
    ref_rows = ref[rows]
    best = jnp.max(ref_rows, axis=-1)
    ref_row = np.asarray(ref_rows[0], np.float64)

    def row_err(row):
        d = np.asarray(row, np.float64) - ref_row
        return float(np.linalg.norm(d) / np.linalg.norm(ref_row))

    def gaps_of(pick, row):
        g = best - jnp.take_along_axis(ref_rows, pick[:, None], 1)[:, 0]
        return {"max": float(jnp.max(g)), "sum": float(jnp.sum(g)),
                "n": int(g.shape[0]),
                "row_err": 0.0 if row is None else row_err(row)}

    out = {"f32": gaps_of(jnp.asarray(toks[first:]), first_logits)}
    for mode in modes:
        if mode != "f32":
            low = logits(weights, jnp.asarray(feed), key, mode)[rows]
            out[mode] = gaps_of(jnp.argmax(low, axis=-1), low[0])
    return out
