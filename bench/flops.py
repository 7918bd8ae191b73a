"""Operations and bytes of a decoder's work, from its shapes.

``m`` is a configuration's model dict (HF config keys).  Matmul FLOPs are
2 per multiply-add; attention adds ``4 * H * D`` FLOPs per key a query
attends (scores and the weighted sum of values), per layer.  The
embedding lookup is a gather and counts no FLOPs.
"""

from __future__ import annotations


def _dims(m: dict) -> tuple:
    d, H = m["hidden_size"], m["num_attention_heads"]
    D = m.get("head_dim", d // H)
    return (m["num_hidden_layers"], d, H, m["num_key_value_heads"], D,
            m["intermediate_size"], m["vocab_size"])


def matmul_params(m: dict) -> int:
    """Weights a token is multiplied by: every layer's projections and MLP,
    and the head."""
    L, d, H, KV, D, F, V = _dims(m)
    per_layer = d * H * D + 2 * d * KV * D + H * D * d + 3 * d * F
    return L * per_layer + d * V


def step_flops(m: dict, tokens: int, keys: float) -> float:
    """FLOPs of ``tokens`` forward passes that attend ``keys`` keys in
    all."""
    L, d, H, KV, D, F, V = _dims(m)
    return 2.0 * matmul_params(m) * tokens + L * 4.0 * H * D * keys


def paged_attention_work(m: dict, queries: int, keys: float,
                         kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of attention for ``queries`` tokens that attend
    ``keys`` keys in all, over all layers: the live K and V rows read
    once, q read and the output written."""
    L, d, H, KV, D, F, V = _dims(m)
    flops = L * 4.0 * H * D * keys
    nbytes = L * (2.0 * KV * D * kv_bytes * keys
                  + 2.0 * H * D * act_bytes * queries)
    return flops, nbytes
