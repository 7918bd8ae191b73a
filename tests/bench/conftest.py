"""Shared set-up of the benchmark's CPU tests: ``bench`` importable, and a
tiny serving configuration with its traffic."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "vocab_size": 512,
              "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
              "tie_word_embeddings": False, "qkv_bias": True}

TINY_CONFIG = {
    "name": "tiny", "kind": "serve", "arch": "qwen1.5-4b",
    "model": TINY_MODEL,
    "deployment": {"dtype": "float32", "attn_impl": "kernel",
                   "max_active": 4, "block_size": 4, "prefill_chunk": 4,
                   "num_blocks": 64},
    "reference": "qwen2_decoder",
    # the program computes in float32 on the CPU: rounding alone
    "check": {"logit_gap_mean": 1e-4, "first_logits_err": 1e-4,
              "max_seqs": 4, "min_tokens": 40, "min_tokens_compared": 10,
              "drain_s": 5, "finish_s": 5},
}

TINY_TRAFFIC = {
    "sessions": {
        "arrival": {"kind": "poisson", "rate_per_s": 6.0, "period_s": 0.5},
        "requests": 80,
        "shape_seed": 0,
        "sessions": {"count": 4, "zipf": 1.1, "system_prompts": 2,
                     "system_prompt_tokens": 16, "max_prompt_tokens": 48},
        "prompt_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "output_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}},
    "batch": {
        "arrival": {"kind": "backlog", "depth": 8}, "requests": 400,
        "shape_seed": 0,
        "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 24},
        "output_tokens": {"median": 5, "sigma": 0.5, "min": 2, "max": 10}},
}


@pytest.fixture
def bench_env(monkeypatch, tmp_path):
    """The compile cache and the run's scratch files under ``tmp_path``."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    from bench import harness

    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    return tmp_path
