"""The control of the serving check at a test size: the reference put in
the program's place, at a precision below the configuration's, fails the
limit that sound runs pass."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

from conftest import TINY_CONFIG, TINY_MODEL


@pytest.fixture(scope="module")
def ref():
    return harness.load_reference("qwen2_decoder")


def greedy_sequence(ref, w, prompt, n):
    toks = list(prompt)
    key = ref.shape_key(TINY_MODEL)
    for _ in range(n):
        lg = ref.logits(w, jnp.asarray(toks, jnp.int32), key, "f32")
        toks.append(int(jnp.argmax(lg[-1])))
    return toks


@pytest.mark.parametrize("mode", ["int8", "fp8", "int8-all", "fp8-all"])
def test_lower_precision_control_fails_the_limit(ref, mode):
    w = ref.init_weights(jax.random.key(3), TINY_MODEL, "float32")
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, TINY_MODEL["vocab_size"], 200).tolist()
    toks = greedy_sequence(ref, w, prompt, 8)
    limit = TINY_CONFIG["check"]["logit_gap_mean"]
    # the control's reading at every position after the first: what the
    # token it puts first loses against the reference's best
    g = ref.served_gaps(w, TINY_MODEL, toks, 1, ("f32", mode))[mode]
    assert g["n"] == len(toks) - 1
    assert g["sum"] / g["n"] > limit
    assert g["row_err"] > TINY_CONFIG["check"]["first_logits_err"]
    # put in the program's place, the harness's own verdict is "incorrect"
    from bench.drivers import serve

    gaps = {"served_tokens": g["n"], mode: g["sum"] / g["n"],
            mode + "_row": g["row_err"]}
    correct, checks = serve.decide(gaps, mode, TINY_CONFIG["check"], 0)
    assert correct is False
    assert checks["logit_gap_mean"]["value"] > limit
    # the reference's own greedy tokens read 0
    own = ref.served_gaps(w, TINY_MODEL, toks, len(prompt), ("f32",))
    assert own["f32"]["max"] == pytest.approx(0.0, abs=1e-6)


def test_reference_matches_the_program_forward():
    """The plain reference and the program's own float32 forward agree on
    the same weights (tiny size, CPU)."""
    from bench.drivers import serve
    from repro.models import forward, ModelOptions

    ref = harness.load_reference("qwen2_decoder")
    w = ref.init_weights(jax.random.key(5), TINY_MODEL, "float32")
    toks = np.random.default_rng(1).integers(0, 512, 37).astype(np.int32)
    arch = serve.arch_config(TINY_CONFIG)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(w, arch, jnp.asarray(toks)[None],
                         opts=ModelOptions(compute_dtype="float32"))
    want = ref.logits(w, jnp.asarray(toks), ref.shape_key(TINY_MODEL))
    np.testing.assert_allclose(np.asarray(got[0, :, :512]),
                               np.asarray(want), atol=2e-3, rtol=2e-3)
