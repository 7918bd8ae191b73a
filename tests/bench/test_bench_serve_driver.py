"""The serve driver end to end on the CPU at a tiny size: ``bench/run.py``
with the look for a chip replaced in the test, the result line, and
``correct`` turning false under faults planted in the timed path."""

from __future__ import annotations

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_CONFIG, TINY_TRAFFIC

SEED = 2**31 + 12345  # past 32 signed bits, as the driver's seeds are


def tiny_cell(kind: str, trace: bool = False):
    from bench import harness

    spec = harness.load_spec()
    name = f"qwen1.5-4b.{kind}"
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    return harness.Cell("tiny." + kind, 1, copy.deepcopy(TINY_CONFIG),
                        copy.deepcopy(TINY_TRAFFIC[kind]), e2e, layer)


def run_cell(monkeypatch, capsys, kind: str, trace: int = 0,
             seconds: float = 1.5) -> dict:
    """``bench/run.py`` as the driver calls it, on CPU devices."""
    from bench import harness, peaks, run

    cell = tiny_cell(kind)
    monkeypatch.setattr(harness, "resolve", lambda spec, name: cell)
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    run.main(["--workload", cell.name, "--seed", str(SEED), "--seconds",
              str(seconds), "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("kind", ["sessions", "batch"])
def test_driver_runs_end_to_end(monkeypatch, capsys, bench_env, kind):
    line = run_cell(monkeypatch, capsys, kind)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["device"]["platform"] == "cpu"
    want = {"itl_p95_ms", "setup_s"}
    want.add("ttft_p50_ms" if kind == "sessions" else "out_tok_s")
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["checks"]["compiles_in_window"]["value"] == 0
    assert line["checks"]["first_logits_err"]["value"] < 1e-4


@pytest.mark.parametrize("kind,want", [
    ("sessions", {"gen_lag_p95_ms", "prefix_hit_share", "step_ms.prefill"}),
    ("batch", {"batch_occupancy", "serve_mfu"})])
def test_traced_run_reports_per_layer_metrics(monkeypatch, capsys,
                                              bench_env, kind, want):
    line = run_cell(monkeypatch, capsys, kind, trace=1)
    assert line["correct"] is True
    # the CPU trace has no TPU plane: device metrics are left out
    assert "paged_attn_roofline" not in line["metrics"]
    assert want <= set(line["metrics"])
    for name in ("prefix_hit_share", "batch_occupancy"):
        if name in line["metrics"]:
            assert 0 < line["metrics"][name]["value"] <= 100


def test_no_accelerator_exits_without_result(monkeypatch, capsys):
    from bench import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen1.5-4b.sessions", "--seed", "1",
                  "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def _alter_tokens(monkeypatch):
    """Every token the engine samples is replaced by the next id."""
    import repro.serve.engine as eng_mod

    class NP:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def argmax(x, *a, **k):
            return (np.argmax(x, *a, **k) + 1) % TINY_CONFIG["model"][
                "vocab_size"]

    monkeypatch.setattr(eng_mod, "np", NP())


def _state_unchanged(monkeypatch):
    """The tick returns the cache state it was given: no K/V is kept."""
    import repro.serve.engine as eng_mod

    make = eng_mod.make_paged_tick

    def broken(*a, **k):
        tick = make(*a, **k)

        def run(params, state, *rest):
            keep = jax.tree.map(jnp.copy, state)
            logits, _ = tick(params, state, *rest)
            return logits, keep

        return run

    monkeypatch.setattr(eng_mod, "make_paged_tick", broken)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_faults_make_the_run_incorrect(monkeypatch, capsys, bench_env,
                                       fault):
    fault(monkeypatch)
    line = run_cell(monkeypatch, capsys, "sessions")
    assert line["correct"] is False
    gap = line["checks"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]


def _exact_work(server):
    """Per step, (tokens, keys) read from the engine's own slots: the
    per-row record the harness cannot see, for the test to hold the
    harness's reading from public counters against."""
    eng = server.engine
    before = {}
    inner = eng.on_metrics

    def on_metrics(m):  # once admitted, before the tick
        before.clear()
        before.update({i: (s.req, s.seq.length)
                       for i, s in enumerate(eng.slots) if s is not None})
        inner(m)

    eng.on_metrics = on_metrics

    def after():
        tokens, keys = 0, 0
        for i, (req, len0) in before.items():
            s = eng.slots[i]
            fed = s.seq.length - len0 if s is not None and s.req is req \
                else 1  # retired after its last decode
            tokens += fed
            keys += sum(len0 + j + 1 for j in range(fed))
        return tokens, keys

    return after


@pytest.mark.parametrize("case,exact", [
    ("one at a time", True), ("equal prompts together", True),
    ("shared prefix, one at a time", True),
    ("unequal prompts together", False)])
def test_work_from_public_counters(bench_env, case, exact):
    from bench.drivers import serve
    from repro.serve import Request

    server = serve.Server(tiny_cell("batch"), SEED)
    exact_after = _exact_work(server)
    rng = np.random.default_rng(4)
    V = TINY_CONFIG["model"]["vocab_size"]
    head = rng.integers(0, V, 11).tolist()
    waves = {
        "one at a time": [[13], [6], [9]],
        "equal prompts together": [[8, 8, 8]],
        "shared prefix, one at a time": [[0], [5], [3]],
        "unequal prompts together": [[5, 13, 9, 3]],
    }[case]
    got, want, rid = [], [], 0
    for wave in waves:
        for n in wave:
            prompt = (head + rng.integers(0, V, n).tolist()
                      if case.startswith("shared")
                      else rng.integers(0, V, n).tolist())
            server.submit(Request(rid=rid, prompt=prompt, max_new_tokens=3))
            rid += 1
        while server.busy():
            tick, _ = server.step()
            want.append(exact_after())
            got.append((tick.tokens, tick.keys) if tick else (0, 0))
    assert [g[0] for g in got] == [w[0] for w in want]
    if case.startswith("shared"):
        assert server.engine.metrics()["prefixHitRate"] > 0
    if exact:
        assert [g[1] for g in got] == pytest.approx([w[1] for w in want])
    else:
        total = sum(w[1] for w in want)
        assert sum(g[1] for g in got) == pytest.approx(total, rel=0.2)
