"""The engine's ``serve.*`` spans and the work on its step spans, in a
real profile of the tiny engine on the CPU: the work against the engine's
own rows, the phases in order, idle gaps named by the innermost span, and
the per-layer readers built on them."""

from __future__ import annotations

import gc
import os
import shutil
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, loadgen, spans
from bench import trace as tr
from bench.drivers import serve

from conftest import TINY_CONFIG, TINY_MODEL
from test_bench_serve_driver import SEED, _exact_work, tiny_cell

WAVES = {
    "one at a time": [[13], [6], [9]],
    "equal prompts together": [[8, 8, 8]],
    "shared prefix, one at a time": [[0], [5], [3]],
    "unequal prompts together": [[5, 13, 9, 3]],
}
PHASES = {"admit", "prepare", "dispatch", "tick_wait", "logits_copy",
          "sample", "cache_insert", "hook"}
SLOW = 0.2  # seconds a slow callback takes: longer than a collector's pause
PART = "bench.part"  # the test's span around each part of the run


def _run_waves(server, case, base):
    """Submit the case's waves, each once the last has drained; the
    requests, the exact tokens of each step, and per request the prompt
    tokens the prefix cache served."""
    from repro.serve import Request

    eng = server.engine
    outer = eng.on_metrics
    exact_after = _exact_work(server)
    reqs, want, cached = {}, [], {}
    inner = eng.on_metrics

    def on_metrics(m):  # once admitted: a slot's position is its cached part
        for s in eng.slots:
            if s is not None and s.req.rid in reqs:
                cached.setdefault(s.req.rid, s.pos)
        inner(m)

    eng.on_metrics = on_metrics
    rng = np.random.default_rng(4)
    V = TINY_CONFIG["model"]["vocab_size"]
    head = rng.integers(0, V, 11).tolist()
    try:
        for wave in WAVES[case]:
            for n in wave:
                prompt = (head + rng.integers(0, V, n).tolist()
                          if case.startswith("shared")
                          else rng.integers(0, V, n).tolist())
                rid = base + len(reqs)
                reqs[rid] = Request(rid=rid, prompt=prompt, max_new_tokens=3)
                server.submit(reqs[rid])
            while server.busy():
                server.step()
                want.append(exact_after()[0])
    finally:
        eng.on_metrics = outer
    return reqs, want, cached


def _slow_first_logits(eng, rid, raises):
    """One request whose ``on_first_logits`` sleeps ``SLOW``, and raises
    if asked; the engine drained after."""
    from repro.serve import Request

    inner = eng.on_first_logits

    def slow(req, row):
        time.sleep(SLOW)
        if raises:
            raise RuntimeError("callback failed")

    eng.on_first_logits = slow
    eng.submit(Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=2))
    try:
        for _ in range(4):
            eng.step()
    except RuntimeError:
        assert raises
    finally:
        eng.on_first_logits = inner
        while eng.metrics()["slotsBusy"]:
            eng.step()


def _collecting_step(eng, rid):
    """A step whose ``on_metrics`` runs a full collection."""
    from repro.serve import Request

    inner = eng.on_metrics

    def collect(m):
        gc.collect()
        inner(m)

    eng.on_metrics = collect
    eng.submit(Request(rid=rid, prompt=[4, 5], max_new_tokens=1))
    try:
        eng.step()
    finally:
        eng.on_metrics = inner
        while eng.metrics()["slotsBusy"]:
            eng.step()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tiny engine, gathering its attention, run once under the
    profiler inside a ``bench.window``: the four waves, a step that
    collects garbage, and a slow first-logits callback that returns and
    one that raises, each part in a ``bench.part`` span.  The profile's
    path, its host spans and what each wave submitted."""
    cell = tiny_cell("batch")
    # the kernel's interpreted loops would fill the CPU profile; the spans
    # are the same around any attention
    cell.config["deployment"]["attn_impl"] = "gather"
    server = serve.Server(cell, SEED)
    eng = server.engine
    where = tmp_path_factory.mktemp("profile")
    waves = {}
    with jax.profiler.trace(str(where)):
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for k, case in enumerate(WAVES):
                with jax.profiler.TraceAnnotation(PART, part=f"wave{k}"):
                    waves[case] = _run_waves(server, case, 1000 * k)
            with jax.profiler.TraceAnnotation(PART, part="collect"):
                _collecting_step(eng, 78)
            for rid, raises in ((76, False), (77, True)):
                with jax.profiler.TraceAnnotation(PART, part=f"slow{rid}"):
                    _slow_first_logits(eng, rid, raises)
    path = tr.find_xplane(str(where))
    return SimpleNamespace(path=path, host=spans.load(path), waves=waves,
                           chunk=eng.prefill_chunk)


def _part(traced, part):
    """The steps of one part of the run, and the host spans inside it."""
    (lo, hi), = [(s, e) for n, s, e, st in traced.host
                 if n == PART and st.get("part") == part]
    inside = [sp for sp in traced.host if lo <= sp[1] and sp[2] <= hi]
    return spans.steps(inside), inside


@pytest.mark.parametrize("case", list(WAVES))
def test_record_holds_the_exact_work(traced, case):
    """Each step span carries its tokens exactly, also where prompts of
    unequal length prefill together (the harness's rebuild holds only to
    20% there), and each request's prefill ticks."""
    reqs, want, cached = traced.waves[case]
    steps, _ = _part(traced, f"wave{list(WAVES).index(case)}")
    assert len(steps) == len(want)
    assert [s.stats["fed"] + s.stats["decoded"] for s in steps] == want
    assert sum(s.stats["decoded"] for s in steps) == sum(
        len(q.generated) for q in reqs.values())
    assert all(q.done for q in reqs.values())
    if case.startswith("shared"):
        assert sum(cached.values()) > 0
    # each request's prompt completes once, after a tick per chunk of what
    # the prefix cache did not serve
    first = [f for s in steps for f in s.first]
    C = traced.chunk
    assert sorted(first) == sorted(
        (rid, -(-(len(q.prompt) - cached[rid]) // C))
        for rid, q in reqs.items())
    for s in steps:
        assert s.width in (0, 1, C)
        assert (s.width == 0) == (s.stats["rows"] == 0)
        assert s.stats["gc_s"] >= 0
        assert set(s.phases) <= PHASES
        assert sum(s.phases.values()) <= s.seconds * (1 + 1e-9)
    # the phases cover the steps; summed, since a busy CPU can preempt the
    # process between two phases of any one step (on the chip every step
    # is covered to 99.88% or more)
    assert sum(sum(s.phases.values()) for s in steps) >= 0.9 * sum(
        s.seconds for s in steps)


@pytest.mark.parametrize("raises", [False, True])
def test_callback_time_is_hook_not_sample(traced, raises):
    """A slow ``on_first_logits`` is timed under ``serve.hook``, also when
    it raises out of the step."""
    steps, inside = _part(traced, "slow77" if raises else "slow76")
    hooked = [n for n, s, e, _ in inside
              if n == "serve.hook" and e - s >= SLOW * 1e9]
    assert hooked == ["serve.hook"]
    assert [n for n, s, e, _ in inside
            if n == "serve.sample" and e - s >= SLOW * 1e9] == []
    slow = [s for s in steps if s.phase("hook") >= SLOW]
    assert len(slow) == 1 and slow[0].phase("sample") < SLOW
    # a step that raises has its spans, and no work on them
    assert ("C" in slow[0].stats) is not raises


def test_record_counts_the_collector(traced):
    """A collection inside a step is counted in ``gc_s``."""
    (step, *rest), _ = _part(traced, "collect")
    assert 0 < step.stats["gc_s"] <= step.phase("hook")


def test_spans_nest_in_order_in_a_real_profile(traced):
    """Under the profiler on the CPU, each step is a ``serve.step`` span
    holding its phases in the order they run, with its work as the
    span's metadata."""
    prof = spans.profile(traced.host)
    n_steps = sum(len(w[1]) for w in traced.waves.values())
    assert len(prof.steps) == len(prof.window_steps()) > n_steps
    order = ["admit", "hook", "prepare", "dispatch", "tick_wait",
             "logits_copy", "sample", "cache_insert", "hook"]
    step_spans = [(s, e) for n, s, e, _ in traced.host if n == "serve.step"]
    assert len(step_spans) == len(prof.steps)
    for (s0, e0), step in zip(step_spans, prof.steps):
        inside = [n[len("serve."):] for n, s, e, _ in traced.host
                  if n.startswith("serve.") and n != "serve.step"
                  and s0 <= s and e <= e0]
        it = iter(order)  # the phases run in this order, each optional
        assert all(any(p == q for q in it) for p in inside), inside
        assert sorted(set(inside)) == sorted(step.phases)
        if "C" not in step.stats:
            continue  # the step that raised
        ticked = step.width > 0
        assert ticked == ("tick_wait" in inside)
        if ticked:
            assert 0 < step.tick_s and 0 < step.host_s < step.seconds
    assert {s.width for s in prof.steps} == {0, 1, traced.chunk}


# ------------------------------------------------- idle gaps by span

D0, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6


def span(name, start_ms, dur_ms):
    return (name, start_ms * MS, (start_ms + dur_ms) * MS, {})


def test_idle_gaps_take_the_innermost_serve_span():
    """The hand-built trace of ``test_bench_trace.py`` with the engine's
    spans inside its steps: gaps under a step take the phase's name; the
    gaps outside them keep their names."""
    from test_bench_trace import EVENTS

    host = [span(n, s / MS, d / MS) for p, _, n, s, d in EVENTS
            if p == HOST]
    host += [span("serve.step", 5, 40), span("serve.admit", 5, 3),
             span("serve.dispatch", 8, 2), span("serve.tick_wait", 10, 30),
             span("serve.sample", 40, 5),
             span("serve.step", 60, 35), span("serve.tick_wait", 60, 35)]
    host.sort(key=lambda x: (x[1], -x[2]))
    dev = [e for e in EVENTS if e[0].startswith(tr.DEVICE_PREFIX)]
    got = spans.idle_by_span(dev, host, (0, 100 * MS))
    # device 0 idle: [0,10) mid 5 as the step's admission opens; [40,60)
    # mid 50 in the submit; [95,100) after the last step closed
    assert got["idle_s"] == pytest.approx(0.035)
    names = dict(got["idle_by_span"])
    assert names == {"bench.submit": pytest.approx(0.02),
                     "serve.admit": pytest.approx(0.01),
                     spans.NO_SPAN: pytest.approx(0.005)}
    # the same trace as the benchmark reduces it: unchanged
    red = tr.reduce(EVENTS)
    assert [n for n, _ in red.idle_gaps] == ["bench.submit",
                                             "bench.engine_step",
                                             "no benchmark span"]
    assert [round(s, 6) for _, s in got["idle_gaps"]] == [0.02, 0.01, 0.005]


def test_segments_name_the_innermost_open_span():
    host = [span("a", 0, 10), span("b", 2, 3), span("c", 5, 0),
            span("d", 6, 2)]
    starts, ends, names = spans.segments(host)
    assert list(zip(starts, ends, names)) == [
        (0, 2 * MS, "a"), (2 * MS, 5 * MS, "b"), (5 * MS, 6 * MS, "a"),
        (6 * MS, 8 * MS, "d"), (8 * MS, 10 * MS, "a")]


def test_recorded_v5e_trace_has_no_engine_spans():
    """The recorded trace predates the engine's spans: nothing to read,
    and its gaps keep the benchmark's name."""
    import json

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "trace_v5e_sessions.json")
    with open(path) as f:
        events = [tuple(e) for e in json.load(f)]
    host = sorted(((n, s, s + d, {}) for p, _, n, s, d in events
                   if not p.startswith(tr.DEVICE_PREFIX)),
                  key=lambda x: (x[1], -x[2]))
    assert spans.profile(host) is None
    dev = [e for e in events if e[0].startswith(tr.DEVICE_PREFIX)]
    win = next((s, e) for n, s, e, _ in host if n == tr.WINDOW_SPAN)
    got = spans.idle_by_span(dev, host, win)
    red = tr.reduce(events)
    assert got["idle_s"] == pytest.approx(
        red.window_s - red.busy_s[D0], rel=1e-9)
    assert {n for n, _ in got["idle_gaps"]} == {"bench.engine_step"}


# ------------------------------------------------------------ readers


def _step(start_ms, dur_ms, C, rows=1, first="", **phase_ms):
    s = spans.Step(start_ms * MS, (start_ms + dur_ms) * MS,
                   {"C": C, "rows": rows, "first": first})
    s.phases = {k: v * 1e-3 for k, v in phase_ms.items()}
    return s


def _record(open_loop=True):
    rec = serve.Record(TINY_MODEL, 4, t0=100.0, t_end=110.0,
                       open_loop=open_loop)
    for rid, due in ((0, 100.5), (1, 104.0), (2, 109.0), (3, 111.0)):
        it = loadgen.Item(rid, [1, 2, 3], 4, due - 100.0)
        rec.reqs[rid] = serve.ReqRec(it, due, due)
    return rec


PROFILE = spans.Profile(
    [_step(0, 5, 4, dispatch=1, tick_wait=2),  # before the window
     _step(10, 10, 4, rows=2, first="0:3", admit=1, dispatch=1, tick_wait=6,
           hook=1),
     _step(20, 4, 1, first="1:5", dispatch=0.5, tick_wait=2.5),
     _step(24, 6, 4, first="-4:2", prepare=1, dispatch=1, tick_wait=2),
     _step(30, 1, 0, admit=0.5),  # no tick ran
     _step(90, 5, 4, first="3:1 2:9", dispatch=1, tick_wait=3)],
    (10 * MS, 80 * MS))



def _only(keep):
    return spans.Profile([s for s in PROFILE.steps if keep(s)],
                         PROFILE.window)


def _in_window(s):
    return PROFILE.window[0] <= s.start and s.end <= PROFILE.window[1]


# per reader: its reading of PROFILE, and a profile without the steps it
# reads
READERS = {
    "tick_ms.prefill": ((7 + 3) / 2, _only(lambda s: s.width != 4)),
    "tick_ms.decode": (3.0, _only(lambda s: s.width != 1)),
    "host_ms.step": ((2 + 1 + 3 + 1) / 4, _only(lambda s: not _in_window(s))),
    # requests due in [100, 110]: 0, 1 and 2 took 3, 5 and 9 ticks (2
    # after the close); -4 is a warm-up request, 3 is due after the close
    "prefill_ticks_p50": (5, _only(lambda s: not s.first)),
}


@pytest.mark.parametrize("name", list(READERS))
def test_span_readers(monkeypatch, name):
    want, without = READERS[name]
    read = harness.load_reader(name).read
    monkeypatch.setattr(spans, "of", lambda rec: PROFILE)
    assert read(_record()) == pytest.approx(want)
    monkeypatch.setattr(spans, "of", lambda rec: without)
    assert read(_record()) is None
    monkeypatch.setattr(spans, "of", lambda rec: None)  # no engine spans
    assert read(_record()) is None


def _own_run(traced, root, cell="tiny"):
    """The fixture's profile where a traced run of ``cell`` leaves it under
    ``root``, and a record of its waves as that run would make it."""
    where = os.path.join(root, ".bench", "trace", cell, "plugins")
    os.makedirs(where, exist_ok=True)
    shutil.copy(traced.path, where)
    win = next(e - s for n, s, e, _ in traced.host if n == tr.WINDOW_SPAN)
    rec = serve.Record(TINY_MODEL, 4, t0=0.0, t_end=1.0)
    rec.trace = SimpleNamespace(window_s=win * 1e-9)  # as ``run`` reduces it
    for reqs, _, _ in traced.waves.values():
        for rid, q in reqs.items():
            it = loadgen.Item(rid, q.prompt, q.max_new_tokens, 0.5)
            rec.reqs[rid] = serve.ReqRec(it, 0.5, 0.5)
    return rec


def test_readers_find_the_runs_own_profile(traced, monkeypatch, tmp_path):
    """The readers take the run's own profile under the runs' trace
    directory, as a traced run leaves it, and read the engine's spans
    there."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    assert spans.of(_record()) is None  # no profile yet
    rec = _own_run(traced, str(tmp_path))
    read = {m: harness.load_reader(m).read(rec) for m in READERS}
    # the same from the raw spans: a step's tick is its dispatch and wait
    host = traced.host
    ticks = {1: [], traced.chunk: []}
    for n, s0, e0, st in host:
        if n == "serve.step" and st.get("C") in ticks:
            ticks[st["C"]].append(sum(
                e - s for m, s, e, _ in host if s0 <= s and e <= e0
                and m in ("serve.dispatch", "serve.tick_wait")) * 1e-6)
    assert ticks[1] and ticks[traced.chunk]
    assert read["tick_ms.prefill"] == pytest.approx(
        sum(ticks[traced.chunk]) / len(ticks[traced.chunk]))
    assert read["tick_ms.decode"] == pytest.approx(
        sum(ticks[1]) / len(ticks[1]))
    assert 0 < read["host_ms.step"] < read["tick_ms.prefill"]
    firsts = [int(f.split(":")[1]) for n, _, _, st in host
              if n == "serve.step" for f in str(st.get("first", "")).split()
              if int(f.split(":")[0]) in rec.reqs]
    assert read["prefill_ticks_p50"] == harness.percentile(firsts, 50)


@pytest.mark.parametrize("where", ["another window", "no device trace"])
def test_a_profile_of_another_run_is_not_read(traced, monkeypatch, tmp_path,
                                              where):
    """A profile whose window is not the record's, as one left by another
    cell's run is, gives the readers nothing; nor does a record that was
    reduced from no trace."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    rec = _own_run(traced, str(tmp_path), cell="tiny.other")
    assert spans.of(rec) is not None
    if where == "another window":
        rec.trace.window_s += 1e-3
    else:
        rec.trace = None
    assert spans.of(rec) is None
    for m in READERS:
        assert harness.load_reader(m).read(rec) is None
