"""The engine's own spans in a traced run's profile.

``PagedServeEngine.step`` (``src/repro/serve/engine.py``) writes one
``serve.step`` host span per step into the profiler's trace, a
``serve.<phase>`` span per phase inside it, and the step's work (tick
width ``C``, rows, tokens fed and decoded, ``first``, ``gc_s``) as the
step span's metadata.  Here:

- ``load`` reads those spans, and the benchmark's own ``bench.*`` spans,
  from an ``.xplane.pb``;
- ``steps`` puts each phase under the step that holds it;
- ``of`` finds the traced run's own profile for the per-layer readers:
  the newest one under the runs' trace directory, taken only where its
  ``bench.window`` is the window the run's device numbers were reduced
  from;
- ``idle_by_span`` names each idle gap of the busiest device by the
  innermost ``bench.*`` or ``serve.*`` span open at its midpoint, and sums
  the window's idle seconds by that name.

A program that writes no ``serve.*`` span gives no steps, and the readers
then report nothing.  The command line prints, for one profile, the phases
of the window's steps and the idle time by span:

  python3 bench/spans.py [trace directory, default .bench/trace]
"""

from __future__ import annotations

import bisect
import json
import os
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from bench import harness, trace  # noqa: E402

PREFIXES = ("serve.", "bench.")
STEP = "serve.step"
NO_SPAN = "no span"


@dataclass
class Step:
    start: float  # ns, the trace's clock
    end: float
    stats: dict  # the step's work, as the span's metadata
    phases: dict = field(default_factory=dict)  # phase -> seconds

    @property
    def width(self) -> int:
        """The tick's width C: the chunk while a row prefills, 1 when
        every row decodes, 0 when no tick ran."""
        return int(self.stats.get("C", 0))

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    def phase(self, name: str) -> float:
        return self.phases.get(name, 0.0)

    @property
    def tick_s(self) -> float:
        """Host time from the tick's dispatch until its logits are ready."""
        return self.phase("dispatch") + self.phase("tick_wait")

    @property
    def host_s(self) -> float:
        """The rest of the step less the callbacks: the engine's own host
        work, in phases and between them."""
        return self.seconds - self.tick_s - self.phase("hook")

    @property
    def first(self) -> list:
        """``(rid, prefill ticks)`` of the requests whose prompt completed."""
        out = []
        for pair in str(self.stats.get("first", "")).split():
            rid, ticks = pair.split(":")
            out.append((int(rid), int(ticks)))
        return out


@dataclass
class Profile:
    steps: list  # Step, by start
    window: tuple  # (start, end) ns of the bench.window span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def window_steps(self) -> list:
        lo, hi = self.window
        return [s for s in self.steps if s.start >= lo and s.end <= hi]


def load(path: str) -> list:
    """Host spans named ``serve.*`` or ``bench.*`` as ``(name, start_ns,
    end_ns, metadata)``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns),
                                dict(e.stats)))
    out.sort(key=lambda x: (x[1], -x[2]))
    return out


def steps(spans: list) -> list:
    """Each ``serve.step`` span with the seconds of the ``serve.*`` phase
    spans inside it, summed by phase name."""
    out = [Step(s, e, st) for n, s, e, st in spans if n == STEP]
    starts = [s.start for s in out]
    for n, s, e, _ in spans:
        if not n.startswith("serve.") or n == STEP:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= out[i].end:
            ph = out[i].phases
            name = n[len("serve."):]
            ph[name] = ph.get(name, 0.0) + (e - s) * 1e-9
    return out


def profile(spans: list) -> Profile | None:
    """The steps and the ``bench.window`` span; None where the trace holds
    no window or no step."""
    win = [(s, e) for n, s, e, _ in spans if n == trace.WINDOW_SPAN]
    st = steps(spans)
    if not win or not st:
        return None
    return Profile(st, win[0])


_cache: dict = {}


def of(rec) -> Profile | None:
    """The profile of the traced run that produced ``rec``: the newest
    ``.xplane.pb`` under the runs' trace directory, if its ``bench.window``
    lasts exactly as long as the window of ``rec.trace``, which the run
    reduced from its own profile.  None where there is no such profile, or
    it holds no engine step: a profile left by another run, or written
    elsewhere, is not read."""
    if getattr(rec.trace, "window_s", None) is None:
        return None
    try:
        path = trace.find_xplane(harness.scratch_dir("trace"))
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = profile(load(path))
    prof = _cache[key]
    if prof is None or abs(prof.window_s - rec.trace.window_s) > 1e-6:
        return None
    return prof


def segments(spans: list) -> tuple:
    """The host timeline cut into pieces, each named by the innermost span
    open in it (the one opened last): sorted starts, ends and names."""
    spans = [sp for sp in spans if sp[2] > sp[1]]
    edges = sorted([(s, 1, i) for i, (_, s, _, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _, e, _) in enumerate(spans)])
    starts, ends, names, open_ = [], [], [], []
    prev = None
    for t, is_start, i in edges:
        if open_ and prev is not None and t > prev:
            starts.append(prev)
            ends.append(t)
            names.append(spans[open_[-1]][0])
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
        prev = t
    return starts, ends, names


def idle_by_span(device_events: list, spans: list, window: tuple,
                 top: int = 10) -> dict:
    """The idle time of the busiest device in ``window`` (ns), each gap
    named by the innermost host span open at its midpoint (the window's
    own span aside): seconds by name, and the ``top`` longest gaps."""
    lo, hi = window
    busy: dict = {}
    for p, _, _, s, d in device_events:
        busy.setdefault(p, []).append((s, s + d))
    merged = {p: trace.clip(trace.union(v), lo, hi) for p, v in busy.items()}
    dev = max(merged, key=lambda p: trace.length(merged[p]))
    idle = trace.subtract([[lo, hi]], merged[dev])
    starts, ends, names = segments(
        [sp for sp in spans if sp[0] != trace.WINDOW_SPAN])
    by_name: dict = {}
    gaps = []
    for s, e in idle:
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        name = names[i] if i >= 0 and mid < ends[i] else NO_SPAN
        by_name[name] = by_name.get(name, 0.0) + (e - s) * 1e-9
        gaps.append((name, (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {"idle_s": trace.length(idle) * 1e-9,
            "idle_by_span": sorted(by_name.items(), key=lambda kv: -kv[1]),
            "idle_gaps": gaps[:top]}


def _mean(xs) -> float:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else float("nan")


def summary(path: str) -> dict:
    """The window's steps by tick width (means in ms, rows, tokens, the
    tick's time by rows issued), its garbage-collection seconds and the
    device's idle time by span."""
    spans = load(path)
    prof = profile(spans)
    if prof is None:
        return {}
    win = prof.window_steps()
    out: dict = {"window_s": prof.window_s,
                 "steps": len(win),
                 "gc_s": sum(float(s.stats.get("gc_s", 0.0)) for s in win)}
    for C in sorted({s.width for s in win}):
        mine = [s for s in win if s.width == C]
        names = sorted({n for s in mine for n in s.phases})
        by_rows: dict = {}
        for s in mine:
            by_rows.setdefault(int(s.stats.get("rows", 0)), []).append(s)
        out[f"C={C}"] = {
            "steps": len(mine),
            "step_ms": 1e3 * _mean(s.seconds for s in mine),
            "tick_ms": 1e3 * _mean(s.tick_s for s in mine),
            "host_ms": 1e3 * _mean(s.host_s for s in mine),
            "phase_ms": {n: 1e3 * _mean(s.phase(n) for s in mine)
                         for n in names},
            "rows": _mean(s.stats.get("rows", 0) for s in mine),
            "fed": _mean(s.stats.get("fed", 0) for s in mine),
            "decoded": _mean(s.stats.get("decoded", 0) for s in mine),
            "tick_ms_by_rows": {r: [len(v), 1e3 * _mean(s.tick_s for s in v)]
                                for r, v in sorted(by_rows.items())},
        }
    dev = [e for e in trace.load(path) if e[0].startswith(trace.DEVICE_PREFIX)]
    if dev:
        idle = idle_by_span(dev, spans, prof.window)
        waiting = sum(v for n, v in idle["idle_by_span"]
                      if n == "bench.wait_arrival")
        named = sum(v for n, v in idle["idle_by_span"]
                    if n.startswith("serve."))
        rest = idle["idle_s"] - waiting
        idle["serve_share_of_idle_outside_wait"] = (named / rest if rest > 0
                                                    else None)
        idle["idle_by_span"] = idle["idle_by_span"][:10]
        out.update(idle)
    return out


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    where = argv[0] if argv else harness.scratch_dir("trace")
    print(json.dumps(summary(trace.find_xplane(where))), flush=True)


if __name__ == "__main__":
    main()
