"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a flat
list of events ``(plane, line, name, start_ns, dur_ns)``; ``reduce``
turns that list, and the host span that marks the traced window, into:

- busy seconds per device: the union of the intervals in which an
  operation ran (``XLA Ops`` lines of each ``/device:TPU:n`` plane), cut
  to the window;
- device self time by operation name, summed over devices: an event's
  time less that of the events nested in it (a ``while`` loop holds the
  operations of its body), so a kernel's time is its own;
- per device, the seconds in which a collective ran and no other
  operation did (exposed collective time);
- the idle gaps of the busiest device, each named by the innermost
  benchmark host span (``bench.*``) open at its midpoint.

The reduction runs on plain lists, so a small recorded trace checks it
without a chip.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "allreduce",
                    "allgather", "reducescatter")


@dataclass
class Reduced:
    window_s: float
    busy_s: dict  # device plane -> seconds busy in the window
    op_seconds: dict  # op name -> device seconds (all devices)
    op_events: dict  # op name -> number of events
    exposed_collective_s: dict  # device plane -> seconds
    idle_gaps: list  # [(host span, seconds)], longest first
    devices: list = field(default_factory=list)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(text: str) -> str:
    """``%paged_decode_attention.10 = bf16[...] custom-call(...)`` ->
    ``paged_decode_attention.10``: the HLO instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> list:
    """Every event of the trace as ``(plane, line, name, start_ns,
    dur_ns)``; only device op lines (named by their HLO instruction) and
    benchmark host spans are kept."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for e in line.events:
                if dev:
                    out.append((plane.name, line.name, op_name(e.name),
                                float(e.start_ns), float(e.duration_ns)))
                elif e.name.startswith(HOST_SPAN_PREFIX):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(events) -> list:
    """``[name, start, end, self, leaf]`` of each ``(name, start, end)``:
    its length less that of the events nested in it (wholly inside it,
    found through the stack of open events), and whether it holds none."""
    out, stack = [], []  # stack of indices into out
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and not (s < out[stack[-1]][2]
                             and e <= out[stack[-1]][2]):
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= e - s
            parent[4] = False
        out.append([n, s, e, e - s, True])
        stack.append(len(out) - 1)
    return out


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(w in n for w in COLLECTIVE_WORDS)


def reduce(events: list, max_gaps: int = 10) -> Reduced | None:
    """The device numbers of the window marked by the ``bench.window`` host
    span; None where the trace holds no device operation."""
    win = [(s, s + d) for _, _, n, s, d in events if n == WINDOW_SPAN]
    dev_events = [e for e in events if e[0].startswith(DEVICE_PREFIX)]
    if not win or not dev_events:
        return None
    lo, hi = win[0]
    devices = sorted({e[0] for e in dev_events})
    busy, exposed, merged_by_dev = {}, {}, {}
    op_s, op_n = {}, {}
    for dev in devices:
        mine = [(n, s, s + d) for p, _, n, s, d in dev_events if p == dev]
        allv = clip(union((s, e) for _, s, e in mine), lo, hi)
        merged_by_dev[dev] = allv
        busy[dev] = length(allv) * 1e-9
        nodes = self_times(mine)
        # a loop or call that holds other operations is no compute of its
        # own: exposure is judged against the operations it holds
        coll = clip(union((s, e) for n, s, e, _, leaf in nodes
                          if leaf and is_collective(n)), lo, hi)
        comp = clip(union((s, e) for n, s, e, _, leaf in nodes
                          if leaf and not is_collective(n)), lo, hi)
        exposed[dev] = length(subtract(coll, comp)) * 1e-9
        for n, s, e, own, _ in nodes:
            if s >= lo and e <= hi:
                op_s[n] = op_s.get(n, 0.0) + own * 1e-9
                op_n[n] = op_n.get(n, 0) + 1
    # idle gaps of the busiest device, named by the host span open then
    top = max(devices, key=lambda d: busy[d])
    idle = subtract([[lo, hi]], merged_by_dev[top])
    spans = [(s, s + d, n) for _, _, n, s, d in events
             if n.startswith(HOST_SPAN_PREFIX) and n != WINDOW_SPAN]
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:max_gaps]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[0] <= mid < sp[1]]
        name = (max(open_, key=lambda sp: sp[0])[2] if open_
                else "no benchmark span")
        gaps.append((name, (e - s) * 1e-9))
    return Reduced((hi - lo) * 1e-9, busy, op_s, op_n, exposed, gaps,
                   devices)


def kernel_seconds(red: Reduced, pattern: str) -> tuple:
    """Device seconds and events of operations whose name holds
    ``pattern``."""
    secs = sum(v for k, v in red.op_seconds.items() if pattern in k)
    n = sum(v for k, v in red.op_events.items() if pattern in k)
    return secs, n


def breakdown(red: Reduced, top: int = 10) -> dict:
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps[:top]]}
