"""The benchmark's shared parts: the spec, name resolution, devices, the
compile clock, statistics and the result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``bench/configs/<config>.json`` holds
the configuration (its ``kind`` picks ``bench/drivers/<kind>.py``),
``bench/traffic/<mix>.json`` the mix, and ``bench/metrics/<metric>.py`` the
reader of each per-layer metric.  Adding a cell adds files and entries;
no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


# ------------------------------------------------------------------ spec


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1


def scratch_dir(*parts: str) -> str:
    """A fixed directory inside the checkout (``.bench/``, not committed)
    for what a run writes: traces."""
    path = os.path.join(ROOT, ".bench", *parts)
    os.makedirs(path, exist_ok=True)
    return path


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec: dict, workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell named ``workload``, with its config and traffic files read
    and its metrics selected."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(os.path.dirname(bench_dir), conf["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    layer = [m for m in spec["per_layer"] if _reports(m, workload)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)


def load_module(path: str, name: str):
    """Import a file by path; a name may hold dots (``step_ms.prefill``)."""
    mod_name = "bench_dyn_" + name.replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "drivers", kind + ".py"),
                       "driver_" + kind)


def load_reader(metric: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "metrics", metric + ".py"),
                       "metric_" + metric)


def load_reference(name: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "references", name + ".py"),
                       "reference_" + name)


# --------------------------------------------------------------- devices


def require_chips(chips: int, platform: str = "tpu") -> list:
    """The first ``chips`` chips JAX sees.  No chip, another platform or
    too few chips is an error, never a fallback to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"no {platform.upper()} found (JAX platform "
                         f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def device_record(devices: list) -> dict:
    d = devices[0]
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use") for x in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


class CompileClock:
    """Backend compiles counted and timed from JAX's own event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


# ------------------------------------------------------------ statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks, numpy's default."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo or xs[hi] == xs[lo]:
        return float(xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


# ---------------------------------------------------------------- result


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown=None) -> dict:
    """The run's last stdout line; ``checks`` comes last, each compared
    number with its limit."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def emit(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
