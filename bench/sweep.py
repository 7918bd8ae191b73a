"""Open-loop rate sweep of a serving cell, to find its knee once.

  python3 bench/sweep.py --workload qwen1.5-4b.sessions --seed 5 \
      --seconds 90 --rates 0.05 0.1 0.2

Per rate (lowest first), a fresh set-up and one window of the cell's mix
at that rate (the same sizes and sessions; each period holds that rate's
share of arrivals).  Each window prints
one JSON line: requests due, served, the queue length at each quarter of
the window, time to first token and output
tokens per second.  The knee is the highest rate at which the queue does
not grow across the window; the cell's mix file then states 0.8 of it.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness, loadgen  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    harness.require_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    serve = harness.load_driver(cell.config["kind"])
    for rate in sorted(args.rates):
        mix = copy.deepcopy(cell.traffic)
        mix["arrival"]["rate_per_s"] = rate
        server = serve.Server(cell, args.seed)
        sched = loadgen.generate(mix, args.seed, server.model["vocab_size"])
        serve._warm(server, sched, args.seed)
        rec = serve.window(server, sched, args.seconds, drain_s=0.0)
        ticks = rec.window_ticks()
        quarter = [ticks[min(len(ticks) - 1, int(len(ticks) * q))].queued
                   for q in (0.25, 0.5, 0.75, 1.0)] if ticks else []
        due = [r for r in rec.reqs.values() if r.due <= rec.t_end]
        e2e = serve.end_to_end(rec, drain_s=0.0)
        print(json.dumps({
            "rate_per_s": rate, "window_s": rec.window_s, "due": len(due),
            "first_token_by_close": sum(1 for r in due if r.times),
            "finished": sum(1 for r in due if r.req.done),
            "queue_quarters": quarter, **e2e}), flush=True)
        server.free()
        server.weights = None
        del server
        gc.collect()


if __name__ == "__main__":
    main()
