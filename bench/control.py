"""Readings that set a serving cell's limit: the program's and the
control's, on many seeds in one process.

  python3 bench/control.py --workload qwen1.5-4b.sessions --seconds 51 \
      --seeds 101 102 103 --modes int8 fp8

For each seed it runs the cell's set-up and window as ``bench/run.py``
does, draws the same sample of finished requests, and prints one JSON
line: the program's numbers against the float32 reference (``f32``: the
lower readings) and, for each ``--modes`` precision, the same numbers for
the reference computed in that precision in the program's place (the
control: the upper readings), each judged by the harness's own
``correct`` (``decide`` in the driver).  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness, loadgen  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="*", default=["int8", "fp8"])
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    harness.require_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    serve = harness.load_driver(cell.config["kind"])
    chk = cell.config["check"]
    for i, seed in enumerate(args.seeds):
        t = time.time()
        server = serve.Server(cell, seed)
        sched = loadgen.generate(cell.traffic, seed,
                                 server.model["vocab_size"])
        serve._warm(server, sched, seed)
        rec = serve.window(server, sched, args.seconds, chk["drain_s"],
                           chk["finish_s"])
        server.free()
        picks = serve.sample(rec, seed, chk["max_seqs"], chk["min_tokens"])
        modes = ("f32",) + tuple(args.modes)
        gaps = serve.compare(server, picks,
                             loadgen.max_tokens(cell.traffic), modes)
        line = {"workload": cell.name, "seed": seed,
                "served_tokens": gaps["served_tokens"]}
        for mode in modes:
            correct, checks = serve.decide(gaps, mode, chk, 0)
            line[mode] = {"correct": correct,
                          "logit_gap_mean": gaps[mode],
                          "logit_gap_max": gaps[mode + "_max"],
                          "first_logits_err": gaps[mode + "_row"],
                          **{k: c["value"] for k, c in checks.items()}}
        line["limits"] = {k: c["limit"] for k, c in checks.items()}
        line["seconds"] = time.time() - t
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        server.weights = None
        del server
        gc.collect()


if __name__ == "__main__":
    main()
