"""One run of one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload qwen1.5-4b.sessions --seed 7 \
      --seconds 51 --trace 0

The cell is looked up in ``BENCHMARK.json``; its configuration's ``kind``
picks the driver (``bench/drivers/<kind>.py``).  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, last, ``checks``: each number compared for
``correct`` with its limit.  A machine without the chips the cell asks for
exits nonzero and prints no result.
"""

from __future__ import annotations

import time

STARTED = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    devices = harness.require_chips(cell.chips)
    driver = harness.load_driver(cell.config["kind"])
    line = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, STARTED)
    harness.emit(line)


if __name__ == "__main__":
    main()
