#!/usr/bin/env python3
"""The control of a cell's check, on several seeds in one process:

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs the cell's own traffic at its own size for a short
window with the control (``harness.faults.stale``: answers from a graph
that lacks a share of its edges) in the program's place, and prints the
run's result line, whose ``correct`` must read false. The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402
from harness import faults  # noqa: E402


def control_run(env: dict, seed: int, seconds: float) -> dict:
    """The result line of one run with the control in the program's place."""
    n, src, dst, _, _ = env["graph"]
    faults.stale(env["session"], faults.stale_reference(n, src, dst, seed))
    try:
        return bench.execute(env, seed, seconds, False, time.perf_counter())[0]
    finally:
        faults.restore(env["session"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    env = bench.prepare(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps({"seed": seed, **control_run(env, seed,
                                                      args.seconds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
