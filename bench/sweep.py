#!/usr/bin/env python3
"""Sweep of the offered rate of an open-loop cell, on the chip, in one
process: the cell's configuration and mix at each rate in turn.

    python bench/sweep.py --workload citeseer.open --rates 8000,12000,12000 --seconds 51

A rate may be listed more than once; each entry runs on its own seed
(``--seed`` plus its place in the list). Per entry it prints one JSON line:
the latency percentiles, the requests that failed, how late the generator
ran, the longest single submit and poll, and the median latency of the
first and second half of the requests (the full garbage collections in
the window are on the line before). A rate is sustained when no request
fails and the second half waits no longer than twice the first: a backlog
that grows through the run shows there. A cell is then set at about four
fifths of the highest sustained rate, as a number in its mix's file.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    env = bench.prepare(args.workload)
    traffic = env["found"]["traffic"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic["rate"] = rate
        res, served = bench.execute(env, args.seed + i, args.seconds, False,
                                    time.perf_counter())
        b = served["backlog"]
        print(json.dumps({
            "rate": rate, "seed": args.seed + i, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            **served["end_to_end"], **b,
            "sustained": res["failed"] == 0
            and b["p50_second_half_ms"] <= 2 * b["p50_first_half_ms"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
