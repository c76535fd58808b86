#!/usr/bin/env python3
"""Knee sweep of a service cell: one run per offered rate, in one process.

    python3 bench/sweep.py --workload road-city.zipf-live \
        --rates 2,4,6,8 --seconds 30 --seed 5

Prints, per rate, the cell's end-to-end metrics and whether the run was
correct; each run's window note before it gives the queries answered in
the window and those still queued when it closed.  The knee is the
highest rate whose queue does not grow over the window; the cell's mix
then offers a fixed share of it (``rate_qps`` in its traffic file, with
``knee_qps`` beside it).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated qps")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench.harness import run_cell

    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        res = run_cell(args.workload, args.seed + i, args.seconds, False,
                       t_start=time.perf_counter(),
                       traffic={"rate_qps": rate})
        print(json.dumps({"sweep": args.workload, "rate_qps": rate,
                          "metrics": {k: m["value"] for k, m
                                      in res["metrics"].items()},
                          "correct": res["correct"],
                          "attempted": res["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
