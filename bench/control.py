#!/usr/bin/env python3
"""The control: the plain reference in bfloat16, in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 10

For each seed, runs the cell through the harness with the program
replaced by :class:`bench.reference.Bf16Fixpoint` (the reference's
recurrence with every weight and sum rounded to bfloat16, the
precision below the engine's float32) and prints the numbers the
harness compared, each beside its limit.  Every run must come out not
correct; the limits in ``bench/reference.py`` sit between these
readings and those of the program's own runs.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class ControlBatch:
    """Stands in for ``drivers/batch.Program``."""

    backend = "control-bf16"

    def __init__(self, ctx):
        from bench.reference import Bf16Fixpoint

        self.solver = Bf16Fixpoint(ctx.n, ctx.src, ctx.dst)
        self.w = ctx.w

    def solve(self, roots):
        return self.solver.distances(self.w, roots)

    def fetch(self, handle):
        dist, sweeps = handle
        return dist, np.full(len(dist), sweeps)


class ControlService:
    """Stands in for ``drivers/service.Program``: answers each query
    from a bfloat16 solve of its origin on its own copy of the weights."""

    backend = "control-bf16"

    def __init__(self, ctx):
        from bench.reference import Bf16Fixpoint

        self.solver = Bf16Fixpoint(ctx.n, ctx.src, ctx.dst)
        self.w = ctx.w.copy()
        self.arc = {(int(s), int(t)): i
                    for i, (s, t) in enumerate(zip(ctx.src, ctx.dst))}
        self.queries = 0

    def serve(self, pairs):
        pairs = np.asarray(pairs)
        srcs, inv = np.unique(pairs[:, 0], return_inverse=True)
        out = np.empty(len(pairs))
        for at in range(0, len(srcs), 8):
            chunk = srcs[at: at + 8]
            d, _ = self.solver.distances(
                self.w, np.resize(chunk, 8))
            for k, s in enumerate(chunk):
                sel = inv == at + k
                out[sel] = d[k, pairs[sel, 1]]
        self.queries += len(pairs)
        return out

    def apply_delta(self, src, dst, new_w):
        for s, t, w in zip(src, dst, new_w):
            self.w[self.arc[(int(s), int(t))]] = w

    def counters(self):
        return dict(queries=self.queries, cache_hits=0, batches=0,
                    p2p_solves=0, bidi_solves=0, deltas=0)


CONTROLS = {"batch": ControlBatch, "service": ControlService}


def control_factory(layout, workload: str):
    return CONTROLS[layout.cell(workload).traffic["driver"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench.harness import run_cell
    from bench.layout import Layout

    layout = Layout()
    factory = control_factory(layout, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(args.workload, seed, args.seconds, False,
                       t_start=time.perf_counter(), layout=layout,
                       system_factory=factory)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
