"""Closed-loop batch job: ``Solver.solve_batch`` over ``lanes`` roots at
a time, back to back, each batch's distances fetched before the next
is sent.  The window ends at the first batch that completes after
``seconds``; its length is the time up to that completion.

Traffic keys: ``lanes``.  Every batch is a fresh draw from the run's
seed of ``lanes`` distinct roots among vertices of degree >= 1.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import reference
from bench import traffic as tr


class Program:
    """The system under test: a compiled ``Solver`` over the graph."""

    def __init__(self, ctx):
        from repro.core.graph import build_graph
        from repro.sssp import Solver

        g = build_graph(ctx.n, ctx.src, ctx.dst, ctx.w)
        self.solver = Solver(g, backend=ctx.config["solver"]["backend"])
        self.backend = self.solver.backend

    def solve(self, roots):
        return self.solver.solve_batch(roots)

    def warm(self, roots) -> None:
        """Compile the window's program without running a whole solve:
        each lane's target is its own root, so it exits at once (the
        target is a traced operand of the same program)."""
        np.asarray(self.solver.solve_batch(roots, targets=roots).dist)

    def fetch(self, handle):
        """``(float dist[B, n], int rounds[B])`` on the host."""
        return np.asarray(handle.dist), np.asarray(handle.rounds)


def build(ctx):
    return Program(ctx)


def roots(ctx, stream: int):
    """Endless batches of roots drawn from the seed's ``stream``."""
    lanes = int(ctx.traffic["lanes"])
    gen = tr.rng(ctx.seed, stream)
    while True:
        yield tr.roots(gen, ctx.out_deg, lanes)


def warm(ctx, system) -> None:
    r = next(roots(ctx, tr.WARMUP))
    if hasattr(system, "warm"):
        system.warm(r)
    else:
        system.fetch(system.solve(r))


@dataclasses.dataclass
class Records:
    roots: list            # int32[B] per batch
    dist: list             # float[B, n] per batch
    rounds: list           # int[B] per batch
    done_at: list          # seconds from window start, per batch
    window_s: float = 0.0
    reached_arcs: int = 0  # arcs whose tail a root reaches, summed

    @property
    def attempted(self) -> int:
        return sum(len(r) for r in self.roots)

    failed = 0


def measure(ctx, system, seconds: float) -> Records:
    rec = Records([], [], [], [])
    stream = roots(ctx, tr.ROOTS)
    with ctx.span("window"):
        t0 = time.perf_counter()
        while True:
            r = next(stream)
            with ctx.span("solve_batch"):
                handle = system.solve(r)
            with ctx.span("fetch"):
                dist, rounds = system.fetch(handle)
            done = time.perf_counter() - t0
            rec.roots.append(r)
            rec.dist.append(dist)
            rec.rounds.append(rounds)
            rec.done_at.append(done)
            if done >= seconds:
                break
    rec.window_s = rec.done_at[-1]
    rec.reached_arcs = reached_arcs(ctx, rec)
    return rec


def window_notes(rec: Records) -> dict:
    return dict(batches=len(rec.roots), roots=rec.attempted,
                window_s=rec.window_s, queued_at_end=0,
                harness_lateness_s=0.0,
                rounds_max=int(max(np.max(r) for r in rec.rounds)),
                rounds_min=int(min(np.max(r) for r in rec.rounds)))


def reached_arcs(ctx, rec: Records) -> int:
    """Arcs whose tail each root reaches, summed over the roots."""
    return int(sum(np.isfinite(d).astype(np.int64) @ ctx.out_deg
                   for batch in rec.dist for d in batch))


def check(ctx, rec: Records) -> reference.Tally:
    adj = reference.adjacency(ctx.n, ctx.src, ctx.dst, ctx.w)
    tally = reference.Tally()
    for r, dist in zip(rec.roots, rec.dist):
        tally.add(dist, reference.distances(adj, r))
    return tally
