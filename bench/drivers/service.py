"""Open-loop query service: ``SSSPService.serve`` and ``apply_delta``.

Queries arrive evenly spaced at the mix's rate, whether or not earlier
ones are answered.  Whenever the service is free, the harness hands it
every query that has arrived and not been answered, as one wave, and
records when the wave's answers came back.  Weight deltas fall due on
their own schedule and are applied between waves.  After the window
closes, queries that arrived in it and are still queued are served on
for up to ``DRAIN_S`` seconds and checked: a late answer is late, not
missing; one that never comes is missing.  A query's latency runs from
its scheduled arrival to its answer.

Traffic keys: ``rate_qps``; ``generator_seed`` (draws the trips and
their order, so every run sends the same trips at the same times,
turned with the graph); ``origins`` (``hot``, ``hot_share``,
``zipf_s``); ``delta`` (``period_s``, ``share``, ``scale``) or ``null``.
The deltas and the warm-up are drawn from the run's seed.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import reference
from bench import traffic as tr

DRAIN_S = 60.0
COUNTERS = ("queries", "cache_hits", "batches", "p2p_solves",
            "bidi_solves", "deltas")


class Program:
    """The system under test: ``SSSPService`` over the graph."""

    def __init__(self, ctx):
        from repro.core.graph import build_graph
        from repro.runtime.sssp_service import SSSPService

        g = build_graph(ctx.n, ctx.src, ctx.dst, ctx.w)
        self.service = SSSPService(g, **ctx.config["service"])
        self.backend = self.service.solver.backend

    def serve(self, pairs: np.ndarray) -> np.ndarray:
        """Distances for ``pairs`` (NaN where a query came back unanswered)."""
        from repro.runtime.sssp_service import Query

        qs = [Query(source=int(s), target=int(t)) for s, t in pairs]
        self.service.serve(qs)
        return np.array([q.distance if q.done and q.distance is not None
                         else np.nan for q in qs], np.float64)

    def apply_delta(self, src, dst, new_w) -> None:
        from repro.core.sssp.dynamic import make_delta_from_endpoints

        self.service.apply_delta(make_delta_from_endpoints(
            self.service.solver.graph, src, dst, new_w))

    def counters(self) -> dict:
        return {k: self.service.stats[k] for k in COUNTERS}


def build(ctx):
    return Program(ctx)


def apply_delta(ctx, system, gen) -> None:
    """Draw a delta against the newest weights, apply it to the program
    and to the harness's copy (a new graph version)."""
    d = ctx.traffic["delta"]
    w = ctx.weights[-1]
    idx, new_w = tr.delta(gen, w, float(d["share"]), tuple(d["scale"]))
    system.apply_delta(ctx.src[idx], ctx.dst[idx], new_w)
    w = w.copy()
    w[idx] = new_w
    ctx.weights.append(w)


def _near_pairs(ctx, gen, k: int) -> np.ndarray:
    """``k`` queries from distinct origins to one of their out-neighbours
    (few rounds each: warm-up of shapes, not of distances)."""
    arcs = gen.choice(len(ctx.src), size=k, replace=False)
    return np.stack([ctx.src[arcs], ctx.dst[arcs]], axis=1)


def warm(ctx, system) -> None:
    """Compile every program the window uses: targeted waves of 1, 2,
    4 and 8 lanes, a lookup in a partial entry, a full 8-lane solve that
    fills the cache with eight full entries, and, where the mix has
    deltas, two warm refreshes of them (the refresh shape of every later
    delta)."""
    gen = tr.rng(ctx.seed, tr.WARMUP)
    for k in (1, 2, 3, 5, 8):
        near = _near_pairs(ctx, gen, k)
        system.serve(near)
    # an origin with a partial (early-exited) entry, asked for another
    # target: the cache reads that entry's fixed mask
    near[:, 1] = ctx.relabel[gen.integers(0, ctx.n, len(near))]
    system.serve(near)
    hot = np.repeat(_near_pairs(ctx, gen, 8)[:, 0], 4)
    promote = np.stack([hot, ctx.relabel[gen.integers(0, ctx.n, len(hot))]],
                       axis=1)
    system.serve(promote)      # 4 distinct targets per origin: full route
    system.serve(promote)
    if ctx.traffic.get("delta"):
        for _ in range(2):
            apply_delta(ctx, system, gen)
            system.serve(np.repeat(_near_pairs(ctx, gen, 2), 2, axis=0))


@dataclasses.dataclass
class Records:
    arrival: np.ndarray          # scheduled arrival (s from window start)
    pairs: np.ndarray            # int32[N, 2]
    answer: np.ndarray           # float64[N], NaN = never answered
    answered_at: np.ndarray      # float64[N], NaN = never answered
    version: np.ndarray          # graph version each answer was served on
    seconds: float
    delta_ms: list = dataclasses.field(default_factory=list)
    lateness_s: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    queued_at_end: int = 0
    drain_s: float = 0.0

    @property
    def window_s(self) -> float:
        return self.seconds

    @property
    def attempted(self) -> int:
        return len(self.arrival)

    @property
    def failed(self) -> int:
        return int(np.sum(np.isnan(self.answered_at)))

    @property
    def answered_in_window(self) -> int:
        return int(np.sum(self.answered_at <= self.seconds))

    @property
    def latency_ms(self) -> np.ndarray:
        """Scheduled arrival to answer, for every answered query."""
        ok = ~np.isnan(self.answered_at)
        return 1e3 * (self.answered_at[ok] - self.arrival[ok])


def measure(ctx, system, seconds: float) -> Records:
    t = ctx.traffic
    arrival = tr.arrivals(float(t["rate_qps"]), seconds)
    # the same trips in the same order every run (the mix's
    # generator_seed), turned with the graph: ordered by the run's seed,
    # which trips shared a wave moved the mean latency by 19% from seed
    # to seed on one TPU v5e
    pairs = ctx.relabel[tr.pairs(tr.rng(t["generator_seed"], tr.QUERIES),
                                 ctx.n, len(arrival), t["origins"])]
    n_q = len(arrival)
    rec = Records(arrival=arrival, pairs=pairs,
                  answer=np.full(n_q, np.nan),
                  answered_at=np.full(n_q, np.nan),
                  version=np.full(n_q, -1), seconds=seconds)
    dgen = tr.rng(ctx.seed, tr.DELTAS)
    d = t.get("delta")
    due = (list(np.arange(1, int(np.ceil(seconds / d["period_s"])))
                * float(d["period_s"])) if d else [])
    due = [x for x in due if x < seconds]
    before = system.counters()
    head = 0
    idle = True          # the harness was waiting when the next event fell due

    def serve(upto: int, t0: float) -> None:
        nonlocal head
        with ctx.span("serve"):
            got = system.serve(pairs[head:upto])
        done = time.perf_counter() - t0
        ok = ~np.isnan(got)
        sl = np.arange(head, upto)
        rec.answer[sl] = got
        rec.answered_at[sl[ok]] = done
        rec.version[sl] = len(ctx.weights) - 1
        head = upto

    with ctx.span("window"):
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            arrived = int(np.searchsorted(arrival, now, side="right"))
            if due and due[0] <= now:
                if idle:
                    rec.lateness_s.append(now - due[0])
                due.pop(0)
                with ctx.span("apply_delta"):
                    ts = time.perf_counter()
                    apply_delta(ctx, system, dgen)
                    rec.delta_ms.append((time.perf_counter() - ts) * 1e3)
                idle = False
                continue
            if arrived > head:
                if idle:
                    rec.lateness_s.append(now - arrival[head])
                serve(arrived, t0)
                idle = False
                continue
            nxt = min(arrival[head] if head < n_q else seconds,
                      due[0] if due else seconds, seconds)
            with ctx.span("wait"):
                time.sleep(max(0.0, nxt - (time.perf_counter() - t0)))
            idle = True
    rec.counters = {k: v - before[k] for k, v in system.counters().items()}
    rec.queued_at_end = n_q - int(np.sum(rec.answered_at <= seconds))
    while head < n_q and time.perf_counter() - t0 < seconds + DRAIN_S:
        serve(n_q, t0)
    rec.drain_s = max(0.0, time.perf_counter() - t0 - seconds)
    return rec


def window_notes(rec: Records) -> dict:
    late = np.asarray(rec.lateness_s or [0.0])
    lat = rec.latency_ms if len(rec.latency_ms) else np.zeros(1)
    return dict(queries=rec.attempted, answered_in_window=
                rec.answered_in_window, queued_at_end=rec.queued_at_end,
                latency_ms=dict(mean=float(np.mean(lat)),
                                p50=float(np.percentile(lat, 50)),
                                p80=float(np.percentile(lat, 80)),
                                p95=float(np.percentile(lat, 95)),
                                max=float(np.max(lat))),
                drain_s=rec.drain_s, deltas=len(rec.delta_ms),
                harness_lateness_s=dict(p50=float(np.median(late)),
                                        max=float(np.max(late))),
                counters=rec.counters)


def check(ctx, rec: Records) -> reference.Tally:
    """Every answer against the reference on the weights of the graph
    version it was served on."""
    tally = reference.Tally()
    tally.unanswered = rec.failed
    ok = ~np.isnan(rec.answered_at)
    for v in np.unique(rec.version[ok]):
        adj = reference.adjacency(ctx.n, ctx.src, ctx.dst, ctx.weights[v])
        sel = np.flatnonzero(ok & (rec.version == v))
        srcs, inv = np.unique(rec.pairs[sel, 0], return_inverse=True)
        want = reference.distances(adj, srcs)
        tally.add(rec.answer[sel], want[inv, rec.pairs[sel, 1]])
    return tally
