#!/usr/bin/env python3
"""Chip smoke: the SSSP service's main path, end to end on a TPU.

    python3 chip_smoke.py              # one chip: serve + backends phases
    python3 chip_smoke.py --chips 4    # four chips: the edge-sharded
                                       # distributed backend, nothing else

Every graph is generated from ``--seed``.  Each phase prints its graph
size, compile seconds, solve seconds and check result on its own line
(set-up observations, not metrics) and raises on a failed check.  The
last line of standard output is one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Phases (one chip):

* road — the grid at ``ROAD_SIDE`` (2^20 vertices, ~4.2M arcs, a
  regional road graph's scale): one point-to-point query, a one-lane
  ``solve_batch`` that exits early once its target ``ROAD_HOPS`` hops
  away is fixed, on ``segment``, ``frontier`` and ``frontier`` + Pallas.  The target's distance and every vertex the solve fixed
  must match scipy; ``frontier`` must equal ``segment`` bit for bit.
* serve — a ``grid`` graph of side ``GRID_SIDE`` behind
  ``SSSPService(backend="auto", batch=8, landmarks=8, planner=True,
  bidirectional=True)``, built as ``launch/serve_sssp.py`` builds it.
  256 Zipf queries over 32 hot sources in two waves, one weight delta
  on 1% of the edges between them; the final wave is checked against
  scipy's Dijkstra on the post-delta graph.
* backends — ``Solver.solve_batch`` over 8 sources of the same grid on
  ``segment``, ``frontier``, ``frontier`` + Pallas and ``pallas``:
  ``segment`` must match scipy, ``frontier`` must equal ``segment`` bit
  for bit, the Pallas routes must match it within the test
  tolerance.  Then ``gnp`` with mean out-degree 16 (Graph500's edge
  factor) on ``segment`` over ``GNP_LANES`` sources, each checked
  against scipy.

Four chips: ``gnp``, mean out-degree 16, on ``backend="distributed"``
over 2 sources, compared bit for bit with a single-device ``segment``
solve in the same process.

Sizes.  The target was a regional road graph (grid side 1024: 2^20
vertices, ~4.2M arcs) for every grid phase, gnp at 2^21 vertices over
8 sources, and gnp at 2^22 on four chips.  A full solve of the grid
takes about 2.7 rounds per unit of side (766 at side 256, ~2,800 at
side 1024), and on one TPU v5e a one-lane round over the side-1024
grid takes 0.25 s on ``segment`` and 0.6 s on ``frontier``, so one
full side-1024 solve would use most of the run's 1200 s.  At side 1024
the smoke therefore runs a point-to-point query, whose rounds grow
with the hops to its target (about 2 per hop), and the serve and
backends phases run full solves on a grid cut to side ``GRID_SIDE``:
the largest power of two whose phases fit the limit.  At side 256 the
serve phase took 580 s; at side 512 its rounds double and no round
gets cheaper, so it alone would pass 1,160 s.  gnp needs ~20 rounds,
so it keeps 2^21 vertices but runs ``GNP_LANES`` sources (a batch
cut): its random gathers make a round cost 7.3 s for 2 sources on one
chip.  The four-chip run repeats that single-chip solve as its
reference, more than twice as dear per round at 2^22 as at 2^21, so
its gnp is cut to ``2^DIST_LOG2N`` vertices.

There is no CPU fallback: without a TPU the script exits non-zero
before any phase.  All phases run in this one process, which holds the
chip(s).  The compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

RTOL, ATOL = 1e-5, 1e-4          # the test suite's tolerance
ROAD_SIDE = 1024      # road grid: 2^20 vertices, 4,190,208 arcs
ROAD_HOPS = 16        # grid hops from a road query's source to its target
GRID_SIDE = 256       # serve + backends graph (see "Sizes" above)
GNP_LOG2N = 21        # one-chip gnp: 2^21 vertices, ~33.5M arcs
GNP_LANES = 2         # its sources (see "Sizes" above)
DIST_LOG2N = 18       # four-chip gnp: 2^18 vertices, ~4.2M arcs
PALLAS_LANES = 2      # batch cut for the pallas backend (see BACKENDS)
# lowering and XLA compilation (tracing events nest, so they would
# count inner jits twice; tracing time stays in the solve seconds)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeCheckFailed(RuntimeError):
    """A phase's output disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckFailed(what)


class CompileClock:
    """Seconds JAX spent lowering and compiling while open."""

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += secs

    def __enter__(self) -> "CompileClock":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)


def report(phase: str, **fields) -> None:
    print(json.dumps(dict(phase=phase, **fields)), flush=True)


def timed(fn):
    """``(result, compile_s, solve_s)`` of a ``solve_batch`` call,
    blocked on its distances; solve seconds are the wall time less
    compile time."""
    import jax

    with CompileClock() as clock:
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out.dist)
        wall = time.perf_counter() - t0
    return out, clock.seconds, wall - clock.seconds


def device_info(chips: int) -> dict:
    """The device JAX sees; exits unless it is ``chips`` or more TPUs."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees {len(devs)} "
                 f"{d.platform} device(s), kind {d.device_kind!r}); "
                 "this smoke has no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPUs, "
                 f"JAX sees {len(devs)}")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def grid_graph(side: int, seed: int):
    """Device ``Graph`` of the 4-neighbour grid, built from COO arrays
    (no host adjacency lists)."""
    from repro.core import generators as gen
    from repro.core.graph import build_graph

    return build_graph(*gen.grid(side, seed=seed))


def gnp_graph(n: int, avg_deg: float, seed: int):
    from repro.core import generators as gen
    from repro.core.graph import build_graph

    return build_graph(*gen.gnp(n, avg_deg=avg_deg, seed=seed))


def reference(g, sources):
    """float64[len(sources), n] scipy Dijkstra distances on ``g``."""
    from repro.core.sssp.reference import scipy_dijkstra

    return scipy_dijkstra(g.to_host(), sources)


def close(got, want) -> bool:
    import numpy as np

    got = np.asarray(got, np.float64)
    return bool(np.allclose(got, want, rtol=RTOL, atol=ATOL))


def zipf_queries(n: int, count: int, hot: int, seed: int):
    """``count`` queries: sources Zipf-ranked over ``hot`` popular
    origins, targets uniform."""
    import numpy as np
    from repro.runtime.sssp_service import Query

    rng = np.random.default_rng(seed)
    pool = rng.choice(n, size=min(hot, n), replace=False)
    p = 1.0 / np.arange(1, len(pool) + 1) ** 1.2
    srcs = rng.choice(pool, size=count, p=p / p.sum())
    tgts = rng.integers(0, n, count)
    return [Query(source=int(s), target=int(t)) for s, t in zip(srcs, tgts)]


def serve_phase(g, *, queries: int = 256, hot: int = 32, batch: int = 8,
                landmarks: int = 8, check_sources: int = 8,
                seed: int = 0) -> None:
    """Two Zipf waves through ``SSSPService`` with a 1% weight delta
    between them; the final wave is checked against scipy."""
    import numpy as np
    from repro.runtime.sssp_service import SSSPService
    from repro.sssp import random_delta

    steps = {}

    def step(name, fn):
        t = time.perf_counter()
        out = fn()
        steps[name] = time.perf_counter() - t
        return out

    qs = zipf_queries(g.n, queries, hot, seed)
    half = len(qs) // 2
    k = max(1, g.e // 100)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        service = step("setup_wall_s", lambda: SSSPService(
            g, backend="auto", batch=batch, landmarks=landmarks,
            planner=True, bidirectional=True))
        step("wave1_wall_s", lambda: service.serve(qs[:half]))
        step("delta_wall_s", lambda: service.apply_delta(
            random_delta(service.solver.graph, k, seed=seed + 1)))
        final = step("wave2_wall_s", lambda: service.serve(qs[half:]))
        wall = time.perf_counter() - t0
    check(all(q.done for q in qs), "serve: unanswered queries")
    picked = list(dict.fromkeys(q.source for q in final))[:check_sources]
    want = reference(service.solver.graph, picked)
    row = {s: i for i, s in enumerate(picked)}
    checked = [q for q in final if q.source in row]
    got = np.array([np.inf if q.distance is None else q.distance
                    for q in checked])
    exp = np.array([want[row[q.source], q.target] for q in checked])
    ok = close(got, exp)
    st = service.stats
    report("serve", n=g.n, e=g.e, backend=service.solver.backend,
           queries=len(qs), delta_edges=k, compile_s=clock.seconds,
           solve_s=wall - clock.seconds, **steps,
           routes=st["planner_routes"],
           batches=st["batches"], bidi_solves=st["bidi_solves"],
           cache_hits=st["cache_hits"],
           checked_queries=len(checked), checked_sources=len(picked),
           check="ok" if ok else "MISMATCH")
    check(ok, f"serve: final wave disagrees with scipy on "
              f"{int(np.sum(~np.isclose(got, exp, rtol=RTOL, atol=ATOL)))}"
              f" of {len(checked)} answers")


# (report name, Solver backend, use_pallas, lanes).  The pallas backend's
# ELL layout pads the grid's in-degree 4 to 128 lanes, so each of its
# relax gathers touches 32x the edges: it runs the first PALLAS_LANES
# sources only (a batch cut; n is kept).
BACKENDS = (("segment", "segment", False, None),
            ("frontier", "frontier", False, None),
            ("frontier+pallas", "frontier", True, None),
            ("pallas", "pallas", True, PALLAS_LANES))
# The road phase leaves out ``pallas``: at side 1024 its padded ELL holds
# 134M slots and one road query took 109 s of the run's 1200 s.
ROAD_BACKENDS = BACKENDS[:3]


def backends_phase(g, sources, backends=BACKENDS) -> None:
    """``solve_batch`` on every backend: segment matches scipy, frontier
    == segment bitwise, the Pallas routes within tolerance."""
    import numpy as np
    from repro.core.sssp.engine import SP4_CONFIG
    from repro.sssp import Solver

    base = None
    for name, backend, use_pallas, lanes in backends:
        cfg = dataclasses.replace(SP4_CONFIG, use_pallas=use_pallas)
        sv = Solver(g, cfg, backend=backend)
        srcs = sources[:lanes]
        res, compile_s, solve_s = timed(lambda: sv.solve_batch(srcs))
        dist = np.asarray(res.dist)
        if base is None:
            base, ok = dist, close(dist, reference(g, list(srcs)))
        elif backend == "frontier" and not use_pallas:
            ok = bool(np.array_equal(dist, base))
        else:
            ok = close(dist, base[:len(srcs)])
        report("backends", graph="grid", n=g.n, e=g.e, backend=name,
               batch=len(srcs), rounds=int(np.max(res.rounds)),
               compile_s=compile_s, solve_s=solve_s,
               check="ok" if ok else "MISMATCH")
        check(ok, f"backends: {name} disagrees with segment")


def road_pair(side: int, hops: int, seed: int) -> tuple[int, int]:
    """A source and a target ``hops`` grid steps down and to the right
    of it (half each way)."""
    import numpy as np

    half = hops // 2
    i, j = np.random.default_rng(seed).integers(0, side - half, 2)
    s = int(i) * side + int(j)
    return s, s + half * (side + 1)


def road_phase(g, source: int, target: int,
               backends=ROAD_BACKENDS) -> None:
    """One point-to-point query (a one-lane ``solve_batch`` that exits
    at its target) on every backend: the target and every vertex the
    solve fixed match scipy; frontier == segment bit for bit."""
    import numpy as np
    from repro.core.sssp.engine import SP4_CONFIG
    from repro.sssp import Solver

    want = reference(g, [source])[0]
    base = None
    for name, backend, use_pallas, _ in backends:
        cfg = dataclasses.replace(SP4_CONFIG, use_pallas=use_pallas)
        sv = Solver(g, cfg, backend=backend)
        res, compile_s, solve_s = timed(
            lambda: sv.solve_batch([source], targets=[target]))
        dist = np.asarray(res.dist[0])
        fixed = np.asarray(res.fixed[0])
        ok = bool(fixed[target]) and close(dist[fixed], want[fixed])
        if base is None:
            base = dist
        elif backend == "frontier" and not use_pallas:
            ok = ok and bool(np.array_equal(dist, base))
        report("road", graph="grid", n=g.n, e=g.e, backend=name,
               source=source, target=target, batch=1,
               rounds=int(res.rounds[0]), fixed=int(fixed.sum()),
               compile_s=compile_s, solve_s=solve_s,
               check="ok" if ok else "MISMATCH")
        check(ok, f"road: {name} disagrees with scipy or segment")


def gnp_phase(g, sources, *, check_sources: int = 2) -> None:
    """``segment`` batch solve on a Graph500-shaped gnp, spot-checked
    against scipy."""
    import numpy as np
    from repro.sssp import Solver

    sv = Solver(g, backend="segment")
    res, compile_s, solve_s = timed(lambda: sv.solve_batch(sources))
    picked = list(sources[:check_sources])
    ok = close(res.dist[:check_sources], reference(g, picked))
    report("backends", graph="gnp", n=g.n, e=g.e, backend="segment",
           batch=len(sources), rounds=int(np.max(res.rounds)),
           compile_s=compile_s, solve_s=solve_s,
           checked_sources=len(picked), check="ok" if ok else "MISMATCH")
    check(ok, "backends: gnp segment disagrees with scipy")


def distributed_phase(g, sources) -> None:
    """Edge-sharded ``distributed`` backend over every device vs one
    device's ``segment`` solve: bit for bit."""
    import jax
    import numpy as np
    from repro.sssp import Solver

    out = {}
    for backend in ("distributed", "segment"):
        sv = Solver(g, backend=backend)
        res, compile_s, solve_s = timed(lambda: sv.solve_batch(sources))
        out[backend] = np.asarray(res.dist)
        report("distributed", n=g.n, e=g.e, backend=backend,
               devices=len(jax.devices()) if backend == "distributed" else 1,
               batch=len(sources), rounds=int(np.max(res.rounds)),
               compile_s=compile_s, solve_s=solve_s)
    ok = bool(np.array_equal(out["distributed"], out["segment"]))
    report("distributed", check="ok" if ok else "MISMATCH")
    check(ok, "distributed: sharded solve differs from single-device")


def spread_sources(n: int, count: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=count, replace=False)).astype(np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_info(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    report("setup", device=device, compile_cache=enable_compile_cache())

    if args.chips == 4:
        t0 = time.perf_counter()
        g = gnp_graph(1 << DIST_LOG2N, 16, args.seed)
        report("build", graph="gnp", n=g.n, e=g.e,
               seconds=time.perf_counter() - t0)
        distributed_phase(g, spread_sources(g.n, 2, args.seed))
    else:
        t0 = time.perf_counter()
        g = grid_graph(ROAD_SIDE, args.seed)
        report("build", graph="grid", n=g.n, e=g.e,
               seconds=time.perf_counter() - t0)
        road_phase(g, *road_pair(ROAD_SIDE, ROAD_HOPS, args.seed))
        del g
        t0 = time.perf_counter()
        g = grid_graph(GRID_SIDE, args.seed)
        report("build", graph="grid", n=g.n, e=g.e,
               seconds=time.perf_counter() - t0)
        serve_phase(g, seed=args.seed)
        backends_phase(g, spread_sources(g.n, 8, args.seed))
        del g
        t0 = time.perf_counter()
        g = gnp_graph(1 << GNP_LOG2N, 16, args.seed)
        report("build", graph="gnp", n=g.n, e=g.e,
               seconds=time.perf_counter() - t0)
        gnp_phase(g, spread_sources(g.n, GNP_LANES, args.seed))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
