"""SSSP serving launcher: batched shortest-path queries over one graph.

  python -m repro.launch.serve_sssp --family gnp --n 5000 \
      --queries 256 --batch 8 --backend segment

Generates a graph, stands up the continuous-batching
:class:`~repro.runtime.sssp_service.SSSPService`, fires a synthetic
query stream with a Zipf-ish repeated-source distribution (the
realistic serving regime: popular origins dominate), and reports
queries/sec, batch count, and cache hit rate, after a line naming the
device it ran on.  ``--verify`` re-checks a sample of answers against
scipy's compiled Dijkstra (``reference.scipy_dijkstra``).

``--deltas K`` interleaves K random weight deltas (``--delta-edges``
edges each) between query waves — the dynamic-graph serving regime:
each delta warm-refreshes the hot sources through the compiled
incremental re-solve and version-stamps the rest of the cache stale.

``--landmarks K`` builds a K-landmark index and routes scalar-target
queries through the goal-directed fast path (seeded lower bounds +
early-exit targeted solves) instead of full per-source solves.

Query-engine v2: ``--planner`` turns on the cost-based wave planner
(cache / targeted / bidirectional / full routing per wave),
``--bidirectional`` attaches the meet-in-the-middle point-to-point
solver, and ``--reselect-threshold T`` re-selects landmark positions
when observed seed tightness drops below T.  A ``stats`` line reports
the planner route counts and ``seed_tightness_mean``.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="gnp",
                    choices=["gnp", "dag", "unweighted", "grid",
                             "power_law", "chain", "geometric"])
    ap.add_argument("--n", type=int, default=5000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--hot-sources", type=int, default=32,
                    help="size of the popular-origin pool queries draw from")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "segment", "ell", "pallas",
                             "distributed", "frontier"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--deltas", type=int, default=0,
                    help="weight deltas interleaved between query waves")
    ap.add_argument("--delta-edges", type=int, default=None,
                    help="edges per delta (default: 1%% of edges)")
    ap.add_argument("--landmarks", type=int, default=0,
                    help="landmark count for the goal-directed fast path "
                         "(0 = full solves, the pre-PR-3 serving path)")
    ap.add_argument("--planner", action="store_true",
                    help="cost-based wave planner: route each wave's "
                         "misses to cache/targeted/bidirectional/full")
    ap.add_argument("--bidirectional", action="store_true",
                    help="attach the meet-in-the-middle point-to-point "
                         "solver (the planner's 'bidirectional' route; "
                         "without --planner, every scalar-target miss)")
    ap.add_argument("--reselect-threshold", type=float, default=None,
                    help="re-select landmark positions when mean seed "
                         "tightness drops below this (needs --landmarks)")
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.core import generators as gen
    from repro.core.graph import build_graph
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime.sssp_service import Query, SSSPService

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")
    g = build_graph(*gen.make(args.family, args.n, seed=args.seed))
    n = g.n
    print(f"graph: {args.family} n={n} e={g.e}  backend={args.backend}")

    service = SSSPService(g, backend=args.backend,
                          batch=args.batch,
                          landmarks=args.landmarks or None,
                          planner=args.planner,
                          bidirectional=args.bidirectional,
                          reselect=args.reselect_threshold)
    rng = np.random.default_rng(args.seed)
    hot = rng.choice(n, size=min(args.hot_sources, n), replace=False)
    queries = [Query(source=int(rng.choice(hot)),
                     target=int(rng.integers(0, n)))
               for _ in range(args.queries)]

    waves = max(1, args.deltas + 1)
    per_wave = -(-len(queries) // waves)   # ceil: exactly `waves` waves
    t0 = time.time()
    final_wave: list[Query] = queries
    for i in range(0, len(queries), per_wave):
        wave = queries[i: i + per_wave]
        service.serve(wave)
        final_wave = wave
        if args.deltas and i + per_wave < len(queries):
            from repro.sssp import random_delta
            k = (max(1, g.e // 100) if args.delta_edges is None
                 else args.delta_edges)
            dstats = service.apply_delta(
                random_delta(service.solver.graph, k,
                             seed=args.seed + 31 * i))
            print(f"  delta v{service.version}: {k} edges, "
                  f"warm-refreshed {dstats['warm_refreshed']} hot sources "
                  f"in <= {max(dstats['warm_rounds'] or [0])} rounds "
                  f"({dstats['sweeps']} taint sweeps)")
    dt = time.time() - t0

    st = service.stats
    answered = sum(q.done for q in queries)
    reachable = sum(q.path is not None for q in queries)
    print(f"answered {answered} queries in {dt:.2f}s "
          f"({answered / dt:.1f} queries/s)")
    print(f"  solve batches: {st['batches']}  sources solved: "
          f"{st['sources_solved']}  targeted solves: {st['p2p_solves']}  "
          f"cache hits: {st['cache_hits']}  deltas: {st['deltas']}")
    print(f"  device solve time: {st['solve_seconds']:.2f}s  "
          f"reachable targets: {reachable}/{answered}")
    routes = st["planner_routes"]
    tight = st["seed_tightness_mean"]
    print(f"stats: routes cache={routes['cache']} "
          f"targeted={routes['targeted']} "
          f"bidirectional={routes['bidirectional']} full={routes['full']}  "
          f"bidi_solves={st['bidi_solves']} reselects={st['reselects']}  "
          f"seed_tightness_mean="
          f"{'n/a' if tight is None else f'{tight:.3f}'}")

    if args.verify:
        # verify against the CURRENT (post-delta) graph version; only the
        # final wave's answers are guaranteed to reflect it.
        from repro.core.sssp.reference import scipy_dijkstra
        final = final_wave[:16]
        srcs = list(dict.fromkeys(q.source for q in final))
        ref = scipy_dijkstra(service.solver.graph.to_host(), srcs)
        row = {s: i for i, s in enumerate(srcs)}
        got = np.array([np.inf if q.distance is None else q.distance
                        for q in final])
        exp = np.array([ref[row[q.source], q.target] for q in final])
        bad = int(np.sum(~np.isclose(got, exp, rtol=1e-5, atol=1e-4)))
        print(f"  verified {len(final)} answers against scipy dijkstra: "
              f"{'OK' if bad == 0 else f'{bad} MISMATCHES'}")
        if bad:
            sys.exit(1)


if __name__ == "__main__":
    main()
