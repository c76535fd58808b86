"""Benchmark harness: one module per paper claim.

  bench_heap_ops    — SP1/SP2 heap-op reduction vs Dijkstra (§III/§IV)
  bench_rounds      — rounds-to-fixpoint collapse + per-rule ablation +
                      Crauser in/out comparison (§V/§VI, Thm 4, Lem 9)
  bench_optimality  — Thm 2 (DAG O(e)) and Thm 3 (unweighted BFS)
  bench_throughput  — engine vs Bellman-Ford vs delta-stepping (CPU)
  bench_batch       — batched multi-source Solver + serving queries/sec
  bench_dynamic     — warm incremental re-solve vs cold after weight deltas
  bench_p2p         — goal-directed point-to-point vs full solves (ALT)
  bench_frontier    — sparse-frontier rounds vs dense (edges relaxed)
  bench_serve       — query-engine v2: planner vs always-full under Zipf
  bench_fleet       — many-graph congestion replay: fleet vs per-graph
                      loop, chaos (dropout/straggler) live
  bench_kernels     — kernel microbench (jnp path)

``python -m benchmarks.run [--quick]`` prints CSV blocks per bench.
"""
from __future__ import annotations

import argparse
import time


def emit(name: str, rows: list[dict]) -> None:
    print(f"\n# === {name} ===")
    if not rows:
        print("(no rows)")
        return
    cols: list[str] = []
    for r in rows:
        for k in r:
            if k not in cols:
                cols.append(k)
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_batch, bench_dynamic, bench_fleet,
                            bench_frontier, bench_heap_ops, bench_kernels,
                            bench_optimality, bench_p2p, bench_rounds,
                            bench_serve, bench_throughput)

    n = 600 if args.quick else 2000
    sizes = (1000, 4000) if args.quick else (2000, 8000, 32000)
    benches = {
        "heap_ops": lambda: bench_heap_ops.run(n=n),
        "rounds": lambda: bench_rounds.run(n=n),
        "optimality": lambda: bench_optimality.run(
            n=900 if args.quick else 3000),
        "throughput": lambda: bench_throughput.run(sizes=sizes),
        "batch": lambda: bench_batch.run(
            n=400 if args.quick else 2000, batch=8 if args.quick else 16,
            reps=1 if args.quick else 3),
        "dynamic": lambda: bench_dynamic.run(
            n=400 if args.quick else 2000,
            fractions=(0.01, 0.10) if args.quick else (0.005, 0.02, 0.10),
            deltas_per_point=1 if args.quick else 3),
        "p2p": lambda: bench_p2p.run(
            n=400 if args.quick else 2000, pairs=4 if args.quick else 8,
            reps=1 if args.quick else 3),
        "frontier": lambda: bench_frontier.run(
            n=400 if args.quick else 2000, reps=1 if args.quick else 3),
        "serve": lambda: bench_serve.run(
            n=300 if args.quick else 2000, wave=16 if args.quick else 32,
            waves_a=2 if args.quick else 4, waves_b=2 if args.quick else 4,
            waves_c=2 if args.quick else 4, k=4 if args.quick else 8),
        "fleet": lambda: bench_fleet.run(
            fleet=8 if args.quick else 64, n=120 if args.quick else 200,
            ticks=4 if args.quick else 10,
            queries_per_tick=2 if args.quick else 32),
        "kernels": bench_kernels.run,
    }
    t_all = time.time()
    for name, fn in benches.items():
        if args.only and args.only != name:
            continue
        t0 = time.time()
        rows = fn()
        emit(name, rows)
        print(f"# ({name}: {time.time() - t0:.1f}s)")
    print(f"\n# total {time.time() - t_all:.1f}s")


if __name__ == "__main__":
    main()
