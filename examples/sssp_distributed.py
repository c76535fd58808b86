"""Distributed SP4 over every device: edges sharded over a (data, model)
mesh built from the device count, vertex state replicated, pmin
all-reduces per round — bitwise identical to the single-device engine.

On the CPU this launcher-style script gives itself 8 virtual devices
(the override touches only the host platform; the library and tests
never set it); on a TPU host it uses the chips it finds.

  python examples/sssp_distributed.py --n 20000
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--deg", type=float, default=8.0)
    args = ap.parse_args()

    import jax
    from jax.sharding import Mesh
    from repro.core import generators as gen
    from repro.core.graph import build_graph
    from repro.sssp import SP4_CONFIG, Solver

    print(f"devices: {len(jax.devices())}")
    n, src, dst, w = gen.gnp(args.n, avg_deg=args.deg, seed=0)
    g = build_graph(n, src, dst, w)
    print(f"graph n={n} e={g.e}")

    devs = np.asarray(jax.devices())
    rows = 2 if devs.size % 2 == 0 else 1
    mesh = Mesh(devs.reshape(rows, -1), ("data", "model"))
    sharded = Solver(g, SP4_CONFIG, backend="distributed",
                     mesh=mesh, axes=("data", "model"))
    t0 = time.time()
    res = sharded.solve(0)
    D = res.dist
    jax.block_until_ready(D)
    t_dist = time.time() - t0

    local = Solver(g, SP4_CONFIG)
    t0 = time.time()
    single = local.solve(0)
    jax.block_until_ready(single.dist)
    t_single = time.time() - t0

    assert np.array_equal(np.asarray(single.dist), np.asarray(D)), \
        "distributed must be bitwise identical (min is associative)"
    reach = int(np.isfinite(np.asarray(D)).sum())
    print(f"rounds={res.rounds}  reachable={reach}/{n}")
    print(f"single-device {t_single*1e3:.0f} ms | "
          f"{devs.size}-device sharded {t_dist*1e3:.0f} ms "
          f"on {devs.flat[0].platform} (first calls: compile included)")
    print("bitwise identical ✓")


if __name__ == "__main__":
    main()
