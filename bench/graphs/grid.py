"""Seeded 4-neighbour grid (the square lattice) with uniform travel
times: a synthetic city (low degree, high diameter), copied from the
program's generator so the yardstick does not move with it.

The weights come from the configuration's ``generator_seed``; the run's
seed picks one of the grid's eight symmetries (rotations and
reflections) to relabel the vertices.  Every seed thus solves the same
city, turned: the same work in another order.  With weights drawn from
the run's seed, the service cells' readings moved by up to 25% from
seed to seed.
"""
from __future__ import annotations

import numpy as np


def relabel(graph: dict, seed: int) -> np.ndarray:
    """int[n]: the vertex id of each canonical vertex under the
    symmetry ``seed`` picks."""
    side = int(graph["side"])
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    k = int(seed) % 8
    if k & 4:
        ii, jj = jj, ii
    if k & 2:
        ii = side - 1 - ii
    if k & 1:
        jj = side - 1 - jj
    return (ii * side + jj).ravel()


def make(graph: dict, seed: int):
    side = int(graph["side"])
    lo, hi = (float(x) for x in graph["weight_range"])
    rng = np.random.default_rng(int(graph["generator_seed"]))
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    vid = (ii * side + jj).ravel()
    srcs, dsts = [], []
    for di, dj in ((0, 1), (1, 0), (0, -1), (-1, 0)):
        ni, nj = ii + di, jj + dj
        ok = ((ni >= 0) & (ni < side) & (nj >= 0) & (nj < side)).ravel()
        srcs.append(vid[ok])
        dsts.append((ni * side + nj).ravel()[ok])
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    w = rng.uniform(lo, hi, len(src)).astype(np.float32)
    perm = relabel(graph, seed)
    return (side * side, perm[src].astype(np.int32),
            perm[dst].astype(np.int32), w)
