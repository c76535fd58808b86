"""Graph500 Kronecker generator, vectorised over edges.

Follows the Graph500 specification's reference generator: ``M =
edge_factor * 2**scale`` undirected edges, each placed bit by bit in
the quadrant drawn from the initiator ``(A, B, C, D)``; vertex labels
are then permuted at random (here by the run's seed).  Weights are
uniform on [0, 1) as the SSSP kernel asks, redrawn where zero because
the engine needs ``w > 0``.
Self-loops are dropped, parallel edges kept, and each undirected edge
is stored as two arcs with one weight.
"""
from __future__ import annotations

import numpy as np


def make(graph: dict, seed: int):
    """The configuration's graph, relabelled by ``seed``.

    The edges and weights come from the configuration's
    ``generator_seed``, so every run solves the same graph; ``seed``
    draws the random vertex labelling (Graph500's permutation) and the
    order of the arcs.  Different seeds thus give isomorphic graphs:
    the same work in another order.  Drawn from ``seed`` itself, the
    graphs' depths differed enough that a run's round count moved by
    +-8% from seed to seed.
    """
    scale = int(graph["scale"])
    a, b, c, _ = (float(x) for x in graph["initiator"])
    rng = np.random.default_rng(int(graph["generator_seed"]))
    n = 1 << scale
    m = int(graph["edge_factor"]) * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        i |= ii.astype(np.int64) << bit
        j |= jj.astype(np.int64) << bit
    w = rng.random(m, dtype=np.float32)
    zero = w == 0
    while zero.any():
        w[zero] = rng.random(int(zero.sum()), dtype=np.float32)
        zero = w == 0
    keep = i != j
    i, j, w = i[keep], j[keep], w[keep]
    relabel = np.random.default_rng(int(seed) & (2**63 - 1))
    perm = relabel.permutation(n)
    order = relabel.permutation(len(i))
    i, j, w = perm[i[order]], perm[j[order]], w[order]
    src = np.concatenate([i, j]).astype(np.int32)
    dst = np.concatenate([j, i]).astype(np.int32)
    return n, src, dst, np.concatenate([w, w])
