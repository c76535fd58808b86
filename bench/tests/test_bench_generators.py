"""The seeded generators: graphs, roots, arrivals, origins, deltas."""
from __future__ import annotations

import numpy as np

from bench import traffic as tr
from bench.graphs import grid, kronecker

KRON = {"scale": 10, "edge_factor": 16, "generator_seed": 5,
        "initiator": [0.57, 0.19, 0.19, 0.05]}
BIG_SEED = 2**33 + 12345


def test_kronecker_is_seeded_loop_free_positive_and_symmetric():
    n, src, dst, w = kronecker.make(KRON, BIG_SEED)
    n2, src2, dst2, w2 = kronecker.make(KRON, BIG_SEED)
    assert n == 1024 and np.array_equal(src, src2) and np.array_equal(w, w2)
    # another seed relabels the same graph: same arcs and weights up to
    # a vertex permutation and the arcs' order
    _, src3, dst3, w3 = kronecker.make(KRON, 1)
    assert not np.array_equal(src, src3)
    assert np.array_equal(np.sort(w), np.sort(w3))
    assert np.array_equal(np.sort(np.bincount(src, minlength=n)),
                          np.sort(np.bincount(src3, minlength=n)))
    assert (src != dst).all() and (w > 0).all() and w.dtype == np.float32
    assert (w < 1).all()
    half = len(src) // 2
    assert np.array_equal(src[:half], dst[half:])
    assert np.array_equal(w[:half], w[half:])
    # about edge_factor * n undirected edges, less the self-loops
    assert 0.9 * 16 * n < half <= 16 * n
    # the initiator skews degrees: hubs far above the mean
    deg = np.bincount(src, minlength=n)
    assert deg.max() > 10 * deg.mean()


def test_grid_is_one_city_turned_by_the_seed():
    conf = {"side": 6, "weight_range": [0.05, 1.0], "generator_seed": 2}
    n, src, dst, w = grid.make(conf, 3)
    assert n == 36 and len(src) == 4 * 6 * 5
    assert (w >= 0.05).all() and (w < 1.0).all()
    assert ((np.abs(src - dst) == 1) | (np.abs(src - dst) == 6)).all()
    # every seed: the same weights on the same canonical arcs, relabelled
    # by one of the eight symmetries
    canon = grid.make(conf, 0)
    seen = set()
    for seed in range(8):
        perm = grid.relabel(conf, seed)
        assert sorted(perm) == list(range(n))
        _, s2, d2, w2 = grid.make(conf, seed)
        assert np.array_equal(s2, perm[canon[1]])
        assert np.array_equal(d2, perm[canon[2]])
        assert np.array_equal(w2, canon[3])
        seen.add(tuple(perm))
    assert len(seen) == 8


def test_roots_respect_degree_and_are_distinct():
    deg = np.array([0, 3, 0, 1, 5, 0, 2, 0])
    r = tr.roots(tr.rng(5, tr.ROOTS), deg, 4)
    assert len(set(r.tolist())) == 4 and (deg[r] >= 1).all()


def test_arrivals_match_rate_and_count_on_every_seed():
    t = tr.arrivals(8.0, 45.0)
    assert len(t) == 360
    assert t[0] == 0 and t[-1] < 45.0
    # evenly spaced at the rate: every run offers the same load
    np.testing.assert_allclose(np.diff(t), 1 / 8.0, rtol=1e-12)
    assert len(tr.arrivals(1.2, 45.0)) == 54


def test_zipf_origins_share_and_profile():
    origins = {"hot": 64, "hot_share": 0.8, "zipf_s": 1.2}
    count = 2000
    p = tr.pairs(tr.rng(9, tr.QUERIES), 10_000, count, origins)
    assert p.shape == (count, 2) and p.dtype == np.int32
    src, counts = np.unique(p[:, 0], return_counts=True)
    top = np.sort(counts)[::-1]
    # 80% of origins fall on 64 hot vertices (uniform draws rarely
    # repeat among 10,000); rank 1 takes 1/H(64, 1.2) of them
    assert abs(top[:64].sum() / count - 0.8) < 0.02
    h = np.sum(1.0 / np.arange(1, 65) ** 1.2)
    assert abs(top[0] / (0.8 * count) - 1 / h) < 0.02
    uni = tr.pairs(tr.rng(9, tr.QUERIES), 10_000, count,
                   {"hot": 0, "hot_share": 0.0})
    assert np.unique(uni[:, 0]).size > 0.8 * count


def test_delta_share_and_scale():
    w = np.full(10_000, 0.5, np.float32)
    idx, new_w = tr.delta(tr.rng(3, tr.DELTAS), w, 0.01, (0.5, 2.0))
    assert len(idx) == 100 == len(np.unique(idx))
    assert new_w.dtype == np.float32
    assert ((new_w >= 0.25) & (new_w <= 1.0)).all()
