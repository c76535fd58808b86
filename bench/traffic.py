"""The one traffic generator: every mix is a data file it reads.

All draws come from ``numpy.random.default_rng([seed, stream])``, so
the same seed gives the same roots, queries and deltas.  Arrivals are
evenly spaced at the mix's rate, so every run offers the same load;
the Zipf ranks of the hot origins are the quantiles of the Zipf law,
shuffled, so every draw has the same popularity profile.
"""
from __future__ import annotations

import numpy as np

# stream ids: each use of a seed draws from its own stream; an id is
# part of every draw made from it, so changing one changes the traffic
ROOTS, QUERIES, DELTAS, WARMUP = 1, 2, 4, 5


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def roots(gen: np.random.Generator, out_deg: np.ndarray,
          count: int) -> np.ndarray:
    """``count`` distinct roots among vertices of out-degree at least 1
    (Graph500's rule)."""
    pool = np.flatnonzero(out_deg >= 1)
    return gen.choice(pool, size=min(count, len(pool)),
                      replace=False).astype(np.int32)


def arrivals(rate: float, seconds: float) -> np.ndarray:
    """Open-loop arrival times in ``[0, seconds)``: ``round(rate *
    seconds)`` arrivals, one every ``1 / rate`` seconds."""
    count = max(1, int(round(rate * seconds)))
    return np.arange(count) * (seconds / count)


def zipf_ranks(count: int, hot: int, s: float) -> np.ndarray:
    """The Zipf(s) law over ranks ``0..hot-1`` as ``count`` quantiles."""
    p = 1.0 / np.arange(1, hot + 1) ** s
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(count) + 0.5) / count
    return np.minimum(np.searchsorted(cdf, u), hot - 1)


def pairs(gen: np.random.Generator, n: int, count: int,
          origins: dict) -> np.ndarray:
    """int32[count, 2] (origin, target) pairs.

    ``origins``: ``hot`` vertices (drawn at random) get ``hot_share``
    of the origins, ranked by Zipf(``zipf_s``); the rest are uniform.
    Targets are uniform.
    """
    hot_n = int(origins.get("hot", 0))
    n_hot = int(round(count * float(origins.get("hot_share", 0.0))))
    src = gen.integers(0, n, count)
    if hot_n and n_hot:
        pool = gen.choice(n, size=hot_n, replace=False)
        ranks = gen.permutation(zipf_ranks(n_hot, hot_n,
                                           float(origins["zipf_s"])))
        where = gen.choice(count, size=n_hot, replace=False)
        src[where] = pool[ranks]
    dst = gen.integers(0, n, count)
    return np.stack([src, dst], axis=1).astype(np.int32)


def delta(gen: np.random.Generator, w: np.ndarray, share: float,
          scale: tuple[float, float]) -> tuple[np.ndarray, np.ndarray]:
    """``(arc ids, new float32 weights)``: ``share`` of the arcs, each
    rescaled by uniform ``scale``."""
    k = max(1, int(round(share * len(w))))
    idx = np.sort(gen.choice(len(w), size=k, replace=False))
    lo, hi = scale
    new_w = (w[idx] * gen.uniform(lo, hi, k).astype(np.float32))
    return idx, new_w.astype(np.float32)
