"""The plain reference, the comparison that decides ``correct``, and
the lower-precision control.

* :func:`distances` -- float64 Dijkstra (``scipy.sparse.csgraph``) on
  the harness's own copy of the arcs.  Parallel arcs keep their minimum
  weight (a sparse matrix would sum them).
* :func:`compare` -- what the timed path returned against it.
* :class:`Bf16Fixpoint` -- the reference's recurrence
  (``d[v] = min_u d[u] + w(u, v)``) solved to its fixpoint with every
  weight and every sum rounded to bfloat16, the precision below the
  float32 the engine states.  It stands in for the program in the
  control runs (``bench/control.py``), which must come out not correct.
"""
from __future__ import annotations

import numpy as np

# Limits on the numbers compared; PERF.md gives the readings each was
# set from.  reach_mismatch and unanswered are exact comparisons.
LIMITS = {"dist_rel_err": 2e-4, "reach_mismatch": 0, "unanswered": 0}


def adjacency(n: int, src, dst, w):
    """CSR matrix of the arcs with parallel arcs min-reduced."""
    from scipy.sparse import csr_matrix

    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    key = src * n + dst
    order = np.argsort(key, kind="stable")
    key = key[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    wmin = np.minimum.reduceat(np.asarray(w, np.float64)[order], first)
    return csr_matrix((wmin, (src[order][first], dst[order][first])),
                      shape=(n, n))


def distances(adj, sources) -> np.ndarray:
    """float64[len(sources), n] shortest-path distances."""
    from scipy.sparse.csgraph import dijkstra

    return dijkstra(adj, directed=True,
                    indices=np.asarray(sources, np.int64))


class Tally:
    """Running worst case of the compared numbers."""

    def __init__(self):
        self.dist_rel_err = 0.0
        self.reach_mismatch = 0
        self.unanswered = 0
        self.compared = 0

    def add(self, got, want) -> None:
        """Compare distances ``got`` (any float) with ``want``
        (float64) elementwise: relative error where both are finite,
        a reachability mismatch where exactly one is."""
        got = np.asarray(got, np.float64).ravel()
        want = np.asarray(want, np.float64).ravel()
        self.compared += got.size
        fin_g, fin_w = np.isfinite(got), np.isfinite(want)
        self.reach_mismatch += int(np.sum(fin_g != fin_w))
        both = fin_g & fin_w
        if both.any():
            g, r = got[both], want[both]
            err = np.abs(g - r) / np.where(r > 0, r, 1.0)
            # a source's own distance is 0: any other value is wrong
            err = np.where((r == 0) & (g != 0), np.inf, err)
            self.dist_rel_err = max(self.dist_rel_err, float(np.max(err)))

    def checks(self, limits: dict = LIMITS) -> dict:
        return {k: {"value": getattr(self, k), "limit": limits[k]}
                for k in ("dist_rel_err", "reach_mismatch", "unanswered")}


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


class Bf16Fixpoint:
    """The control: the reference's recurrence in bfloat16.

    ``d[v] = min(d[v], bf16(d[u] + bf16(w)))`` over every arc, swept
    until nothing changes, on the default JAX device.  One compiled
    program per lane count.
    """

    def __init__(self, n: int, src, dst):
        import jax
        import jax.numpy as jnp

        self.n = n
        self.src = jnp.asarray(np.asarray(src, np.int32))
        self.dst = jnp.asarray(np.asarray(dst, np.int32))
        self._solve = jax.jit(self._fixpoint)

    def _fixpoint(self, src, dst, w, sources):
        import jax
        import jax.numpy as jnp

        bf = jnp.bfloat16
        lanes = sources.shape[0]
        d0 = jnp.full((lanes, self.n), jnp.inf, bf)
        d0 = d0.at[jnp.arange(lanes), sources].set(0)

        def sweep(carry):
            d, _, it = carry
            cand = (d[:, src] + w[None, :]).astype(bf)
            best = jax.vmap(lambda c: jax.ops.segment_min(
                c, dst, num_segments=self.n))(cand)
            new = jnp.minimum(d, best)
            return new, jnp.any(new != d), it + 1

        d, _, it = jax.lax.while_loop(lambda c: c[1], sweep,
                                      (d0, jnp.bool_(True), 0))
        return d.astype(jnp.float32), it

    def distances(self, w, sources):
        """``(float32[len(sources), n], sweeps)``."""
        import jax.numpy as jnp

        d, it = self._solve(self.src, self.dst,
                            jnp.asarray(np.asarray(w, np.float32),
                                        jnp.bfloat16),
                            jnp.asarray(np.asarray(sources, np.int32)))
        return np.asarray(d), int(it)
