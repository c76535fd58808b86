"""Bidirectional targeted solves: meet-in-the-middle point-to-point.

A targeted solve from ``s`` pays rounds proportional to the ball around
``s`` that must be certified before ``t`` is fixed; growing two half-
radius balls — forward from ``s`` on the graph and backward from ``t``
on its transpose — touches far fewer vertices on everything road-like.
This is the heuristic bidirectional search of Yu et al. (arXiv
2506.19349) grafted onto the paper's criteria engine, and the Kainer &
Träff per-round parallelism point (arXiv 1903.12085) is what makes the
two searches free to run *simultaneously*: both lanes are one vmapped
program over a stacked ``[2, ...]`` graph pytree, sharing the engine's
``_round`` body — the same bulk-synchronous round, twice the frontier
per step.

Termination (the bidirectional invariant; README mirrors this):

    stop when  bound_f + bound_b  >=  mu,
    where  bound_lane = min D over (active | fixed-but-unexplored)
    and    mu         = min_v (D_f[v] + D_b[v]).

``mu`` is always an upper bound on d(s, t) (both D fields are
relaxation values).  ``bound_lane`` lower-bounds the true distance of
every vertex its lane has NOT fixed: for any such vertex, the first
non-fixed vertex u on its shortest path has either an explored
predecessor (whose final-D relax made ``D[u] <= d(s,u)``, so u is
active and counted) or a fixed-but-unexplored predecessor p (whose
exact ``D[p] <= d(s,u)`` is counted via the pending term — the
bulk-synchronous twist: a vertex fixed late in a round relaxes its
out-edges only next round, so the classic "min heap key" must include
it).  At the stop, suppose d(s,t) < mu: no vertex of the shortest path
is fixed in both lanes (it would witness ``mu <= d(s,t)``), so the
first fwd-unfixed vertex u and last bwd-unfixed vertex x satisfy either
u <= x — then ``d(s,t) >= d(s,u) + d(x,t) >= bound_f + bound_b >= mu``,
contradiction — or u > x with x fwd-fixed: x unexplored puts
``D[x] = d(s,x)`` in bound_f (same contradiction), x explored means its
relaxed successor y on the path is bwd-fixed and witnesses
``mu <= D_f[y] + D_b[y] <= d(s,t)``, contradiction.  Hence mu = d(s,t)
exactly — and the meeting vertex ``argmin(D_f + D_b)`` has BOTH its
lane distances exact (the min pinches the triangle inequality), which
is what lets :meth:`BidiResult.path` stitch an exact s→t path across it
even when neither lane fixed it.

Seeding: both lanes take landmark (ALT) lower bounds from the SAME
:class:`~repro.core.sssp.landmarks.LandmarkIndex` tables — the forward
lane via ``seed_lower_bounds(d_from, d_to, s)``, the backward lane via
the table swap ``seed_lower_bounds(d_to, d_from, t)`` (distances from
``t`` on the transpose are distances TO ``t``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.core.graph import Graph, HostGraph, INF
from repro.core.sssp import backends
from repro.core.sssp.engine import (SP4_CONFIG, SSSPConfig, SSSPResult,
                                    _fixed_by_dict, _init_state, _round,
                                    _solve_warm, delta_taint_seeds)
from repro.core.sssp.solver import _frontier_fits, _next_pow2

BIDI_BACKENDS = ("auto", "segment", "frontier")


def _stack2(a, b):
    """Stack two same-structure pytrees along a new leading lane axis.

    Static aux data (n / e / e_pad / max_out_deg) must match — the
    treedef comparison inside ``tree.map`` enforces it — so the result
    is the *same* dataclass with ``[2, ...]`` leaves: exactly what
    ``vmap(in_axes=0)`` unstacks back into two well-formed graphs.
    """
    return jax.tree.map(lambda x, y: jnp.stack([x, y]), a, b)


@dataclasses.dataclass
class BidiResult:
    """One bidirectional point-to-point answer + both lanes' state.

    ``distance`` is exact (== d(source, target); inf = unreachable) and
    is re-folded left-to-right along the stitched path, so its float32
    bits match a forward solve's ``dist[target]`` (a meet-in-the-middle
    sum associates the same real value differently; ``mu`` keeps that
    raw two-lane value).  ``meeting`` is the argmin of ``D_f + D_b`` —
    a vertex whose forward
    AND backward distances are both exact at termination (see module
    docstring), possibly fixed by neither lane.  Lane 0 of every [2, n]
    field is the forward search, lane 1 the backward search (distances
    on the reverse graph = distances TO the target).
    """

    source: int
    target: int
    distance: float
    meeting: int | None
    rounds: int
    D: jax.Array            # float32[2, n]
    C: jax.Array            # float32[2, n]
    fixed: jax.Array        # bool[2, n]
    fixed_by: dict[str, int]
    graph: Graph
    rgraph: Graph
    mu: float = float("inf")
    edges_relaxed: int | None = None
    _path: list[int] | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def forward_result(self) -> SSSPResult:
        """The forward lane as a partial :class:`SSSPResult`.

        Its ``fixed`` mask certifies exactly which entries are exact —
        the same contract as an early-exited targeted solve, so serving
        layers may cache it ``partial=True``.
        """
        return SSSPResult(
            dist=self.D[0], C=self.C[0], fixed=self.fixed[0],
            rounds=self.rounds, fixed_by=self.fixed_by,
            source=self.source, graph=self.graph, target=self.target,
            partial=True)

    def path(self) -> list[int] | None:
        """Exact s→t vertex list stitched across the meeting vertex.

        Forward half via parent pointers on ``D_f`` (graph), backward
        half via parent pointers on ``D_b`` (reverse graph), walked
        t→meeting and flipped.  Both walks stay on exact vertices: the
        meeting vertex is exact in both lanes, and a feasible parent of
        an exact vertex is itself exact and on a shortest path (the
        partial-result argument of ``SSSPResult.path_to``).
        """
        if self._path is not None:
            return self._path
        if not np.isfinite(self.distance):
            return None
        from repro.core.sssp.parents import extract_path, parent_pointers
        m = int(self.meeting)
        fwd = extract_path(np.asarray(parent_pointers(self.graph, self.D[0])),
                           m, self.source)
        bwd = extract_path(np.asarray(parent_pointers(self.rgraph, self.D[1])),
                           m, self.target)
        if fwd is None or bwd is None:
            return None
        self._path = fwd + bwd[::-1][1:]
        return self._path


@contract(
    "bidi.pair_lanes",
    routes=("bidi.*",),
    require=("scatter-min",),
    dense_budget={"bidi.warm": 8, "bidi.*": 6},
    notes="Forward and reverse searches run as TWO LANES of one "
          "vmapped segment-backend program (one dispatch per round "
          "pair, not two); the lanes share the round body, so the "
          "segment scatter-min relax and the segment dense budget "
          "apply per lane.")
class BidirectionalSolver:
    """Compiled bidirectional point-to-point solver over one graph.

    Parameters
    ----------
    graph:   device :class:`Graph` or :class:`HostGraph`.
    cfg:     engine configuration (shared by both lanes).
    backend: "auto" | "segment" | "frontier" — the two lanes run the
             same backend; "auto" picks "frontier" when BOTH the graph
             and its transpose predict thin wavefronts.
    rgraph:  pre-built transpose (``graph.reverse()`` when omitted);
             must share n / e / e_pad with ``graph``.
    landmarks: optional :class:`LandmarkIndex` — ``solve`` then seeds
             both lanes via :meth:`LandmarkIndex.seed_pair`.
    frontier_cap: buffer size for the frontier backend.  Defaults to
             ``next_pow2(n)`` — a buffer that can never overflow, so
             the overflow ``lax.cond`` vanishes statically and the
             two-lane vmap never pays the linearized both-branch round
             (the same escape hatch ``Solver`` documents for batches).

    ``apply_delta(delta)`` keeps both lanes' graphs (and CSR views)
    coherent with a forward-graph :class:`GraphDelta` — the reverse
    side goes through the precomputed forward→reverse edge permutation,
    the same remap ``LandmarkIndex`` uses.  Solves never retrace across
    versions: the stacked graph is a traced operand.

    ``update(delta, warm=[...])`` additionally re-solves hot ``(s, t)``
    pairs WARM from their cached two-lane state — the pair-cache mirror
    of ``DynamicSolver``'s hot-source refresh.  Each pair's stacked
    ``[2, n]`` D/fixed arrays re-enter the engine through the same
    taint-cone warm start (``delta_taint_seeds`` + ``_solve_warm``),
    both lanes in one vmapped program.  Warm-starting from a PARTIAL
    (early-stopped) lane is exact: every finite ``D0[v]`` was achieved
    by some relaxation path whose steps are tight in D0, so if that
    path used an increased edge the taint sweep walks the same tight
    chain and resets ``v`` — stale-low bounds cannot survive.  The warm
    re-solve then runs each lane to its FULL fixpoint (the standard
    cond, not the bidirectional cut), so the refreshed forward lane is
    a complete distance vector and the re-folded pair distance is
    bitwise what a cold solve on the new graph returns (property-tested
    in ``tests/test_fleet.py``).
    """

    def __init__(self, graph, cfg: SSSPConfig = SP4_CONFIG,
                 backend: str = "auto", *, rgraph: Graph | None = None,
                 landmarks=None, frontier_cap: int | None = None):
        if backend not in BIDI_BACKENDS:
            raise ValueError(f"unknown bidirectional backend {backend!r}; "
                             f"expected one of {BIDI_BACKENDS}")
        if isinstance(graph, HostGraph):
            graph = graph.to_device()
        if not isinstance(graph, Graph):
            raise TypeError(f"graph must be Graph/HostGraph, "
                            f"got {type(graph)!r}")
        if rgraph is None:
            rgraph = graph.reverse()
        if (rgraph.n, rgraph.e, rgraph.e_pad) != (graph.n, graph.e,
                                                  graph.e_pad):
            raise ValueError(
                f"reverse graph shape {(rgraph.n, rgraph.e, rgraph.e_pad)} "
                f"must match forward {(graph.n, graph.e, graph.e_pad)} "
                "(build it via graph.reverse())")
        if backend == "auto":
            backend = ("frontier" if _frontier_fits(graph)
                       and _frontier_fits(rgraph) else "segment")
        if backend != "frontier" and cfg.use_pallas:
            cfg = dataclasses.replace(cfg, use_pallas=False)
        self.graph, self.rgraph = graph, rgraph
        self.cfg = cfg
        self.backend = backend
        self.landmarks = landmarks
        self.trace_count = 0
        self.warm_trace_count = 0
        self.solves = 0
        self.warm_solves = 0

        # forward edge i (dst-sorted) -> its row in the reverse graph's
        # dst-sorted list (same derivation as LandmarkIndex.reverse_delta)
        e = graph.e
        order = np.argsort(np.asarray(graph.src[:e]), kind="stable")
        self._rev_perm = np.empty(e, np.int64)
        self._rev_perm[order] = np.arange(e)

        self._wmap = None
        self.frontier_cap = 0
        self._csr_f = self._csr_b = None
        if backend == "frontier":
            self.frontier_cap = _next_pow2(
                graph.n if frontier_cap is None else max(1, int(frontier_cap)))
            csr_f, csr_b = graph.csr(), rgraph.csr()
            # the lanes' CSR views stack into one vmapped operand, so
            # their static gather widths must agree — the max is safe
            # (extra slots gather padding) and keeps one compiled kernel.
            wide = max(csr_f.max_out_deg, csr_b.max_out_deg)
            wide_in = max(csr_f.max_in_deg, csr_b.max_in_deg)
            self._csr_f = dataclasses.replace(
                csr_f, max_out_deg=wide, max_in_deg=wide_in)
            self._csr_b = dataclasses.replace(
                csr_b, max_out_deg=wide, max_in_deg=wide_in)
        self._restack()

        cap, use_pallas = self.frontier_cap, cfg.use_pallas

        def prims_for(g, csr):
            if csr is not None:
                return backends.frontier_prims(g, csr, cap, use_pallas)
            return backends.segment_prims(g)

        def program(g2, csr2, ends, C0):
            # ends int32[2] = [s, t]; C0 float32[2, n] per-lane seeds.
            self.trace_count += 1
            init = jax.vmap(
                lambda g, c, s, c0: _init_state(g, s, c0, prims_for(g, c))
            )(g2, csr2, ends, C0)

            def body(st):
                return jax.vmap(
                    lambda g, c, s: _round(g, cfg, s, prims=prims_for(g, c))
                )(g2, csr2, st)

            max_rounds = cfg.max_rounds or g2.n + 2

            def cond(st):
                frontier = (((st.D < INF) & ~st.fixed)
                            | (st.fixed & ~st.explored))
                bound = jnp.min(jnp.where(frontier, st.D, INF), axis=1)
                mu = jnp.min(st.D[0] + st.D[1])
                go = jnp.any(frontier) & (st.round[0] < max_rounds)
                return go & (bound[0] + bound[1] < mu)

            final = jax.lax.while_loop(cond, body, init)
            score = final.D[0] + final.D[1]
            return final, jnp.min(score), jnp.argmin(score)

        self._jit = jax.jit(program)

        def warm_program(g2_old, g2_new, delta2, D0, F0):
            # both lanes of one cached pair warm re-solve to their full
            # fixpoints; dense segment prims — warm refresh is a batched
            # path, same routing as DynamicSolver's (bitwise-identical
            # rounds either way).
            self.warm_trace_count += 1

            def one(g_old, g_new, d, D0l, f0l):
                seeds, pure = delta_taint_seeds(g_old, d, D0l)
                st, _, _ = _solve_warm(
                    g_new, cfg, D0l, f0l, seeds, pure,
                    prims=backends.segment_prims(g_new))
                return st

            st = jax.vmap(one)(g2_old, g2_new, delta2, D0, F0)
            score = st.D[0] + st.D[1]
            return st, jnp.min(score), jnp.argmin(score)

        self._jit_warm = jax.jit(warm_program)

    # ------------------------------------------------------------------
    def _restack(self) -> None:
        self._g2 = _stack2(self.graph, self.rgraph)
        self._csr2 = (None if self._csr_f is None
                      else _stack2(self._csr_f, self._csr_b))

    def apply_delta(self, delta, rdelta=None) -> None:
        """Mutate both lanes coherently with a forward-graph delta.

        ``rdelta`` (the same updates remapped onto the transpose) is
        derived via the precomputed permutation when omitted; pass the
        one ``LandmarkIndex.reverse_delta`` already built to avoid
        computing it twice.
        """
        self.update(delta, rdelta)

    def update(self, delta, rdelta=None, *,
               warm=None) -> dict[tuple[int, int], BidiResult]:
        """Apply a delta and warm re-solve hot cached pairs.

        ``warm`` is a list of ``(source, target, D, fixed)`` — each
        pair's two-lane ``[2, n]`` state exactly as a pre-delta
        :class:`BidiResult` carried it.  Both lanes re-enter the engine
        through the taint-cone warm start against the OLD stacked graph
        (taint is judged on the weights the state was computed with)
        and run to their full fixpoints on the new one, one vmapped
        program for the pair (one trace for all pairs and all future
        deltas).  Returns ``{(s, t): fresh BidiResult}`` with the exact
        re-folded distance; the stitched path comes from the refreshed
        parent structure as usual.
        """
        if rdelta is None:
            from repro.core.sssp.dynamic import make_delta
            kk = delta.k
            idx = np.asarray(delta.edge_idx)[:kk]
            rdelta = make_delta(self.rgraph, self._rev_perm[idx],
                                np.asarray(delta.new_w)[:kk])
        g2_old = self._g2
        self.graph = self.graph.apply_delta(delta)
        self.rgraph = self.rgraph.apply_delta(rdelta)
        if self._csr_f is not None:
            self._csr_f = self._csr_f.apply_delta(delta)
            self._csr_b = self._csr_b.apply_delta(rdelta)
        self._wmap = None
        self._restack()
        out: dict[tuple[int, int], BidiResult] = {}
        if not warm:
            return out
        # forward + reverse updates stack like the graphs do (same k →
        # same k_pad, both built by make_delta → same treedef)
        delta2 = _stack2(delta, rdelta)
        for source, target, D0, F0 in warm:
            final, mu, meet = self._jit_warm(
                g2_old, self._g2, delta2,
                jnp.asarray(D0, jnp.float32), jnp.asarray(F0, bool))
            self.warm_solves += 1
            dist = float(mu)
            fb = np.asarray(final.fixed_by).sum(axis=0)
            res = BidiResult(
                source=int(source), target=int(target), distance=dist,
                meeting=int(meet) if np.isfinite(dist) else None,
                rounds=int(final.round[0]),
                D=final.D, C=final.C, fixed=final.fixed,
                fixed_by=_fixed_by_dict(fb),
                graph=self.graph, rgraph=self.rgraph, mu=dist)
            if np.isfinite(dist):
                p = res.path()
                if p is not None:
                    res.distance = float(self._refold(p))
            out[(int(source), int(target))] = res
        return out

    def _refold(self, path) -> np.float32:
        """Fold the path's weights left-to-right in float32.

        The engine relaxes ``D[u] + w`` one edge at a time from the
        source, so a full solve's ``dist[t]`` is exactly this fold of
        its shortest path; re-folding the stitched path reproduces
        those bits, where the raw ``D_f[m] + D_b[m]`` sum (two halves
        accumulated independently) can differ in the last ulp.
        """
        if self._wmap is None:
            g = self.graph
            e = g.e
            src = np.asarray(g.src[:e])
            dst = np.asarray(g.dst[:e])
            w = np.asarray(g.w[:e], np.float32)
            wmap: dict[tuple[int, int], np.float32] = {}
            for a, b, ww in zip(src.tolist(), dst.tolist(), w):
                k = (a, b)
                prev = wmap.get(k)
                if prev is None or ww < prev:
                    wmap[k] = ww
            self._wmap = wmap
        d = np.float32(0.0)
        for a, b in zip(path, path[1:]):
            d = np.float32(d + self._wmap[(a, b)])
        return d

    # ------------------------------------------------------------------
    def solve(self, source: int, target: int, C0=None) -> BidiResult:
        """Exact d(source, target) + stitched path via two-lane search.

        ``C0`` (float32[2, n], optional) seeds both lanes' lower
        bounds; defaults to :meth:`LandmarkIndex.seed_pair` when the
        solver carries an index that can vouch for its tables, else
        trivial bounds.  One compiled program per graph shape — source,
        target, seeds, and the stacked graph are all traced operands.
        """
        n = self.graph.n
        for name, v in (("source", source), ("target", target)):
            if not 0 <= int(v) < n:
                raise ValueError(f"{name} {v} out of range [0, {n})")
        if C0 is None and self.landmarks is not None:
            C0 = self.landmarks.seed_pair(source, target)
        if C0 is None:
            C0 = jnp.zeros((2, n), jnp.float32)
        else:
            C0 = jnp.asarray(C0, jnp.float32)
            if C0.shape != (2, n):
                raise ValueError(f"C0 shape {C0.shape} != (2, {n})")
        ends = jnp.asarray([int(source), int(target)], jnp.int32)
        final, mu, meet = self._jit(self._g2, self._csr2, ends, C0)
        self.solves += 1
        dist = float(mu)
        fb = np.asarray(final.fixed_by).sum(axis=0)
        res = BidiResult(
            source=int(source), target=int(target), distance=dist,
            meeting=int(meet) if np.isfinite(dist) else None,
            rounds=int(final.round[0]),
            D=final.D, C=final.C, fixed=final.fixed,
            fixed_by=_fixed_by_dict(fb),
            graph=self.graph, rgraph=self.rgraph, mu=dist,
            edges_relaxed=None if final.edges is None
            else int(np.asarray(final.edges).sum()))
        if np.isfinite(dist):
            p = res.path()
            if p is not None:
                res.distance = float(self._refold(p))
        return res
