"""Dynamic-graph subsystem: streaming weight updates + warm re-solve.

A production shortest-path service sees graphs whose weights drift
continuously (road congestion, link latencies) while the topology stays
put.  This module makes weight change a first-class, *compiled* event
instead of a cold restart:

  * :class:`GraphDelta` — a fixed-shape, jit-safe batch of
    ``(edge_idx, new_w)`` weight updates.  The ``Graph``/``EllGraph``
    pytrees take it through ``apply_delta`` without retracing (shapes
    static, only weight values change), and one delta updates BOTH the
    CSC edge list and the dense ELL layout coherently.

  * warm-started incremental re-solve — the paper's dual-bound state is
    exactly the machinery for incremental repair:

      - upper bounds ``D`` of the previous solve stay valid wherever no
        *increased* edge sits on a tight path (the affected cone, found
        by ``engine.delta_taint_seeds`` + a few relax-style sweeps in
        ``engine._init_state_warm``); only that cone is un-fixed.
      - weight *decreases* leave old ``D`` merely stale-HIGH, which the
        warm round body heals in flight (``engine._round(warm=True)``
        un-fixes any fixed vertex relaxation improves).
      - under a pure increase old distances are still valid *lower*
        bounds, so ``C`` warm-starts at the old ``D`` and the lb rule
        re-fixes untouched parts of the cone immediately.

    The warm state then re-enters the SAME ``lax.while_loop`` round body
    as a cold solve, so every backend of the primitives protocol
    (segment / ELL / Pallas / edge-sharded distributed) gets
    incrementality for free.

  * :class:`DynamicSolver` — the Solver facade grown a time axis:
    ``update(delta)`` mutates the graph and warm-refreshes tracked
    sources in one compiled program (one trace per (delta shape, batch
    shape), counted by ``warm_trace_count``); ``resolve(sources)``
    serves post-update distances, warm results first.

This extends the Kainer–Träff amortization story (arXiv:1903.12085)
from "amortize compile cost across sources" to "amortize solve cost
across graph versions"; the road-network-style serving workload is the
regime of Yu et al. (arXiv:2506.19349).
"""
from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.core.graph import Graph
from repro.core.sssp import spans
from repro.core.sssp.engine import (SP4_CONFIG, SSSPConfig, SSSPResult,
                                    _fixed_by_dict, _scope, _solve_warm,
                                    _solve_warm_frontier,
                                    delta_decrease_sources,
                                    delta_taint_seeds)
from repro.core.sssp.solver import Solver, SSSPBatchResult, _next_pow2

# padding sentinel for the ELL cell coordinates: out of bounds for any
# layout, so padded delta rows are scatter-dropped by every consumer.
_ELL_PAD = np.int32(1 << 30)

# dst-sorted -> CSR inverse permutations, keyed by id(g.src).  The
# permutation depends only on topology, which apply_delta never changes
# — and apply_delta also keeps the src/dst array OBJECTS (it replaces
# only the weight-bearing fields), so every graph version of a delta
# stream shares one cache entry.  The weakref finalizer evicts the
# entry when the edge array dies, which also makes id reuse harmless.
_CSR_INV_CACHE: dict[int, np.ndarray] = {}


def _csr_inverse_perm(g: Graph) -> np.ndarray:
    key = id(g.src)
    inv = _CSR_INV_CACHE.get(key)
    if inv is None:
        order = np.argsort(np.asarray(g.src[: g.e]), kind="stable")
        inv = np.empty(g.e, np.int64)
        inv[order] = np.arange(g.e)
        _register_csr_perm(g.src, inv)
    return inv


def _register_csr_perm(src_arr, inv: np.ndarray) -> None:
    key = id(src_arr)
    if key not in _CSR_INV_CACHE:
        _CSR_INV_CACHE[key] = inv
        weakref.finalize(src_arr, _CSR_INV_CACHE.pop, key, None)


def _carry_csr_perm(old_src, new_src) -> None:
    """Propagate a cached permutation across a graph-version bump.

    The compiled update program returns a fresh pytree, so ``new_src``
    is a different array OBJECT with identical contents (apply_delta
    never touches topology) — the old version's permutation is still
    exact for the new one."""
    inv = _CSR_INV_CACHE.get(id(old_src))
    if inv is not None and old_src is not new_src:
        _register_csr_perm(new_src, inv)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """A fixed-shape batch of edge-weight updates (jit-safe pytree).

    ``edge_idx`` indexes the owning Graph's dst-sorted padded edge
    arrays; ``ell_row``/``ell_col`` are the same edges' cells in the
    dense ELL layout (row = dst, col = rank within the dst segment).
    Rows are padded to ``k_pad`` (power of two, so delta sizes reuse a
    handful of compiled update programs); padding rows carry
    out-of-bounds indices (``edge_idx = e_pad``, ``ell_row = 2^30``) and
    are dropped by every scatter, masked in every gather.

    Build via :func:`make_delta` / :func:`make_delta_from_endpoints`,
    which validate (index range, strict positivity) host-side — the
    compiled update path cannot inspect traced values.

    ``k`` (the real-update count) is a pytree LEAF, not static metadata:
    it never drives a shape, and keeping it dynamic lets deltas of
    different sizes that pad to the same ``k_pad`` share one compiled
    update program.
    """

    k: int          # number of real (non-padding) updates
    edge_idx: jax.Array  # int32[k_pad]
    new_w: jax.Array     # float32[k_pad]
    ell_row: jax.Array   # int32[k_pad]
    ell_col: jax.Array   # int32[k_pad]
    csr_pos: jax.Array | None = None  # int32[k_pad]: the same edges'
    #   positions in the src-sorted CSR view (padding >= e_pad, scatter-
    #   dropped).  ``make_delta`` always fills it; ``None`` (hand-built
    #   deltas) only forfeits ``CsrGraph.apply_delta``.

    @property
    def k_pad(self) -> int:
        return int(self.edge_idx.shape[0])


def make_delta(g: Graph, edge_idx, new_w, *, min_pad: int = 8) -> GraphDelta:
    """Host-side GraphDelta builder from edge indices into ``g``.

    Validates loudly (the post-construction analogue of the builder's
    ``w > 0`` assert): indices must name real (non-padding) edges and
    weights must be strictly positive and finite.  Duplicate indices
    keep the LAST update (stream semantics).
    """
    edge_idx = np.asarray(edge_idx, np.int64).ravel()
    new_w = np.asarray(new_w, np.float32).ravel()
    if edge_idx.shape != new_w.shape:
        raise ValueError(f"edge_idx {edge_idx.shape} and new_w "
                         f"{new_w.shape} must match")
    if edge_idx.size == 0:
        raise ValueError("empty delta")
    if edge_idx.min() < 0 or edge_idx.max() >= g.e:
        bad = edge_idx[(edge_idx < 0) | (edge_idx >= g.e)]
        raise ValueError(f"edge indices {bad.tolist()} outside the real "
                         f"edge range [0, {g.e}) (padding edges are not "
                         "updatable — topology is fixed)")
    if not (np.isfinite(new_w).all() and (new_w > 0).all()):
        raise ValueError(
            "update weights must be strictly positive and finite "
            f"(got min={new_w.min()!r}); the engine assumes w > 0")
    # stream semantics: last write to an edge wins
    _, last = np.unique(edge_idx[::-1], return_index=True)
    keep = np.sort(edge_idx.size - 1 - last)
    edge_idx, new_w = edge_idx[keep], new_w[keep]

    # dense-layout cell per edge: row = dst, col = rank within dst run
    # (Graph is dst-sorted-stable and build_ell fills in the same order).
    dst_sorted = np.asarray(g.dst[: g.e])
    dst = dst_sorted[edge_idx]
    col = edge_idx - np.searchsorted(dst_sorted, dst, side="left")

    # CSR-view position per edge: dst-sorted edge i sits at row
    # csr_perm⁻¹[i] of the src-sorted list (build_csr sorts stably by
    # src over the same dst-sorted order).  Topology-constant — cached
    # per edge array so a streaming delta sequence computes it once.
    csr_pos = _csr_inverse_perm(g)[edge_idx]

    k = int(edge_idx.size)
    k_pad = max(min_pad, _next_pow2(k))
    pad = k_pad - k

    def _p(x, fill, dtype):
        return jnp.asarray(np.concatenate(
            [x, np.full(pad, fill, x.dtype)]).astype(dtype))

    return GraphDelta(
        k=k,
        edge_idx=_p(edge_idx, g.e_pad, np.int32),
        new_w=_p(new_w, 1.0, np.float32),   # positive: passes validation
        ell_row=_p(dst, _ELL_PAD, np.int32),
        ell_col=_p(col, _ELL_PAD, np.int32),
        csr_pos=_p(csr_pos, g.e_pad, np.int32),
    )


def make_delta_from_endpoints(g: Graph, src, dst, new_w, **kw) -> GraphDelta:
    """GraphDelta from ``(u, v, w_new)`` endpoint triples.

    Each (u, v) must name an existing edge of ``g``; for parallel edges
    the first (lowest-index) one is updated.  Raises on absent edges —
    topology changes are out of scope for weight deltas.
    """
    src = np.asarray(src, np.int64).ravel()
    dst = np.asarray(dst, np.int64).ravel()
    key = np.asarray(g.src[: g.e], np.int64) * g.n + np.asarray(
        g.dst[: g.e], np.int64)
    order = np.argsort(key, kind="stable")
    want = src * g.n + dst
    pos = np.searchsorted(key[order], want)
    pos_ok = pos < g.e
    found = np.zeros(len(want), bool)
    found[pos_ok] = key[order][pos[pos_ok]] == want[pos_ok]
    if not found.all():
        missing = [(int(s), int(d))
                   for s, d in zip(src[~found], dst[~found])]
        raise ValueError(f"edges {missing} not present in the graph; "
                         "GraphDelta updates weights of existing edges only")
    return make_delta(g, order[pos], new_w, **kw)


def random_delta(g: Graph, k: int, *, seed: int = 0, lo: float = 0.5,
                 hi: float = 2.0) -> GraphDelta:
    """k random edges rescaled by uniform[lo, hi] — bench/test helper."""
    rng = np.random.default_rng(seed)
    k = min(int(k), g.e)
    idx = rng.choice(g.e, size=k, replace=False)
    old = np.asarray(g.w[: g.e])[idx]
    return make_delta(g, idx, old * rng.uniform(lo, hi, k).astype(np.float32))


@contract(
    "warm.incremental_repair",
    routes=("*.warm",),
    require=("gather", "reduce_min"),
    notes="Every warm path is one compiled program over (delta shape, "
          "refresh-batch shape): taint the decreased-key seeds, then "
          "re-run the round body for the tracked lanes.  The hot "
          "region must still contain the relax gather + masked "
          "min-reduction — a warm path that lost them is returning "
          "stale distances, not repairing them.")
class DynamicSolver(Solver):
    """A Solver whose graph can change between solves.

    On top of the inherited cold paths (``solve``/``solve_batch``, which
    now also *track* their results), ``update(delta)`` applies a weight
    delta and warm-refreshes tracked sources through one compiled
    program:

        g_new  = g.apply_delta(delta)            # CSC + ELL coherently
        state0 = engine._init_state_warm(...)    # un-fix affected cone
        state  = while_loop(engine._round(warm=True), state0)

    vmapped over the tracked sources' previous states — the Solver's
    no-retrace discipline extended along the time axis: one trace per
    (delta shape, refresh-batch shape), counted by ``warm_trace_count``,
    however many deltas stream in.  ``graph``/``ell`` always hold the
    newest version (``version`` counts deltas applied); cold solves
    reuse the original compiled programs because the graph is a traced
    operand of those programs, not a baked-in constant.

    ``track_sources`` bounds the LRU of per-source previous states kept
    for warm refresh (each costs two [n] vectors on device).
    """

    def __init__(self, graph, cfg: SSSPConfig = SP4_CONFIG,
                 backend: str = "auto", *, track_sources: int = 128, **kw):
        super().__init__(graph, cfg, backend, **kw)
        self.version = 0
        self.warm_trace_count = 0
        self.track_sources = max(1, int(track_sources))
        # source -> dict(version, D, C, fixed [device], rounds, fixed_by)
        self._states: OrderedDict[int, dict] = OrderedDict()
        self._jit_warm = None
        if self.backend != "distributed":
            self._jit_warm = jax.jit(self._warm_program)
        else:
            from repro.core.sssp.distributed import make_sharded_warm
            self._jit_warm = make_sharded_warm(
                self.graph, self.cfg, self._mesh, self._axes,
                on_trace=self._count_warm_trace)

    def _count_warm_trace(self):
        self.warm_trace_count += 1  # python side effect: runs per TRACE

    def _warm_program(self, g_old: Graph, ell_old, csr_old,
                      delta: GraphDelta, prev_D, prev_fixed):
        """(g_old, delta, [B,n] prev states) -> (g_new, layouts, states).

        Taint seeds are per-source (tightness is a property of each
        source's distance field); the graph mutation is shared.  On the
        frontier backend the refresh batch goes straight into the
        batch-aware warm driver (``engine._solve_warm_frontier``) — NOT
        ``jax.vmap`` over per-lane solves, which would batch the
        overflow predicates and linearize the sparse/dense cond to
        select.  The lanes share one union-compacted seed frontier and
        the decreased-edge sources narrow it (``delta_decrease_sources``
        — shared: decrease-ness is a property of the delta, not of any
        lane).  Warm results stay bitwise-identical to the dense body.
        """
        self._count_warm_trace()
        with _scope("sssp.taint"):
            g_new = g_old.apply_delta(delta)
            ell_new = (None if ell_old is None
                       else ell_old.apply_delta(delta))
            csr_new = (None if csr_old is None
                       else csr_old.apply_delta(delta))
        prims = self._make_prims(g_new, ell_new, csr_new)
        if getattr(prims, "relax_frontier_b", None) is not None:
            seeds, pure = jax.vmap(
                lambda D0: delta_taint_seeds(g_old, delta, D0))(prev_D)
            dec = delta_decrease_sources(g_old, delta)
            states, sweeps, taint = _solve_warm_frontier(
                g_new, self.cfg, prev_D, prev_fixed, seeds, pure, prims,
                dec_src=dec)
        else:
            def one(D0, f0):
                seeds, pure = delta_taint_seeds(g_old, delta, D0)
                return _solve_warm(g_new, self.cfg, D0, f0, seeds, pure,
                                   prims=prims)

            states, sweeps, taint = jax.vmap(one)(prev_D, prev_fixed)
        with _scope("sssp.count"):
            tainted = jnp.sum(taint, axis=1)
        return g_new, ell_new, csr_new, states, sweeps, tainted

    # ------------------------------------------------------------------
    def _track(self, source: int, *, D, C, fixed, rounds, fixed_by) -> None:
        self._states[source] = dict(version=self.version, D=D, C=C,
                                    fixed=fixed, rounds=int(rounds),
                                    fixed_by=fixed_by)
        self._states.move_to_end(source)
        while len(self._states) > self.track_sources:
            self._states.popitem(last=False)

    def _fresh(self, source: int) -> dict | None:
        st = self._states.get(source)
        if st is not None and st["version"] == self.version:
            self._states.move_to_end(source)
            return st
        return None

    def solve(self, source: int, target: int | None = None,
              C0=None) -> SSSPResult:
        res = super().solve(source, target=target, C0=C0)
        # partial (early-exited) results are NOT tracked: unfixed entries
        # are upper bounds, and the warm re-solve would first have to
        # finish the solve they skipped — a full state is the asset here.
        if not res.partial:
            self._track(int(source), D=res.dist, C=res.C, fixed=res.fixed,
                        rounds=res.rounds, fixed_by=res.fixed_by)
        return res

    def solve_batch(self, sources, targets=None, C0=None) -> SSSPBatchResult:
        batch = super().solve_batch(sources, targets=targets, C0=C0)
        if not batch.partial:
            for i, s in enumerate(batch.sources):
                self._track(int(s), D=batch.dist[i], C=batch.C[i],
                            fixed=batch.fixed[i], rounds=batch.rounds[i],
                            fixed_by=batch.fixed_by[i])
        return batch

    # ------------------------------------------------------------------
    def update(self, delta: GraphDelta, *, refresh=None) -> dict:
        """Apply a weight delta; warm-refresh tracked sources; stats.

        ``refresh`` selects which sources to re-solve eagerly (default:
        every tracked source).  Sources with a tracked previous state go
        through the compiled warm program; requested sources without one
        are cold-solved on the mutated graph.  Untouched tracked states
        become stale (version mismatch) and are refreshed lazily by
        ``resolve``.  Returns a stats dict (see keys below):
        ``warm_rounds`` and ``tainted`` per warm lane, ``sweeps`` the
        most over them, ``cold_rounds`` the cold refresh's rounds (the
        most over its lanes; 0 without one).
        """
        if not isinstance(delta, GraphDelta):
            raise TypeError(f"update() wants a GraphDelta (see make_delta); "
                            f"got {type(delta)!r}")
        didx, dw = spans.fetch((delta.edge_idx, delta.new_w))
        didx, dw = didx[: delta.k], dw[: delta.k]
        old_src = self.graph.src   # carry the CSR perm across versions
        # async device gather of the k OLD weights (for the stats
        # counters); the blocking np.asarray happens only after the warm
        # program is dispatched, keeping the hot path sync-free.
        old_w_dev = self.graph.w[didx]

        tracked = [s for s in self._states
                   if self._states[s]["version"] == self.version]
        want = tracked if refresh is None else [int(s) for s in refresh]
        warm_src = [s for s in dict.fromkeys(want) if s in self._states
                    and self._states[s]["version"] == self.version]
        cold_src = [s for s in dict.fromkeys(want) if s not in warm_src]

        stats = dict(edges_changed=delta.k, increased=0, decreased=0,
                     warm_refreshed=len(warm_src),
                     cold_refreshed=len(cold_src), sweeps=0,
                     warm_rounds=[], tainted=[], cold_rounds=0)
        if warm_src:
            b = len(warm_src)
            b_pad = _next_pow2(b)
            padded = warm_src + [warm_src[-1]] * (b_pad - b)
            prev_D = jnp.stack([self._states[s]["D"] for s in padded])
            prev_F = jnp.stack([self._states[s]["fixed"] for s in padded])
            (g_new, ell_new, csr_new, states, sweeps,
             tainted) = self._jit_warm(
                self.graph, self.ell, self.csr, delta, prev_D, prev_F)
            self.graph, self.ell, self.csr = g_new, ell_new, csr_new
            self.version += 1
            fb, rounds, sweeps, tainted = spans.fetch(
                (states.fixed_by, states.round, sweeps, tainted))
            for i, s in enumerate(warm_src):
                self._track(s, D=states.D[i], C=states.C[i],
                            fixed=states.fixed[i], rounds=rounds[i],
                            fixed_by=_fixed_by_dict(fb[i]))
            stats["sweeps"] = int(np.max(sweeps[:b]))
            stats["warm_rounds"] = [int(r) for r in rounds[:b]]
            stats["tainted"] = [int(t) for t in tainted[:b]]
        else:
            # no warm candidates: mutate the layouts eagerly (still no
            # retrace — apply_delta is shape-stable), bump the version.
            self.graph = self.graph.apply_delta(delta)
            if self.ell is not None:
                self.ell = self.ell.apply_delta(delta)
            if self.csr is not None:
                self.csr = self.csr.apply_delta(delta)
            self.version += 1
        _carry_csr_perm(old_src, self.graph.src)
        if cold_src:
            stats["cold_rounds"] = int(np.max(
                self.solve_batch(cold_src).rounds))
        old_w = spans.fetch(old_w_dev)  # blocks AFTER the update dispatched
        stats["increased"] = int(np.sum(dw > old_w))
        stats["decreased"] = int(np.sum(dw < old_w))
        return stats

    def resolve(self, sources) -> SSSPBatchResult:
        """Post-update distances for ``sources`` on the current graph.

        Warm-refreshed (or otherwise current-version) results are served
        from tracked state; the rest are cold-solved in one batch.
        Always reflects the newest graph version.
        """
        sources = np.asarray(sources, np.int32).ravel()
        if sources.size == 0:
            raise ValueError("resolve needs at least one source")
        # snapshot fresh rows BEFORE solving the misses: the batch solve
        # tracks its results, and the LRU may evict a currently-fresh
        # source while doing so.  Misses are answered straight from the
        # batch result, so the tracker never bounds a resolve().
        rows_by_src = {}
        for s in dict.fromkeys(sources.tolist()):
            st = self._fresh(int(s))
            if st is not None:
                rows_by_src[int(s)] = (st["D"], st["C"], st["fixed"],
                                       st["rounds"], st["fixed_by"])
        missing = [int(s) for s in dict.fromkeys(sources.tolist())
                   if int(s) not in rows_by_src]
        if missing:
            mb = self.solve_batch(missing)
            for i, s in enumerate(mb.sources):
                rows_by_src[int(s)] = (mb.dist[i], mb.C[i], mb.fixed[i],
                                       int(mb.rounds[i]), mb.fixed_by[i])

        rows = [rows_by_src[int(s)] for s in sources]
        return SSSPBatchResult(
            sources=sources,
            dist=jnp.stack([r[0] for r in rows]),
            C=jnp.stack([r[1] for r in rows]),
            fixed=jnp.stack([r[2] for r in rows]),
            rounds=np.asarray([r[3] for r in rows], np.int32),
            fixed_by=[r[4] for r in rows],
            graph=self.graph)
