"""The unified SSSP solver: one object, any backend, batched sources.

``Solver`` amortizes everything that is per-graph — device transfer,
layout build (ELL), shard re-padding, and XLA compilation — so that
answering a new source is a pure execution, never a retrace:

  * the source is a TRACED int32 argument of the compiled program, so k
    distinct sources on one graph shape share a single compilation;
  * ``solve_batch`` is a ``jax.vmap`` over that traced source — one
    program solves B sources at once (the bulk-synchronous rounds of the
    slowest source dominate; everything else rides along masked);
  * backends are instances of the primitives protocol (backends.py), so
    ``"segment"``, ``"ell"``, ``"pallas"`` and ``"distributed"`` all run
    the SAME round body (engine._round).

This is the Kainer–Träff observation operationalized: the paper's
criteria machinery pays off most when its fixed costs are amortized
across many queries.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.core.graph import (CsrGraph, EllGraph, Graph, HostGraph,
                              build_ell)
from repro.core.sssp import backends, spans
from repro.core.sssp.engine import (SP4_CONFIG, SSSPConfig, SSSPResult,
                                    _fixed_by_dict, _solve, _solve_frontier)

BACKENDS = ("auto", "segment", "ell", "pallas", "distributed", "frontier")


@dataclasses.dataclass
class SSSPBatchResult:
    """Distances for B sources on one graph; indexable into SSSPResults.

    ``dist``/``C``/``fixed`` have a leading batch dim; ``rounds`` is the
    per-source round count.  ``result(i)`` (or ``batch[i]``) views one
    source as a plain :class:`SSSPResult` with lazy parents/paths.

    ``targets``/``partial`` mark goal-directed (point-to-point) batches:
    each lane may have early-exited once its own target was fixed, so
    only fixed vertices of a partial lane carry exact distances
    (``dist[i, targets[i]]`` always does).
    """

    sources: np.ndarray      # int32[B]
    dist: jax.Array          # float32[B, n]
    C: jax.Array             # float32[B, n]
    fixed: jax.Array         # bool[B, n]
    rounds: np.ndarray       # int32[B]
    fixed_by: list[dict[str, int]]
    graph: Graph | None = None
    targets: np.ndarray | None = None   # int32[B] (-1 = untargeted lane)
    partial: bool = False               # lanes may have early-exited
    edges_relaxed: np.ndarray | None = None  # int32[B] (frontier backend)

    def __len__(self) -> int:
        return len(self.sources)

    def result(self, i: int) -> SSSPResult:
        t = None
        if self.targets is not None and int(self.targets[i]) >= 0:
            t = int(self.targets[i])
        return SSSPResult(
            dist=self.dist[i], C=self.C[i], fixed=self.fixed[i],
            rounds=int(self.rounds[i]), fixed_by=self.fixed_by[i],
            source=int(self.sources[i]), graph=self.graph,
            target=t, partial=self.partial and t is not None,
            edges_relaxed=None if self.edges_relaxed is None
            else int(self.edges_relaxed[i]))

    __getitem__ = result


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _frontier_fits(g: Graph) -> bool:
    """``backend="auto"`` proxy for thin wavefronts.

    The frontier backend wins when |frontier| / n stays low round over
    round — that can't be known before solving, but two cheap structural
    proxies track it well: low average degree (the wavefront grows
    slowly: chain, grid) or bounded out-degree (kNN/road-like expansion:
    geometric).  High fan-out families (gnp, dag, power_law hubs) blow
    the wavefront to O(n) within a few rounds — dense wins there, and
    hub out-degrees would bloat the ``cap * max_out_deg`` gather anyway.
    """
    if g.e == 0:
        return False
    max_out = int(np.max(np.asarray(g.out_deg))) if g.n else 0
    return (g.e <= 4 * g.n or max_out <= 8) and max_out <= 64


def _default_frontier_cap(n: int) -> int:
    return _next_pow2(min(max(n // 4, 32), 4096))


@contract(
    "solver.targeted_early_exit",
    routes=("*.cold", "*.targeted", "*.batched"),
    require_cond=("dynamic_slice|gather",),
    notes="Cold and targeted solves share ONE compiled program (the "
          "target is a traced operand, -1 meaning none); the while-"
          "loop cond must therefore contain the fixed[target] read — "
          "dynamic_slice in scalar routes, gather in the vmapped "
          "batched/fleet routes.  If it disappears, targeted solves "
          "quietly run to full convergence and the p2p speedup is "
          "gone with no output change to catch it.")
class Solver:
    """Compiled multi-source SSSP over one graph.

    Parameters
    ----------
    graph:    a device ``Graph``, a ``HostGraph``, or an ``(n, src, dst,
              w)`` tuple of host arrays.
    cfg:      engine configuration (rules / label-correcting / c-prop).
    backend:  "auto" | "segment" | "ell" | "pallas" | "distributed" |
              "frontier".
              "auto" picks "pallas" when ``cfg.use_pallas``, else
              "frontier" when the graph's structure predicts thin
              wavefronts (low average degree or bounded out-degree —
              chain/grid/road-like), else "segment" (robust for every
              family, including power-law in-degree skew that the dense
              ELL layout hates).
    ell:      pre-built :class:`EllGraph` for the ell/pallas backends
              (built from the graph's edges when omitted).
    mesh/axes: mesh placement for the "distributed" backend.
    frontier_cap: compacted-buffer size for the "frontier" backend
              (rounded up to a power of two; default scales with n).  A
              round whose wavefront outgrows it falls back to the dense
              relax for that round — results stay bitwise-identical,
              only the work bound degrades.  Scope: EVERY route —
              ``solve``, ``solve_batch``, and the warm-refresh program —
              runs the sparse round body.  Batched lanes share ONE
              union-compacted frontier (the union of the lanes' fresh
              sets, one compaction and one shared edge gather per
              round); the overflow rule is per round on the union size,
              and the extra union vertices a lane didn't produce are
              value-identical re-sends, so lanes stay bitwise-identical
              to their solo solves (docs/round-anatomy.md).

    ``trace_count`` counts XLA traces actually performed — the regression
    tests assert it stays at one per (program, batch-shape), however many
    sources are solved.
    """

    def __init__(self, graph, cfg: SSSPConfig = SP4_CONFIG,
                 backend: str = "auto", *, ell: EllGraph | None = None,
                 mesh=None, axes: tuple[str, ...] = ("data",),
                 max_deg_cap: int | None = None,
                 frontier_cap: int | None = None):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected one of {BACKENDS}")
        if isinstance(graph, HostGraph):
            graph = graph.to_device()
        elif isinstance(graph, tuple):
            from repro.core.graph import build_graph
            graph = build_graph(*graph)
        if not isinstance(graph, Graph):
            raise TypeError(f"graph must be Graph/HostGraph/tuple, "
                            f"got {type(graph)!r}")
        if backend == "auto":
            if cfg.use_pallas:
                backend = "pallas"
            elif _frontier_fits(graph):
                backend = "frontier"
            else:
                backend = "segment"
        # normalize cfg.use_pallas to the chosen backend in BOTH
        # directions: "pallas" forces it on, every other backend forces
        # it off — otherwise SSSPConfig(use_pallas=True) silently routes
        # the "ell" backend through the Pallas kernels.  "frontier" is
        # the exception that honors the flag as given: it routes its OWN
        # scatter-min kernel (never the ELL kernels), with the jnp
        # oracle as the default path.
        if backend == "pallas":
            cfg = dataclasses.replace(cfg, use_pallas=True)
        elif cfg.use_pallas and backend != "frontier":
            cfg = dataclasses.replace(cfg, use_pallas=False)
        self.graph = graph
        self.cfg = cfg
        self.backend = backend
        self.trace_count = 0
        self.ell: EllGraph | None = None
        self.csr: CsrGraph | None = None
        self.frontier_cap = 0

        if backend in ("ell", "pallas"):
            if ell is None:
                e = graph.e
                ell = build_ell(graph.n, np.asarray(graph.src[:e]),
                                np.asarray(graph.dst[:e]),
                                np.asarray(graph.w[:e]),
                                max_deg_cap=max_deg_cap)
            self.ell = ell
        if backend == "frontier":
            self.csr = graph.csr()
            self.frontier_cap = _next_pow2(
                _default_frontier_cap(graph.n) if frontier_cap is None
                else max(1, int(frontier_cap)))

        def _count_trace():
            self.trace_count += 1  # python side effect: runs per TRACE

        # ``ell``/``csr`` ride through jit as traced pytree operands
        # (None where unused): baked-in constants would bloat every
        # compiled batch shape with the layout arrays.
        cap, use_pallas = self.frontier_cap, cfg.use_pallas

        def _prims(g, ell, csr):
            if csr is not None:
                return backends.frontier_prims(g, csr, cap, use_pallas)
            if ell is not None:
                return backends.ell_prims(g, ell, use_pallas)
            return backends.segment_prims(g)

        self._make_prims = _prims  # DynamicSolver builds warm programs
        self._mesh, self._axes = mesh, axes

        if backend == "distributed":
            from repro.core.sssp.distributed import (default_mesh,
                                                     make_sharded_solver)
            if mesh is None:
                self._mesh, self._axes = default_mesh()
            self.graph, self._sharded_batch = make_sharded_solver(
                graph, cfg, self._mesh, self._axes, on_trace=_count_trace)
            self._jit_one = None
            self._jit_batch = None
        else:
            # target (int32, -1 = none) and C0 (lower-bound seeds) are
            # TRACED operands like the source: targeted, seeded, and
            # plain solves all share one compiled program per shape.
            def solve_one(g, ell, csr, source, target, C0):
                _count_trace()
                return _solve(g, cfg, source, prims=_prims(g, ell, csr),
                              C0=C0, target=target)

            def solve_many(g, ell, csr, sources, targets, C0):
                _count_trace()
                if csr is not None:
                    # shared batch frontier: the batch-aware round body
                    # (engine._round_shared) runs the lanes over ONE
                    # union-compacted frontier buffer — every overflow
                    # predicate stays scalar (no vmap, so no cond->select
                    # linearization) and one shared edge gather serves
                    # all lanes.  Bitwise-identical to the vmapped dense
                    # round below.
                    return _solve_frontier(g, cfg, sources,
                                           _prims(g, ell, csr),
                                           C0=C0, targets=targets)
                return jax.vmap(
                    lambda s, t, c: _solve(g, cfg, s,
                                           prims=_prims(g, ell, csr),
                                           C0=c, target=t)
                )(sources, targets, C0)

            self._jit_one = jax.jit(solve_one)
            self._jit_batch = jax.jit(solve_many)
            self._sharded_batch = None

    # ------------------------------------------------------------------
    def _check_sources(self, sources: np.ndarray, what: str = "source") -> None:
        # out-of-range indices would be silently DROPPED by jax .at[].set
        # under jit (all-INF distances), so reject them loudly here.
        sources = np.asarray(sources, np.int64)
        bad = sources[(sources < 0) | (sources >= self.graph.n)]
        if bad.size:
            raise ValueError(
                f"{what} vertices {bad.tolist()} out of range "
                f"[0, {self.graph.n})")

    def solve(self, source: int, target: int | None = None,
              C0=None) -> SSSPResult:
        """Distances from one source (compiled once per graph shape).

        ``target`` switches on the goal-directed fast path: the solve
        early-exits once ``dist[target]`` is certified exact (result
        stamped ``partial=True`` — only fixed vertices carry exact
        distances; ``path_to(target)`` stays exact).  ``C0`` optionally
        seeds the lower bounds, e.g. ``LandmarkIndex.seed(source)``.
        """
        self._check_sources([source])
        if target is not None:
            self._check_sources([target], what="target")
        if self._jit_one is None:  # distributed: batch of one
            return self.solve_batch(
                [source], targets=None if target is None else [target],
                C0=None if C0 is None else jnp.asarray(C0)[None])[0]
        t = jnp.int32(-1 if target is None else int(target))
        c0 = (jnp.zeros((self.graph.n,), jnp.float32) if C0 is None
              else jnp.asarray(C0, jnp.float32))
        state = self._jit_one(self.graph, self.ell, self.csr,
                              jnp.int32(source), t, c0)
        rounds, fb, edges = spans.fetch(
            (state.round, state.fixed_by, state.edges))
        partial = target is not None and self.cfg.early_exit
        return SSSPResult(
            dist=state.D, C=state.C, fixed=state.fixed,
            rounds=int(rounds), fixed_by=_fixed_by_dict(fb),
            source=int(source), graph=self.graph,
            target=target, partial=partial,
            edges_relaxed=None if edges is None else int(edges))

    def solve_batch(self, sources, targets=None, C0=None) -> SSSPBatchResult:
        """Distances from B sources via one vmapped program.

        The batch is right-padded (repeating the last source) to the next
        power of two so arbitrary request counts reuse a handful of
        compiled batch shapes; padding lanes are sliced off the result.

        ``targets`` (int32[B], optional) makes every lane a goal-directed
        point-to-point solve (see :meth:`solve`); under vmap a lane
        freezes once its own target is fixed, so the batch runs for the
        max over lanes of the per-lane (early-exited) round counts.
        ``C0`` (float32[B, n], optional) seeds per-lane lower bounds.

        Spans: ``sssp.solve_batch`` (``lanes``) holds ``sssp.prepare``
        (padding, C0, host->device transfers), ``sssp.launch`` (the
        compiled call) and ``sssp.fetch`` (the one blocking read of the
        per-lane counters; distances stay on the device).
        """
        sources = np.asarray(sources, np.int32).ravel()
        if sources.size == 0:
            raise ValueError("solve_batch needs at least one source")
        self._check_sources(sources)
        b = len(sources)
        with spans.span("sssp.solve_batch", lanes=b):
            with spans.span("sssp.prepare"):
                b_pad = _next_pow2(b)
                padded = np.concatenate(
                    [sources, np.full(b_pad - b, sources[-1], np.int32)])
                if targets is None:
                    tpad = np.full(b_pad, -1, np.int32)
                else:
                    targets = np.asarray(targets, np.int32).ravel()
                    if targets.size != b:
                        raise ValueError(f"targets {targets.shape} must "
                                         f"match sources ({b},)")
                    self._check_sources(targets, what="target")
                    # pad with the last lane's target (not -1): an
                    # untargeted padding lane would run to full fixpoint
                    # and dominate rounds
                    tpad = np.concatenate(
                        [targets, np.full(b_pad - b, targets[-1], np.int32)])
                if C0 is None:
                    c0 = jnp.zeros((b_pad, self.graph.n), jnp.float32)
                else:
                    c0 = jnp.asarray(C0, jnp.float32)
                    if c0.shape != (b, self.graph.n):
                        raise ValueError(f"C0 shape {c0.shape} != "
                                         f"({b}, {self.graph.n})")
                    if b_pad > b:
                        c0 = jnp.concatenate(
                            [c0, jnp.broadcast_to(c0[-1:], (b_pad - b,
                                                            self.graph.n))])
                if self._sharded_batch is None:
                    padded, tpad = jnp.asarray(padded), jnp.asarray(tpad)
            with spans.span("sssp.launch"):
                if self._sharded_batch is not None:
                    state = self._sharded_batch(padded, self.graph, tpad,
                                                c0)
                else:
                    state = self._jit_batch(self.graph, self.ell, self.csr,
                                            padded, tpad, c0)
            fb, rounds, edges = spans.fetch(
                (state.fixed_by, state.round, state.edges))
        return SSSPBatchResult(
            sources=sources,
            dist=state.D[:b], C=state.C[:b], fixed=state.fixed[:b],
            rounds=rounds[:b],
            fixed_by=[_fixed_by_dict(fb[i]) for i in range(b)],
            graph=self.graph,
            targets=None if targets is None else targets,
            partial=targets is not None and self.cfg.early_exit,
            edges_relaxed=None if edges is None else edges[:b])
