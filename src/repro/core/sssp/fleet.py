"""Graph fleets: many same-shape graphs device-resident, one program.

The engine already amortizes across *sources* (``Solver.solve_batch``)
and *lanes* (``BidirectionalSolver``); this module amortizes across
*graphs*.  A :class:`GraphFleet` stacks F same-shape :class:`Graph`
pytrees along a new leading fleet axis — the pgx move (thousands of
game states device-resident under one vmapped step) applied to
shortest paths: per-city road networks or per-tenant topologies whose
(n, e_pad) agree become ONE pytree whose leaves are ``[F, ...]``, and
:class:`FleetSolver` runs ``engine._round`` vmapped over ``[fleet]``
or ``[fleet, batch]`` so every member shares a single compiled program
(``trace_count``-tested, like every other solver facade here).

The stacking idiom generalizes ``bidirectional._stack2``: static aux
data (n / e / e_pad) must match — the treedef comparison inside
``jax.tree.map`` enforces it — so the stacked object is the *same*
dataclass with ``[F, ...]`` leaves, exactly what ``vmap(in_axes=0)``
unstacks back into F well-formed graphs.  Members whose true edge
counts differ are normalized to a shared padded shape by
:func:`build_fleet` (padding edges are inert: ``src = dst = n``,
``w = +inf``); the true per-member ``e`` is kept host-side so
``member(i)`` returns a faithful single graph.

Fleet rounds come in two backends.  ``backend="segment"`` (default)
runs the dense segment body vmapped over the fleet axis; results are
bitwise-identical to per-graph ``Solver(backend="segment")`` solves.
``backend="frontier"`` runs the shared-batch-frontier round body
(``engine._round_shared``) per member, python-UNROLLED over the F
members inside one compiled program: each member keeps its own scalar
overflow predicate and its own union frontier over its ``[B]`` source
lanes — vmapping members instead would batch the predicates and
linearize the sparse/dense ``lax.cond`` to ``select`` (both branches
every round), the exact failure the shared frontier exists to avoid.
Unrolled members still share ONE dispatch and one trace
(``trace_count``), and every lane is bitwise-identical to a solo
``Solver(backend="frontier")`` solve (docs/round-anatomy.md).

Per-graph delta streams stack the same way: :func:`stack_deltas` pads
F :class:`GraphDelta` batches to a common ``k_pad`` and stacks their
leaves, so ``FleetSolver.update`` applies every member's own delta —
and warm re-solves every member's tracked state through the same
fleet-wide while_loop — in ONE dispatch (``warm_trace_count``-tested).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.core.graph import Graph, HostGraph
from repro.core.sssp import backends
from repro.core.sssp.engine import (SP4_CONFIG, SSSPConfig, SSSPResult,
                                    _fixed_by_dict, _solve, _solve_frontier,
                                    _solve_warm, _solve_warm_frontier,
                                    delta_decrease_sources, delta_taint_seeds)
from repro.core.sssp.dynamic import _ELL_PAD, GraphDelta
from repro.core.sssp.solver import (_default_frontier_cap, _frontier_fits,
                                    _next_pow2)

# out-of-bounds sentinel for stacked-delta padding rows: every consumer
# scatter-drops or gather-masks indices >= e_pad, and 2^30 clears any
# member's e_pad without knowing it here.
_IDX_PAD = np.int32(1 << 30)


def _stack_trees(trees):
    """Stack same-structure pytrees along a new leading axis.

    The F-ary generalization of ``bidirectional._stack2``: static aux
    data must match across all inputs (treedef comparison inside
    ``tree.map`` enforces it)."""
    if len(trees) == 1:
        return jax.tree.map(lambda x: x[None], trees[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@jax.jit
def _apply_fleet(g: Graph, deltas: GraphDelta) -> Graph:
    """Vmapped per-member delta application: each fleet member consumes
    its own delta row in one dispatch.  Weight validation is host-side
    work (``make_delta``); the traced values skip it by design."""
    return jax.vmap(lambda gi, di: gi.apply_delta(di))(g, deltas)


class GraphFleet:
    """F same-shape graphs stacked into one device-resident pytree.

    ``g`` is a :class:`Graph` whose leaves carry a leading fleet axis
    (``src``/``dst``/``w``: ``[F, e_pad]``, vertex arrays: ``[F, n]``);
    the static metadata (n, e, e_pad) is shared.  ``es`` keeps each
    member's TRUE edge count so :meth:`member` can slice out a faithful
    single graph (the stacked ``e`` is the padded maximum).

    Build via :meth:`stack` (device Graphs with matching n/e_pad) or
    :func:`build_fleet` (host graphs normalized to a common pad).
    """

    def __init__(self, g: Graph, es: tuple[int, ...]):
        self.g = g
        self.es = tuple(int(e) for e in es)

    @property
    def size(self) -> int:
        return len(self.es)

    @property
    def n(self) -> int:
        return self.g.n

    @property
    def e_pad(self) -> int:
        return self.g.e_pad

    @classmethod
    def stack(cls, graphs) -> "GraphFleet":
        """Stack device :class:`Graph` members sharing (n, e_pad).

        Members may differ in true edge count ``e`` (their padding rows
        are inert); use :func:`build_fleet` to normalize host graphs
        whose pads disagree.
        """
        graphs = list(graphs)
        if not graphs:
            raise ValueError("empty fleet")
        for i, g in enumerate(graphs):
            if not isinstance(g, Graph):
                raise TypeError(f"fleet member {i} must be a device Graph, "
                                f"got {type(g)!r} (see build_fleet)")
        shapes = {(g.n, g.e_pad) for g in graphs}
        if len(shapes) > 1:
            raise ValueError(
                f"fleet members must share (n, e_pad); got {sorted(shapes)} "
                "— build them with a common edge_pad_multiple "
                "(build_fleet does this)")
        es = tuple(g.e for g in graphs)
        e_max = max(es)
        norm = [g if g.e == e_max else dataclasses.replace(g, e=e_max)
                for g in graphs]
        return cls(_stack_trees(norm), es)

    def member(self, i: int) -> Graph:
        """Member ``i`` as a faithful single :class:`Graph` (true e)."""
        i = int(i)
        if not 0 <= i < self.size:
            raise IndexError(f"member {i} out of range [0, {self.size})")
        g = jax.tree.map(lambda x: x[i], self.g)
        return dataclasses.replace(g, e=self.es[i])

    def members(self) -> list[Graph]:
        return [self.member(i) for i in range(self.size)]

    def apply_deltas(self, deltas: GraphDelta) -> "GraphFleet":
        """New fleet with each member's own delta applied (one dispatch).

        ``deltas`` is a stacked :class:`GraphDelta` (``[F, k_pad]``
        leaves — see :func:`stack_deltas`).
        """
        if int(np.ndim(deltas.edge_idx)) != 2 or \
                deltas.edge_idx.shape[0] != self.size:
            raise ValueError(
                f"stacked delta shape {tuple(deltas.edge_idx.shape)} must "
                f"be [{self.size}, k_pad] (see stack_deltas)")
        return GraphFleet(_apply_fleet(self.g, deltas), self.es)

    def with_arrays(self, **leaves) -> "GraphFleet":
        """New fleet with stacked leaf arrays replaced (checkpoint
        restore path: w/in_weight/out_weight come back from a snapshot
        bitwise, no recompute)."""
        return GraphFleet(dataclasses.replace(self.g, **leaves), self.es)


def build_fleet(members, *, edge_pad_multiple: int = 128) -> GraphFleet:
    """Normalize host members to one padded shape and stack them.

    ``members``: HostGraphs, ``(n, src, dst, w)`` tuples, or device
    Graphs (rebuilt host-side when their pads disagree).  All must share
    ``n``; edge lists are padded to the common ``e_pad`` (the max over
    members of the rounded-up edge count).
    """
    hosts = []
    for i, m in enumerate(members):
        if isinstance(m, Graph):
            m = m.to_host()
        if isinstance(m, HostGraph):
            hosts.append((m.n, m.src, m.dst, m.w))
        elif isinstance(m, tuple) and len(m) == 4:
            hosts.append(m)
        else:
            raise TypeError(f"fleet member {i}: expected HostGraph, Graph, "
                            f"or (n, src, dst, w), got {type(m)!r}")
    if not hosts:
        raise ValueError("empty fleet")
    ns = {int(h[0]) for h in hosts}
    if len(ns) > 1:
        raise ValueError(f"fleet members must share n; got {sorted(ns)}")
    from repro.core.graph import build_graph, round_up
    pad = max(round_up(max(len(h[1]), 1), edge_pad_multiple) for h in hosts)
    return GraphFleet.stack(
        [build_graph(*h, edge_pad_multiple=pad) for h in hosts])


def stack_deltas(deltas) -> GraphDelta:
    """Stack F per-member :class:`GraphDelta` batches into one pytree.

    Leaves become ``[F, k_pad]`` (padded to the common ``k_pad``, a
    power of two, so delta streams whose per-tick sizes wobble reuse a
    handful of compiled fleet-update programs); ``k`` becomes an
    ``int32[F]`` leaf.  Padding rows carry out-of-bounds indices and
    positive weights — dropped/masked by every consumer, exactly like
    single-delta padding.
    """
    deltas = list(deltas)
    if not deltas:
        raise ValueError("stack_deltas needs at least one delta")
    kp = _next_pow2(max(d.k_pad for d in deltas))

    def pad(x, fill, dtype):
        x = np.asarray(x)
        return np.concatenate(
            [x, np.full(kp - len(x), fill, x.dtype)]).astype(dtype)

    has_csr = all(d.csr_pos is not None for d in deltas)
    # inputs are per-member deltas that already went through make_delta's
    # host-side validation; this only restacks them
    return GraphDelta(  # astlint: ignore[raw-graphdelta]
        k=jnp.asarray([d.k for d in deltas], jnp.int32),
        edge_idx=jnp.stack([jnp.asarray(pad(d.edge_idx, _IDX_PAD, np.int32))
                            for d in deltas]),
        new_w=jnp.stack([jnp.asarray(pad(d.new_w, 1.0, np.float32))
                         for d in deltas]),
        ell_row=jnp.stack([jnp.asarray(pad(d.ell_row, _ELL_PAD, np.int32))
                           for d in deltas]),
        ell_col=jnp.stack([jnp.asarray(pad(d.ell_col, _ELL_PAD, np.int32))
                           for d in deltas]),
        csr_pos=(jnp.stack([jnp.asarray(pad(d.csr_pos, _IDX_PAD, np.int32))
                            for d in deltas]) if has_csr else None),
    )


@dataclasses.dataclass
class FleetResult:
    """One source per fleet member: distances + certificates, indexable.

    ``result(i)`` views member i as a plain :class:`SSSPResult` carrying
    that member's faithful graph (lazy parents/paths work as usual).
    """

    sources: np.ndarray        # int32[F]
    dist: jax.Array            # float32[F, n]
    C: jax.Array               # float32[F, n]
    fixed: jax.Array           # bool[F, n]
    rounds: np.ndarray         # int32[F]
    fixed_by: list[dict[str, int]]
    fleet: GraphFleet
    edges_relaxed: np.ndarray | None = None  # int32[F] (frontier backend)

    def __len__(self) -> int:
        return len(self.sources)

    def result(self, i: int) -> SSSPResult:
        return SSSPResult(
            dist=self.dist[i], C=self.C[i], fixed=self.fixed[i],
            rounds=int(self.rounds[i]), fixed_by=self.fixed_by[i],
            source=int(self.sources[i]), graph=self.fleet.member(i),
            edges_relaxed=None if self.edges_relaxed is None
            else int(self.edges_relaxed[i]))

    __getitem__ = result


@dataclasses.dataclass
class FleetBatchResult:
    """B sources per fleet member ([F, B] lanes, one program)."""

    sources: np.ndarray        # int32[F, B]
    dist: jax.Array            # float32[F, B, n]
    C: jax.Array               # float32[F, B, n]
    fixed: jax.Array           # bool[F, B, n]
    rounds: np.ndarray         # int32[F, B]
    fixed_by: list[list[dict[str, int]]]
    fleet: GraphFleet
    edges_relaxed: np.ndarray | None = None  # int32[F, B] (frontier)

    def result(self, f: int, i: int) -> SSSPResult:
        return SSSPResult(
            dist=self.dist[f, i], C=self.C[f, i], fixed=self.fixed[f, i],
            rounds=int(self.rounds[f, i]), fixed_by=self.fixed_by[f][i],
            source=int(self.sources[f, i]), graph=self.fleet.member(f),
            edges_relaxed=None if self.edges_relaxed is None
            else int(self.edges_relaxed[f, i]))


@contract(
    "fleet.lockstep",
    routes=("fleet.*",),
    require=("scatter-min",),
    dense_budget={"fleet.warm": 8, "fleet.*": 6},
    notes="F graphs solve in ONE dispatch: the round body is vmapped "
          "over the fleet axis on the shape-unified edge layout.  The "
          "per-member program is the segment backend, so the segment "
          "scatter-min relax and dense budget hold per member — a "
          "budget regression here costs F-fold wall time.")
@contract(
    "fleet.frontier",
    routes=("fleet_frontier.*",),
    require=("cumsum", "scatter-min"),
    dense_budget={"fleet_frontier.warm": 16, "fleet_frontier.*": 12},
    notes="backend='frontier' python-unrolls the members through the "
          "shared-batch-frontier round body — the compiled program "
          "must contain each member's cumsum union compaction and "
          "scatter-min relax, and the dense budget is PER PROGRAM "
          "(F x the solo frontier budget at the probe's F=2): only "
          "each member's step-1 overflow-fallback branch, inWeight_nf "
          "sweep, Eqn-(1) C-propagation sweep and warm taint sweep may "
          "touch e_pad (docs/round-anatomy.md).")
class FleetSolver:
    """Compiled SSSP over a whole :class:`GraphFleet`.

    ``solve(sources)`` takes one source per member (``int32[F]``) and
    runs the engine's round body vmapped over the fleet axis;
    ``solve_batch(sources)`` takes ``[F, B]`` and nests a batch vmap
    inside the fleet vmap (B right-padded to a power of two).  Both are
    one compiled program per shape — sources and the stacked graph are
    traced operands, so delta'd fleets never retrace
    (``trace_count``).

    ``backend="frontier"`` routes every member through the shared-
    batch-frontier round body instead (``engine._round_shared``),
    python-unrolled over members so each keeps its own scalar overflow
    predicate and its own union frontier across its source lanes (see
    the module docstring); ``backend="auto"`` picks it when every
    member passes the :func:`~repro.core.sssp.solver._frontier_fits`
    structural proxy.  Per-member :class:`CsrGraph` views live in
    ``self.csrs`` and stay GraphDelta-coherent through ``update``
    (stacked deltas must then carry ``csr_pos``).  Results are
    bitwise-identical to the segment backend; ``edges_relaxed`` is
    metered per lane.

    ``update(deltas)`` consumes one :func:`stack_deltas` pytree: every
    member's graph mutates AND every member's tracked per-member state
    (the last ``solve``) warm re-solves — taint cone, un-fix, re-entry
    into the same fleet-wide while_loop — in a single vmapped program
    (``warm_trace_count``), mirroring ``DynamicSolver.update`` along
    the fleet axis instead of the source axis.

    ``state_dict()``/``load_state_dict()`` expose the device-resident
    fleet state (weights + tracked solves) as a flat pytree for
    checkpoint/restart — restoring is bitwise (arrays land back
    verbatim, nothing is recomputed).
    """

    def __init__(self, fleet, cfg: SSSPConfig = SP4_CONFIG,
                 backend: str = "segment", *,
                 frontier_cap: int | None = None):
        if isinstance(fleet, (list, tuple)):
            fleet = GraphFleet.stack(fleet)
        if not isinstance(fleet, GraphFleet):
            raise TypeError(f"fleet must be a GraphFleet or a list of "
                            f"Graphs, got {type(fleet)!r}")
        if backend not in ("segment", "frontier", "auto"):
            raise ValueError(f"unknown fleet backend {backend!r}; "
                             "expected 'segment', 'frontier', or 'auto'")
        if cfg.use_pallas:
            cfg = dataclasses.replace(cfg, use_pallas=False)
        if backend == "auto":
            backend = ("frontier"
                       if all(_frontier_fits(m) for m in fleet.members())
                       else "segment")
        self.fleet = fleet
        self.cfg = cfg
        self.backend = backend
        self.version = 0
        self.trace_count = 0
        self.warm_trace_count = 0
        self.solves = 0
        self._tracked: dict | None = None  # last solve(): sources + states

        # frontier mode: one CSR view per member (their max_out/max_in
        # statics may differ — which is exactly why the closures UNROLL
        # members instead of vmapping them), one shared union-buffer cap.
        self.frontier_cap = 0
        self.csrs: list | None = None
        if backend == "frontier":
            self.csrs = [m.csr() for m in fleet.members()]
            self.frontier_cap = _next_pow2(
                _default_frontier_cap(fleet.n) if frontier_cap is None
                else max(1, int(frontier_cap)))
        cap = self.frontier_cap

        def _count():
            self.trace_count += 1   # python side effect: runs per TRACE

        def _count_warm():
            self.warm_trace_count += 1

        def _member(gF, f):
            return jax.tree.map(lambda x: x[f], gF)

        def _fprims(g, csr):
            return backends.frontier_prims(g, csr, cap, False)

        def solve_fleet(gF, csrs, sources, targets, C0):
            _count()
            if csrs is not None:
                outs = [_solve_frontier(_member(gF, f), cfg,
                                        sources[f][None], _fprims(
                                            _member(gF, f), csr),
                                        C0=C0[f][None],
                                        targets=targets[f][None])
                        for f, csr in enumerate(csrs)]
                return jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
            return jax.vmap(
                lambda g, s, t, c: _solve(g, cfg, s,
                                          prims=backends.segment_prims(g),
                                          C0=c, target=t)
            )(gF, sources, targets, C0)

        def solve_fleet_batch(gF, csrs, sources, targets, C0):
            _count()
            if csrs is not None:
                outs = [_solve_frontier(_member(gF, f), cfg, sources[f],
                                        _fprims(_member(gF, f), csr),
                                        C0=C0[f], targets=targets[f])
                        for f, csr in enumerate(csrs)]
                return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

            def per_member(g, ss, tt, cc):
                prims = backends.segment_prims(g)
                return jax.vmap(
                    lambda s, t, c: _solve(g, cfg, s, prims=prims,
                                           C0=c, target=t))(ss, tt, cc)

            return jax.vmap(per_member)(gF, sources, targets, C0)

        def warm_fleet(gF_old, csrs, deltas, prev_D, prev_fixed):
            _count_warm()
            if csrs is not None:
                g_news, csr_news, outs = [], [], []
                for f, csr in enumerate(csrs):
                    g_old = _member(gF_old, f)
                    d = jax.tree.map(lambda x: x[f], deltas)
                    g_new = g_old.apply_delta(d)
                    csr_new = csr.apply_delta(d)
                    seeds, pure = delta_taint_seeds(g_old, d, prev_D[f])
                    dec = delta_decrease_sources(g_old, d)
                    st, sweeps, taint = _solve_warm_frontier(
                        g_new, cfg, prev_D[f][None], prev_fixed[f][None],
                        seeds[None], pure[None], _fprims(g_new, csr_new),
                        dec_src=dec)
                    g_news.append(g_new)
                    csr_news.append(csr_new)
                    outs.append((st, sweeps, jnp.sum(taint, axis=1)))
                gF_new = jax.tree.map(lambda *xs: jnp.stack(xs), *g_news)
                sts = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                   *[o[0] for o in outs])
                sw = jnp.concatenate([o[1] for o in outs])
                tn = jnp.concatenate([o[2] for o in outs])
                return gF_new, csr_news, sts, sw, tn

            def per_member(g_old, d, D0, f0):
                g_new = g_old.apply_delta(d)
                seeds, pure = delta_taint_seeds(g_old, d, D0)
                st, sweeps, taint = _solve_warm(
                    g_new, cfg, D0, f0, seeds, pure,
                    prims=backends.segment_prims(g_new))
                return g_new, st, sweeps, jnp.sum(taint)

            g_new, st, sweeps, tainted = jax.vmap(per_member)(
                gF_old, deltas, prev_D, prev_fixed)
            return g_new, None, st, sweeps, tainted

        self._jit_solve = jax.jit(solve_fleet)
        self._jit_batch = jax.jit(solve_fleet_batch)
        self._jit_warm = jax.jit(warm_fleet)

    # ------------------------------------------------------------------
    @property
    def size(self) -> int:
        return self.fleet.size

    def _check_sources(self, sources: np.ndarray) -> None:
        bad = sources[(sources < 0) | (sources >= self.fleet.n)]
        if bad.size:
            raise ValueError(f"source vertices {bad.tolist()} out of range "
                             f"[0, {self.fleet.n})")

    # ------------------------------------------------------------------
    def solve(self, sources, targets=None, C0=None) -> FleetResult:
        """One source per member — F solves, one vmapped program.

        The result is tracked (per-member D/fixed) so the next
        :meth:`update` can warm re-solve the whole fleet.  ``targets``
        (int32[F], optional) makes every member's lane goal-directed
        (early-exited partial results are NOT tracked, same contract as
        ``DynamicSolver.solve``); ``C0`` (float32[F, n]) seeds lower
        bounds per member.
        """
        F, n = self.size, self.fleet.n
        sources = np.asarray(sources, np.int32).ravel()
        if sources.shape != (F,):
            raise ValueError(f"sources shape {sources.shape} != ({F},) "
                             "(one source per fleet member)")
        self._check_sources(sources)
        partial = targets is not None and self.cfg.early_exit
        if targets is None:
            tgts = np.full(F, -1, np.int32)
        else:
            tgts = np.asarray(targets, np.int32).ravel()
            if tgts.shape != (F,):
                raise ValueError(f"targets shape {tgts.shape} != ({F},)")
        c0 = (jnp.zeros((F, n), jnp.float32) if C0 is None
              else jnp.asarray(C0, jnp.float32))
        if c0.shape != (F, n):
            raise ValueError(f"C0 shape {c0.shape} != ({F}, {n})")
        state = self._jit_solve(self.fleet.g, self.csrs,
                                jnp.asarray(sources), jnp.asarray(tgts), c0)
        self.solves += F
        fb = np.asarray(state.fixed_by)
        res = FleetResult(
            sources=sources, dist=state.D, C=state.C, fixed=state.fixed,
            rounds=np.asarray(state.round),
            fixed_by=[_fixed_by_dict(fb[i]) for i in range(F)],
            fleet=self.fleet,
            edges_relaxed=None if state.edges is None
            else np.asarray(state.edges))
        if not partial:
            self._tracked = dict(version=self.version, sources=sources,
                                 D=state.D, C=state.C, fixed=state.fixed,
                                 rounds=np.asarray(state.round), fb=fb)
        return res

    def solve_batch(self, sources, targets=None, C0=None) -> FleetBatchResult:
        """``[F, B]`` sources — F×B solves, one doubly-vmapped program.

        B is right-padded (repeating each member's last source) to the
        next power of two; padding lanes are sliced off.
        """
        F, n = self.size, self.fleet.n
        sources = np.asarray(sources, np.int32)
        if sources.ndim != 2 or sources.shape[0] != F:
            raise ValueError(f"sources shape {sources.shape} must be "
                             f"[{F}, B]")
        self._check_sources(sources.ravel())
        b = sources.shape[1]
        if b == 0:
            raise ValueError("solve_batch needs at least one source")
        b_pad = _next_pow2(b)
        padded = np.concatenate(
            [sources, np.repeat(sources[:, -1:], b_pad - b, axis=1)], axis=1)
        if targets is None:
            tpad = np.full((F, b_pad), -1, np.int32)
        else:
            targets = np.asarray(targets, np.int32)
            if targets.shape != (F, b):
                raise ValueError(f"targets shape {targets.shape} != "
                                 f"({F}, {b})")
            self._check_sources(targets.ravel())
            tpad = np.concatenate(
                [targets, np.repeat(targets[:, -1:], b_pad - b, axis=1)],
                axis=1)
        if C0 is None:
            c0 = jnp.zeros((F, b_pad, n), jnp.float32)
        else:
            c0 = jnp.asarray(C0, jnp.float32)
            if c0.shape != (F, b, n):
                raise ValueError(f"C0 shape {c0.shape} != ({F}, {b}, {n})")
            if b_pad > b:
                c0 = jnp.concatenate(
                    [c0, jnp.broadcast_to(c0[:, -1:],
                                          (F, b_pad - b, n))], axis=1)
        state = self._jit_batch(self.fleet.g, self.csrs,
                                jnp.asarray(padded), jnp.asarray(tpad), c0)
        self.solves += F * b
        fb = np.asarray(state.fixed_by)
        return FleetBatchResult(
            sources=sources,
            dist=state.D[:, :b], C=state.C[:, :b], fixed=state.fixed[:, :b],
            rounds=np.asarray(state.round[:, :b]),
            fixed_by=[[_fixed_by_dict(fb[f, i]) for i in range(b)]
                      for f in range(F)],
            fleet=self.fleet,
            edges_relaxed=None if state.edges is None
            else np.asarray(state.edges[:, :b]))

    # ------------------------------------------------------------------
    def update(self, deltas: GraphDelta, *, refresh: bool = True) -> dict:
        """Apply per-member deltas; warm re-solve the tracked fleet state.

        ``deltas`` is a stacked delta (:func:`stack_deltas`) — row i is
        member i's own update stream batch.  With a fresh tracked state
        (the last untargeted :meth:`solve`) and ``refresh=True``, every
        member's graph mutation AND warm re-solve run in one vmapped
        program; otherwise only the weights mutate and the tracker goes
        stale (the next solve re-tracks cold).
        """
        F = self.size
        if int(np.ndim(deltas.edge_idx)) != 2 or \
                deltas.edge_idx.shape[0] != F:
            raise ValueError(
                f"stacked delta shape {tuple(deltas.edge_idx.shape)} must "
                f"be [{F}, k_pad] (see stack_deltas)")
        if self.csrs is not None and deltas.csr_pos is None:
            raise ValueError(
                "frontier fleet updates need the csr_pos permutation on "
                "every member delta (build them via make_delta against "
                "the member graphs before stack_deltas)")
        tracked = (self._tracked is not None
                   and self._tracked["version"] == self.version)
        stats = dict(edges_changed=int(np.asarray(deltas.k).sum()),
                     warm_refreshed=0, sweeps=0, warm_rounds=[], tainted=[])
        if refresh and tracked:
            g_new, csr_news, states, sweeps, tainted = self._jit_warm(
                self.fleet.g, self.csrs, deltas, self._tracked["D"],
                self._tracked["fixed"])
            self.fleet = GraphFleet(g_new, self.fleet.es)
            if csr_news is not None:
                self.csrs = list(csr_news)
            self.version += 1
            fb = np.asarray(states.fixed_by)
            rounds = np.asarray(states.round)
            self._tracked = dict(
                version=self.version, sources=self._tracked["sources"],
                D=states.D, C=states.C, fixed=states.fixed,
                rounds=rounds, fb=fb)
            stats["warm_refreshed"] = F
            stats["sweeps"] = int(np.max(np.asarray(sweeps)))
            stats["warm_rounds"] = [int(r) for r in rounds]
            stats["tainted"] = [int(t) for t in np.asarray(tainted)]
        else:
            self.fleet = self.fleet.apply_deltas(deltas)
            if self.csrs is not None:
                self.csrs = [
                    csr.apply_delta(jax.tree.map(lambda x: x[f], deltas))
                    for f, csr in enumerate(self.csrs)]
            self.version += 1
        return stats

    def resolve(self) -> FleetResult:
        """The tracked per-member results on the CURRENT graph version
        (fresh after :meth:`update`; re-solved cold when stale)."""
        if self._tracked is None:
            raise ValueError("nothing tracked yet — call solve() first")
        if self._tracked["version"] != self.version:
            return self.solve(self._tracked["sources"])
        t = self._tracked
        F = self.size
        return FleetResult(
            sources=t["sources"], dist=t["D"], C=t["C"], fixed=t["fixed"],
            rounds=t["rounds"],
            fixed_by=[_fixed_by_dict(t["fb"][i]) for i in range(F)],
            fleet=self.fleet)

    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        """Device-resident fleet state as a flat pytree (checkpointable).

        Covers everything :meth:`load_state_dict` needs to resume
        bitwise: the weight-bearing graph leaves and the tracked solve.
        """
        if self._tracked is None:
            raise ValueError("nothing tracked yet — call solve() first")
        t = self._tracked
        return dict(
            w=self.fleet.g.w, in_weight=self.fleet.g.in_weight,
            out_weight=self.fleet.g.out_weight,
            sources=jnp.asarray(t["sources"], jnp.int32),
            D=t["D"], C=t["C"], fixed=t["fixed"],
            rounds=jnp.asarray(t["rounds"], jnp.int32),
            fb=jnp.asarray(t["fb"], jnp.int32),
            version=jnp.int32(self.version))

    def load_state_dict(self, state: dict) -> None:
        """Restore :meth:`state_dict` output verbatim (bitwise resume)."""
        self.fleet = self.fleet.with_arrays(
            w=jnp.asarray(state["w"]),
            in_weight=jnp.asarray(state["in_weight"]),
            out_weight=jnp.asarray(state["out_weight"]))
        if self.csrs is not None:
            # CSR weights are a src-sorted permutation of the restored
            # g.w — rebuilding from the members lands them bitwise.
            self.csrs = [m.csr() for m in self.fleet.members()]
        self.version = int(state["version"])
        self._tracked = dict(
            version=self.version,
            sources=np.asarray(state["sources"], np.int32),
            D=jnp.asarray(state["D"]), C=jnp.asarray(state["C"]),
            fixed=jnp.asarray(state["fixed"]),
            rounds=np.asarray(state["rounds"], np.int32),
            fb=np.asarray(state["fb"], np.int32))
