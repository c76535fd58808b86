"""The paper's contribution as a composable, bulk-synchronous JAX engine.

Garg's four algorithms share one structure: *per round, fix as many
vertices as the available evidence allows, then relax*.  On a TPU (and
in JAX's SPMD model) the heaps/worklists of SP1–SP3 become dense masked
min-reductions and boolean frontiers — exactly the move the paper itself
makes for SP4 ("Step 1 … doubly logarithmic tree").  The engine exposes
each fixing rule as an independent predicate so SP1/SP2/SP3/SP4 are
*configurations* of one program:

  R_min  — Dijkstra:          fix x with  D[x] == minD            (progress)
  R_pred — SP1  (Lemma 2):    fix x whose in-edges are all relaxed
  R_in   — SP2  (Lemma 5):    fix x with  D[x] <= minD + inWeight_nf[x]
  R_out  — Lemma 8 (Crauser): fix x with  D[x] <= min(D+outWeight | ¬fixed)
  R_lb   — SP3/SP4 (Lem 6+7): fix x with  C[x] == D[x] after C-propagation

where ``inWeight_nf[x]`` is the min weight over in-edges whose source is
not yet fixed (the bulk-synchronous strengthening of the paper's
"exclude the discoverer" refinement: every edge that can still lower
D[x] must come from a vertex whose final cost is ≥ minD).

Label-setting configurations relax only out-edges of fixed vertices
(SP1–SP3); the label-correcting configuration (SP4) relaxes every
discovered edge each round, Bellman-Ford style.

``c_prop_iters > 1`` is a *beyond-paper* knob: applying Eqn (1) k times
per round lets lower bounds chase the upper bounds along chains of k
vertices, fixing whole runs per round (the paper applies it once).

The same configuration move applies to execution substrates: ``_round``
is THE round body — the only place the min/pred/in/out/lb rules appear —
and is parameterized by a backend-primitives protocol (backends.py), so
the segment-op path, the dense-ELL path (jnp oracle or Pallas kernels),
and the edge-sharded ``shard_map`` path are instances of one program.
The public surface is the :class:`~repro.core.sssp.solver.Solver` facade
(``repro.sssp``); the ``run_sssp*`` functions below remain as thin
compatibility shims.

Every device operation of a round carries the pass it belongs to as
the outermost ``sssp.*`` named scope in its ``op_name`` metadata, so a
profiler trace can sum device time by pass (docs/round-anatomy.md):

  sssp.relax     step-1 D relaxation (dense fallback and ``relax2``)
  sssp.lb        step 3: Lemma-7 lift, Eqn (1), the c_fix/cone walks
  sssp.inw       inWeight_nf, dense or the incremental refresh
  sssp.fix       step-2 reductions and every fixing/un-fixing rule
  sssp.frontier  next-round fresh mask and its compaction
  sssp.freeze    loop predicates, lane liveness and select-freeze
  sssp.count     edges, fixed_by and round accounting
  sssp.taint     delta set-up: layouts, taint seeds, cone sweeps
  sssp.init      initial state and carries inside the program

Scopes are metadata only: they change no jaxpr equation.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.core.graph import Graph, INF
from repro.core.sssp import backends, spans

Rules = frozenset
_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class SSSPConfig:
    rules: frozenset[str] = frozenset({"min", "pred", "in", "out", "lb"})
    label_correcting: bool = False   # SP4 relaxes all discovered edges
    c_prop_iters: int = 1            # Eqn-(1) applications per round
    max_rounds: int | None = None    # default n
    use_pallas: bool = False         # route relax through the Pallas kernel
    early_exit: bool = True          # targeted solves stop once the target
    #   is fixed AND explored (ablation knob for the goal-directed path;
    #   has no effect on untargeted solves)

    def __post_init__(self):
        unknown = self.rules - {"min", "pred", "in", "out", "lb"}
        if unknown:
            raise ValueError(f"unknown rules {unknown}")
        if not ({"min", "out"} & self.rules):
            raise ValueError("need 'min' or 'out' for progress guarantee")


SP1_RULES = frozenset({"min", "pred"})
SP2_RULES = frozenset({"min", "pred", "in"})
SP3_RULES = frozenset({"min", "pred", "in", "out", "lb"})
SP3_CONFIG = SSSPConfig(rules=SP3_RULES, label_correcting=False)
SP4_CONFIG = SSSPConfig(rules=SP3_RULES, label_correcting=True)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSSPState:
    D: jax.Array        # float32[n] upper bounds
    C: jax.Array        # float32[n] lower bounds
    fixed: jax.Array    # bool[n]
    explored: jax.Array  # bool[n]: fixed AND out-edges relaxed at final D.
    #   The paper's fixed-vs-explored distinction (R = fixed ∧ ¬explored) is
    #   load-bearing: a vertex fixed by the lb rule late in round r has its
    #   out-edges relaxed only in round r+1, so the fixing rules of round
    #   r+1 must run *after* that relaxation — hence relax-first ordering —
    #   and termination must wait for fixed ∧ ¬explored to drain.
    round: jax.Array    # int32 scalar
    fixed_by: jax.Array  # int32[5] cumulative per-rule fix counts (ablation)
    # --- sparse-frontier extension (None on dense backends) ---
    f_idx: jax.Array | None = None  # int32[cap] compacted frontier buffer:
    #   vertex ids whose out-edge offers are NEW this round (padding: n).
    f_cnt: jax.Array | None = None  # int32 scalar true frontier size;
    #   f_cnt > cap flags OVERFLOW — the buffer holds only a prefix, so
    #   the next round falls back to the dense relax (bitwise-safe) and
    #   the frontier re-compacts from that round's changes.
    edges: jax.Array | None = None  # int32 scalar cumulative edges the
    #   D-relaxation OPERATED ON (live relax ops: out-degrees of masked
    #   buffer slots on sparse rounds, e_pad on dense-fallback rounds).
    #   The physical gather of a sparse round touches up to
    #   cap * max_out_deg padded slots regardless of how many are live —
    #   the bench reports that bound separately (slot_ratio).
    # --- shared-batch-frontier carries (engine-internal state of
    # ``_round_shared``; None on every other path) ---
    in_w_nf: jax.Array | None = None  # float32[B, n] incremental
    #   inWeight_nf: min in-edge weight over NON-fixed sources, valid for
    #   this round-start ``fixed``; refreshed end-of-round only at the
    #   out-neighbourhoods of vertices whose fixed bit flipped.
    c_fix: jax.Array | None = None  # float32[B, n] min over FIXED
    #   in-sources u of D[u] + w — the fixed-source half of the Eqn-(1)
    #   C-propagation input, maintained over the same flip cones.
    cfix_stale: jax.Array | None = None  # bool[B, n] sources whose fixed
    #   bit flipped AFTER the last c_fix maintenance (lb fixes of the
    #   previous round; warm un-fixes join at the next round's step 1).


@dataclasses.dataclass
class SSSPResult:
    """Distances + certificates for one source, with lazy tree extraction.

    ``parents()``/``path_to()`` fold the old standalone ``parents.py``
    workflow into the result: parent pointers are computed (and cached)
    only when first asked for, from the same graph the solve ran on.
    """

    dist: jax.Array
    C: jax.Array
    fixed: jax.Array
    rounds: int
    fixed_by: dict[str, int]
    trace: list | None = None
    source: int | None = None
    graph: Graph | None = None
    target: int | None = None     # the goal of a targeted (p2p) solve
    edges_relaxed: int | None = None  # frontier backend: edge slots the
    #   D-relaxation gathered over the whole solve (None on dense
    #   backends, whose relax always touches all e_pad slots per round).
    partial: bool = False         # early-exited: only FIXED vertices carry
    #   exact distances (dist[target] always does); unfixed entries are
    #   upper bounds.  ``path_to(target)`` remains exact on a partial
    #   result: every feasible parent u of an exact vertex v satisfies
    #   d(s,u) <= D[u] and d(s,u)+w >= d(s,v) = D[u]+w, so D[u] is exact
    #   and on a shortest path — the walked chain never leaves exactness.
    _parents: np.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def parents(self) -> np.ndarray:
        """int32[n] shortest-path-tree parent per vertex (lazy, cached)."""
        if self._parents is None:
            if self.graph is None:
                raise ValueError("result carries no graph; "
                                 "solve via Solver/run_sssp to attach one")
            from repro.core.sssp.parents import parent_pointers
            self._parents = spans.fetch(parent_pointers(self.graph,
                                                        self.dist))
        return self._parents

    def path_to(self, target: int) -> list[int] | None:
        """Vertex list source..target along a shortest path, or None."""
        if self.source is None:
            raise ValueError("result carries no source vertex")
        from repro.core.sssp.parents import extract_path
        return extract_path(self.parents(), int(target), int(self.source))


_RULE_ORDER = ("min", "pred", "in", "out", "lb")


def _fixed_by_dict(fixed_by) -> dict[str, int]:
    fb = np.asarray(fixed_by)
    return {r: int(c) for r, c in zip(_RULE_ORDER, fb)}


def _frontier_cap(prims) -> int:
    return getattr(prims, "frontier_cap", 0) if prims is not None else 0


def _compact_frontier(mask: jax.Array, cap: int, n: int):
    """Compacted index buffer of the True positions of ``mask``.

    ``cumsum``-compaction inside the round body: position of vertex v in
    the buffer is the number of True entries before it.  Returns
    ``(f_idx int32[cap], f_cnt int32)``; when the true count exceeds
    ``cap`` the surplus scatters are dropped (the buffer holds a prefix)
    and the caller must treat ``f_cnt > cap`` as overflow — the dense
    round for that iteration keeps results bitwise-identical.
    """
    pos = jnp.cumsum(mask, dtype=jnp.int32) - 1
    f_cnt = jnp.sum(mask, dtype=jnp.int32)
    at = jnp.where(mask, pos, cap)  # cap (and beyond) -> dropped
    f_idx = jnp.full((cap,), n, jnp.int32).at[at].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return f_idx, f_cnt


def _init_state(g: Graph, source, C0=None,
                prims: "backends.Primitives | None" = None) -> SSSPState:
    """``source`` may be a python int or a traced int32 scalar — keeping it
    traced is what lets the Solver vmap over sources without retracing.

    ``C0`` (optional float32[n]) seeds the LOWER bounds with non-trivial
    values — e.g. landmark/ALT bounds (sssp/landmarks.py).  Caller's
    contract: ``C0[v] <= d(source, v)`` for every v (``+inf`` is allowed
    and asserts unreachability).  Seeded bounds let the lb rule fix
    vertices rounds earlier; invalid seeds give wrong distances.

    A frontier-capable ``prims`` additionally seeds the compacted
    frontier buffer with the source (the only vertex whose offers are
    new at round 1 — the label-setting round 1 relaxes nothing and masks
    it out, bitwise-identical either way).
    """
    with _scope("sssp.init"):
        D = jnp.full((g.n,), INF, jnp.float32).at[source].set(0.0)
        if C0 is None:
            C = jnp.zeros((g.n,), jnp.float32)
        else:
            C = jnp.maximum(C0.astype(jnp.float32), 0.0)
        fixed = jnp.zeros((g.n,), bool)
        cap = _frontier_cap(prims)
        f_idx = f_cnt = edges = None
        if cap:
            f_idx = jnp.full((cap,), g.n, jnp.int32).at[0].set(
                jnp.int32(source))
            f_cnt = jnp.int32(1)
            edges = jnp.int32(0)
        return SSSPState(D=D, C=C, fixed=fixed, explored=fixed,
                         round=jnp.int32(0),
                         fixed_by=jnp.zeros(5, jnp.int32),
                         f_idx=f_idx, f_cnt=f_cnt, edges=edges)


def delta_taint_seeds(g_old: Graph, delta, D0: jax.Array):
    """Taint seeds for a warm start: heads of increased-and-tight edges.

    ``delta`` is a ``sssp.dynamic.GraphDelta`` (duck-typed: ``edge_idx``
    int32[k_pad] into the dst-sorted edge arrays, padding ``>= e_pad``;
    ``new_w`` float32[k_pad]).  ``g_old`` / ``D0`` are the graph and
    distance vector the previous solve ran on.  Returns

      seeds:         bool[n] — v such that some in-edge (u, v) both
                     *increased* (new_w > old_w) and was *tight* under the
                     old solve (D0[u] + w_old <= D0[v]).  Only through
                     such an edge can an old distance certificate break.
      pure_increase: bool scalar — no edge decreased, so every old D is
                     still a valid LOWER bound (distances only grow) and
                     the warm start may seed C with it.

    Everything is jit-safe: invalid/padding delta rows are neutralized by
    clipped gathers + the masked conditions, never by data-dependent
    shapes.
    """
    with _scope("sssp.taint"):
        valid = delta.edge_idx < g_old.e_pad
        idx = jnp.minimum(delta.edge_idx, g_old.e_pad - 1)  # clip gathers
        w_old = g_old.w[idx]
        src, dst = g_old.src[idx], g_old.dst[idx]
        D0_ext = jnp.concatenate([D0, jnp.full((1,), INF, D0.dtype)])
        Ds = D0_ext[jnp.minimum(src, g_old.n)]
        Dd = D0_ext[jnp.minimum(dst, g_old.n)]
        increased = valid & (delta.new_w > w_old)
        tight = (Ds + w_old <= Dd) & (Ds < INF) & (Dd < INF)
        seed_at = jnp.where(increased & tight, dst, g_old.n)  # n = drop
        seeds = jnp.zeros((g_old.n,), bool).at[seed_at].set(
            True, mode="drop")
        pure_increase = ~jnp.any(valid & (delta.new_w < w_old))
        return seeds, pure_increase


def delta_decrease_sources(g_old: Graph, delta) -> jax.Array:
    """bool[n] — tails of *decreased* delta edges (jit-safe).

    The sparse-frontier warm start needs these: a decreased edge's tail
    is the one fixed vertex whose out-edge offers genuinely changed
    without its own distance changing, so it must be seeded into the
    warm frontier buffer alongside the taint cone's in-boundary
    (``_init_state_warm``).  Source-independent — one mask serves every
    vmapped lane of a warm refresh batch.
    """
    with _scope("sssp.taint"):
        valid = delta.edge_idx < g_old.e_pad
        idx = jnp.minimum(delta.edge_idx, g_old.e_pad - 1)
        dec = valid & (delta.new_w < g_old.w[idx])
        at = jnp.where(dec, g_old.src[idx], g_old.n)  # n = drop
        return jnp.zeros((g_old.n,), bool).at[at].set(True, mode="drop")


def _warm_seed_mask(g: Graph, taint: jax.Array, fixed: jax.Array,
                    D: jax.Array, dec_src: jax.Array | None) -> jax.Array:
    """Fixed vertices whose warm round-1 out-edge offers are NOT already
    folded into the warm state: the taint cone's in-boundary plus tails
    of decreased delta edges (see ``_init_state_warm``).  ``dec_src=None``
    degrades to seeding every surviving fixed vertex — still exact."""
    with _scope("sssp.taint"):
        if dec_src is None:
            return fixed & (D < INF)
        # in-boundary of the cone: fixed tails of edges into taint
        at = jnp.where(g.gather_dst(taint.astype(jnp.int32), fill=0) > 0,
                       g.src, g.n)
        bnd = jnp.zeros((g.n,), bool).at[at].set(True, mode="drop")
        return (bnd | dec_src) & fixed & (D < INF)


def _init_state_warm(g: Graph, prev_D: jax.Array, prev_fixed: jax.Array,
                     seeds: jax.Array, pure_increase: jax.Array,
                     prims: backends.Primitives | None = None,
                     dec_src: jax.Array | None = None):
    """Warm-start state after a batch of weight changes (dynamic.py).

    The *affected cone* (``taint``) is every vertex whose old distance
    certificate may route through an increased edge: starting from the
    ``delta_taint_seeds`` heads, taint propagates along tight edges
    (D0[u] + w <= D0[v]) to a fixpoint via ``prims.relax``-style sweeps —
    one relax per sweep, so a local delta costs a handful of sweeps, not
    a re-solve.  Propagation may use the NEW weights: non-delta edges are
    unchanged, decreased edges only get tighter (a superset — safe), and
    increased edges need no propagation because their heads are already
    seeds.  That keeps the warm program single-graph after the seeds are
    computed (which is what lets the edge-sharded backend run it without
    shipping the old weights into the mesh).

    The cone is un-fixed with D reset to INF (its old bounds may now be
    too LOW — the one staleness relaxation can never repair); everything
    else keeps its old D and stays fixed.  Weight *decreases* need no
    cone at all: they leave old bounds stale-HIGH, which the warm round
    body heals by un-fixing on improvement (``_round(warm=True)``).
    Under a pure-increase delta old distances are still valid lower
    bounds, so C warm-starts at D0 for previously-fixed vertices and the
    lb rule re-fixes the untouched parts of the cone immediately.

    ``explored`` starts all-False so ``_cond`` forces at least one full
    relaxation round over the surviving fixed set under the new weights.

    A frontier-capable ``prims`` seeds the compacted buffer from the
    taint cone: the only surviving-fixed vertices whose round-1 offers
    are not already folded into the warm state are (a) the cone's
    in-boundary (the cone's D was reset to INF, so it needs fresh offers
    from its fixed in-neighbours) and (b) tails of *decreased* delta
    edges (``dec_src``; their offers got cheaper with no D change of
    their own).  Every other fixed vertex's offers are no-ops against a
    completed solve's triangle inequality — so the sparse round 1 is
    bitwise-identical to the dense one.  ``dec_src=None`` (caller can't
    name the delta) degrades to seeding ALL surviving fixed vertices —
    still exact, usually overflowing into one dense round.

    Requires ``prev_fixed`` vertices to carry exact distances (any state
    a completed cold or warm solve returns).  Returns ``(state, sweeps,
    taint)`` with ``sweeps`` the number of propagation iterations.
    """
    if prims is None:
        prims = backends.segment_prims(g)
    n = g.n

    def cond(carry):
        _, changed, i = carry
        return changed & (i < n + 1)

    def body(carry):
        taint, _, i = carry
        reach = prims.relax(prev_D, taint)
        taint2 = taint | ((reach <= prev_D) & (prev_D < INF))
        return taint2, jnp.any(taint2 != taint), i + jnp.int32(1)

    with _scope("sssp.taint"):
        taint, _, sweeps = jax.lax.while_loop(
            cond, body, (seeds, jnp.any(seeds), jnp.int32(0)))

        fixed = prev_fixed & ~taint
        D = jnp.where(taint, INF, prev_D)
        C = jnp.where(
            fixed, D,
            jnp.where(pure_increase & prev_fixed & (prev_D < INF), prev_D,
                      0.0))
        cap = _frontier_cap(prims)
        f_idx = f_cnt = edges = None
        if cap:
            seed_mask = _warm_seed_mask(g, taint, fixed, D, dec_src)
            f_idx, f_cnt = _compact_frontier(seed_mask, cap, g.n)
            edges = jnp.int32(0)
        state = SSSPState(D=D, C=C, fixed=fixed,
                          explored=jnp.zeros_like(fixed),
                          round=jnp.int32(0),
                          fixed_by=jnp.zeros(5, jnp.int32),
                          f_idx=f_idx, f_cnt=f_cnt, edges=edges)
        return state, sweeps, taint


def _solve_warm(g: Graph, cfg: SSSPConfig, prev_D, prev_fixed, seeds,
                pure_increase, prims: backends.Primitives | None = None,
                dec_src=None):
    """Warm re-solve to fixpoint on the (already-mutated) graph ``g``.

    Same ``lax.while_loop``/round body as ``_solve``, entered from
    ``_init_state_warm`` with ``warm=True`` rounds.  The round cap is
    doubled vs cold: un-fix-on-improve can transiently re-open vertices,
    so net-fixes-per-round is no longer >= 1 (termination itself is
    guaranteed by per-vertex monotone D).  Returns (state, sweeps, taint).

    Batch-capable frontier ``prims`` (``relax_frontier_b`` set) route to
    the shared-frontier driver at B=1 — warm rounds then run the same
    sparse round body (incremental inWeight_nf, cone C-propagation) as
    warm *batches* do, instead of the dense body.
    """
    if getattr(prims, "relax_frontier_b", None) is not None:
        st, sweeps, taint = _solve_warm_frontier(
            g, cfg, prev_D[None], prev_fixed[None], seeds[None],
            jnp.asarray(pure_increase).reshape((1,)), prims,
            dec_src=dec_src)
        return jax.tree.map(lambda x: x[0], st), sweeps[0], taint[0]
    state, sweeps, taint = _init_state_warm(
        g, prev_D, prev_fixed, seeds, pure_increase, prims, dec_src)
    max_rounds = (2 * cfg.max_rounds) if cfg.max_rounds else 2 * g.n + 4
    state = jax.lax.while_loop(
        lambda s: _cond(s, max_rounds),
        partial(_round, g, cfg, prims=prims, warm=True), state)
    return state, sweeps, taint


@contract(
    "engine.round_body",
    routes=("*",),
    forbid=("callback", "infeed", "outfeed"),
    forbid_hot=("sort", "top_k"),
    notes="The round body is bulk-synchronous device code: no host "
          "round-trip may appear anywhere in a compiled route (the "
          "callback family covers pure/io/debug callbacks), no sort "
          "inside the hot relax (masked min-reductions only), and the "
          "whole engine is f32/i32 (allow_wide_dtypes defaults False: "
          "a single f64 value doubles the bandwidth of the round).")
def _round(g: Graph, cfg: SSSPConfig, state: SSSPState,
           prims: backends.Primitives | None = None,
           warm: bool = False) -> SSSPState:
    """One bulk-synchronous round — THE round body.

    ``prims`` is the backend-primitives protocol (backends.py): segment
    ops by default; the ELL/Pallas and edge-sharded distributed backends
    pass their own.  Every fixing rule below is written once, against
    ``prims`` only.

    ``prims.relax2`` (optional) fuses the TWO independent reductions of
    step 1 into one call — the distributed backend stacks them into a
    single pmin all-reduce.  Exactness: both reductions depend only on
    round-start state (the relax candidates use old D/fixed; inWeight_nf
    uses old fixed), so fusing changes no semantics (§Perf 3.1).

    Note the pred rule needs no reduction of its own when the in rule is
    active: "no non-fixed in-edge" ⟺ inWeight_nf == +inf (§Perf 3.2).

    ``warm=True`` enables the dynamic-graph repair move (sssp/dynamic.py):
    a fixed vertex whose D the relaxation can still LOWER (possible only
    when the state was warm-started across weight decreases — a cold solve
    never lowers a fixed D) is un-fixed and rejoins the active set.  This
    makes transiently-stale fixed vertices self-healing: D is monotone
    non-increasing per vertex, so un-fix events are finite and the loop
    still ends only when a full round changed nothing — at which point D
    is a relaxation fixpoint with D[source]=0, i.e. exact.

    A frontier-capable ``prims`` (``relax_frontier`` set) replaces ONLY
    the step-1 D-relaxation with a gather over the compacted buffer of
    vertices whose offers are new (see the frontier-maintenance block at
    the end).  Everything a repeated offer could touch is monotone-min,
    so skipping value-identical repeats is bitwise-neutral; on overflow
    (``f_cnt > cap``) the round falls back to the dense relax.  In THIS
    legacy single-lane body the other reductions (inWeight_nf,
    C-propagation, minD) stay dense; it survives for callers that vmap
    the round directly over their own lanes (bidirectional.py's two-lane
    program, whose ``cap >= n`` keeps the sparse branch static).  Every
    Solver/Dynamic/Fleet frontier route instead takes ``_round_shared``
    below, where those passes are wavefront-proportional too (see
    docs/round-anatomy.md).
    """
    if prims is None:
        prims = backends.segment_prims(g)
    D, C, fixed = state.D, state.C, state.fixed
    use_frontier = (getattr(prims, "relax_frontier", None) is not None
                    and state.f_idx is not None)

    # --- Step 1: D relaxation (the R-exploration of SP1–SP3 / Step 3 of
    # SP4).  Relax FIRST, from previously-fixed sources (whose D is final),
    # so every fixing rule below sees a D in which all out-edges of all
    # fixed vertices have been applied — the invariant Lemma 2/5/8 need.
    with _scope("sssp.relax"):
        if cfg.label_correcting:
            relax_src = D < INF  # Bellman-Ford style: every discovered edge
        else:
            relax_src = fixed    # label-setting: out-edges of fixed vertices

    need_inw = ("in" in cfg.rules) or ("pred" in cfg.rules)
    in_w_nf = None
    edges = state.edges
    if use_frontier:
        cap = prims.frontier_cap
        with _scope("sssp.relax"):
            if cap >= g.n:
                # a buffer the size of the vertex set can never overflow,
                # so the fallback branch vanishes STATICALLY — this
                # matters for vmapped (batched) solves, where a
                # data-dependent lax.cond linearizes to select and would
                # execute BOTH branches every round (dense + sparse);
                # frontier_cap >= n is the escape hatch that keeps
                # batches single-branch.
                overflow = jnp.bool_(False)
                D_relax = prims.relax_frontier(D, state.f_idx, relax_src)
            else:
                overflow = state.f_cnt > cap
                D_relax = jax.lax.cond(
                    overflow,
                    lambda: prims.relax(D, relax_src),
                    lambda: prims.relax_frontier(D, state.f_idx, relax_src))
        if need_inw:
            with _scope("sssp.inw"):
                in_w_nf = prims.in_weight_nf(~fixed)
        # edges-relaxed accounting: actual out-degrees of the masked
        # buffer on sparse rounds, the whole padded edge list on dense
        # fallback rounds.
        with _scope("sssp.count"):
            u = jnp.minimum(state.f_idx, g.n - 1)
            deg = jnp.where(relax_src, g.out_deg, 0)[u]
            sparse_edges = jnp.sum(jnp.where(state.f_idx < g.n, deg, 0),
                                   dtype=jnp.int32)
            edges = edges + jnp.where(overflow, jnp.int32(g.e_pad),
                                      sparse_edges)
    elif need_inw and prims.relax2 is not None:
        with _scope("sssp.relax"):
            D_relax, in_w_nf = prims.relax2(D, relax_src, ~fixed)
    else:
        with _scope("sssp.relax"):
            D_relax = prims.relax(D, relax_src)
        if need_inw:
            with _scope("sssp.inw"):
                in_w_nf = prims.in_weight_nf(~fixed)
    if warm:
        # weight decreases can leave a warm-started fixed vertex stale-high;
        # un-fix it the moment relaxation offers something strictly better
        # (its old D stays a valid upper bound meanwhile, so the relax it
        # sourced this round was still sound).
        with _scope("sssp.fix"):
            improved = fixed & (D_relax < D)
            fixed = fixed & ~improved
            # its C had been lifted to the now-stale D; drop it back to a
            # trivially-valid lower bound before the lb rule sees it again.
            C = jnp.where(improved, 0.0, C)
    with _scope("sssp.relax"):
        D = jnp.where(~fixed, jnp.minimum(D, D_relax), D)
    explored = fixed  # all currently-fixed vertices are now relaxed-at-final-D

    # --- Step 2: global reductions (the heap minima of SP1–SP3) ---
    with _scope("sssp.fix"):
        discovered = D < INF
        active = discovered & ~fixed
        minD = prims.masked_min(D, active)
        new_fix = jnp.zeros_like(fixed)
        rule_counts = []

        def count(mask):
            rule_counts.append(jnp.sum(mask & active & ~new_fix,
                                       dtype=jnp.int32))
            return mask

        # R_min (Dijkstra's own rule; guarantees >=1 vertex fixed per round)
        if "min" in cfg.rules:
            new_fix = new_fix | count(active & (D <= minD))
        else:
            rule_counts.append(jnp.int32(0))

        # R_pred (SP1, Lemma 2): no in-edge from a non-fixed source
        # remains; all in-edges relaxed (step 1) => D final.  Derived from
        # inWeight_nf (min over an empty set is +inf) — no separate
        # reduction.
        if "pred" in cfg.rules:
            has_nf_pred = ~jnp.isinf(in_w_nf)
            new_fix = new_fix | count(active & ~has_nf_pred)
        else:
            rule_counts.append(jnp.int32(0))

        # R_in (SP2, Lemma 5 strengthened): D[x] <= minD + min in-weight
        # over edges that can still relax (source not yet fixed).  Any
        # pending contribution is cost[v]+w >= minD + inWeight_nf[x] >= D[x].
        if "in" in cfg.rules:
            new_fix = new_fix | count(active & (D <= minD + in_w_nf))
        else:
            rule_counts.append(jnp.int32(0))

        # R_out (Lemma 8 / Crauser out-version)
        if "out" in cfg.rules:
            threshold = prims.masked_min(D + g.out_weight, active)
            new_fix = new_fix | count(active & (D <= threshold))
        else:
            rule_counts.append(jnp.int32(0))

        fixed1 = fixed | new_fix

    # --- Step 3: C update (Lemma 7 lift, then Lemma 6 / Eqn (1)) ---
    if "lb" in cfg.rules:
        with _scope("sssp.lb"):
            C = jnp.where(fixed1, D, jnp.maximum(C, minD))
            all_src = jnp.ones_like(fixed)
            for _ in range(cfg.c_prop_iters):
                c_in = prims.relax(C, all_src)
                C = jnp.where(~fixed1, jnp.maximum(C, c_in), C)
        with _scope("sssp.fix"):
            fix_lb = ~fixed1 & discovered & (C >= D)
            rule_counts.append(jnp.sum(fix_lb, dtype=jnp.int32))
            fixed2 = fixed1 | fix_lb
    else:
        rule_counts.append(jnp.int32(0))
        fixed2 = fixed1
    with _scope("sssp.lb"):
        C = jnp.where(fixed2, D, C)

    f_idx, f_cnt = state.f_idx, state.f_cnt
    if use_frontier:
        # --- frontier maintenance: compact the vertices whose NEXT-round
        # offers are new.  Label-correcting relaxes from every discovered
        # vertex, so new offers come exactly from D changes; label-setting
        # relaxes from fixed vertices, so they come from fix events (incl.
        # a warm unfix-refix, which always moves D).  Repeats the dense
        # path would re-send are value-identical and min-folded — skipping
        # them is bitwise-neutral.
        with _scope("sssp.frontier"):
            if cfg.label_correcting:
                fresh = D != state.D
            else:
                fresh = fixed2 & (~state.fixed | (D != state.D))
            f_idx, f_cnt = _compact_frontier(fresh, prims.frontier_cap, g.n)
    with _scope("sssp.count"):
        return SSSPState(
            D=D, C=C, fixed=fixed2, explored=explored,
            round=state.round + 1,
            fixed_by=state.fixed_by + jnp.stack(rule_counts),
            f_idx=f_idx, f_cnt=f_cnt, edges=edges)


def _chunked_apply(apply_chunk, idx: jax.Array, cnt: jax.Array, cap: int,
                   carry):
    """Fold ``apply_chunk(chunk int32[cap], carry) -> carry`` over
    ``cap``-sized chunks of a full compacted index list ``idx``
    (int32[n], padding n) until ``cnt`` entries are consumed.

    This is how the incremental inWeight_nf / c_fix / cone-propagation
    updates stay wavefront-proportional WITHOUT a dense fallback branch:
    a round pays ``ceil(cnt / cap)`` chunk sweeps under a
    ``lax.while_loop`` — never a full-``e_pad`` pass, and no dense
    rebuild ever appears in the compiled program.  Chunks partition the
    target set, and every chunk's updates are full recomputes at its
    targets (order-independent), so chunking is bitwise-neutral.
    """
    n = idx.shape[0]
    idx_pad = jnp.concatenate([idx, jnp.full((cap,), n, idx.dtype)])

    def cond(c):
        return c[0] < cnt

    def body(c):
        start, cur = c
        chunk = jax.lax.dynamic_slice(idx_pad, (start,), (cap,))
        return start + jnp.int32(cap), apply_chunk(chunk, cur)

    _, carry = jax.lax.while_loop(cond, body, (jnp.int32(0), carry))
    return carry


def _round_shared(g: Graph, cfg: SSSPConfig, state: SSSPState,
                  f_idx: jax.Array, f_cnt: jax.Array,
                  prims: backends.Primitives, warm: bool = False):
    """One bulk-synchronous round over ``[B, n]`` lanes sharing ONE
    compacted union frontier — the batch-aware sibling of ``_round``.

    Same rules, same ordering, bitwise-identical per-lane results; the
    differences are purely in how each pass is executed:

    * **Step-1 relax** gathers the shared buffer ``f_idx`` (the union of
      every lane's fresh vertices) once and scatter-mins per lane
      (``prims.relax_frontier_b``).  A union vertex that is not fresh
      for some lane only re-sends offers that lane already min-folded —
      value-identical, hence bitwise-neutral.  The overflow predicate is
      a SCALAR (one shared count), so the dense fallback stays a real
      ``lax.cond`` branch even though the lanes are batched — the exact
      failure mode of vmapping ``_round`` (batched predicate -> select
      -> both branches every round) that this body exists to avoid.
    * **inWeight_nf** is an incremental carry (``state.in_w_nf``): valid
      for round-start ``fixed`` by induction, refreshed end-of-round
      only at out-neighbours of vertices whose fixed bit flipped
      (full in-neighbourhood recompute per target via ``prims.in_min_at``
      — a min is order-independent, so recompute-at-a-superset is exact).
    * **C-propagation** is cone-bounded: ``c_fix`` carries the
      fixed-source half ``min_{u fixed} D[u] + w``; non-cone vertices
      get the closed form ``max(C, min(c_fix, minD + inWeight_nf))``
      (their non-fixed in-sources all sit exactly at ``C == minD`` after
      the Lemma-7 lift, and their in-sources' fixed bits are unchanged —
      both guaranteed by routing every violator through the cone), and
      cone vertices — out-neighbours of flipped-bit sources and of
      sources with ``C > minD`` — get a full Eqn-(1) recompute.
    * The three maintenance sweeps run through ``_chunked_apply``:
      wavefront-proportional with NO dense branch in the program at all.

    Returns ``(state, fresh)`` with ``fresh`` the per-lane bool[B, n]
    next-round frontier mask; the driver unions it, compacts once, and
    select-freezes finished lanes (mirroring ``vmap``-of-``while_loop``
    batching semantics so per-lane round counts stay bitwise).
    """
    D, C, fixed = state.D, state.C, state.fixed          # [B, n]
    cap = prims.frontier_cap
    B = D.shape[0]

    # --- Step 1: shared-buffer D relaxation --------------------------
    with _scope("sssp.relax"):
        if cfg.label_correcting:
            relax_src = D < INF
        else:
            relax_src = fixed
        if cap >= g.n:
            overflow = jnp.bool_(False)
            D_relax = prims.relax_frontier_b(D, f_idx, relax_src)
        else:
            overflow = f_cnt > cap  # scalar: a real branch under batching
            D_relax = jax.lax.cond(
                overflow,
                lambda: jax.vmap(prims.relax)(D, relax_src),
                lambda: prims.relax_frontier_b(D, f_idx, relax_src))
    with _scope("sssp.count"):
        u = jnp.minimum(f_idx, g.n - 1)
        deg = jnp.where(relax_src, g.out_deg[None, :], 0)[:, u]
        sparse_edges = jnp.sum(jnp.where((f_idx < g.n)[None, :], deg, 0),
                               axis=1, dtype=jnp.int32)
        edges = state.edges + jnp.where(overflow, jnp.int32(g.e_pad),
                                        sparse_edges)

    in_w_nf = state.in_w_nf   # invariant: == in_weight_nf(~round-start fixed)
    cfix_stale = state.cfix_stale
    if warm:
        with _scope("sssp.fix"):
            improved = fixed & (D_relax < D)
            fixed = fixed & ~improved
            C = jnp.where(improved, 0.0, C)
            if cfix_stale is not None:
                # an un-fixed vertex leaves the fixed-source set (and its
                # D is about to drop): its out-neighbours' c_fix is stale.
                cfix_stale = cfix_stale | improved
    with _scope("sssp.relax"):
        D = jnp.where(~fixed, jnp.minimum(D, D_relax), D)
    explored = fixed

    # --- Step 2: per-lane reductions + fixing rules ------------------
    with _scope("sssp.fix"):
        discovered = D < INF
        active = discovered & ~fixed
        minD = jax.vmap(prims.masked_min)(D, active)          # [B]
        new_fix = jnp.zeros_like(fixed)
        rule_counts = []

        def count(mask):
            rule_counts.append(jnp.sum(mask & active & ~new_fix, axis=1,
                                       dtype=jnp.int32))
            return mask

        if "min" in cfg.rules:
            new_fix = new_fix | count(active & (D <= minD[:, None]))
        else:
            rule_counts.append(jnp.zeros((B,), jnp.int32))
        if "pred" in cfg.rules:
            has_nf_pred = ~jnp.isinf(in_w_nf)
            new_fix = new_fix | count(active & ~has_nf_pred)
        else:
            rule_counts.append(jnp.zeros((B,), jnp.int32))
        if "in" in cfg.rules:
            new_fix = new_fix | count(active
                                      & (D <= minD[:, None] + in_w_nf))
        else:
            rule_counts.append(jnp.zeros((B,), jnp.int32))
        if "out" in cfg.rules:
            threshold = jax.vmap(prims.masked_min)(
                D + g.out_weight[None, :], active)
            new_fix = new_fix | count(active & (D <= threshold[:, None]))
        else:
            rule_counts.append(jnp.zeros((B,), jnp.int32))

        fixed1 = fixed | new_fix

    # --- Step 3: cone-bounded C update (Lemma 7 lift + Eqn (1)) ------
    if "lb" in cfg.rules:
        with _scope("sssp.lb"):
            # (a) c_fix maintenance: recompute at out-neighbours of every
            # source whose fixed bit flipped since the last maintenance.
            stale_src = cfix_stale | new_fix
            s_idx, s_cnt = _compact_frontier(
                jnp.any(stale_src, axis=0), g.n, g.n)
            c_fix = state.c_fix

            def cfix_chunk(chunk, cf):
                tgts = prims.out_nbrs(chunk)            # [cap, max_out]
                vals = prims.in_min_at(D, tgts, fixed1)  # [B, cap, max_out]
                return cf.at[:, tgts].set(vals, mode="drop")

            c_fix = _chunked_apply(cfix_chunk, s_idx, s_cnt, cap, c_fix)

            # (b) lift, then propagate lower bounds through the cone only
            C = jnp.where(fixed1, D, jnp.maximum(C, minD[:, None]))
            for _ in range(cfg.c_prop_iters):
                prop_src = stale_src | (~fixed1 & (C > minD[:, None]))
                p_idx, p_cnt = _compact_frontier(
                    jnp.any(prop_src, axis=0), g.n, g.n)
                # non-cone closed form (exact off the cone — see docstring)
                base = jnp.minimum(c_fix, minD[:, None] + in_w_nf)
                C_new = jnp.where(~fixed1, jnp.maximum(C, base), C)
                C_pre = C

                def prop_chunk(chunk, cn, C_pre=C_pre):
                    tgts = prims.out_nbrs(chunk)
                    cin = prims.in_min_at(C_pre, tgts, None)  # all sources
                    tc = jnp.minimum(tgts, g.n - 1)
                    cur = C_pre[:, tc]
                    nf = jnp.where(fixed1, 0, 1)[:, tc] > 0  # no bool gather
                    upd = nf & (tgts < g.n)[None]
                    val = jnp.where(upd, jnp.maximum(cur, cin), cur)
                    return cn.at[:, tgts].set(val, mode="drop")

                C = _chunked_apply(prop_chunk, p_idx, p_cnt, cap, C_new)

        with _scope("sssp.fix"):
            fix_lb = ~fixed1 & discovered & (C >= D)
            rule_counts.append(jnp.sum(fix_lb, axis=1, dtype=jnp.int32))
            fixed2 = fixed1 | fix_lb
        with _scope("sssp.lb"):
            C = jnp.where(fixed2, D, C)
        cfix_stale = fix_lb   # applied at the NEXT round's maintenance
    else:
        rule_counts.append(jnp.zeros((B,), jnp.int32))
        fixed2 = fixed1
        with _scope("sssp.lb"):
            C = jnp.where(fixed2, D, C)
        c_fix = state.c_fix

    # --- incremental inWeight_nf refresh (restores the invariant for
    # the next round's round-start fixed = fixed2) --------------------
    if in_w_nf is not None:
        with _scope("sssp.inw"):
            stale2 = state.fixed ^ fixed2     # every bit flip this round
            w_idx, w_cnt = _compact_frontier(
                jnp.any(stale2, axis=0), g.n, g.n)

            def inw_chunk(chunk, iw):
                tgts = prims.out_nbrs(chunk)
                vals = prims.in_min_at(None, tgts, ~fixed2)   # min weight
                return iw.at[:, tgts].set(vals, mode="drop")

            in_w_nf = _chunked_apply(inw_chunk, w_idx, w_cnt, cap, in_w_nf)

    # --- next-round frontier mask (same freshness law as ``_round``) -
    with _scope("sssp.frontier"):
        if cfg.label_correcting:
            fresh = D != state.D
        else:
            fresh = fixed2 & (~state.fixed | (D != state.D))
    with _scope("sssp.count"):
        new_state = SSSPState(
            D=D, C=C, fixed=fixed2, explored=explored,
            round=state.round + 1,
            fixed_by=state.fixed_by + jnp.stack(rule_counts, axis=-1),
            f_idx=None, f_cnt=None, edges=edges,
            in_w_nf=in_w_nf, c_fix=c_fix, cfix_stale=cfix_stale)
    return new_state, fresh


def _attach_carries(g: Graph, cfg: SSSPConfig, prims, state: SSSPState):
    """Seed the shared-frontier round carries onto a freshly-initialized
    ``[B, n]`` state.  These are init-region dense reductions — they run
    ONCE per solve, outside the round loop, which is why the hot-region
    dense-pass budgets don't see them."""
    B = state.D.shape[0]
    need_inw = (("in" in cfg.rules) or ("pred" in cfg.rules)
                or ("lb" in cfg.rules))
    use_lb = "lb" in cfg.rules
    with _scope("sssp.init"):
        in_w_nf = (jax.vmap(prims.in_weight_nf)(~state.fixed) if need_inw
                   else None)
        c_fix = (jax.vmap(prims.relax)(state.D, state.fixed) if use_lb
                 else None)
        cfix_stale = jnp.zeros_like(state.fixed) if use_lb else None
        return dataclasses.replace(
            state, f_idx=None, f_cnt=None,
            edges=jnp.zeros((B,), jnp.int32),
            in_w_nf=in_w_nf, c_fix=c_fix, cfix_stale=cfix_stale)


def _strip_carries(state: SSSPState) -> SSSPState:
    return dataclasses.replace(state, in_w_nf=None, c_fix=None,
                               cfix_stale=None)


def _frontier_fixpoint(g: Graph, cfg: SSSPConfig, prims,
                       state: SSSPState, f_idx: jax.Array, f_cnt: jax.Array,
                       max_rounds: int, targets=None,
                       warm: bool = False) -> SSSPState:
    """Shared-frontier ``while_loop`` driver over ``[B, n]`` lanes.

    The carry is ``(state, f_idx, f_cnt)`` with the frontier buffer
    SHARED (one union compaction and one gather per round).  Lane
    liveness replicates exactly what ``vmap`` does to a batched
    ``while_loop`` — run while ANY lane's ``_cond`` holds, select-freeze
    the carries of finished lanes — so per-lane rounds, fixed_by, and
    targeted early exit are bitwise-identical to the vmapped dense path.
    """
    B = state.D.shape[0]
    cap = prims.frontier_cap

    def lane_go(st):
        with _scope("sssp.freeze"):
            active = (st.D < INF) & ~st.fixed
            pending = st.fixed & ~st.explored
            go = ((jnp.any(active, axis=1) | jnp.any(pending, axis=1))
                  & (st.round < max_rounds))
            if targets is not None:
                t = jnp.maximum(targets, 0)
                settled = jnp.where(st.fixed & st.explored, 1, 0)  # no bool
                t_done = (targets >= 0) & (settled[jnp.arange(B), t] > 0)
                go = go & ~t_done
            return go

    def cond(carry):
        st, _, _ = carry
        go = lane_go(st)
        with _scope("sssp.freeze"):
            return jnp.any(go)

    def body(carry):
        st, fi, fc = carry
        go = lane_go(st)
        st2, fresh = _round_shared(g, cfg, st, fi, fc, prims, warm=warm)

        def sel(new, old):
            keep = go.reshape((B,) + (1,) * (new.ndim - 1))
            return jnp.where(keep, new, old)

        with _scope("sssp.freeze"):
            st3 = jax.tree.map(sel, st2, st)
        with _scope("sssp.frontier"):
            union = jnp.any(fresh & go[:, None], axis=0)
            nfi, nfc = _compact_frontier(union, cap, g.n)
        return st3, nfi, nfc

    state, _, _ = jax.lax.while_loop(cond, body, (state, f_idx, f_cnt))
    return state


def _solve_frontier(g: Graph, cfg: SSSPConfig, sources: jax.Array,
                    prims, C0=None, targets=None) -> SSSPState:
    """Batched frontier solve: B lanes, ONE shared union frontier.

    ``sources`` int32[B]; ``C0`` float32[B, n] or None; ``targets``
    int32[B] (sentinel -1 = untargeted lane) or None.  Returns a state
    with [B, ...] leaves, engine-internal carries stripped.  The initial
    buffer is the union of the lane sources — label-setting round 1
    relaxes nothing, and label-correcting lanes mask foreign sources out
    via ``relax_src``, so the union seed is bitwise-neutral.
    """
    cap = prims.frontier_cap
    if C0 is None:
        state = jax.vmap(lambda s: _init_state(g, s))(sources)
    else:
        state = jax.vmap(lambda s, c: _init_state(g, s, c))(sources, C0)
    state = _attach_carries(g, cfg, prims, state)
    with _scope("sssp.init"):
        src_mask = jnp.zeros((g.n,), bool).at[sources].set(True)
        f_idx, f_cnt = _compact_frontier(src_mask, cap, g.n)
    max_rounds = cfg.max_rounds or g.n + 2
    tgt = targets if cfg.early_exit else None
    state = _frontier_fixpoint(g, cfg, prims, state, f_idx, f_cnt,
                               max_rounds, targets=tgt)
    return _strip_carries(state)


def _solve_warm_frontier(g: Graph, cfg: SSSPConfig, prev_D, prev_fixed,
                         seeds, pure_increase, prims, dec_src=None):
    """Batched warm re-solve on the shared union frontier.

    Per-lane taint cones and warm states come from the same
    ``_init_state_warm`` the dense path uses (vmapped, minus its
    frontier seeding); the shared buffer seeds from the UNION of the
    per-lane ``_warm_seed_mask``s — a superset of each lane's seed set,
    and every extra vertex is a fixed one whose offers that lane already
    folded (no-op under min), so round 1 stays bitwise.  ``dec_src`` is
    lane-independent (tails of decreased delta edges).  Returns
    ``(state, sweeps int32[B], taint bool[B, n])``.
    """
    cap = prims.frontier_cap

    def init_one(D0, F0, sd, pure):
        return _init_state_warm(g, D0, F0, sd, pure, None, None)

    state, sweeps, taint = jax.vmap(init_one)(
        prev_D, prev_fixed, seeds, pure_increase)
    state = _attach_carries(g, cfg, prims, state)
    with _scope("sssp.taint"):
        seed = jax.vmap(
            lambda t, f, d: _warm_seed_mask(g, t, f, d, dec_src))(
                taint, state.fixed, state.D)
        f_idx, f_cnt = _compact_frontier(jnp.any(seed, axis=0), cap, g.n)
    max_rounds = (2 * cfg.max_rounds) if cfg.max_rounds else 2 * g.n + 4
    state = _frontier_fixpoint(g, cfg, prims, state, f_idx, f_cnt,
                               max_rounds, warm=True)
    return _strip_carries(state), sweeps, taint


def _cond(state: SSSPState, max_rounds: int, target=None):
    """Keep-going predicate.  ``target`` (python None, or an int32 scalar
    with sentinel ``-1`` = none, possibly traced) enables goal-directed
    early exit: once the target is fixed (D[target] certified exact by
    the fixing-rule lemmas) AND explored (its out-edges relaxed at final
    D), the remaining rounds can no longer change dist[target] — stop.
    An unreachable target is never discovered, so the loop falls back to
    the normal drain-to-fixpoint termination."""
    with _scope("sssp.freeze"):
        active = (state.D < INF) & ~state.fixed
        pending = state.fixed & ~state.explored  # fixed but not yet relaxed
        go = ((jnp.any(active) | jnp.any(pending))
              & (state.round < max_rounds))
        if target is not None:
            t = jnp.maximum(target, 0)       # clamp sentinel for the gather
            # an int32 gather: bool gathers under vmap miscompiled on TPU
            # v5e (backends.segment_prims)
            settled = jnp.where(state.fixed & state.explored, 1, 0)
            t_done = (target >= 0) & (settled[t] > 0)
            go = go & ~t_done
        return go


def _solve(g: Graph, cfg: SSSPConfig, source,
           prims: backends.Primitives | None = None,
           C0=None, target=None) -> SSSPState:
    """while_loop to fixpoint (or to ``target`` fixed, when given);
    ``source``/``target``/``C0`` may all be traced (vmap-able).

    Batch-capable frontier ``prims`` (``relax_frontier_b`` set) route to
    the shared-frontier driver at B=1: single solves then run the very
    round body batches run — incremental inWeight_nf, cone-bounded
    C-propagation — not just the sparse relax."""
    if getattr(prims, "relax_frontier_b", None) is not None:
        src = jnp.asarray(source, jnp.int32).reshape((1,))
        c0 = None if C0 is None else C0.reshape((1, -1))
        tgt = (None if target is None
               else jnp.asarray(target, jnp.int32).reshape((1,)))
        st = _solve_frontier(g, cfg, src, prims, C0=c0, targets=tgt)
        return jax.tree.map(lambda x: x[0], st)
    state = _init_state(g, source, C0, prims)
    max_rounds = cfg.max_rounds or g.n + 2
    tgt = target if cfg.early_exit else None
    return jax.lax.while_loop(
        lambda s: _cond(s, max_rounds, tgt),
        partial(_round, g, cfg, prims=prims), state)


# jit with the graph as a traced pytree (weights/topology can change without
# recompiling as long as n/e_pad match) and the SOURCE TRACED as well — k
# distinct sources on one graph shape share a single compilation.
@partial(jax.jit, static_argnames=("cfg",))
def _run_traced_graph(g: Graph, cfg: SSSPConfig, source) -> SSSPState:
    return _solve(g, cfg, source)


@partial(jax.jit, static_argnames=("cfg",))
def _run_traced_ell(g: Graph, ell, cfg: SSSPConfig, source) -> SSSPState:
    return _solve(g, cfg, source,
                  prims=backends.ell_prims(g, ell, cfg.use_pallas))


def run_sssp(g: Graph, source: int = 0,
             cfg: SSSPConfig = SP4_CONFIG) -> SSSPResult:
    """Run the engine under jit (lax.while_loop).

    Compatibility shim — prefer ``repro.sssp.Solver`` which amortizes
    prep/compilation across sources and batches them.
    """
    state = _run_traced_graph(g, cfg, jnp.int32(source))
    return SSSPResult(
        dist=state.D, C=state.C, fixed=state.fixed,
        rounds=int(state.round), fixed_by=_fixed_by_dict(state.fixed_by),
        source=int(source), graph=g)


def run_sssp_ell(g: Graph, ell, source: int = 0,
                 cfg: SSSPConfig = SP4_CONFIG) -> SSSPResult:
    """Engine rounds on the dense ELL layout via kernels/ops.

    Compatibility shim over the ELL backend primitives — the SAME
    ``_round``/``lax.while_loop`` program as ``run_sssp``, with every
    per-round reduction one call of the fused relax kernel
    (min over in-edges of x[src]+w, masked):
      D_relax  = relax(D, mask=relax_src)
      inW_nf   = relax(0, mask=~fixed)        (x=0 -> plain min weight)
      c_in     = relax(C, mask=all)
      pred     = via masked weight min == inf (no non-fixed in-edge)
    ``cfg.use_pallas=True`` selects the Pallas kernels (TPU deployment
    path); the jnp oracle otherwise.
    """
    state = _run_traced_ell(g, ell, cfg, jnp.int32(source))
    return SSSPResult(
        dist=state.D, C=state.C, fixed=state.fixed, rounds=int(state.round),
        fixed_by=_fixed_by_dict(state.fixed_by), source=int(source), graph=g)


def run_sssp_traced(g: Graph, source: int = 0,
                    cfg: SSSPConfig = SP4_CONFIG,
                    max_rounds: int | None = None) -> SSSPResult:
    """Eager (python-loop) execution recording a per-round trace.

    The trace is the benchmark harness's data source: per-round counts of
    vertices fixed by each rule, minD, and invariant checks (C <= cost <= D,
    monotonicity) are asserted by the property tests.
    """
    state = _init_state(g, source)
    limit = max_rounds or cfg.max_rounds or g.n + 1
    trace = []
    round_fn = jax.jit(partial(_round, g, cfg))
    prev_fb = np.zeros(5, np.int64)
    while bool(np.asarray(_cond(state, limit))):
        prev_D = np.asarray(state.D)
        prev_C = np.asarray(state.C)
        state = round_fn(state)
        fb = np.asarray(state.fixed_by, np.int64)
        trace.append(dict(
            round=int(state.round),
            n_fixed=int(np.asarray(jnp.sum(state.fixed))),
            fixed_by_round={r: int(c) for r, c in
                            zip(_RULE_ORDER, fb - prev_fb)},
            minD=float(np.min(np.where(~np.asarray(state.fixed)
                                       & (prev_D < np.inf), prev_D, np.inf),
                              initial=np.inf)),
            D=np.asarray(state.D).copy(),
            C=np.asarray(state.C).copy(),
            prev_D=prev_D, prev_C=prev_C,
        ))
        prev_fb = fb
    return SSSPResult(
        dist=state.D, C=state.C, fixed=state.fixed, rounds=int(state.round),
        fixed_by=_fixed_by_dict(state.fixed_by), trace=trace,
        source=int(source), graph=g)
