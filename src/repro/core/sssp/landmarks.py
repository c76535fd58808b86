"""Landmark (ALT-style) preprocessing: goal-directed lower-bound seeds.

The paper's engine maintains lower bounds ``C`` alongside upper bounds
``D`` — but every cold solve starts from the trivial ``C = 0``, so the
lb rule (fix when ``C == D``) only fires once the in-graph Eqn-(1)
propagation has caught up.  Landmarks give the lb rule a head start:
precompute exact distance tables from/to a few well-spread vertices, and
the triangle inequality turns them into *non-trivial initial lower
bounds* for any query source ``s``:

    d(s, v)  >=  d(L, v) - d(L, s)        (forward table, d(L, ·))
    d(s, v)  >=  d(s, L) - d(v, L)        (backward table, d(·, L))

    C0[v] = max(0, max_L(d(L,v) - d(L,s)), max_L(d(s,L) - d(v,L)))

This is the classic ALT preprocessing (Goldberg & Harrelson) recast into
the paper's dual-bound machinery — instead of steering a priority queue,
the bounds are fed straight into ``engine._init_state`` where the lb
rule consumes them, and combined with the traced ``target`` early exit
(``engine._cond``) they make point-to-point queries terminate rounds
before the full fixpoint.  This is the heuristic-search direction of
Yu et al. (arXiv:2506.19349) grafted onto Garg's criteria engine.

Construction uses only existing machinery: ``d(L, ·)`` rows are plain
``Solver`` solves from each landmark, ``d(·, L)`` rows are solves on the
transpose graph (:meth:`Graph.reverse`), and landmark selection is the
farthest-point heuristic driven by the same solver.

Dynamic graphs: the tables are just ``k`` more tracked sources.
:meth:`LandmarkIndex.apply_delta` routes a :class:`GraphDelta` through
the owning forward ``DynamicSolver`` (shared mode) and a private reverse
``DynamicSolver`` (the delta's edge indices remapped through the
precomputed forward→reverse permutation), warm-refreshing the tables.
With ``refresh=False`` the tables go stale — still *valid* lower bounds
while every delta since the last refresh only increased weights (old
distances only under-estimate a grown metric), so seeding stays on; the
first decrease flips ``seed_ok`` off and :meth:`seed` degrades to "no
seed" until the next refresh.  Targeted solves stay exact either way —
seeding only ever accelerates fixing when the bounds are valid.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, HostGraph
from repro.core.sssp import spans
from repro.core.sssp.engine import SP4_CONFIG, SSSPConfig
from repro.core.sssp.dynamic import DynamicSolver, GraphDelta, make_delta


def seed_lower_bounds(d_from: jax.Array, d_to: jax.Array,
                      source) -> jax.Array:
    """jit-able ALT seed: float32[n] lower bounds on d(source, ·).

    ``d_from[L, v] = d(landmark_L, v)`` and ``d_to[L, v] = d(v,
    landmark_L)`` are the [k, n] tables; ``source`` may be traced — one
    broadcast max over the tables, no per-query host work.

    +inf entries are *information*, not failure: ``d(L,v) = inf`` with
    ``d(L,s)`` finite proves v unreachable from s (a path s→v would
    extend L→s→v), so the bound +inf is valid; likewise ``d(s,L) = inf``
    with ``d(v,L)`` finite.  Only inf−inf (landmark sees neither or
    both endpoints at inf) carries no information and drops to −inf
    before the max.
    """
    ds = d_from[:, source][:, None]   # [k, 1]  d(L, s)
    ts = d_to[:, source][:, None]     # [k, 1]  d(s, L)
    fwd = d_from - ds                 # d(L, v) − d(L, s)
    bwd = ts - d_to                   # d(s, L) − d(v, L)
    fwd = jnp.where(jnp.isnan(fwd), -jnp.inf, fwd)
    bwd = jnp.where(jnp.isnan(bwd), -jnp.inf, bwd)
    best = jnp.max(jnp.maximum(fwd, bwd), axis=0)
    return jnp.maximum(best, 0.0).astype(jnp.float32)


def select_landmarks(solver, k: int, *, seed: int = 0,
                     first: int | None = None) -> np.ndarray:
    """Farthest-point landmark selection via the existing Solver.

    Greedy: start from ``first`` (default: random), then repeatedly add
    the vertex maximizing the distance to its nearest already-chosen
    landmark (finite distances only — an unreachable vertex is "far"
    from everything and would hoard picks; if nothing reachable remains,
    fall back to a random unused vertex so disconnected components still
    get coverage).  k solves, one compiled program.
    """
    n = solver.graph.n
    k = max(1, min(int(k), n))
    rng = np.random.default_rng(seed)
    lms = [int(first) if first is not None else int(rng.integers(n))]
    d_min = np.asarray(solver.solve(lms[0]).dist, np.float64)
    while len(lms) < k:
        cand = np.where(np.isfinite(d_min), d_min, -1.0)
        cand[np.asarray(lms)] = -1.0
        nxt = int(np.argmax(cand))
        if cand[nxt] <= 0.0:
            unused = np.setdiff1d(np.arange(n), np.asarray(lms))
            if unused.size == 0:
                break
            nxt = int(rng.choice(unused))
        lms.append(nxt)
        d_min = np.minimum(d_min,
                           np.asarray(solver.solve(nxt).dist, np.float64))
    return np.asarray(lms, np.int32)


@dataclasses.dataclass(frozen=True)
class ReselectPolicy:
    """When to ACT on :meth:`LandmarkIndex.needs_reselect`.

    The shipped hook is a metric; this is the policy: re-run
    farthest-point selection on the *drifted* graph when observed seed
    tightness says the old landmark positions stopped explaining the
    metric — with cadence and hysteresis so the (k solves) rebuild cost
    is amortized, never thrashed:

      * ``threshold`` — trigger level for mean ``C0[t]/dist[t]``
        tightness (below = landmarks drifting).
      * ``min_observations`` — hysteresis: a reselect resets the
        tightness accumulator, so at least this many served-query
        ratios must accumulate again before the trigger can re-arm.
        (Also guards cold starts: no reselect off a handful of
        unlucky queries.)
      * ``cooldown_deltas`` — cadence: at least this many graph deltas
        must land between reselects (tightness can only have changed
        because the metric did; re-picking positions on an unchanged
        graph re-picks the same positions).
    """

    threshold: float = 0.5
    min_observations: int = 32
    cooldown_deltas: int = 1


class LandmarkIndex:
    """Landmark distance tables + seeded lower bounds over one graph.

    Parameters
    ----------
    graph:   device :class:`Graph` or :class:`HostGraph`.
    k:       number of landmarks (tables cost two [k, n] device arrays).
    solver:  optional *shared* forward :class:`DynamicSolver` — the one
             the serving layer already runs.  The landmark solves are
             then tracked sources of that solver ("k more sources") and
             ride its compiled warm-refresh programs through deltas.
             When omitted, the index owns a private forward solver.
    cfg/backend/seed: engine config, backend, selection RNG seed for the
             owned solvers (ignored for the forward side in shared mode).

    ``seed(source)`` / ``seed_batch(sources)`` return ``C0`` arrays for
    ``Solver.solve(source, target=t, C0=...)`` — or ``None`` when the
    tables can no longer vouch for validity (weight decrease without
    refresh), which callers pass through as "no seed".
    """

    def __init__(self, graph, k: int = 8, *, cfg: SSSPConfig = SP4_CONFIG,
                 backend: str = "segment", seed: int = 0,
                 solver: DynamicSolver | None = None):
        if isinstance(graph, HostGraph):
            graph = graph.to_device()
        if not isinstance(graph, Graph):
            raise TypeError(f"graph must be Graph/HostGraph, "
                            f"got {type(graph)!r}")
        self.k = max(1, min(int(k), graph.n))
        self._shared = solver is not None
        self._fwd = solver if solver is not None else DynamicSolver(
            graph, cfg, backend)
        self._rev = DynamicSolver(graph.reverse(), cfg, backend)
        # forward edge i (dst-sorted) sits at row rev_perm[i] of the
        # reverse graph's edge list: Graph.reverse() feeds build_graph in
        # forward-index order, which re-sorts stably by the new dst
        # (= forward src).  This is what remaps GraphDelta batches.
        e = graph.e
        order = np.argsort(np.asarray(graph.src[:e]), kind="stable")
        self._rev_perm = np.empty(e, np.int64)
        self._rev_perm[order] = np.arange(e)
        self._seed_one = jax.jit(seed_lower_bounds)
        self._seed_many = jax.jit(
            jax.vmap(seed_lower_bounds, in_axes=(None, None, 0)))
        self.d_from: jax.Array | None = None   # float32[k, n]  d(L, v)
        self.d_to: jax.Array | None = None     # float32[k, n]  d(v, L)
        self.stale = False
        self.seed_ok = True
        # seed-tightness telemetry (mean C0[target]/dist[target] over
        # served queries, fed by SSSPService): the re-selection signal.
        self._tight_sum = 0.0
        self._tight_cnt = 0
        self._select_seed = int(seed)
        # re-selection bookkeeping: deltas seen, reselects done, and the
        # delta count at the last reselect (the cadence clock).
        self.deltas_applied = 0
        self.reselects = 0
        self._deltas_at_reselect = 0
        self.landmarks = select_landmarks(self._fwd, self.k, seed=seed)
        self.refresh()

    # ------------------------------------------------------------------
    def refresh(self) -> None:
        """Recompute both tables on the solvers' current graphs.

        Warm-refreshed tracked states answer this without new cold
        solves when the deltas went through :meth:`apply_delta` with
        ``refresh=True``; otherwise the stale sources re-solve here.
        """
        lms = [int(v) for v in self.landmarks]
        self.d_from = jnp.asarray(self._fwd.resolve(lms).dist)
        self.d_to = jnp.asarray(self._rev.resolve(lms).dist)
        self._host_tables = None   # invalidate the estimate_pairs cache
        self.stale = False
        self.seed_ok = True

    def seed(self, source: int) -> jax.Array | None:
        """C0 float32[n] for one query source (None: seeding unsound)."""
        if not self.seed_ok:
            return None
        return self._seed_one(self.d_from, self.d_to, jnp.int32(source))

    def seed_batch(self, sources) -> jax.Array | None:
        """C0 float32[B, n] for a batch of sources (None: unsound)."""
        if not self.seed_ok:
            return None
        return self._seed_many(self.d_from, self.d_to,
                               jnp.asarray(sources, jnp.int32))

    def seed_pair(self, source: int, target: int) -> jax.Array | None:
        """float32[2, n] seeds for a bidirectional (s, t) solve.

        Row 0 lower-bounds ``d(source, ·)`` (the forward lane); row 1
        lower-bounds ``d(·, target)`` — i.e. distances from ``target``
        on the REVERSE graph, which is the same triangle-inequality
        bound with the two tables swapped:

            d(v, t) >= d(v, L) - d(t, L)   (the d(·,L) table as "from")
            d(v, t) >= d(L, t) - d(L, v)   (the d(L,·) table as "to")

        so the backward seed is ``seed_lower_bounds(d_to, d_from, t)``
        verbatim.  ``None`` when the tables can't vouch (same contract
        as :meth:`seed`).
        """
        if not self.seed_ok:
            return None
        fwd = self._seed_one(self.d_from, self.d_to, jnp.int32(source))
        bwd = self._seed_one(self.d_to, self.d_from, jnp.int32(target))
        return jnp.stack([fwd, bwd])

    def estimate_pairs(self, pairs) -> np.ndarray | None:
        """float64[B] seeded lower bound ``C0[t]`` per (source, target).

        The scalar slice of :meth:`seed_batch` a query's own target
        sees, computed host-side from the table columns (two [k, B]
        gathers — no per-pair [n] vector is built).  The serving layer
        sorts queued targeted queries by this at enqueue time, so
        vmapped waves group short queries with short batches instead of
        every lane paying the slowest one's rounds.  ``None`` when the
        tables can't vouch for their bounds (same contract as ``seed``).
        """
        if not self.seed_ok or not len(pairs):
            return None
        s = np.asarray([p[0] for p in pairs], np.int64)
        t = np.asarray([p[1] for p in pairs], np.int64)
        # one device pull per table generation, not per serve wave.  The
        # cache is keyed by the IDENTITY of the live device table (not
        # just cleared in refresh()): any path that swaps d_from/d_to —
        # refresh, reselect, a future direct assignment — invalidates it
        # by construction, so a graph-version bump can never leave stale
        # host tables feeding the planner's estimates.
        if self._host_tables is None or self._host_tables[0] is not self.d_from:
            d_from, d_to = spans.fetch((self.d_from, self.d_to))
            self._host_tables = (self.d_from, d_from.astype(np.float64),
                                 d_to.astype(np.float64))
        df, dt = self._host_tables[1:]  # [k, n] each
        with np.errstate(invalid="ignore"):
            fwd = df[:, t] - df[:, s]              # [k, B]
            bwd = dt[:, s] - dt[:, t]
        fwd = np.where(np.isnan(fwd), -np.inf, fwd)
        bwd = np.where(np.isnan(bwd), -np.inf, bwd)
        return np.maximum(np.maximum(fwd, bwd).max(axis=0), 0.0)

    # ------------------------------------------------------------------
    def record_tightness(self, ratios) -> None:
        """Accumulate observed ``C0[target] / dist[target]`` ratios.

        Fed by the serving layer for queries it answered with seeded
        targeted solves (finite, nonzero distances only).  1.0 means the
        seed was already exact; drifting toward 0 means the landmarks
        have stopped explaining the metric (accumulated weight deltas)
        and re-selection would pay.
        """
        ratios = np.asarray(ratios, np.float64).ravel()
        ratios = ratios[np.isfinite(ratios)]
        if ratios.size:
            self._tight_sum += float(ratios.sum())
            self._tight_cnt += int(ratios.size)

    def tightness(self) -> float | None:
        """Mean observed seed tightness (None before any observation)."""
        if not self._tight_cnt:
            return None
        return self._tight_sum / self._tight_cnt

    @property
    def tightness_count(self) -> int:
        """Number of ratios behind :meth:`tightness`."""
        return self._tight_cnt

    def needs_reselect(self, threshold: float = 0.5) -> bool:
        """Re-selection hook: has mean seed tightness degraded below
        ``threshold``?  Policy-free — the caller decides when to act
        (and on True would typically re-run ``select_landmarks`` +
        :meth:`refresh`, then reset via :meth:`reset_tightness`).
        Never True without observations, or while seeding is already
        disabled (``seed_ok=False`` has its own recovery: refresh)."""
        m = self.tightness()
        return bool(self.seed_ok and m is not None and m < float(threshold))

    def reset_tightness(self) -> None:
        self._tight_sum = 0.0
        self._tight_cnt = 0

    def reselect(self, *, seed: int | None = None) -> np.ndarray:
        """Re-run farthest-point selection on the CURRENT (drifted)
        graph and rebuild both tables.

        The selection solves run on the shared forward
        :class:`DynamicSolver`, so they are *tracked* — the
        :meth:`refresh` that follows serves the new forward rows
        straight from those tracked states (no second solve), and
        subsequent deltas warm-refresh the new rows like any other
        tracked source.  Resets the tightness accumulator (the new
        positions start with a clean signal) and re-enables seeding.
        Returns the new landmark array.
        """
        self.reselects += 1
        self._deltas_at_reselect = self.deltas_applied
        # vary the RNG stream per reselect so a tie-heavy graph doesn't
        # re-pick the exact drifted set out of first-pick luck.
        sel_seed = (self._select_seed + 7919 * self.reselects
                    if seed is None else int(seed))
        self.landmarks = select_landmarks(self._fwd, self.k, seed=sel_seed)
        self.refresh()
        self.reset_tightness()
        return self.landmarks

    def maybe_reselect(self, policy: ReselectPolicy | float) -> bool:
        """Act on :meth:`needs_reselect` under a :class:`ReselectPolicy`
        (a bare float is shorthand for ``ReselectPolicy(threshold=f)``).

        Fires — and returns True — only when ALL of: enough tightness
        observations accumulated since the last reselect (hysteresis:
        :meth:`reselect` resets the accumulator), the mean is below the
        threshold, and at least ``cooldown_deltas`` graph deltas landed
        since the last reselect (cadence: an unchanged metric would
        re-pick the same positions).
        """
        if not isinstance(policy, ReselectPolicy):
            policy = ReselectPolicy(threshold=float(policy))
        if self._tight_cnt < policy.min_observations:
            return False
        if (self.deltas_applied - self._deltas_at_reselect
                < policy.cooldown_deltas):
            return False
        if not self.needs_reselect(policy.threshold):
            return False
        self.reselect()
        return True

    # ------------------------------------------------------------------
    def reverse_delta(self, delta: GraphDelta) -> GraphDelta:
        """The same weight updates, as a delta on the transpose graph."""
        kk = delta.k
        idx = np.asarray(delta.edge_idx)[:kk]
        w = np.asarray(delta.new_w)[:kk]
        return make_delta(self._rev.graph, self._rev_perm[idx], w)

    def apply_delta(self, delta: GraphDelta, *,
                    refresh: bool = True) -> dict:
        """Keep the index coherent with a forward-graph weight delta.

        In shared mode call this AFTER the owning solver's ``update``
        (the forward side is then already mutated and — if the landmarks
        were in its refresh list — warm-refreshed); in standalone mode
        the index updates its own forward solver too.  The reverse
        solver always updates here, through the remapped delta.

        ``refresh=False`` defers the table rebuild: the tables go stale,
        and stay usable as seeds only while no delta since the last
        refresh decreased a weight (stale exact distances of a
        weights-only-grew graph are still valid lower bounds); the first
        decrease disables seeding until :meth:`refresh`.  Returns the
        reverse solver's update stats (same counters as
        ``DynamicSolver.update``).
        """
        self.deltas_applied += 1
        lms = [int(v) for v in self.landmarks]
        want = lms if refresh else []
        rev_stats = self._rev.update(self.reverse_delta(delta), refresh=want)
        if not self._shared:
            self._fwd.update(delta, refresh=want)
        if refresh:
            self.refresh()
        else:
            self.stale = True
            if rev_stats["decreased"]:
                self.seed_ok = False
        return rev_stats
