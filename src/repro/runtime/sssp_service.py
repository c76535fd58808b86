"""SSSP query service: continuous batching over a compiled Solver.

The serving analogue of ``runtime/serve_loop.BatchServer``, for shortest
-path traffic instead of tokens: incoming ``(source, target)`` queries
are coalesced by source, deduplicated against an LRU cache of solved
sources, and the misses are batched into ``Solver.solve_batch`` calls —
one compiled program execution answers up to ``batch`` sources at once,
and every query against an already-solved source is a dictionary lookup.

The service runs on a :class:`~repro.core.sssp.dynamic.DynamicSolver`,
so the graph may change mid-flight: ``apply_delta`` applies a weight
delta, *warm-refreshes* the hottest sources through the compiled
incremental re-solve (instead of dropping the LRU), and version-stamps
the cache so every remaining entry goes stale atomically — a stale hit
is a miss, re-solved on demand against the new graph.

Goal-directed serving (``landmarks=``/``p2p=``): a ``Query(target=t)``
no longer pays for the whole distance vector — it takes the targeted
fast path (``Solver.solve_batch(..., targets=...)``), early-exiting each
lane once its own target is fixed, with lower bounds seeded from a
:class:`~repro.core.sssp.landmarks.LandmarkIndex`.  The partial results
this produces are admitted to the cache stamped ``partial=True``: they
answer later queries only for vertices their ``fixed`` mask certifies
exact, and they NEVER satisfy a full-vector lookup (``distances()`` /
``Query(target=None)``), so a partial entry cannot poison a full one.

Query-engine v2 (``planner=`` / ``bidirectional=`` / ``reselect=``):
instead of the fixed p2p pipeline, a :class:`WavePlanner` routes each
wave's misses to the cheapest engine path — full batched solve for
sources hogging a batch's worth of slots, bidirectional meet-in-the-
middle solves for the far tail of the landmark estimates, est-sorted
power-of-two targeted waves for the rest — with an EMA cost model fed
by observed per-query seconds.  Bidirectional answers land in a
version-stamped ``(source, target)`` pair cache (their forward lane is
also admitted ``partial=True``), and a :class:`ReselectPolicy` acts on
the drift signal: when seed tightness degrades past the threshold the
landmarks are re-selected on the drifted graph, restoring estimate
quality instead of just reporting its loss.

This is the amortization story of Kainer & Träff made concrete: the
engine's per-graph fixed costs (layout, compile) are paid once by the
Solver, the per-source costs are shared across a batch, the per-query
cost of a repeated source is ~zero — and now the per-*delta* cost is a
warm repair, not a cold restart, and the per-*target* cost is rounds
proportional to the goal, not the graph.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict

import numpy as np

from repro.analysis.contracts import contract
from repro.core.sssp import spans
from repro.core.sssp.bidirectional import BidirectionalSolver
from repro.core.sssp.engine import SP4_CONFIG, SSSPConfig, SSSPResult
from repro.core.sssp.dynamic import DynamicSolver, GraphDelta
from repro.core.sssp.landmarks import LandmarkIndex, ReselectPolicy
from repro.runtime.planner import WavePlan, WavePlanner


@dataclasses.dataclass
class Query:
    """One shortest-path request; answered in place by the service.

    ``target=None`` asks for the whole distance vector: the service
    attaches it as ``dist`` (float array over vertices) and leaves the
    scalar ``distance``/``path`` fields None.
    """

    source: int
    target: int | None = None     # None: whole distance vector wanted
    distance: float | None = None
    path: list[int] | None = None
    dist: np.ndarray | None = None  # filled for target=None queries
    done: bool = False


@contract(
    "service.rides_solver_routes",
    routes=(),
    composes=("segment.*", "*.targeted", "bidi.pair", "*.warm"),
    notes="The service compiles nothing of its own — every wave the "
          "planner emits executes a solver program (batched cold "
          "solves, targeted waves, bidirectional pair solves, warm "
          "refresh after apply_delta).  The gate checks composition: "
          "each of these route families must exist and must not FAIL, "
          "or the serving layer is riding a broken program.")
class SSSPService:
    """Continuous-batching SSSP server over one (mutable-weight) graph.

    Parameters mirror :class:`Solver`; ``batch`` is the number of source
    slots per solve (requests padded up to it reuse one compiled batch
    shape), ``cache_sources`` bounds the LRU of solved sources.

    Goal-directed serving:

    ``landmarks``
        ``int k`` builds a :class:`LandmarkIndex` with k landmarks
        SHARING this service's DynamicSolver (the landmark tables are k
        more tracked sources, warm-refreshed through deltas); a
        pre-built index is used as-is; ``None`` disables seeding.
    ``p2p``
        route ``Query(target=t)`` through targeted early-exit solves
        (default: on exactly when ``landmarks`` is given; ``p2p=True``
        alone gives early exit with trivial bounds).
    ``refresh_landmarks``
        eagerly rebuild the landmark tables on every ``apply_delta``
        (default).  ``False`` defers: stale tables keep seeding only
        while deltas are pure weight increases, and seeding drops after
        the first decrease until the index is refreshed.

    Query-engine v2:

    ``planner``
        ``True`` (or a pre-built :class:`WavePlanner`) routes each p2p
        wave's misses through the cost-based planner instead of the
        fixed targeted pipeline; route counts land in
        ``stats["planner_routes"]``.
    ``bidirectional``
        attach a :class:`BidirectionalSolver` (sharing this service's
        landmark index for two-lane seeds).  With the planner on it
        serves the planner's ``bidirectional`` route; without it, every
        scalar-target miss meets in the middle.
    ``reselect``
        a tightness threshold (float) or :class:`ReselectPolicy`: act
        on landmark drift by re-selecting landmark positions on the
        mutated graph (checked after every delta and every served
        wave).  ``None`` keeps re-selection off (metric-only, as
        before).

    Tracing: every wave runs under an ``sssp.serve`` span (``wave``,
    ``queries``) holding ``sssp.lookup`` (cache probes), ``sssp.plan``,
    one ``sssp.dispatch`` (``route``, ``lanes``) per solver call and
    ``sssp.answer``; every delta under ``sssp.apply_delta``
    (``version``) holding ``sssp.delta.update``, ``sssp.delta.bidi`` and
    ``sssp.delta.resolve``.  Every span inside carries the wave's or the
    delta's id, down through the solver facades' ``sssp.solve_batch``,
    and every blocking device->host read is an ``sssp.fetch``
    (:mod:`repro.core.sssp.spans`).  Counters in ``stats``, built from
    values already on the host: ``query_rounds`` (per query-path
    dispatch, its rounds, the most over its lanes), ``delta_rounds``
    (per delta, the warm program's rounds plus any cold or pair
    refresh's), ``delta_sweeps``, ``delta_tainted`` (the largest taint
    cone per delta) and ``host_syncs`` (``sssp.fetch`` reads while
    serving).
    """

    def __init__(self, graph, cfg: SSSPConfig = SP4_CONFIG,
                 backend: str = "auto", *, batch: int = 8,
                 cache_sources: int = 1024,
                 landmarks: int | LandmarkIndex | None = None,
                 p2p: bool | None = None, refresh_landmarks: bool = True,
                 landmark_seed: int = 0,
                 planner: bool | WavePlanner | None = None,
                 bidirectional: bool = False,
                 reselect: float | ReselectPolicy | None = None,
                 **solver_kw):
        self.solver = DynamicSolver(graph, cfg, backend, **solver_kw)
        self.batch = int(batch)
        self.cache_sources = max(1, int(cache_sources))
        # source -> (version at solve time, result, partial); entries
        # whose version trails the solver's are stale == misses; partial
        # entries only answer targets their fixed mask certifies.
        self._cache: OrderedDict[
            int, tuple[int, SSSPResult, bool]] = OrderedDict()
        # (source, target) -> (version, distance, path, lanes):
        # bidirectional answers, same staleness rule as the source
        # cache; `lanes` keeps the answer's two-lane (D, fixed) state so
        # a delta can warm re-solve hot pairs instead of dropping them.
        self._pairs: OrderedDict[
            tuple[int, int],
            tuple[int, float, list | None, tuple | None]] = OrderedDict()
        self.landmarks: LandmarkIndex | None = None
        if isinstance(landmarks, LandmarkIndex):
            self.landmarks = landmarks
        elif landmarks is not None:
            self.landmarks = LandmarkIndex(
                self.solver.graph, int(landmarks), cfg=self.solver.cfg,
                backend=backend if backend != "auto" else "segment",
                seed=landmark_seed, solver=self.solver)
        self.refresh_landmarks = bool(refresh_landmarks)
        self.planner: WavePlanner | None = None
        if isinstance(planner, WavePlanner):
            self.planner = planner
        elif planner:
            self.planner = WavePlanner()
        self._bidi: BidirectionalSolver | None = None
        if bidirectional:
            self._bidi = BidirectionalSolver(
                self.solver.graph, self.solver.cfg,
                landmarks=self.landmarks)
        # the v2 routes live on the p2p pipeline: asking for the planner
        # or the bidirectional solver opts scalar-target queries into it
        # even without landmarks (targeted waves then run unseeded).
        self.p2p = bool(self.landmarks is not None
                        or self.planner is not None
                        or self._bidi is not None
                        if p2p is None else p2p)
        self.reselect_policy: ReselectPolicy | None = None
        if isinstance(reselect, ReselectPolicy):
            self.reselect_policy = reselect
        elif reselect is not None:
            self.reselect_policy = ReselectPolicy(threshold=float(reselect))
        self._waves = 0
        self.stats = dict(queries=0, batches=0, sources_solved=0,
                          cache_hits=0, solve_seconds=0.0, deltas=0,
                          warm_refreshed=0,
                          p2p_solves=0, seed_tightness_mean=None,
                          seed_tightness_count=0, bidi_solves=0,
                          reselects=0, pair_warm_refreshed=0,
                          query_rounds=0, delta_rounds=0, delta_sweeps=0,
                          delta_tainted=0, host_syncs=0,
                          planner_routes=dict(cache=0, targeted=0,
                                              bidirectional=0, full=0,
                                              full_vector=0))

    # ------------------------------------------------------------------
    @property
    def version(self) -> int:
        """Graph version (number of deltas applied)."""
        return self.solver.version

    def _lookup(self, source: int,
                target: int | None = None) -> SSSPResult | None:
        """Fresh cached result usable for this request, else None.

        A full entry answers anything; a partial entry answers only a
        scalar ``target`` its ``fixed`` mask certifies exact — and never
        a full-vector request (``target=None``).
        """
        entry = self._cache.get(source)
        if entry is None:
            return None
        ver, res, partial = entry
        if ver != self.version:        # stale: solved on an older graph
            del self._cache[source]
            return None
        if partial:
            if target is None or not bool(spans.fetch(res.fixed[target])):
                return None            # keep the entry: other targets may hit
        self._cache.move_to_end(source)
        return res

    def _admit(self, source: int, res: SSSPResult, *,
               partial: bool = False) -> None:
        if partial and self._cached(source):
            return  # never downgrade a fresh full entry to a partial one
        self._cache[source] = (self.version, res, partial)
        self._cache.move_to_end(source)
        while len(self._cache) > self.cache_sources:
            self._cache.popitem(last=False)

    def _cached(self, source: int) -> bool:
        """Fresh FULL entry present (partial entries don't count)."""
        entry = self._cache.get(source)
        return (entry is not None and entry[0] == self.version
                and not entry[2])

    def _pair_lookup(self, source: int,
                     target: int) -> tuple[float, list | None] | None:
        """Fresh bidirectional pair-cache answer, else None."""
        entry = self._pairs.get((source, target))
        if entry is None:
            return None
        if entry[0] != self.version:
            del self._pairs[(source, target)]
            return None
        self._pairs.move_to_end((source, target))
        return entry[1], entry[2]

    def _pair_admit(self, source: int, target: int, distance: float,
                    path: list | None, lanes: tuple | None = None) -> None:
        self._pairs[(source, target)] = (self.version, distance, path, lanes)
        self._pairs.move_to_end((source, target))
        while len(self._pairs) > self.cache_sources:
            self._pairs.popitem(last=False)

    def _solve_missing(self, sources: list[int]) -> None:
        """Batch-solve sources not freshly cached, ``self.batch`` at a time."""
        missing = [s for s in dict.fromkeys(sources)
                   if not self._cached(s)]
        for at in range(0, len(missing), self.batch):
            chunk = missing[at: at + self.batch]
            padded = chunk + [chunk[-1]] * (self.batch - len(chunk))
            batch_res, dt = self._dispatch("full", padded, len(chunk))
            self.stats["solve_seconds"] += dt
            self.stats["batches"] += 1
            for i, s in enumerate(chunk):
                self._admit(s, batch_res[i])
            self.stats["sources_solved"] += len(chunk)

    def _dispatch(self, route: str, sources: list[int], lanes: int, *,
                  targets=None, C0=None):
        """One query-path ``solve_batch`` of ``lanes`` real lanes, run
        to completion: ``(result, seconds)``, its rounds counted."""
        with spans.span("sssp.dispatch", route=route, lanes=lanes):
            t0 = time.perf_counter()
            batch_res = self.solver.solve_batch(sources, targets=targets,
                                                C0=C0)
            spans.fetch(batch_res.dist)  # block: count device time honestly
            dt = time.perf_counter() - t0
        self.stats["query_rounds"] += int(np.max(batch_res.rounds))
        return batch_res, dt

    @staticmethod
    def _answer(q: Query, res: SSSPResult) -> None:
        """Fill ``q`` from ``res``: the whole vector, or the target's
        distance and, where it is reachable, the path."""
        if q.target is None:
            q.dist = spans.fetch(res.dist)
            q.distance = None
            q.path = None
        else:
            q.distance = float(spans.fetch(res.dist[q.target]))
            q.path = (res.path_to(q.target)
                      if np.isfinite(q.distance) else None)
        q.done = True

    # ------------------------------------------------------------------
    def apply_delta(self, delta: GraphDelta, *,
                    refresh_hot: int | None = None) -> dict:
        """Apply a weight delta; warm-refresh the hottest cached sources.

        The ``refresh_hot`` most-recently-used *fully*-cached sources
        (default: one solve batch's worth; 0 = refresh nothing eagerly;
        partial entries are skipped — there is no full state to repair)
        are re-solved eagerly through the DynamicSolver's compiled warm
        program and re-admitted fresh; the rest of the LRU stays
        resident but version-stamped stale, so it is re-solved lazily on
        next touch instead of being dropped.  The landmark index (if
        any) rides the same update: its forward tables are tracked
        sources of this solver, its reverse tables go through the
        remapped delta; with ``refresh_landmarks=False`` the tables go
        stale and seeding survives only pure-increase deltas.  Returns
        the solver's update stats.
        """
        with spans.ids(version=self.version + 1), \
                spans.span("sssp.apply_delta"):
            return self._apply_delta(delta, refresh_hot)

    def _apply_delta(self, delta: GraphDelta, refresh_hot) -> dict:
        k = self.batch if refresh_hot is None else int(refresh_hot)
        hot: list[int] = []
        if k > 0:   # newest-first walk for the k hottest FULL entries
            for s in reversed(self._cache):
                if len(hot) == k:
                    break
                if not self._cache[s][2]:
                    hot.append(s)
            hot.reverse()
        # the k hottest still-fresh pairs that carried their lane state:
        # they re-solve WARM through the bidi solver's update (collected
        # before the version bump makes every stamp stale)
        hot_pairs: list[tuple[int, int, object, object]] = []
        if self._bidi is not None and k > 0:
            for key in reversed(self._pairs):
                if len(hot_pairs) == k:
                    break
                ver, _, _, lanes = self._pairs[key]
                if ver == self.version and lanes is not None:
                    hot_pairs.append((key[0], key[1], lanes[0], lanes[1]))
            hot_pairs.reverse()
        eager_lm = self.landmarks is not None and self.refresh_landmarks
        lms = ([int(v) for v in self.landmarks.landmarks]
               if eager_lm else [])
        with spans.span("sssp.delta.update"):
            stats = self.solver.update(
                delta, refresh=list(dict.fromkeys(hot + lms)))
        self.stats["delta_rounds"] += (max(stats["warm_rounds"], default=0)
                                       + stats["cold_rounds"])
        self.stats["delta_sweeps"] += stats["sweeps"]
        self.stats["delta_tainted"] += max(stats["tainted"], default=0)
        if self.landmarks is not None:
            self.landmarks.apply_delta(delta, refresh=eager_lm)
        if self._bidi is not None:
            # both bidi lanes (graph + transpose, and any CSR views)
            # take the same delta, so its solves stay on this version —
            # and the hot pairs re-solve warm from their cached lanes,
            # re-admitted fresh (the pair-cache mirror of the hot-source
            # refresh above; the stale tail re-solves lazily).
            with spans.span("sssp.delta.bidi"):
                warm_out = self._bidi.update(delta, warm=hot_pairs)
                for (s, t), r in warm_out.items():
                    self._pair_admit(s, t, r.distance,
                                     r.path() if np.isfinite(r.distance)
                                     else None, lanes=(r.D, r.fixed))
                    self._admit(s, r.forward_result(), partial=True)
            self.stats["pair_warm_refreshed"] += len(warm_out)
            self.stats["delta_rounds"] += sum(r.rounds
                                              for r in warm_out.values())
        if hot:
            with spans.span("sssp.delta.resolve"):
                refreshed = self.solver.resolve(hot)  # tracked: no solves
                spans.fetch(refreshed.dist)
                for i, s in enumerate(hot):
                    self._admit(int(s), refreshed[i])
        self.stats["deltas"] += 1
        self.stats["warm_refreshed"] += stats["warm_refreshed"]
        self.stats["sources_solved"] += stats["cold_refreshed"]
        self._maybe_reselect()
        return stats

    def _maybe_reselect(self) -> bool:
        """Act on landmark drift under the configured policy (no-op
        when re-selection is off).  Cached results stay valid — partial
        entries certify exactness via their ``fixed`` masks regardless
        of which seeds produced them — so only the seed/estimate tables
        change hands."""
        if self.landmarks is None or self.reselect_policy is None:
            return False
        if not self.landmarks.maybe_reselect(self.reselect_policy):
            return False
        self.stats["reselects"] += 1
        # mirror the reset accumulator (fresh signal for new positions)
        self.stats["seed_tightness_mean"] = self.landmarks.tightness()
        self.stats["seed_tightness_count"] = self.landmarks.tightness_count
        return True

    # ------------------------------------------------------------------
    def serve(self, queries: list[Query]) -> list[Query]:
        """Answer a wave of queries in place (distance + path).

        With ``p2p`` on, scalar-target queries take the goal-directed
        path (targeted early-exit solves, landmark-seeded when an index
        is attached); full-vector queries always take the full path.
        """
        self._waves += 1
        f0 = spans.fetches()
        with spans.ids(wave=self._waves), \
                spans.span("sssp.serve", queries=len(queries)):
            self._serve_wave(queries)
        self.stats["host_syncs"] += spans.fetches() - f0
        return queries

    def _serve_wave(self, queries: list[Query]) -> None:
        n = self.solver.graph.n
        bad = [q for q in queries
               if not (0 <= q.source < n
                       and (q.target is None or 0 <= q.target < n))]
        if bad:
            # eager jnp indexing CLAMPS out-of-range targets (a silently
            # wrong answer), so reject the wave loudly instead.
            raise ValueError(
                f"{len(bad)} queries reference vertices outside [0, {n}): "
                f"first bad query {bad[0]}")
        if not self.p2p:
            if self.planner is not None:
                self._serve_full_planned(queries)
            else:
                self._serve_full(queries)
            return
        full_q = [q for q in queries if q.target is None]
        tgt_q = [q for q in queries if q.target is not None]
        if full_q:
            # full-vector traffic no longer bypasses the planner: it
            # gets pow-2-shaped waves and its own EMA'd route.
            if self.planner is not None:
                self._serve_full_planned(full_q)
            else:
                self._serve_full(full_q)
        if tgt_q:
            if self.planner is not None or self._bidi is not None:
                self._serve_planned(tgt_q)
            else:
                self._serve_p2p(tgt_q)
        self._maybe_reselect()

    def _serve_full(self, queries: list[Query]) -> list[Query]:
        """Original path: full solve per (cache-missing) source."""
        # a hit = a query answered without a solve on its behalf: neither
        # the first query of an initially-missing source (it pays for the
        # batch solve) nor an eviction-triggered mid-wave re-solve.
        with spans.span("sssp.lookup"):
            misses = {q.source for q in queries
                      if not self._cached(q.source)}
        self.stats["queries"] += len(queries)
        self._solve_missing([q.source for q in queries])
        paid = set()   # missing sources whose triggering query is consumed
        with spans.span("sssp.answer"):
            for q in queries:
                res = self._lookup(q.source)
                if res is None:  # evicted mid-wave: cache < the wave
                    self._solve_missing([q.source])
                    res = self._lookup(q.source)
                elif q.source in misses and q.source not in paid:
                    paid.add(q.source)
                else:
                    self.stats["cache_hits"] += 1
                self._answer(q, res)
        return queries

    def _serve_full_planned(self, queries: list[Query]) -> list[Query]:
        """Planner-routed full path: miss sources become pow-2-shaped
        waves (``plan_full_vector``) instead of always-full batches, and
        the route's measured cost feeds the planner EMA under
        ``full_vector`` with per-query ``stats["planner_routes"]``
        accounting (hits count as ``cache``).  Answer semantics are
        identical to :meth:`_serve_full`.
        """
        routes = self.stats["planner_routes"]
        with spans.span("sssp.lookup"):
            misses = {q.source for q in queries
                      if not self._cached(q.source)}
        self.stats["queries"] += len(queries)
        with spans.span("sssp.plan"):
            waves = self.planner.plan_full_vector(sorted(misses),
                                                  batch=self.batch)
        for wave in waves:
            shape = WavePlanner.wave_shape(len(wave), self.batch)
            padded = wave + [wave[-1]] * (shape - len(wave))
            batch_res, dt = self._dispatch("full_vector", padded, len(wave))
            self.stats["solve_seconds"] += dt
            self.stats["batches"] += 1
            for i, s in enumerate(wave):
                self._admit(s, batch_res[i])
            self.stats["sources_solved"] += len(wave)
            self.planner.observe("full_vector", dt, len(wave))
        paid = set()   # missing sources whose triggering query is consumed
        with spans.span("sssp.answer"):
            for q in queries:
                res = self._lookup(q.source)
                if res is None:  # evicted mid-wave: cache < the wave
                    self._solve_missing([q.source])
                    res = self._lookup(q.source)
                    routes["full_vector"] += 1
                elif q.source in misses and q.source not in paid:
                    paid.add(q.source)
                    routes["full_vector"] += 1
                else:
                    self.stats["cache_hits"] += 1
                    routes["cache"] += 1
                self._answer(q, res)
        return queries

    def _serve_p2p(self, queries: list[Query]) -> list[Query]:
        """Goal-directed path for scalar-target queries.

        Cache first (full entries answer anything; partial entries
        answer targets their ``fixed`` mask certifies); remaining
        (source, target) pairs are batched into targeted early-exit
        solves — landmark-seeded when the index vouches for its bounds —
        and the partial results admitted ``partial=True``.  Answers come
        from the wave-local results dict, so mid-wave eviction can never
        orphan a query.
        """
        self.stats["queries"] += len(queries)
        hits: dict[int, SSSPResult] = {}
        need: list[tuple[int, int]] = []
        with spans.span("sssp.lookup"):
            for q in queries:
                res = self._lookup(q.source, target=q.target)
                if res is not None:
                    hits[id(q)] = res
                else:
                    need.append((q.source, q.target))
        need = list(dict.fromkeys(need))
        # Per-lane round capping: a vmapped wave runs for the MAX over
        # lanes of the per-lane (early-exited) round counts, so one far
        # target holds every short query of its batch hostage.  Sorting
        # the queue by the landmark estimate C0[t] at enqueue time
        # groups short queries with short batches (estimate order tracks
        # round-count order because seeded bounds certify near targets
        # in few rounds).  Stable sort: equal estimates keep FIFO order.
        if self.landmarks is not None and len(need) > 1:
            with spans.span("sssp.plan"):
                est = self.landmarks.estimate_pairs(need)
                if est is not None:
                    order = np.argsort(est, kind="stable")
                    need = [need[i] for i in order]
        solved: dict[tuple[int, int], SSSPResult] = {}
        for at in range(0, len(need), self.batch):
            chunk = need[at: at + self.batch]
            solved.update(self._targeted_wave(chunk, self.batch))
        paid: set[tuple[int, int]] = set()
        with spans.span("sssp.answer"):
            for q in queries:
                res = hits.get(id(q))
                if res is not None:
                    self.stats["cache_hits"] += 1
                else:
                    res = solved[(q.source, q.target)]
                    # duplicate pairs in one wave: only the first query
                    # pays for the solve, the rest are hits (same
                    # definition as the full path's `paid` accounting)
                    if (q.source, q.target) in paid:
                        self.stats["cache_hits"] += 1
                    else:
                        paid.add((q.source, q.target))
                self._answer(q, res)
        return queries

    def _targeted_wave(self, chunk: list[tuple[int, int]],
                       shape: int) -> dict[tuple[int, int], SSSPResult]:
        """One targeted early-exit solve over ``chunk``, padded to
        ``shape`` slots; admits partials and feeds the tightness +
        planner cost telemetry.  Returns per-pair results."""
        padded = chunk + [chunk[-1]] * (shape - len(chunk))
        srcs = [s for s, _ in padded]
        tgts = [t for _, t in padded]
        t0 = time.perf_counter()
        C0 = (self.landmarks.seed_batch(srcs)
              if self.landmarks is not None else None)
        batch_res, _ = self._dispatch("targeted", srcs, len(chunk),
                                      targets=tgts, C0=C0)
        dt = time.perf_counter() - t0
        self.stats["solve_seconds"] += dt
        self.stats["batches"] += 1
        self.stats["p2p_solves"] += len(chunk)
        if self.planner is not None:
            self.planner.observe("targeted", dt, len(chunk))
        solved: dict[tuple[int, int], SSSPResult] = {}
        for i, (s, t) in enumerate(chunk):
            res = batch_res[i]
            solved[(s, t)] = res
            self._admit(s, res, partial=batch_res.partial)
        if C0 is not None:
            self._record_tightness(C0, batch_res, chunk)
        return solved

    def _serve_bidi(
            self, pairs: list[tuple[int, int]], est=None,
    ) -> dict[tuple[int, int], tuple[float, list | None]]:
        """Meet-in-the-middle solves for ``pairs``; answers go to the
        pair cache, each forward lane to the source cache as a partial
        entry, and estimate/distance ratios into the tightness signal."""
        out: dict[tuple[int, int], tuple[float, list | None]] = {}
        if not pairs:
            return out
        t0 = time.perf_counter()
        ratios = []
        for i, (s, t) in enumerate(pairs):
            with spans.span("sssp.dispatch", route="bidirectional",
                            lanes=2):
                r = self._bidi.solve(s, t)
                ans = (r.distance,
                       r.path() if np.isfinite(r.distance) else None)
            self.stats["query_rounds"] += r.rounds
            out[(s, t)] = ans
            self._pair_admit(s, t, ans[0], ans[1], lanes=(r.D, r.fixed))
            self._admit(s, r.forward_result(), partial=True)
            if est is not None:
                e = float(est[i])
                if np.isfinite(e) and np.isfinite(ans[0]) and ans[0] > 0:
                    ratios.append(e / ans[0])
        dt = time.perf_counter() - t0
        self.stats["solve_seconds"] += dt
        self.stats["bidi_solves"] += len(pairs)
        if self.planner is not None:
            self.planner.observe("bidirectional", dt, len(pairs))
        if ratios and self.landmarks is not None:
            self.landmarks.record_tightness(np.asarray(ratios))
            self.stats["seed_tightness_mean"] = self.landmarks.tightness()
            self.stats["seed_tightness_count"] = \
                self.landmarks.tightness_count
        return out

    def _serve_planned(self, queries: list[Query]) -> list[Query]:
        """Query-engine v2: plan each wave across the four routes.

        Cache (source entries AND the bidirectional pair cache) is
        probed first; the misses go through :meth:`WavePlanner.plan` —
        or all-bidirectional when ``bidirectional=True`` without a
        planner — and each route's answers are joined wave-locally, so
        mid-wave eviction can never orphan a query.
        """
        self.stats["queries"] += len(queries)
        routes = self.stats["planner_routes"]
        hits: dict[int, SSSPResult | tuple[float, list | None]] = {}
        need: list[tuple[int, int]] = []
        with spans.span("sssp.lookup"):
            for q in queries:
                ans = self._pair_lookup(q.source, q.target)
                if ans is None:
                    ans = self._lookup(q.source, target=q.target)
                if ans is not None:
                    hits[id(q)] = ans
                else:
                    need.append((q.source, q.target))
        need = list(dict.fromkeys(need))
        with spans.span("sssp.plan"):
            est = (self.landmarks.estimate_pairs(need)
                   if self.landmarks is not None and need else None)
            if self.planner is not None:
                plan = self.planner.plan(need, est, batch=self.batch,
                                         bidi_ok=self._bidi is not None)
            else:  # bidirectional-only mode: every miss meets in the middle
                plan = WavePlan(full_sources=[], full_pairs=[],
                                bidi_pairs=list(need), targeted_waves=[])
        if plan.full_sources:
            t0 = time.perf_counter()
            self._solve_missing(plan.full_sources)
            if self.planner is not None:
                self.planner.observe(
                    "full", time.perf_counter() - t0, len(plan.full_pairs))
        if plan.bidi_pairs:
            bidi_est = (None if est is None else
                        [est[need.index(p)] for p in plan.bidi_pairs])
            bidi_out = self._serve_bidi(plan.bidi_pairs, bidi_est)
        else:
            bidi_out = {}
        solved: dict[tuple[int, int], SSSPResult] = {}
        for wave in plan.targeted_waves:
            shape = WavePlanner.wave_shape(len(wave), self.batch)
            solved.update(self._targeted_wave(wave, shape))
        full_keys = set(plan.full_pairs)
        paid: set[tuple[int, int]] = set()
        with spans.span("sssp.answer"):
            for q in queries:
                key = (q.source, q.target)
                ans = hits.get(id(q))
                if ans is not None:
                    routes["cache"] += 1
                    self.stats["cache_hits"] += 1
                    if isinstance(ans, tuple):
                        q.distance, q.path = ans
                        q.done = True
                    else:
                        self._answer(q, ans)
                    continue
                if key in bidi_out:
                    routes["bidirectional"] += 1
                    q.distance, q.path = bidi_out[key]
                    q.done = True
                elif key in full_keys:
                    routes["full"] += 1
                    res = self._lookup(q.source)
                    if res is None:  # evicted mid-wave: re-solve on demand
                        self._solve_missing([q.source])
                        res = self._lookup(q.source)
                    self._answer(q, res)
                else:
                    routes["targeted"] += 1
                    self._answer(q, solved[key])
                # duplicate pairs in one wave: only the first query pays
                if key in paid:
                    self.stats["cache_hits"] += 1
                else:
                    paid.add(key)
        return queries

    def _record_tightness(self, C0, batch_res, chunk) -> None:
        """Seed-tightness telemetry: mean ``C0[target] / dist[target]``
        over served seeded queries (1.0 = seed already exact, → 0 =
        landmarks drifting off the mutated metric).  Kept in ``stats``
        and mirrored into the :class:`LandmarkIndex`, whose
        ``needs_reselect(threshold)`` turns it into the re-selection
        signal (metric + hook; acting on it stays the operator's call).
        """
        c0, d = spans.fetch((C0, batch_res.dist))
        c0, d = c0.astype(np.float64), d.astype(np.float64)
        idx = np.arange(len(chunk))
        tgt = np.asarray([t for _, t in chunk], np.int64)
        dist = d[idx, tgt]
        seed = c0[idx, tgt]
        ok = np.isfinite(dist) & (dist > 0) & np.isfinite(seed)
        if not ok.any():
            return
        self.landmarks.record_tightness(seed[ok] / dist[ok])
        # single source of truth: the index's accumulator (so a
        # reset_tightness() is reflected here too, never a stale fork)
        self.stats["seed_tightness_mean"] = self.landmarks.tightness()
        self.stats["seed_tightness_count"] = self.landmarks.tightness_count

    def distances(self, source: int) -> np.ndarray:
        """Full distance vector for one source (through the cache)."""
        self._solve_missing([source])
        return spans.fetch(self._lookup(source).dist)
