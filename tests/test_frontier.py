"""Sparse-frontier backend: bitwise equality vs the segment backend on
every graph family × {cold, warm-after-delta, targeted early-exit},
overflow fallback, CSR-view coherence, kernel parity, auto routing, and
the serving-layer satellites (wave sorting, seed tightness)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_dist_equal
from repro.core import generators as gen
from repro.core.graph import HostGraph
from repro.core.sssp.dynamic import DynamicSolver, GraphDelta, random_delta
from repro.core.sssp.engine import SP4_CONFIG
from repro.core.sssp.landmarks import LandmarkIndex
from repro.core.sssp.reference import dijkstra
from repro.runtime.sssp_service import Query, SSSPService
from repro.sssp import SSSPConfig, Solver

FAMILIES = ["gnp", "dag", "unweighted", "grid", "power_law", "chain",
            "geometric"]


def _graph(family, n=160, seed=11):
    nn, src, dst, w = gen.make(family, n, seed=seed)
    return HostGraph(nn, src, dst, w)


def _bitwise(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (a) cold solves: bitwise D (and identical round trajectory) per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_cold_bitwise_vs_segment(family):
    hg = _graph(family)
    g = hg.to_device()
    sf = Solver(g, backend="frontier")
    ss = Solver(g, backend="segment")
    for s in (0, 3 % hg.n, hg.n - 1):
        rf, rs = sf.solve(s), ss.solve(s)
        assert _bitwise(rf.dist, rs.dist), family
        assert _bitwise(rf.C, rs.C) and _bitwise(rf.fixed, rs.fixed)
        # skipping value-identical repeated offers is round-for-round
        # neutral, so even the trajectory length matches
        assert rf.rounds == rs.rounds and rf.fixed_by == rs.fixed_by
        assert_dist_equal(rf.dist, dijkstra(hg, source=s).dist)
    # only the frontier backend meters its relax gathers
    assert sf.solve(0).edges_relaxed is not None
    assert ss.solve(0).edges_relaxed is None


def test_cold_bitwise_label_setting_config():
    hg = _graph("chain", n=120)
    cfg = SSSPConfig(label_correcting=False)
    rf = Solver(hg.to_device(), cfg, backend="frontier").solve(0)
    rs = Solver(hg.to_device(), cfg, backend="segment").solve(0)
    assert _bitwise(rf.dist, rs.dist) and rf.rounds == rs.rounds


# ---------------------------------------------------------------------------
# (b) warm re-solve after weight deltas: bitwise vs segment AND vs cold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_warm_after_delta_bitwise(family):
    hg = _graph(family, n=140)
    g = hg.to_device()
    sources = [0, 7 % hg.n, 31 % hg.n]
    df = DynamicSolver(g, backend="frontier")
    ds = DynamicSolver(g, backend="segment")
    for d in (df, ds):
        d.solve_batch(sources)
    # mixed delta: both increases and decreases (seed chosen so random
    # rescale hits both directions), twice — warm-of-warm states too
    for seed in (3, 4):
        delta = random_delta(df.graph, 10, seed=seed)
        stf, sts = df.update(delta), ds.update(delta)
        assert stf["warm_rounds"] == sts["warm_rounds"], family
        rf, rs = df.resolve(sources), ds.resolve(sources)
        assert _bitwise(rf.dist, rs.dist), family
        assert _bitwise(rf.fixed, rs.fixed), family
        cold = Solver(df.graph, backend="segment").solve_batch(sources)
        assert _bitwise(rf.dist, cold.dist), family


@pytest.mark.parametrize("family", ["chain", "grid", "geometric"])
def test_warm_frontier_rounds_engine_level(family):
    """The sparse warm path itself (taint-cone in-boundary +
    decreased-edge-tail seeding): unbatched ``_solve_warm`` with
    frontier prims must be bitwise-identical to segment prims, round
    for round.  (DynamicSolver's vmapped refresh runs dense rounds, so
    this is the direct coverage for the warm frontier machinery.)"""
    import jax
    from repro.core.sssp import backends
    from repro.core.sssp.engine import (_solve_warm,
                                        delta_decrease_sources,
                                        delta_taint_seeds)
    hg = _graph(family, n=140)
    g = hg.to_device()
    prev = Solver(g, backend="segment").solve(0)
    delta = random_delta(g, 10, seed=3)   # mixed increases + decreases
    g2 = g.apply_delta(delta)
    csr2 = g.csr().apply_delta(delta)
    seeds, pure = delta_taint_seeds(g, delta, prev.dist)
    dec = delta_decrease_sources(g, delta)
    fp = backends.frontier_prims(g2, csr2, cap=64)
    sp = backends.segment_prims(g2)
    wf = jax.jit(lambda: _solve_warm(g2, SP4_CONFIG, prev.dist, prev.fixed,
                                     seeds, pure, prims=fp, dec_src=dec))()
    ws = jax.jit(lambda: _solve_warm(g2, SP4_CONFIG, prev.dist, prev.fixed,
                                     seeds, pure, prims=sp))()
    assert _bitwise(wf[0].D, ws[0].D), family
    assert _bitwise(wf[0].fixed, ws[0].fixed), family
    assert int(wf[0].round) == int(ws[0].round), family
    cold = Solver(g2, backend="segment").solve(0)
    assert _bitwise(wf[0].D, cold.dist), family


# ---------------------------------------------------------------------------
# (c) targeted early-exit solves: bitwise at the target, same rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_targeted_bitwise_vs_segment(family):
    hg = _graph(family)
    g = hg.to_device()
    sf = Solver(g, backend="frontier")
    ss = Solver(g, backend="segment")
    s = 5 % hg.n
    for t in (0, hg.n // 2, hg.n - 1):
        rf, rs = sf.solve(s, target=t), ss.solve(s, target=t)
        assert float(rf.dist[t]) == float(rs.dist[t]), family
        assert rf.rounds == rs.rounds and rf.partial and rf.target == t
        assert _bitwise(rf.dist, rs.dist)
    # seeded + targeted batch: the lanes share ONE union-compacted
    # frontier (engine._round_shared) and stay sparse — and metered.
    index = LandmarkIndex(g, k=3, seed=1)
    srcs, tgts = [s, 0], [hg.n - 1, hg.n // 2]
    bf = sf.solve_batch(srcs, targets=tgts, C0=index.seed_batch(srcs))
    bs = ss.solve_batch(srcs, targets=tgts, C0=index.seed_batch(srcs))
    assert _bitwise(bf.dist, bs.dist), family
    assert bf.edges_relaxed is not None
    assert np.array_equal(bf.rounds, bs.rounds)


# ---------------------------------------------------------------------------
# (c2) shared batch frontier: batched lanes run sparse and stay bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_batched_bitwise_vs_segment(family):
    hg = _graph(family)
    g = hg.to_device()
    sf = Solver(g, backend="frontier")
    ss = Solver(g, backend="segment")
    srcs = [0, 3 % hg.n, hg.n - 1]
    bf, bs = sf.solve_batch(srcs), ss.solve_batch(srcs)
    assert _bitwise(bf.dist, bs.dist), family
    assert _bitwise(bf.C, bs.C) and _bitwise(bf.fixed, bs.fixed)
    assert np.array_equal(bf.rounds, bs.rounds), family
    assert bf.edges_relaxed is not None   # sparse rounds are metered
    # the union frontier is bitwise-neutral per lane: every batched lane
    # equals its solo solve, trajectory included
    for i, s in enumerate(srcs):
        solo = sf.solve(s)
        assert _bitwise(bf.dist[i], solo.dist), family
        assert int(bf.rounds[i]) == solo.rounds, family


_ROUND_CASES = [
    pytest.param(family, cfg, warm,
                 id="-".join([family, cid] + (["warm"] if warm else [])))
    for warm in (False, True)
    for family in ("grid", "geometric")
    for cfg, cid in ((SP4_CONFIG, "label_correcting"),
                     (SSSPConfig(), "label_setting"))]


def _warm_start(g, cfg, sources):
    """Lanes warm-started after a weight DECREASE on the solved graph:
    ``(g2, csr2, state, seed_mask)`` as ``_solve_warm_frontier`` builds
    them, so warm rounds un-fix stale-high fixed vertices."""
    import jax
    from repro.core.sssp.dynamic import make_delta
    from repro.core.sssp.engine import (_init_state_warm, _warm_seed_mask,
                                        delta_decrease_sources,
                                        delta_taint_seeds)
    prev = Solver(g, cfg, backend="segment").solve_batch(
        [int(s) for s in sources])
    idx = np.arange(0, g.e, max(1, g.e // 12))[:12]
    delta = make_delta(g, idx, (np.asarray(g.w)[idx] * 0.25).astype(
        np.float32))
    g2, csr2 = g.apply_delta(delta), g.csr().apply_delta(delta)
    prev_D, prev_fixed = jnp.asarray(prev.dist), jnp.asarray(prev.fixed)
    seeds, pure = jax.vmap(lambda d: delta_taint_seeds(g, delta, d))(prev_D)
    dec = delta_decrease_sources(g, delta)
    state, _, taint = jax.vmap(
        lambda d, f, sd, pu: _init_state_warm(g2, d, f, sd, pu))(
            prev_D, prev_fixed, seeds, pure)
    seed = jax.vmap(lambda t, f, d: _warm_seed_mask(g2, t, f, d, dec))(
        taint, state.fixed, state.D)
    return g2, csr2, state, jnp.any(seed, axis=0)


@pytest.mark.parametrize("family, cfg, warm", _ROUND_CASES)
def test_round_shared_matches_dense_round_every_round(family, cfg, warm):
    """The shared-frontier round and the dense round, stepped from the
    same state, agree bitwise on D, C and ``fixed`` after EVERY round:
    the dense inWeight_nf and Eqn-(1) sweeps of ``_round_shared`` are
    the lines ``_round`` runs, so neither can drift even for a round or
    two.  The warm cases start from a warm state after a weight
    decrease, so rounds un-fix vertices and inWeight_nf must read the
    round-start ``fixed``, before the un-fix."""
    import jax
    from repro.core.sssp import backends
    from repro.core.sssp.engine import (_compact_frontier, _init_state,
                                        _round, _round_shared)
    cap = 16
    g = _graph(family, n=120, seed=7).to_device()
    sources = jnp.asarray([0, 11], jnp.int32)
    if warm:
        g, csr, state, seed = _warm_start(g, cfg, sources)
    else:
        csr = g.csr()
        state = jax.vmap(lambda s: _init_state(g, s))(sources)
        seed = jnp.zeros((g.n,), bool).at[sources].set(True)
    prims = backends.frontier_prims(g, csr, cap=cap)
    seg = backends.segment_prims(g)
    shared = dataclasses.replace(state, edges=jnp.zeros_like(state.round))
    f_idx, f_cnt = _compact_frontier(seed, cap, g.n)
    step_shared = jax.jit(
        lambda st, fi, fc: _round_shared(g, cfg, st, fi, fc, prims,
                                         warm=warm))
    step_dense = jax.jit(jax.vmap(
        lambda st: _round(g, cfg, st, seg, warm=warm)))
    unfixed = 0
    for _ in range(2 * g.n + 4):
        shared, fresh = step_shared(shared, f_idx, f_cnt)
        before = state
        state = step_dense(state)
        # a fixed vertex's D moves only if this round un-fixed it
        unfixed += int(jnp.sum(before.fixed & (state.D < before.D)))
        for leaf in ("D", "C", "fixed"):
            assert _bitwise(getattr(shared, leaf), getattr(state, leaf)), (
                leaf, int(state.round[0]))
        if bool(jnp.all(state.fixed | (state.D == jnp.inf))
                & jnp.all(state.explored == state.fixed)):
            break
        f_idx, f_cnt = _compact_frontier(jnp.any(fresh, axis=0), cap, g.n)
    else:
        pytest.fail("lanes never settled")
    assert _bitwise(shared.fixed_by, state.fixed_by)
    assert (unfixed > 0) == warm   # the warm cases reach the un-fix path


def test_batched_union_overflow_falls_back_dense():
    hg = _graph("gnp", n=160, seed=4)   # union blows past cap=2 fast
    g = hg.to_device()
    tiny = Solver(g, backend="frontier", frontier_cap=2)
    ss = Solver(g, backend="segment")
    srcs = [3, 77, 11]
    bt, bs = tiny.solve_batch(srcs), ss.solve_batch(srcs)
    assert _bitwise(bt.dist, bs.dist)
    assert np.array_equal(bt.rounds, bs.rounds)
    # the per-round overflow rule bills the fallback at e_pad
    assert int(np.max(bt.edges_relaxed)) >= g.e_pad
    big = Solver(g, backend="frontier").solve_batch(srcs)
    assert int(np.sum(bt.edges_relaxed)) > int(np.sum(big.edges_relaxed))


# ---------------------------------------------------------------------------
# (c3) fleet lanes on the frontier backend: python-unrolled members
# ---------------------------------------------------------------------------

def test_fleet_frontier_lanes_bitwise():
    from repro.core.sssp.dynamic import make_delta
    from repro.core.sssp.fleet import FleetSolver, build_fleet, stack_deltas
    members = [_graph("chain", n=96, seed=3),
               _graph("geometric", n=96, seed=4)]
    fs = FleetSolver(build_fleet(members), backend="segment")
    ff = FleetSolver(build_fleet(members), backend="frontier")
    # auto routes thin-wavefront member sets to the frontier backend
    assert FleetSolver(build_fleet(members),
                       backend="auto").backend == "frontier"
    src = np.array([0, 5], np.int32)
    rs, rf = fs.solve(src), ff.solve(src)
    assert _bitwise(rs.dist, rf.dist) and _bitwise(rs.fixed, rf.fixed)
    assert np.array_equal(rs.rounds, rf.rounds)
    assert rf.edges_relaxed is not None and rs.edges_relaxed is None
    bsrc = np.array([[0, 7, 11], [5, 2, 9]], np.int32)
    bs, bf = fs.solve_batch(bsrc), ff.solve_batch(bsrc)
    assert _bitwise(bs.dist, bf.dist)
    assert np.array_equal(bs.rounds, bf.rounds)
    # per-member deltas (csr_pos included): warm refresh stays bitwise
    def deltas(solver):
        out = []
        for i in range(2):
            gm = solver.fleet.member(i)
            w = np.asarray(gm.w)[:4] * 0.5
            out.append(make_delta(gm, [0, 1, 2, 3], w.astype(np.float32)))
        return stack_deltas(out)
    fs.update(deltas(fs)), ff.update(deltas(ff))
    r1, r2 = fs.resolve(), ff.resolve()
    assert _bitwise(r1.dist, r2.dist) and np.array_equal(r1.rounds,
                                                         r2.rounds)
    # one trace per program shape, members unrolled inside it
    assert ff.trace_count == 2 and ff.warm_trace_count == 1


# ---------------------------------------------------------------------------
# (d) overflow: a tiny buffer forces the dense fallback mid-solve
# ---------------------------------------------------------------------------

def test_overflow_falls_back_dense_and_stays_exact():
    hg = _graph("gnp", n=160, seed=4)   # wavefront blows past cap=2 fast
    g = hg.to_device()
    tiny = Solver(g, backend="frontier", frontier_cap=2)
    assert tiny.frontier_cap == 2
    ss = Solver(g, backend="segment")
    rt, rs = tiny.solve(3), ss.solve(3)
    assert _bitwise(rt.dist, rs.dist) and rt.rounds == rs.rounds
    # the dense fallback rounds are metered at e_pad — a tiny cap costs
    # measurably more gathered edges than a fitting one
    big = Solver(g, backend="frontier")
    assert rt.edges_relaxed > big.solve(3).edges_relaxed
    # and the fallback really fired: some round was billed at e_pad
    assert rt.edges_relaxed >= g.e_pad


def test_cap_rounds_to_pow2():
    g = _graph("chain", n=64).to_device()
    assert Solver(g, backend="frontier", frontier_cap=5).frontier_cap == 8


# ---------------------------------------------------------------------------
# (e) the wavefront-proportionality claim at test scale
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["chain", "geometric"])
def test_edges_relaxed_reduction(family):
    hg = _graph(family, n=200)
    g = hg.to_device()
    rf = Solver(g, backend="frontier").solve(0)
    dense_edges = rf.rounds * g.e_pad   # dense relax touches e_pad/round
    assert rf.edges_relaxed * 3 <= dense_edges, (
        family, rf.edges_relaxed, dense_edges)


# ---------------------------------------------------------------------------
# (f) CSR view and delta coherence
# ---------------------------------------------------------------------------

def test_csr_apply_delta_coherent():
    g = _graph("grid", n=100, seed=2).to_device()
    csr = g.csr()
    # csr holds the same (src-sorted) multiset of weighted edges
    assert float(jnp.sum(jnp.where(jnp.isinf(csr.w), 0, csr.w))) == \
        pytest.approx(float(jnp.sum(jnp.where(jnp.isinf(g.w), 0, g.w))))
    delta = random_delta(g, 7, seed=9)
    g2, csr2 = g.apply_delta(delta), csr.apply_delta(delta)
    assert _bitwise(jnp.sort(g2.w), jnp.sort(csr2.w))


def test_csr_apply_delta_requires_csr_pos():
    g = _graph("gnp", n=80, seed=1).to_device()
    bad = GraphDelta(k=1, edge_idx=jnp.array([0], jnp.int32),
                     new_w=jnp.array([2.0], jnp.float32),
                     ell_row=jnp.array([0], jnp.int32),
                     ell_col=jnp.array([0], jnp.int32))
    with pytest.raises(ValueError, match="csr_pos"):
        g.csr().apply_delta(bad)


# ---------------------------------------------------------------------------
# (g) Pallas kernel parity + engine on the Pallas path
# ---------------------------------------------------------------------------

def test_frontier_scatter_min_kernel_matches_ref():
    from repro.kernels import ref
    from repro.kernels.frontier_relax import frontier_scatter_min
    rng = np.random.default_rng(0)
    for n, cap, deg in [(50, 8, 3), (130, 16, 5), (7, 4, 9), (260, 2, 1)]:
        tgt = rng.integers(0, n + 1, (cap, deg)).astype(np.int32)
        cand = rng.uniform(0.0, 9.0, (cap, deg)).astype(np.float32)
        cand = np.where(tgt == n, np.inf, cand).astype(np.float32)
        got = frontier_scatter_min(jnp.asarray(tgt), jnp.asarray(cand), n,
                                   interpret=True)
        want = ref.frontier_scatter_min_ref(jnp.asarray(tgt),
                                            jnp.asarray(cand), n)
        assert _bitwise(got, want), (n, cap, deg)


def test_frontier_scatter_min_batch_kernel_matches_ref():
    from repro.kernels import ref
    from repro.kernels.frontier_relax import frontier_scatter_min_batch
    rng = np.random.default_rng(1)
    for n, cap, deg, B in [(50, 8, 3, 2), (130, 16, 5, 4), (7, 4, 9, 1),
                           (260, 2, 1, 3)]:
        tgt = rng.integers(0, n + 1, (cap, deg)).astype(np.int32)
        cand = rng.uniform(0.0, 9.0, (B, cap, deg)).astype(np.float32)
        cand = np.where(tgt[None] == n, np.inf, cand).astype(np.float32)
        got = frontier_scatter_min_batch(jnp.asarray(tgt),
                                         jnp.asarray(cand), n,
                                         interpret=True)
        want = ref.frontier_scatter_min_batch_ref(jnp.asarray(tgt),
                                                  jnp.asarray(cand), n)
        assert _bitwise(got, want), (n, cap, deg, B)


def test_frontier_engine_pallas_path():
    hg = _graph("chain", n=48, seed=5)
    g = hg.to_device()
    cfg = dataclasses.replace(SP4_CONFIG, use_pallas=True)
    rp = Solver(g, cfg, backend="frontier").solve(0)
    rs = Solver(g, backend="segment").solve(0)
    assert _bitwise(rp.dist, rs.dist) and rp.rounds == rs.rounds
    # the batched route drives the batched scatter-min kernel
    bp = Solver(g, cfg, backend="frontier").solve_batch([0, 5])
    bs = Solver(g, backend="segment").solve_batch([0, 5])
    assert _bitwise(bp.dist, bs.dist)


# ---------------------------------------------------------------------------
# (h) routing: the auto heuristic and use_pallas normalization
# ---------------------------------------------------------------------------

def test_auto_picks_frontier_for_thin_wavefronts():
    picks = {f: Solver(_graph(f, n=200).to_device()).backend
             for f in FAMILIES}
    assert picks["chain"] == picks["grid"] == picks["geometric"] \
        == "frontier"
    assert picks["gnp"] == picks["power_law"] == "segment"
    # use_pallas wins over the frontier heuristic under auto
    g = _graph("chain", n=200).to_device()
    assert Solver(g, SSSPConfig(use_pallas=True)).backend == "pallas"
    # frontier keeps the flag as given (its own kernel, not the ELL one)
    assert Solver(g, backend="frontier").cfg.use_pallas is False
    cfg = dataclasses.replace(SP4_CONFIG, use_pallas=True)
    assert Solver(g, cfg, backend="frontier").cfg.use_pallas is True


def test_no_retrace_across_sources_and_targets():
    from repro.analysis.trace_audit import assert_no_retrace
    g = _graph("grid", n=150).to_device()
    solver = Solver(g, backend="frontier")
    with assert_no_retrace(solver, allow=1):
        for s in (0, 5, 9):
            solver.solve(s)
        solver.solve(2, target=40)
    with assert_no_retrace(solver, allow=1):
        solver.solve_batch([0, 1, 2])
        solver.solve_batch([3, 4, 5], targets=[9, 10, 11])


# ---------------------------------------------------------------------------
# (i) serving satellites: wave sorting by seed estimate, tightness stats
# ---------------------------------------------------------------------------

def test_service_frontier_end_to_end_and_tightness():
    hg = _graph("geometric", n=220, seed=2)
    svc = SSSPService(hg.to_device(), backend="frontier", batch=4,
                      landmarks=4)
    rng = np.random.default_rng(1)
    qs = [Query(int(rng.integers(hg.n)), int(rng.integers(hg.n)))
          for _ in range(10)]
    svc.serve(qs)
    for q in qs:
        ref = dijkstra(hg, q.source).dist[q.target]
        if np.isinf(ref):
            assert q.distance == np.inf or q.distance > 1e17
        else:
            assert abs(q.distance - ref) < 1e-3
    assert svc.stats["seed_tightness_count"] > 0
    m = svc.stats["seed_tightness_mean"]
    assert 0.0 <= m <= 1.0 + 1e-6
    assert svc.landmarks.tightness() == pytest.approx(m)
    # hook semantics: no observations / healthy tightness -> False
    assert not svc.landmarks.needs_reselect(threshold=0.0)
    assert svc.landmarks.needs_reselect(threshold=1.1) or m > 1.0 - 1e-9
    svc.landmarks.reset_tightness()
    assert svc.landmarks.tightness() is None
    assert not svc.landmarks.needs_reselect(threshold=0.9)


def test_estimate_pairs_orders_waves():
    hg = _graph("grid", n=196, seed=0)
    g = hg.to_device()
    index = LandmarkIndex(g, k=4, seed=0)
    pairs = [(0, hg.n - 1), (0, 1), (0, hg.n // 2)]
    est = index.estimate_pairs(pairs)
    assert est is not None and est.shape == (3,)
    d = dijkstra(hg, 0).dist
    for (s, t), e in zip(pairs, est):
        assert e <= d[t] + 1e-3    # still a valid lower bound
    # the far corner must not sort before the adjacent vertex
    assert est[1] <= est[0]
