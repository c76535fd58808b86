"""Named scopes on the round's passes, host spans on the serving path,
and the service's round and sync counters."""
from __future__ import annotations

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generators as gen
from repro.core.graph import build_graph
from repro.core.sssp import dynamic, engine, spans
from repro.core.sssp.dynamic import DynamicSolver, random_delta
from repro.core.sssp.solver import Solver
from repro.runtime.sssp_service import Query, SSSPService

SCOPES = {"sssp.relax", "sssp.lb", "sssp.inw", "sssp.fix", "sssp.frontier",
          "sssp.freeze", "sssp.count", "sssp.taint", "sssp.init"}
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"sssp\.[a-z]+")   # also inside ``vmap(sssp.init)``


def _grid(side=6, seed=0):
    return build_graph(*gen.make("grid", side * side, seed=seed))


def _outermost(op_name: str) -> str | None:
    m = SCOPE.search(op_name)
    return m.group(0) if m else None


def _scopes(hlo_text: str) -> set:
    return {s for s in map(_outermost, OP_NAME.findall(hlo_text)) if s}


def _batch_args(solver, b=2):
    n = solver.graph.n
    return (solver.graph, solver.ell, solver.csr,
            jnp.arange(b, dtype=jnp.int32), jnp.full((b,), -1, jnp.int32),
            jnp.zeros((b, n), jnp.float32))


def _warm_args(solver, b=2):
    g = solver.graph
    delta = random_delta(g, 4, seed=1)
    prev_D = jnp.zeros((b, g.n), jnp.float32)
    prev_F = jnp.ones((b, g.n), bool)
    return (g, solver.ell, solver.csr, delta, prev_D, prev_F)


@pytest.mark.parametrize("backend,absent", [
    ("segment", {"sssp.frontier", "sssp.taint"}),
    ("frontier", {"sssp.taint"}),
])
def test_solve_batch_carries_every_round_scope(backend, absent):
    s = Solver(_grid(), backend=backend)
    hlo = s._jit_batch.lower(*_batch_args(s)).compile().as_text()
    assert _scopes(hlo) == SCOPES - absent


@pytest.mark.parametrize("backend,absent", [
    ("segment", {"sssp.frontier", "sssp.init"}),  # the state is the old one
    ("frontier", set()),
])
def test_warm_program_carries_every_scope(backend, absent):
    s = DynamicSolver(_grid(), backend=backend)
    hlo = s._jit_warm.lower(*_warm_args(s)).compile().as_text()
    assert _scopes(hlo) == SCOPES - absent


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.mark.parametrize("backend", ["segment", "frontier"])
def test_scopes_change_no_jaxpr_equation(backend, monkeypatch):
    """The printed jaxpr (equations, no name stacks) is the same with
    the scopes switched off."""
    def programs():
        s = Solver(_grid(), backend=backend)
        d = DynamicSolver(_grid(), backend=backend)
        return (str(jax.make_jaxpr(s._jit_batch)(*_batch_args(s))),
                str(jax.make_jaxpr(d._warm_program)(*_warm_args(d))))

    scoped = programs()
    monkeypatch.setattr(engine, "_scope", _no_scope)
    monkeypatch.setattr(dynamic, "_scope", _no_scope)
    assert programs() == scoped


def test_span_ids_nest_and_fetch_counts():
    x = jnp.arange(4)
    f0 = spans.fetches()
    with spans.ids(wave=3):
        with spans.ids(route="full"):
            assert spans._IDS.get() == {"wave": 3, "route": "full"}
        got = spans.fetch((x, None))
    assert spans._IDS.get() == {}
    assert spans.fetches() == f0 + 1
    assert isinstance(got[0], np.ndarray) and got[1] is None


def _service():
    g = _grid(side=8, seed=2)
    return SSSPService(g, backend="frontier", batch=4, planner=True,
                       bidirectional=True), g


def _host_spans(log_dir: str) -> list[tuple[str, dict]]:
    """``(name, keywords)`` of every host event of the newest trace."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_service_counters_and_spans(tmp_path):
    svc, g = _service()
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, g.n, size=(12, 2))
    svc.serve([Query(int(s), int(t)) for s, t in pairs[:6]])   # warm-up
    svc.distances(int(pairs[0, 0]))     # a full entry to refresh warm
    assert "delta_seconds" not in svc.stats
    before = dict(svc.stats)

    jax.profiler.start_trace(str(tmp_path))
    svc.serve([Query(int(s), int(t)) for s, t in pairs[6:]])
    svc.apply_delta(random_delta(svc.solver.graph, 6, seed=3))
    jax.profiler.stop_trace()

    st = svc.stats
    assert st["query_rounds"] > before["query_rounds"]
    assert st["delta_rounds"] > before["delta_rounds"]
    assert st["delta_sweeps"] >= before["delta_sweeps"]
    assert st["delta_tainted"] >= before["delta_tainted"]
    assert st["host_syncs"] > before["host_syncs"]
    # the warm-up wave dispatched and read too
    assert before["query_rounds"] > 0 and before["host_syncs"] > 0

    got = [(n, kw) for n, kw in _host_spans(str(tmp_path))
           if n.startswith("sssp.")]
    assert {n for n, _ in got} >= {
        "sssp.serve", "sssp.lookup", "sssp.plan", "sssp.dispatch",
        "sssp.answer", "sssp.fetch", "sssp.solve_batch", "sssp.prepare",
        "sssp.launch", "sssp.apply_delta", "sssp.delta.update",
        "sssp.delta.bidi", "sssp.delta.resolve"}
    # ids ride as keywords: every span of the wave or the delta has one
    wave = {n for n, kw in got if kw.get("wave") == 2}
    delta = {n for n, kw in got if kw.get("version") == 1}
    assert wave | delta == {n for n, _ in got}
    assert wave >= {"sssp.serve", "sssp.dispatch", "sssp.solve_batch",
                    "sssp.fetch"}
    assert delta >= {"sssp.apply_delta", "sssp.delta.update", "sssp.fetch"}
    assert ("sssp.serve", {"wave": 2, "queries": 6}) in got


def test_delta_counters_sum_update_stats():
    svc, g = _service()
    svc.serve([Query(s, (s * 7) % g.n) for s in range(0, 40, 5)])
    for s in (0, 9, 18):                     # full entries to refresh
        svc.distances(s)
    st0 = dict(svc.stats)
    out = svc.apply_delta(random_delta(svc.solver.graph, 8, seed=5))
    st = svc.stats
    assert out["warm_rounds"], "the cached sources refresh warm"
    assert (st["delta_rounds"] - st0["delta_rounds"]
            == max(out["warm_rounds"]) + out["cold_rounds"])
    assert st["delta_sweeps"] - st0["delta_sweeps"] == out["sweeps"]
    assert st["delta_tainted"] - st0["delta_tainted"] == max(out["tainted"])
    assert st["host_syncs"] == st0["host_syncs"]    # counted while serving


def test_cold_refresh_rounds_reported():
    d = DynamicSolver(_grid(), backend="segment")
    out = d.update(random_delta(d.graph, 4, seed=2), refresh=[0, 5])
    assert out["cold_refreshed"] == 2 and out["cold_rounds"] > 0
    assert out["warm_rounds"] == []
