"""Device time by round pass, compound idle-gap labels and the per-pass
readers (``bench/scopes.py``, ``bench/metrics/``)."""
from __future__ import annotations

import types
from pathlib import Path

import numpy as np
import pytest
from bench_tiny import tiny_root  # noqa: F401  (fixture)

from bench import scopes, trace
from bench.layout import Layout

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
DEV = "/device:TPU:0"


def _synthetic():
    host = [("window", 0, 100 * MS), ("solve_batch", 0, 60 * MS),
            ("sssp.solve_batch", 0, 60 * MS), ("sssp.launch", 0, 2 * MS),
            ("sssp.fetch", 45 * MS, 60 * MS), ("wait", 70 * MS, 95 * MS),
            ("serve", 60 * MS, 70 * MS), ("sssp.serve", 60 * MS, 70 * MS),
            ("sssp.answer", 64 * MS, 70 * MS)]
    ops = [("%while.1 = f32[] while(%t)", 5 * MS, 40 * MS),
           ("%fusion.2 = f32[] fusion(f32[] %a), kind=kLoop, "
            "calls=%fused_computation.2", 6 * MS, 20 * MS),
           ("%fusion.3 = f32[] fusion(f32[] %b), kind=kLoop, "
            "calls=%fused_computation.3", 21 * MS, 30 * MS),
           ("%copy.4 = f32[] copy(%c)", 31 * MS, 35 * MS),
           ("%fusion.5 = f32[] fusion(%d), calls=%fused_computation.5",
            41 * MS, 45 * MS),
           ("%fusion.6 = f32[] fusion(%e), calls=%fused_computation.6",
            62 * MS, 63 * MS)]
    names = {DEV: {
        ops[0][0]: "jit(solve_many)/vmap()/while",
        ops[1][0]: "jit(solve_many)/vmap()/while/body/sssp.relax/gather:",
        ops[2][0]: "jit(solve_many)/vmap()/while/body/sssp.lb/sssp.fix/x",
        ops[4][0]: "jit(solve_many)/vmap(sssp.init)/scatter:",
        ops[5][0]: "jit(solve_many)/vmap()/while/body/sssp.inw/min:",
    }}
    return {DEV: ops}, host, names


def test_scope_attribution_outermost_and_unscoped():
    dev, host, names = _synthetic()
    r = scopes.reduce(dev, host, names)
    # the loop's own time: 35 ms spanned, 14 + 9 + 4 ms of body
    assert r.scope_s == pytest.approx({
        "unscoped": 0.008 + 0.004, "sssp.relax": 0.014,
        "sssp.lb": 0.009, "sssp.init": 0.004, "sssp.inw": 0.001})
    assert sum(r.scope_s.values()) == pytest.approx(r.busy_s)
    assert r.device_ops[0] == ["sssp.relax|%fusion.2 fusion",
                               pytest.approx(0.014)]
    assert ["unscoped|%copy.4 copy", pytest.approx(0.004)] in r.device_ops
    base = trace.reduce(dev, [h for h in host
                              if not h[0].startswith("sssp.")])
    assert (r.busy_s, r.window_s) == (base.busy_s, base.window_s)


def test_gap_labels_add_the_innermost_program_span():
    dev, host, names = _synthetic()
    gaps = scopes.reduce(dev, host, names).idle_gaps
    # 0-5 ms (mid 2.5) and 40-41: solve_batch, inside sssp.solve_batch
    assert ["solve_batch>sssp.solve_batch", pytest.approx(0.005)] in gaps
    assert ["solve_batch>sssp.solve_batch", pytest.approx(0.001)] in gaps
    # 45-62: mid 53.5 lies in sssp.fetch
    assert ["solve_batch>sssp.fetch", pytest.approx(0.017)] in gaps
    # 63-100: mid 81.5 in wait, no program span: today's label
    assert gaps[0] == ["wait", pytest.approx(0.037)]
    assert len(gaps) == 4
    assert scopes.reduce(dev, host, names).idle_s == pytest.approx({
        "wait": 0.037, "solve_batch>sssp.fetch": 0.017,
        "solve_batch>sssp.solve_batch": 0.006})


def test_mixed_fusions_from_hlo_text():
    dev, host, names = _synthetic()
    hlo = """HloModule jit_solve_many, is_scheduled=true

%fused_computation.2 (param_0: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  %a = f32[] add(%p, %p), metadata={op_name="jit(solve_many)/while/body/sssp.relax/add"}
  ROOT %m = f32[] minimum(%a, %p), metadata={op_name="jit(solve_many)/while/body/sssp.fix/min"}
}

%fused_computation.3 (param_0: f32[]) -> f32[] {
  ROOT %n = f32[] negate(%param_0), metadata={op_name="jit(solve_many)/while/body/sssp.lb/neg"}
}

ENTRY %main (a: f32[]) -> f32[] {
  %fusion.2 = f32[] fusion(%a), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(solve_many)/while/body/sssp.relax/add"}
  ROOT %fusion.3 = f32[] fusion(%b), kind=kLoop, calls=%fused_computation.3, metadata={op_name="x"}
}
"""
    fusions = scopes.fused_scopes([hlo])
    assert fusions[("%fusion.2 = f32[]", "kLoop", "fused_computation.2")] \
        == {"sssp.relax", "sssp.fix"}
    r = scopes.reduce(dev, host, names, fusions)
    assert r.mixed == [["sssp.relax|%fusion.2 fusion", pytest.approx(0.014),
                        ["sssp.fix", "sssp.relax"]]]


def _run(kind, *, scope_s=None, counters=None, rounds=None):
    red = None if scope_s is None else types.SimpleNamespace(scope_s=scope_s)
    rec = types.SimpleNamespace(counters=counters, rounds=rounds)
    return types.SimpleNamespace(kind=kind, reduction=red, records=rec)


@pytest.fixture(scope="module")
def readers():
    layout = Layout()
    return {m: layout.reader(m).read for m in
            ("arc_ms.teps", "vertex_ms.teps", "arc_ms.latency",
             "vertex_ms.latency", "rounds_per_query.latency",
             "delta_rounds.latency", "syncs_per_query.latency")}


def test_pass_readers(readers):
    scope_s = {"sssp.relax": 1.0, "sssp.lb": 0.5, "sssp.inw": 0.5,
               "sssp.fix": 0.2, "sssp.frontier": 0.1, "sssp.freeze": 0.1,
               "sssp.count": 0.1, "sssp.init": 9.0, "unscoped": 9.0}
    batch = _run("batch", scope_s=scope_s,
                 rounds=[np.array([10, 12]), np.array([8, 8])])  # 20 rounds
    assert readers["arc_ms.teps"](batch) == pytest.approx(100.0)
    assert readers["vertex_ms.teps"](batch) == pytest.approx(25.0)
    c = dict(queries=8, deltas=2, query_rounds=30, delta_rounds=10,
             host_syncs=24)
    served = _run("service", scope_s=scope_s, counters=c)
    assert readers["arc_ms.latency"](served) == pytest.approx(50.0)
    assert readers["vertex_ms.latency"](served) == pytest.approx(12.5)
    assert readers["rounds_per_query.latency"](served) == 3.75
    assert readers["delta_rounds.latency"](served) == 5.0
    assert readers["syncs_per_query.latency"](served) == 3.0


@pytest.mark.parametrize("run", [
    _run("batch", rounds=[np.array([3])]),               # no trace scopes
    _run("batch", scope_s={}, rounds=[np.array([3])]),   # nothing scoped
    _run("service", scope_s={"sssp.relax": 1.0},         # the parent's
         counters=dict(queries=4, deltas=1, cache_hits=1)),  # counters
    _run("service", scope_s={"sssp.relax": 1.0},
         counters=dict(queries=0, deltas=0, query_rounds=0, delta_rounds=0,
                       host_syncs=0)),
])
def test_pass_readers_find_nothing(readers, run):
    assert all(read(run) is None for read in readers.values())


def test_recorded_tpu_trace_reduces_as_before():
    """A trace of a program with no scopes: the same busy time, window
    and idle share as ``bench.trace``, and all of it ``unscoped``."""
    path = str(DATA / "tiny_tpu.xplane.pb")
    old, new = trace.reduce_file(path), scopes.reduce_file(path)
    assert (new.busy_s, new.window_s, new.idle_share) == (
        old.busy_s, old.window_s, old.idle_share)
    assert new.scope_s == {"unscoped": pytest.approx(new.busy_s)}


def test_op_names_cover_every_fusion():
    """Every fusion has its op_name; what XLA adds on its own (copies,
    the loop, some broadcasts) may have none and reads unscoped."""
    path = str(DATA / "tiny_tpu.xplane.pb")
    names = scopes.op_names(path)[DEV]
    dev, _ = trace.events(path)
    kinds = {trace.op_label(n).split(" ")[1] for n, _, _ in dev[DEV]
             if n not in names}
    assert kinds <= {"copy", "copy-start", "copy-done", "while",
                     "broadcast"}
    assert any("/while/body/" in v for v in names.values())


def test_recorded_scoped_tpu_trace():
    """A trace recorded on one TPU v5e of a program with scopes: two
    batched frontier solves (two lanes, a side-32 grid) and one warm
    refresh inside a ``window`` span, with the harness's spans over
    them.  The round's passes cover at least 90% of the device's busy
    time (96.8% recorded; XLA's own copies, loop control and the
    pieces it splits a cumsum into carry no op_name)."""
    r = scopes.reduce_file(str(DATA / "tiny_tpu_scoped.xplane.pb"))
    assert r.devices == 1 and 0 < r.busy_s < r.window_s
    scoped = sum(t for s, t in r.scope_s.items() if s != scopes.UNSCOPED)
    assert scoped >= 0.9 * r.busy_s
    assert {"sssp.relax", "sssp.lb", "sssp.inw", "sssp.fix",
            "sssp.frontier", "sssp.freeze", "sssp.taint"} <= set(r.scope_s)
    assert any(">sssp." in n for n, _ in r.idle_gaps)


def test_pass_tool_on_the_cpu(tiny_root, tmp_path):  # noqa: F811
    """``bench/passes.py`` on a tiny service cell: the service's counters
    reach the readers (the CPU trace has no TPU plane to attribute)."""
    from bench import passes

    out = passes.run("grid.zipf", 7, 2.0, tmp_path,
                     layout=Layout(tiny_root), require_chip=False)
    m = out["metrics"]
    assert m["rounds_per_query.latency"] > 0
    assert m["syncs_per_query.latency"] > 0
    assert m["delta_rounds.latency"] >= 0
    assert "arc_ms.latency" not in m and out["scope_s"] == {}
    assert out["counters"]["host_syncs"] > 0
