"""The contract gate itself: jaxpr lint, trace audit, AST rules.

The mutation tests are the teeth: a seeded host sync and a seeded f64
promotion MUST fail the gate.  The frontier dense-fallback-under-vmap
waivers did their job and are GONE: the shared batch frontier landed,
the waivers went stale, and the cumsum requirement hardened — pinned
below as hard PASSes with an empty KNOWN_VIOLATIONS (the lifecycle
docs/contracts.md walks through).
"""
import datetime
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import check
from repro.analysis.astlint import lint_file
from repro.analysis.contracts import (REGISTRY, ContractSpec, Waiver,
                                      contract, match_waiver)
from repro.analysis.jaxpr_lint import (dense_pass_count, lint_route,
                                       walk_jaxpr)
from repro.analysis.trace_audit import (TraceAudit, assert_no_retrace,
                                        trace_counts)


# ---------------------------------------------------------------------------
# mutation tests: the linter must catch seeded defects
# ---------------------------------------------------------------------------

def test_mutation_host_sync_fails_gate(tmp_path):
    """An injected pure_callback (the jaxpr form of .item()/device_get)
    must flag forbid:pure_callback and fail the CLI."""
    out = tmp_path / "contracts.json"
    rc = check.main(["--no-ruff", "--no-astlint", "--mutate", "host_sync",
                     "--out", str(out)])
    assert rc == 1
    doc = json.loads(out.read_text())
    assert doc["gate"] == "fail"
    v = doc["routes"]["mutant.host_sync"]
    assert v["verdict"] == "FAIL"
    assert any(x["rule"] == "forbid:pure_callback" and not x["waived"]
               for x in v["violations"])


def test_mutation_f64_fails_gate(tmp_path):
    """An injected float64 promotion must flag the dtype contract."""
    out = tmp_path / "contracts.json"
    rc = check.main(["--no-ruff", "--no-astlint", "--mutate", "f64",
                     "--out", str(out)])
    assert rc == 1
    doc = json.loads(out.read_text())
    v = doc["routes"]["mutant.f64"]
    assert v["verdict"] == "FAIL"
    assert any(x["rule"] == "dtype:float64" for x in v["violations"])


# ---------------------------------------------------------------------------
# pinning: the shared batch frontier hardened the cumsum contract
# ---------------------------------------------------------------------------

def test_frontier_routes_pass_hard_with_no_waivers():
    """Every frontier route — batched and warm included — now runs the
    union-compacted sparse round body, so the cumsum/scatter-min
    requirement holds as a HARD contract: all four routes verdict PASS
    with zero violations, and the waiver list is empty (the old
    frontier.{batched,warm} dense-under-vmap waivers went stale when
    engine._round_shared landed and were deleted — the lifecycle
    docs/contracts.md documents).  A future change that reroutes
    batched solves through vmap of the dense body fails here AND in
    the gate."""
    from repro.analysis.contracts import KNOWN_VIOLATIONS
    from repro.analysis.routes import build_routes
    assert KNOWN_VIOLATIONS == ()
    routes = build_routes(include=("frontier.*",))
    verdicts = {name: lint_route(name, r.jaxpr, dense_dims=r.dense_dims)
                for name, r in routes.items()}
    for route in ("frontier.cold", "frontier.targeted",
                  "frontier.batched", "frontier.warm"):
        v = verdicts[route]
        assert v.verdict == "PASS", (route, v.violations)
        assert not v.violations


# ---------------------------------------------------------------------------
# jaxpr_lint mechanics
# ---------------------------------------------------------------------------

def _toy_jaxpr():
    def f(x):
        def body(c):
            return jnp.sort(c) * 0.5

        return jax.lax.while_loop(lambda c: c[0] < 10.0, body, x)

    return jax.make_jaxpr(f)(jnp.zeros((128,), jnp.float32))


def test_walk_jaxpr_marks_hot_region():
    sites = walk_jaxpr(_toy_jaxpr())
    hot = {s.prim for s in sites if s.hot}
    assert "sort" in hot
    cond = {s.prim for s in sites if s.in_cond}
    assert cond and "sort" not in cond


def test_walk_jaxpr_recurses_into_shard_map():
    """The distributed route is a ``jax.shard_map`` equation; the walk
    must reach the round loop and its ``pmin`` all-reduce inside it."""
    from repro.analysis.routes import build_routes
    route = build_routes(include=("distributed.batched",))[
        "distributed.batched"]
    sites = walk_jaxpr(route.jaxpr)
    assert "shard_map" in {s.prim for s in sites}
    hot = {s.prim for s in sites if s.hot}
    assert {"pmin", "gather"} <= hot


def test_forbid_hot_sort_and_dense_budget():
    spec = ContractSpec(name="toy", routes=("toy.*",),
                        forbid_hot=("sort",), dense_budget=0)
    v = lint_route("toy.cold", _toy_jaxpr(), dense_dims=frozenset({128}),
                   specs={"toy": spec}, waivers=())
    assert v.verdict == "FAIL"
    rules = {x.rule for x in v.violations}
    assert "forbid_hot:sort" in rules


@pytest.mark.parametrize("dtype,fails", [(jnp.bool_, True),
                                         (jnp.int32, False)])
def test_hot_bool_gather_fails(dtype, fails):
    """A mask gathered as bool inside the round loop fails the route
    (it miscompiled under vmap on TPU v5e); the int32 form passes."""
    def f(x, m, idx):
        def body(c):
            return jnp.where(m.astype(dtype)[idx] > 0, c * 0.5, c)

        return jax.lax.while_loop(lambda c: c[0] < 10.0, body, x)

    jx = jax.make_jaxpr(jax.vmap(f, in_axes=(0, 0, None)))(
        jnp.zeros((2, 128), jnp.float32), jnp.zeros((2, 128), bool),
        jnp.arange(128))
    spec = ContractSpec(name="toy", routes=("toy.*",))
    v = lint_route("toy.batched", jx, specs={"toy": spec}, waivers=())
    rules = {x.rule for x in v.violations}
    assert ("gather:bool" in rules) is fails
    assert (v.verdict == "FAIL") is fails


def test_bool_gather_outside_loop_fails():
    """The rule covers the whole program, not only the round loop: a
    bool gather in set-up code (init, delta application) fails too."""
    def f(x, m, idx):
        return jnp.where(m[idx], x * 0.5, x)

    jx = jax.make_jaxpr(jax.vmap(f, in_axes=(0, 0, None)))(
        jnp.zeros((2, 128), jnp.float32), jnp.zeros((2, 128), bool),
        jnp.arange(128))
    spec = ContractSpec(name="toy", routes=("toy.*",))
    v = lint_route("toy.batched", jx, specs={"toy": spec}, waivers=())
    assert "gather:bool" in {x.rule for x in v.violations}
    assert v.verdict == "FAIL"


def test_dense_pass_count_keys_on_dims():
    def f(x, idx):
        def body(c):
            return c.at[idx].min(c[idx] * 0.5)

        return jax.lax.while_loop(lambda c: c[0] < 10.0, body, x)

    cj = jax.make_jaxpr(f)(jnp.zeros((64,), jnp.float32),
                           jnp.zeros((64,), jnp.int32))
    sites = walk_jaxpr(cj)
    assert dense_pass_count(sites, frozenset({64})) > 0
    assert dense_pass_count(sites, frozenset({999})) == 0


def test_waiver_expiry_and_matching():
    w = Waiver(route="a.*", rule="require:x", reason="r",
               expires="2000-01-01")
    assert w.expired()
    assert match_waiver("a.cold", "require:x", (w,)) is None  # expired
    live = Waiver(route="a.*", rule="require:x", reason="r",
                  expires="2999-01-01")
    assert match_waiver("a.cold", "require:x", (live,)) is live
    assert match_waiver("b.cold", "require:x", (live,)) is None
    today = datetime.date(1999, 1, 1)
    assert w.matches("a.cold", "require:x", today)  # not yet expired then


def test_contract_decorator_registers_and_attaches():
    @contract("toy.decorated", routes=("toy.*",), require=("add",))
    def toy():
        pass

    try:
        assert "toy.decorated" in REGISTRY
        assert toy.__contracts__[-1].name == "toy.decorated"
        assert REGISTRY["toy.decorated"].applies_to("toy.cold")
        assert not REGISTRY["toy.decorated"].applies_to("segment.cold")
    finally:
        del REGISTRY["toy.decorated"]


def test_budget_most_specific_pattern_wins():
    spec = ContractSpec(name="b", routes=("x.*",),
                        dense_budget={"x.warm": 11, "x.*": 8})
    assert spec.budget_for("x.warm") == 11
    assert spec.budget_for("x.cold") == 8


# ---------------------------------------------------------------------------
# trace_audit
# ---------------------------------------------------------------------------

class _FakeSolver:
    def __init__(self):
        self.trace_count = 1
        self.warm_trace_count = 0


def test_trace_counts_both_conventions():
    fs = _FakeSolver()
    assert trace_counts(fs) == {"trace_count": 1, "warm_trace_count": 0}
    from repro.core.sssp import bellman_ford as bf
    counts = trace_counts(bf)  # module-level 0-arg callable convention
    assert set(counts) == {"trace_count"}
    assert isinstance(counts["trace_count"], int)


def test_assert_no_retrace_passes_and_fails():
    fs = _FakeSolver()
    with assert_no_retrace(fs):
        pass
    with pytest.raises(AssertionError, match="expected exactly 0"):
        with assert_no_retrace(fs):
            fs.trace_count += 1
    with assert_no_retrace(fs, allow=2):
        fs.trace_count += 1
        fs.warm_trace_count += 1
    with pytest.raises(ValueError, match="no trace counter"):
        with assert_no_retrace(object()):
            pass


def test_trace_audit_explains_retrace():
    audit = TraceAudit("toy")
    assert audit.record(jnp.zeros((4,), jnp.float32)) is True
    assert audit.record(jnp.zeros((4,), jnp.float32)) is False  # cache hit
    assert audit.record(jnp.zeros((8,), jnp.float32)) is True   # retrace
    assert audit.fresh_count == 2
    msg = audit.explain_last()
    assert "float32[4]" in msg and "float32[8]" in msg


def test_trace_audit_wrap_records_calls():
    audit = TraceAudit("wrapped")
    f = audit.wrap(lambda x: x + 1)
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))
    assert len(audit.calls) == 2 and audit.fresh_count == 1


# ---------------------------------------------------------------------------
# astlint: seeded source-level defects must be flagged
# ---------------------------------------------------------------------------

_BAD_MODULE = '''
import numpy as np


def _round(g, x, cfg):
    if x > 0:                       # tracer branch
        x = x * 2
    y = float(x)                    # tracer cast
    z = x.item()                    # host sync
    w = np.maximum(x, 0)            # numpy on a tracer
    k = x.sum().item()              # astlint: ignore[host-sync]
    if cfg.early_exit:              # static config: NOT flagged
        y = y + 1
    return y + z + w + k
'''


def test_astlint_flags_seeded_defects(tmp_path):
    mod = tmp_path / "bad.py"
    mod.write_text(_BAD_MODULE)
    findings = lint_file(mod, tmp_path, ("_round",))
    rules = [f.rule for f in findings]
    assert rules.count("tracer-branch") == 1   # cfg branch not flagged
    assert "tracer-cast" in rules
    assert "host-sync" in rules                # .item() on x
    assert "numpy-in-traced" in rules
    # the pragma suppressed the second .item()
    assert rules.count("host-sync") == 1


def test_astlint_clean_on_repo_hot_paths():
    """The repo's own traced scopes must stay lint-clean — this is the
    same invariant the CI gate enforces, pinned as a fast test."""
    from repro.analysis import astlint
    findings = astlint.run(check._repo_root())
    assert findings == [], "\n".join(f.format() for f in findings)
