"""Jaxpr lint: verdict compiled solver routes against their contracts.

This pass checks what the test suite cannot: which code path a compiled
program *actually contains*.  It traces every registered solver entry
point (5 backends x {cold, targeted, batched, warm} plus the
bidirectional pair and fleet programs), walks the resulting ClosedJaxpr
— recursing through ``pjit``/``while``/``cond``/``shard_map``/
``pallas_call`` sub-jaxprs, tracking whether a primitive sits inside
the hot region (a ``lax.while_loop`` body or cond) — and verdicts each
route against the :mod:`repro.analysis.contracts` registry:

  * required primitives present in the hot region (e.g. the frontier
    route must contain the ``cumsum`` compaction + scatter-min sparse
    relax — its absence is precisely the "silently falls back to dense"
    bug class the ROADMAP names);
  * forbidden primitives absent (host callbacks anywhere, ``sort``
    inside the round body);
  * 32-bit dtype discipline (no f64/i64 values anywhere);
  * no ``gather`` of a bool array anywhere in the program: on TPU v5e
    (JAX 0.9) a vmapped bool gather inside the round loop miscompiled
    — batched solves stopped after two rounds — and a bool gather alone
    did not, so where the fault lies is not pinned down; masks ride on
    value gathers (+inf for a masked source) or gather as int32;
  * a dense-pass budget: the number of gather/scatter-class eqns in the
    hot region that sweep a full edge-layout dimension.  This pins the
    per-round ``inWeight_nf``/C-propagation cost — a PR that adds a
    dense sweep to the round body trips the gate even though every
    output stays bitwise-identical.

Verdicts are PASS, FAIL, or KNOWN_VIOLATION (a failure matched by an
unexpired :data:`~repro.analysis.contracts.KNOWN_VIOLATIONS` waiver).
Tracing is abstract — no solve runs, no XLA compile; a probe graph of a
few dozen vertices keeps the whole sweep under a few seconds.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Iterator

from repro.analysis.contracts import (REGISTRY, WIDE_DTYPES, ContractSpec,
                                      Waiver, match_waiver)

#: gather/scatter-class primitives that stream an edge-layout array —
#: one such eqn over a full edge dimension is one dense memory pass.
SWEEP_PRIMS = frozenset({"gather", "scatter", "scatter-min", "scatter-max",
                         "scatter-add", "cumsum", "pallas_call"})


@dataclasses.dataclass(frozen=True)
class PrimSite:
    """One equation occurrence in a walked jaxpr."""

    prim: str
    hot: bool        # inside a while_loop body or cond
    in_cond: bool    # inside a while_loop cond specifically
    in_dims: tuple[tuple[int, ...], ...]   # shapes of array invars
    out_dtypes: tuple[str, ...]
    out_dims: tuple[tuple[int, ...], ...] = ()  # shapes of array outvars


def _sub_jaxprs(eqn) -> Iterator:
    """Yield every jaxpr-like object in an eqn's params (closed or raw)."""
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                yield item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                # raw Jaxpr


def walk_jaxpr(closed_jaxpr) -> list[PrimSite]:
    """Flatten a ClosedJaxpr (or Jaxpr) into PrimSites, recursively.

    The hot flag turns on for everything nested under a ``while`` eqn;
    ``in_cond`` additionally marks the while's cond jaxpr (where the
    early-exit predicate must live).
    """
    sites: list[PrimSite] = []
    root = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def visit(jx, hot: bool, in_cond: bool) -> None:
        for eqn in jx.eqns:
            in_dims = tuple(
                tuple(v.aval.shape) for v in eqn.invars
                if hasattr(v, "aval") and hasattr(v.aval, "shape"))
            out_dtypes = tuple(
                str(v.aval.dtype) for v in eqn.outvars
                if hasattr(v.aval, "dtype"))
            out_dims = tuple(
                tuple(v.aval.shape) for v in eqn.outvars
                if hasattr(v, "aval") and hasattr(v.aval, "shape"))
            sites.append(PrimSite(eqn.primitive.name, hot, in_cond,
                                  in_dims, out_dtypes, out_dims))
            if eqn.primitive.name == "while":
                cond = eqn.params.get("cond_jaxpr")
                body = eqn.params.get("body_jaxpr")
                if cond is not None:
                    visit(getattr(cond, "jaxpr", cond), True, True)
                if body is not None:
                    visit(getattr(body, "jaxpr", body), True, in_cond)
            else:
                for sub in _sub_jaxprs(eqn):
                    visit(sub, hot, in_cond)

    visit(root, False, False)
    return sites


def dense_pass_count(sites: list[PrimSite],
                     dense_dims: frozenset[int]) -> int:
    """Hot-region sweep eqns touching a full edge-layout dimension.

    ``gather`` is judged by its OUTPUT shape: a gather only *sweeps* an
    edge layout when it materializes an edge-sized result (the dense
    relax reads ``x[src]`` producing ``[e_pad]``).  A sparse-frontier
    CSR/CSC lookup also *indexes into* an ``[e_pad]`` table, but its
    output is wavefront-sized (``[cap, max_out]``) — counting it would
    charge the sparse route for the very memory traffic it avoids.
    Scatter-class eqns and cumsum keep the input rule: a scatter's dense
    cost is its operand/update stream, whatever the result shape.
    """
    def sweeps(s: PrimSite) -> bool:
        dims = s.out_dims if s.prim == "gather" else s.in_dims
        return any(d in dense_dims for sh in dims for d in sh)

    return sum(1 for s in sites
               if s.hot and s.prim in SWEEP_PRIMS and sweeps(s))


@dataclasses.dataclass
class Violation:
    rule: str        # "require:cumsum" | "forbid:pure_callback" |
    #                  "dense_budget" | "dtype:float64" | "require_cond:…"
    #                  | "gather:bool"
    detail: str
    waiver: Waiver | None = None


@dataclasses.dataclass
class RouteVerdict:
    route: str
    verdict: str                 # "PASS" | "FAIL" | "KNOWN_VIOLATION"
    dense_passes: int
    dense_budget: int | None
    prims_hot: dict[str, int]
    violations: list[Violation]
    contracts: list[str]         # spec names that applied

    def to_json(self) -> dict:
        return dict(
            verdict=self.verdict,
            dense_passes=self.dense_passes,
            dense_budget=self.dense_budget,
            contracts=self.contracts,
            violations=[
                dict(rule=v.rule, detail=v.detail,
                     waived=v.waiver is not None,
                     waiver=None if v.waiver is None else dict(
                         reason=v.waiver.reason, expires=v.waiver.expires))
                for v in self.violations],
        )


@dataclasses.dataclass
class LintReport:
    """All route verdicts of one gate run."""

    routes: dict[str, RouteVerdict]

    @property
    def failed(self) -> list[RouteVerdict]:
        return [v for v in self.routes.values() if v.verdict == "FAIL"]

    @property
    def waived(self) -> list[RouteVerdict]:
        return [v for v in self.routes.values()
                if v.verdict == "KNOWN_VIOLATION"]

    def to_json(self) -> dict:
        return {name: v.to_json() for name, v in
                sorted(self.routes.items())}


def _present(alternatives: str, names: set[str]) -> bool:
    return any(alt in names for alt in alternatives.split("|"))


def lint_route(route: str, closed_jaxpr, *,
               dense_dims: frozenset[int] = frozenset(),
               specs: dict[str, ContractSpec] | None = None,
               waivers=None) -> RouteVerdict:
    """Verdict one route's jaxpr against every applicable contract."""
    from repro.analysis.contracts import KNOWN_VIOLATIONS
    specs = REGISTRY if specs is None else specs
    waivers = KNOWN_VIOLATIONS if waivers is None else waivers
    sites = walk_jaxpr(closed_jaxpr)
    all_names = {s.prim for s in sites}
    hot_names = {s.prim for s in sites if s.hot}
    cond_names = {s.prim for s in sites if s.in_cond}
    hot_counter = Counter(s.prim for s in sites if s.hot)
    passes = dense_pass_count(sites, dense_dims)

    violations: list[Violation] = []
    applied: list[str] = []
    budget: int | None = None

    def add(rule: str, detail: str) -> None:
        violations.append(Violation(rule, detail, match_waiver(
            route, rule, waivers)))

    for spec in specs.values():
        if spec.composes or not spec.applies_to(route):
            continue
        applied.append(spec.name)
        for req in spec.require:
            if not _present(req, hot_names):
                add(f"require:{req}",
                    f"[{spec.name}] hot region lacks required "
                    f"primitive(s) {req!r}")
        for req in spec.require_cond:
            if not _present(req, cond_names):
                add(f"require_cond:{req}",
                    f"[{spec.name}] while-loop cond lacks {req!r} "
                    "(early-exit predicate not compiled in)")
        for bad in spec.forbid:
            hits = [nm for nm in all_names
                    if nm == bad or (bad == "callback" and "callback" in nm)]
            for nm in hits:
                add(f"forbid:{nm}",
                    f"[{spec.name}] forbidden primitive {nm!r} in program"
                    " (host round-trip inside a compiled route)")
        for bad in spec.forbid_hot:
            if bad in hot_names:
                add(f"forbid_hot:{bad}",
                    f"[{spec.name}] forbidden primitive {bad!r} inside "
                    "the round body")
        if not spec.allow_wide_dtypes:
            wide = sorted({dt for s in sites for dt in s.out_dtypes
                           if dt in WIDE_DTYPES})
            for dt in wide:
                add(f"dtype:{dt}",
                    f"[{spec.name}] {dt} value in program — the engine "
                    "is 32-bit by contract (bandwidth-bound rounds)")
        b = spec.budget_for(route)
        if b is not None:
            budget = b if budget is None else min(budget, b)

    bool_gathers = sum(1 for s in sites if s.prim == "gather"
                       and "bool" in s.out_dtypes)
    if applied and bool_gathers:
        add("gather:bool",
            f"{bool_gathers} bool gather(s) in the program — one "
            "miscompiled under vmap on TPU v5e; gather the mask folded "
            "into the values (+inf) or as int32")

    if budget is not None and passes > budget:
        add("dense_budget",
            f"{passes} dense edge sweeps in the hot region exceed the "
            f"declared budget of {budget} (dims {sorted(dense_dims)})")

    # de-duplicate identical rule ids raised by overlapping specs
    seen: dict[str, Violation] = {}
    for v in violations:
        seen.setdefault(v.rule, v)
    violations = list(seen.values())

    if not violations:
        verdict = "PASS"
    elif all(v.waiver is not None for v in violations):
        verdict = "KNOWN_VIOLATION"
    else:
        verdict = "FAIL"
    return RouteVerdict(route=route, verdict=verdict, dense_passes=passes,
                        dense_budget=budget,
                        prims_hot=dict(sorted(hot_counter.items())),
                        violations=violations, contracts=sorted(applied))
