"""Repo-specific AST rules for the traced hot paths.

Generic linters cannot know which functions in this repo run *under
``jax.jit``* — where ordinary Python is a footgun: ``if``/``while`` on a
tracer raises ``TracerBoolConversionError`` at best and silently bakes a
Python-time constant at worst; ``float()``/``.item()`` force a device
sync; ``np.`` calls constant-fold a tracer's *placeholder* value.  This
pass parses the hot-path modules, scopes the rules to the functions
that are actually traced, and applies a conservative staticness
analysis so config/shape arithmetic (``cfg.n_pad``, ``g.e_pad``,
``prims.relax2 is None``) never false-positives.

Rules (ids are stable; suppress one occurrence with a trailing
``# astlint: ignore[<rule>]`` comment):

  tracer-branch      Python ``if``/``while`` whose test is not provably
                     static inside a traced scope (use ``lax.cond`` /
                     ``jnp.where``).
  tracer-cast        ``float()`` / ``int()`` / ``bool()`` on a
                     non-static expression inside a traced scope.
  host-sync          ``.item()`` / ``.tolist()`` / ``np.asarray`` /
                     ``jax.device_get`` on a non-static expression
                     inside a traced scope (host round-trip).
  numpy-in-traced    ``np.*`` call with a non-static argument inside a
                     traced scope (constant-folds the tracer).
  raw-graphdelta     ``GraphDelta(...)`` constructed directly outside
                     its defining module — weights must go through
                     ``make_delta`` (host-side validation *before*
                     device put).
"""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path

#: module (repo-relative) -> function-name patterns whose bodies are
#: traced.  A bare name matches a top-level def OR any def nested in it
#: (the round closures); ``Class.method`` scopes to that method.
TRACED_SCOPES: dict[str, tuple[str, ...]] = {
    "src/repro/core/sssp/engine.py": (
        "_round", "_cond", "_body", "_init_state", "_init_state_warm",
        "_solve", "_solve_warm", "_compact_frontier",
        "delta_taint_seeds", "delta_decrease_sources",
        "_round_shared", "_frontier_fixpoint", "_warm_seed_mask",
        "_solve_frontier", "_solve_warm_frontier",
    ),
    "src/repro/core/sssp/backends.py": (
        "relax", "relax2", "relax_frontier", "in_weight_nf",
        "masked_min", "segment_prims", "ell_prims", "frontier_prims",
        "distributed_prims",
    ),
    "src/repro/core/sssp/solver.py": ("_one", "_batch"),
    "src/repro/core/sssp/dynamic.py": ("_warm_program",),
    "src/repro/core/sssp/bidirectional.py": ("program", "warm_program"),
    "src/repro/core/sssp/fleet.py": ("solve_fleet", "solve_fleet_batch",
                                     "warm_fleet"),
    "src/repro/core/sssp/distributed.py": ("solve_batch", "warm",
                                           "_shard_body"),
    "src/repro/kernels/ops.py": ("*",),
}

#: names that are always static under jit in this codebase: module
#: aliases, configs, backend-primitive bundles, python-level loop vars.
STATIC_BASES = frozenset({
    "jnp", "jax", "lax", "np", "math", "functools", "dataclasses",
    "cfg", "config", "prims", "self", "cls", "partial", "dtype",
    "shape", "mesh", "P", "NamedSharding", "pl", "plgpu", "jtu",
    "INF", "_ELL_PAD", "interpret", "backend", "axis", "cap",
})

#: attributes that are static ints on Graph/ELL/CSR/fleet containers
#: regardless of the base object's staticness (hashable aux_data).
STATIC_ATTRS = frozenset({
    "n", "e", "e_pad", "n_pad", "num_segments", "max_out_deg",
    "max_in_deg", "deg_pad", "size", "lanes", "frontier_cap", "cap",
    "interpret", "shape", "ndim", "dtype", "n_seg",
})

_IGNORE_RE = re.compile(r"#\s*astlint:\s*ignore\[([a-z\-, ]+)\]")


@dataclasses.dataclass(frozen=True)
class AstFinding:
    rule: str
    path: str
    line: int
    detail: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.detail}"


class _Static:
    """Conservative staticness analysis over one traced scope."""

    def __init__(self, static_names: frozenset[str]):
        self.names = set(static_names)

    def absorb_assignments(self, body: list[ast.stmt],
                           protected: frozenset[str] = frozenset()) -> None:
        """Propagate staticness through local ``name = <static expr>``
        assignments (``use_frontier = prims.relax_frontier is not None``
        or ``pad = (-B) % bb`` shape arithmetic is config, not data).
        A name qualifies only if EVERY assignment to it in the scope is
        static; two passes handle forward chains."""
        assigns: list[tuple[str, ast.expr]] = []
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Assign) and node.targets:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            assigns.append((t.id, node.value))
                elif (isinstance(node, ast.AnnAssign)
                        and isinstance(node.target, ast.Name)
                        and node.value is not None):
                    assigns.append((node.target.id, node.value))
        for _ in range(2):
            by_name: dict[str, bool] = {}
            for name, value in assigns:
                ok = self.is_static(value)
                by_name[name] = by_name.get(name, True) and ok
            for name, ok in by_name.items():
                if ok:
                    self.names.add(name)
                elif name not in protected:
                    # a protected name (config bundle like ``prims``)
                    # stays static even when rebuilt from traced parts:
                    # `prims = backends.segment_prims(g)` is python-time
                    # closure construction, not tracer data
                    self.names.discard(name)

    def is_static(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id in self.names
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return True
            return self.is_static(node.value)
        if isinstance(node, ast.Subscript):
            # shape[0], cfg.dims[i]: static iff the base is static
            return self.is_static(node.value)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is always a python-level
            # structural check, never a tracer comparison
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in node.ops):
                return True
            return (self.is_static(node.left)
                    and all(self.is_static(c) for c in node.comparators))
        if isinstance(node, ast.BoolOp):
            return all(self.is_static(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return self.is_static(node.operand)
        if isinstance(node, ast.BinOp):
            return self.is_static(node.left) and self.is_static(node.right)
        if isinstance(node, ast.IfExp):
            return (self.is_static(node.test) and self.is_static(node.body)
                    and self.is_static(node.orelse))
        if isinstance(node, ast.Call):
            return (self.is_static(node.func)
                    and all(self.is_static(a) for a in node.args
                            if not isinstance(a, ast.Starred))
                    and all(self.is_static(k.value)
                            for k in node.keywords))
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self.is_static(e) for e in node.elts)
        return False


def _np_base(node: ast.expr) -> bool:
    while isinstance(node, ast.Attribute):
        node = node.value
    return isinstance(node, ast.Name) and node.id == "np"


class _ScopeChecker(ast.NodeVisitor):
    """Apply the tracer rules inside one traced function body."""

    def __init__(self, path: str, src_lines: list[str],
                 static: _Static, findings: list[AstFinding]):
        self.path = path
        self.lines = src_lines
        self.static = static
        self.findings = findings

    def _suppressed(self, line: int, rule: str) -> bool:
        if 1 <= line <= len(self.lines):
            m = _IGNORE_RE.search(self.lines[line - 1])
            if m:
                rules = {r.strip() for r in m.group(1).split(",")}
                return rule in rules
        return False

    def _flag(self, node: ast.AST, rule: str, detail: str) -> None:
        line = getattr(node, "lineno", 0)
        if not self._suppressed(line, rule):
            self.findings.append(AstFinding(rule, self.path, line, detail))

    def visit_If(self, node: ast.If) -> None:
        if not self.static.is_static(node.test):
            self._flag(node, "tracer-branch",
                       "python `if` on a possibly-traced value — use "
                       "lax.cond / jnp.where "
                       f"(test: {ast.unparse(node.test)!r})")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        if not self.static.is_static(node.test):
            self._flag(node, "tracer-branch",
                       "python `while` on a possibly-traced value — use "
                       "lax.while_loop "
                       f"(test: {ast.unparse(node.test)!r})")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in ("float", "int", "bool"):
            if node.args and not self.static.is_static(node.args[0]):
                self._flag(node, "tracer-cast",
                           f"`{fn.id}()` on a possibly-traced value "
                           "forces a host sync at trace time "
                           f"({ast.unparse(node.args[0])!r})")
        if isinstance(fn, ast.Attribute) and fn.attr in ("item", "tolist"):
            if not self.static.is_static(fn.value):
                self._flag(node, "host-sync",
                           f"`.{fn.attr}()` on a possibly-traced value "
                           "is a device->host round-trip "
                           f"({ast.unparse(fn.value)!r})")
        if isinstance(fn, ast.Attribute) and _np_base(fn) \
                and fn.attr not in ("int32", "int64", "float32", "inf",
                                    "bool_", "uint32", "dtype"):
            dyn = [a for a in node.args
                   if not isinstance(a, ast.Starred)
                   and not self.static.is_static(a)]
            if dyn:
                self._flag(node, "numpy-in-traced",
                           f"`np.{fn.attr}(...)` with a possibly-traced "
                           "argument constant-folds the tracer — use jnp "
                           f"({ast.unparse(dyn[0])!r})")
        self.generic_visit(node)

    # nested defs inherit the scope's rules; their params join the
    # traced (non-static) name set implicitly by not being added.
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.generic_visit(node)


def _iter_scopes(tree: ast.Module, patterns: tuple[str, ...]):
    """Yield (qualname, FunctionDef) for every traced scope in a file."""
    from fnmatch import fnmatch

    def walk(body, prefix, active):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{node.name}"
                hit = active or any(
                    fnmatch(node.name, p) or fnmatch(qual, p)
                    for p in patterns)
                if hit:
                    yield qual, node
                # descend either way: nested defs may match on their own
                yield from walk(node.body, f"{qual}.", hit)
            elif isinstance(node, ast.ClassDef):
                yield from walk(node.body, f"{node.name}.", active)

    yield from walk(tree.body, "", False)


def _scope_static_names(fn: ast.FunctionDef) -> frozenset[str]:
    """Static names for one scope: the global bases minus any parameter
    that shadows them (a param is traced data unless it is a known
    static bundle like ``cfg``/``prims``)."""
    params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                              + fn.args.kwonlyargs)}
    if fn.args.vararg:
        params.add(fn.args.vararg.arg)
    keep_static = {"cfg", "config", "prims", "self", "cls", "interpret",
                   "backend", "dtype", "cap", "axis", "mesh",
                   "use_pallas", "warm"}
    return frozenset((STATIC_BASES | keep_static) - (params - keep_static))


def lint_file(path: Path, repo_root: Path,
              patterns: tuple[str, ...]) -> list[AstFinding]:
    rel = str(path.relative_to(repo_root))
    src = path.read_text()
    tree = ast.parse(src, filename=rel)
    lines = src.splitlines()
    # module-level defs/classes are python-time objects: calling one
    # with all-static args stays static (`_use_pallas(use_pallas)`)
    module_names = frozenset(
        node.name for node in tree.body
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)))
    findings: list[AstFinding] = []
    seen_spans: set[tuple[int, int]] = set()
    for _qual, fn in _iter_scopes(tree, patterns):
        span = (fn.lineno, fn.end_lineno or fn.lineno)
        # a nested def already covered by its parent scope would be
        # visited twice; lint only the outermost matching span
        if any(a <= span[0] and span[1] <= b for a, b in seen_spans):
            continue
        seen_spans.add(span)
        protected = _scope_static_names(fn)
        static = _Static(protected | module_names)
        static.absorb_assignments(fn.body, protected=protected)
        checker = _ScopeChecker(rel, lines, static, findings)
        for stmt in fn.body:
            checker.visit(stmt)
    return findings


def _lint_graphdelta(repo_root: Path) -> list[AstFinding]:
    """GraphDelta must be built via make_delta (validates weights on the
    host *before* device put), everywhere except its defining module."""
    findings: list[AstFinding] = []
    allow = {"src/repro/core/sssp/dynamic.py"}
    for path in sorted((repo_root / "src" / "repro").rglob("*.py")):
        rel = str(path.relative_to(repo_root))
        if rel in allow:
            continue
        src = path.read_text()
        if "GraphDelta(" not in src:
            continue
        tree = ast.parse(src, filename=rel)
        lines = src.splitlines()
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "GraphDelta"):
                line = node.lineno
                if 1 <= line <= len(lines) and _IGNORE_RE.search(
                        lines[line - 1]):
                    m = _IGNORE_RE.search(lines[line - 1])
                    if "raw-graphdelta" in m.group(1):
                        continue
                findings.append(AstFinding(
                    "raw-graphdelta", rel, line,
                    "GraphDelta constructed directly — use make_delta "
                    "(validates edge ids / weight positivity on the "
                    "host before device put)"))
    return findings


def run(repo_root: str | Path) -> list[AstFinding]:
    """Run every AST rule over the repo; returns all findings."""
    root = Path(repo_root)
    findings: list[AstFinding] = []
    for rel, patterns in TRACED_SCOPES.items():
        path = root / rel
        if path.exists():
            findings.extend(lint_file(path, root, patterns))
    findings.extend(_lint_graphdelta(root))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))
