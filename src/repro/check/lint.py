"""Layer 2: AST-based codebase lint enforcing the repo's unit discipline.

The verifier (layer 1) proves individual IR objects; this layer proves the
*source* keeps the conventions that make those proofs meaningful:

  * RPL100 — words are the model currency; multiplying by a dtype width
    (``word_bytes`` / ``in_bytes`` / ``out_bytes`` / ``acc_bytes``) is a unit
    conversion and belongs only in the byte-model modules (``plan.traffic``,
    ``plan.gemm_model``, ``sim``, ...). Everywhere else consumes
    ``TrafficReport.bytes`` / ``Tensor.nbytes``.
  * RPL101 — per-access energy constants live in ``roofline/constants.py``
    and nowhere else; a second definition silently forks the energy model.
  * RPL102 — a ``*_words`` name must never be assigned straight from a
    ``*_bytes`` name (or vice versa): that is a unit error the type system
    cannot see.
  * RPL103 — ``pl.pallas_call`` is invoked in exactly one place
    (``repro.kernels.launch.run``): every kernel goes through a `LaunchPlan`
    so the RPC04x dataflow analyzer certifies the launch that actually runs.
  * RPL104 — ad-hoc wall-clock reads (``time.perf_counter`` & co) live only
    in ``repro.obs``, ``benchmarks/`` and the planserve load generator;
    everywhere else measures through ``obs.Stopwatch`` so the interval can
    double as a trace span.
  * RPL110 — the pre-`repro.plan` shims (``repro.core.bwmodel``,
    ``repro.core.partitioner``) are deprecated import surfaces.

Rules are plain data (`LintRule`): a predicate over the repo-relative path
plus an AST visitor returning `Diagnostic`s. The repo's concrete rule set —
with its allowlists — lives in ``tools/check_rules.py`` and is loaded by
path so the conventions stay versioned next to the code they govern;
`default_rules()` is the built-in fallback with the same semantics.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import importlib.util
import pathlib
from typing import Callable, Iterable, List, Optional, Sequence

from repro.check.diagnostics import Diagnostic

WIDTH_NAMES = frozenset(
    {"word_bytes", "in_bytes", "out_bytes", "acc_bytes", "elem_bytes"})

#: modules allowed to convert words -> bytes (repo-relative glob patterns)
BYTE_MODEL_MODULES = (
    "src/repro/plan/traffic.py",
    "src/repro/plan/gemm_model.py",
    "src/repro/plan/graph.py",
    "src/repro/plan/netplan.py",
    "src/repro/plan/objectives.py",
    "src/repro/plan/schedule.py",
    "src/repro/plan/workload.py",
    "src/repro/sim/*",
    "src/repro/roofline/*",
    "src/repro/check/*",
    "src/repro/obs/export.py",
)

ENERGY_CONSTANT_HOME = ("src/repro/roofline/constants.py",)

#: the only package that may call pl.pallas_call — everything else goes
#: through a LaunchPlan so the dataflow analyzer sees the launch that runs
KERNEL_LAUNCH_HOME = ("src/repro/kernels/*",)

DEPRECATED_MODULES = ("repro.core.bwmodel", "repro.core.partitioner")
DEPRECATED_IMPORT_OK = ("src/repro/core/*",)

#: the only homes for raw wall-clock reads: the tracing package itself,
#: benchmark harnesses, and the planner-service load generator (it wall-times
#: micro-batches on a virtual clock). Everything else uses obs.Stopwatch,
#: so every measured interval is also a potential trace span.
WALL_TIMING_HOME = ("src/repro/obs/*", "benchmarks/*",
                    "src/repro/launch/planserve.py")
WALL_CLOCK_FNS = frozenset(
    {"perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns"})


@dataclasses.dataclass(frozen=True)
class LintRule:
    """One lint rule: a code, a path filter, and an AST visitor."""

    code: str
    visit: Callable[[ast.Module, str], List[Diagnostic]]
    exempt: tuple[str, ...] = ()     # repo-relative fnmatch patterns

    def run(self, tree: ast.Module, rel_path: str) -> List[Diagnostic]:
        if any(fnmatch.fnmatch(rel_path, pat) for pat in self.exempt):
            return []
        return self.visit(tree, rel_path)


def _name_of(node: ast.expr) -> Optional[str]:
    """Terminal identifier of a Name/Attribute chain, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


# --------------------------------------------------------------- RPL100
def _visit_raw_byte_arith(tree: ast.Module, rel: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
            for side in (node.left, node.right):
                name = _name_of(side)
                if name in WIDTH_NAMES:
                    out.append(Diagnostic(
                        "RPL100", rel,
                        f"multiplication by dtype width {name!r} outside "
                        f"the byte-model modules",
                        file=rel, line=node.lineno))
                    break
    return out


def raw_byte_arith_rule(
        allowed: Sequence[str] = BYTE_MODEL_MODULES) -> LintRule:
    return LintRule("RPL100", _visit_raw_byte_arith, tuple(allowed))


# --------------------------------------------------------------- RPL101
def _has_number(node: ast.expr) -> bool:
    return any(isinstance(n, ast.Constant) and isinstance(n.value, (int, float))
               and not isinstance(n.value, bool) for n in ast.walk(node))


def _visit_magic_energy(tree: ast.Module, rel: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        targets: List[ast.expr] = []
        value: Optional[ast.expr] = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        for t in targets:
            name = _name_of(t)
            if name and name.startswith("ENERGY_PJ_") and value is not None \
                    and _has_number(value):
                out.append(Diagnostic(
                    "RPL101", rel,
                    f"energy constant {name} defined outside "
                    f"roofline/constants.py",
                    file=rel, line=node.lineno))
    return out


def magic_energy_rule(
        allowed: Sequence[str] = ENERGY_CONSTANT_HOME) -> LintRule:
    return LintRule("RPL101", _visit_magic_energy, tuple(allowed))


# --------------------------------------------------------------- RPL102
def _unit_of(name: Optional[str]) -> Optional[str]:
    if name is None:
        return None
    if name.endswith("_words") or name == "words":
        return "words"
    if name.endswith("_bytes") or name in ("bytes", "nbytes"):
        return "bytes"
    return None


def _visit_cross_assign(tree: ast.Module, rel: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        pairs: List[tuple[ast.expr, ast.expr]] = []
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            pairs.append((node.targets[0], node.value))
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            pairs.append((node.target, node.value))
        elif isinstance(node, ast.keyword) and node.arg is not None:
            pairs.append((ast.Name(id=node.arg), node.value))
        for target, value in pairs:
            tu = _unit_of(_name_of(target))
            vu = _unit_of(_name_of(value))   # bare name/attr only, by design
            if tu and vu and tu != vu:
                out.append(Diagnostic(
                    "RPL102", rel,
                    f"{_name_of(target)} ({tu}) assigned from "
                    f"{_name_of(value)} ({vu}) with no unit conversion",
                    file=rel, line=value.lineno))
    return out


def cross_assign_rule() -> LintRule:
    return LintRule("RPL102", _visit_cross_assign)


# --------------------------------------------------------------- RPL103
def _visit_raw_pallas(tree: ast.Module, rel: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _name_of(node.func) == "pallas_call":
            out.append(Diagnostic(
                "RPL103", rel,
                "pl.pallas_call outside repro.kernels bypasses the "
                "LaunchPlan the dataflow analyzer (RPC04x) certifies",
                file=rel, line=node.lineno))
    return out


def raw_pallas_rule(
        allowed: Sequence[str] = KERNEL_LAUNCH_HOME) -> LintRule:
    return LintRule("RPL103", _visit_raw_pallas, tuple(allowed))


# --------------------------------------------------------------- RPL104
def _visit_adhoc_timing(tree: ast.Module, rel: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _name_of(node.func) \
                in WALL_CLOCK_FNS:
            out.append(Diagnostic(
                "RPL104", rel,
                f"ad-hoc wall-clock timing ({_name_of(node.func)}) outside "
                f"repro.obs / benchmarks — use obs.Stopwatch (or a span)",
                file=rel, line=node.lineno))
    return out


def adhoc_timing_rule(
        allowed: Sequence[str] = WALL_TIMING_HOME) -> LintRule:
    return LintRule("RPL104", _visit_adhoc_timing, tuple(allowed))


# --------------------------------------------------------------- RPL105
#: where swallowed exceptions are tolerable: harnesses and scripts, not the
#: library — `bare_except_rule` exempts these so RPL105 governs src/repro
NON_LIBRARY_CODE = ("benchmarks/*", "examples/*", "tools/*")


def _noop_body(body: Sequence[ast.stmt]) -> bool:
    """True when a handler body does nothing: only pass / ... / a string."""
    for stmt in body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant) \
                and (isinstance(stmt.value.value, str)
                     or stmt.value.value is Ellipsis):
            continue
        return False
    return True


def _visit_bare_except(tree: ast.Module, rel: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            out.append(Diagnostic(
                "RPL105", rel,
                "bare `except:` swallows every failure — catch a typed "
                "repro.errors exception (or re-raise)",
                file=rel, line=node.lineno))
            continue
        caught = (node.type.elts if isinstance(node.type, ast.Tuple)
                  else [node.type])
        broad = any(_name_of(c) in ("Exception", "BaseException")
                    for c in caught)
        if broad and _noop_body(node.body):
            out.append(Diagnostic(
                "RPL105", rel,
                "`except Exception: pass` silently swallows faults — handle "
                "a typed repro.errors exception or re-raise",
                file=rel, line=node.lineno))
    return out


def bare_except_rule(
        allowed: Sequence[str] = NON_LIBRARY_CODE) -> LintRule:
    return LintRule("RPL105", _visit_bare_except, tuple(allowed))


# --------------------------------------------------------------- RPL110
def _visit_deprecated_import(tree: ast.Module, rel: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for node in ast.walk(tree):
        hit: Optional[str] = None
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name in DEPRECATED_MODULES:
                    hit = alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module in DEPRECATED_MODULES:
                hit = node.module
            elif node.module == "repro.core":
                bad = {a.name for a in node.names} & {"bwmodel", "partitioner"}
                if bad:
                    hit = f"repro.core.{bad.pop()}"
        if hit:
            out.append(Diagnostic(
                "RPL110", rel,
                f"import of deprecated shim {hit}",
                file=rel, line=node.lineno))
    return out


def deprecated_import_rule(
        allowed: Sequence[str] = DEPRECATED_IMPORT_OK) -> LintRule:
    return LintRule("RPL110", _visit_deprecated_import, tuple(allowed))


def default_rules() -> List[LintRule]:
    return [raw_byte_arith_rule(), magic_energy_rule(), cross_assign_rule(),
            raw_pallas_rule(), adhoc_timing_rule(), bare_except_rule(),
            deprecated_import_rule()]


# ----------------------------------------------------------------- driver
LINT_ROOTS = ("src", "benchmarks", "examples", "tools", "chip_smoke.py")


def find_repo_root(start: Optional[pathlib.Path] = None) -> pathlib.Path:
    """Walk up from `start` (default: this file) to the checkout root —
    the first directory holding pyproject.toml."""
    here = (start or pathlib.Path(__file__)).resolve()
    for cand in [here] + list(here.parents):
        if (cand / "pyproject.toml").is_file():
            return cand
    return pathlib.Path.cwd()


def load_rules(repo_root: Optional[pathlib.Path] = None) -> List[LintRule]:
    """The repo's rule set from tools/check_rules.py, else the built-ins."""
    root = repo_root or find_repo_root()
    rules_py = root / "tools" / "check_rules.py"
    if not rules_py.is_file():
        return default_rules()
    spec = importlib.util.spec_from_file_location("check_rules", rules_py)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rules = list(getattr(mod, "RULES"))
    assert all(isinstance(r, LintRule) for r in rules), rules_py
    return rules


def lint_file(path: pathlib.Path, rel: str,
              rules: Sequence[LintRule]) -> List[Diagnostic]:
    try:
        tree = ast.parse(path.read_text(), filename=rel)
    except SyntaxError as exc:     # pragma: no cover - repo parses
        return [Diagnostic("RPL100", rel, f"unparseable: {exc}",
                           file=rel, line=exc.lineno or 1)]
    out: List[Diagnostic] = []
    for rule in rules:
        out += rule.run(tree, rel)
    return out


def lint_repo(repo_root: Optional[pathlib.Path] = None,
              rules: Optional[Sequence[LintRule]] = None,
              roots: Iterable[str] = LINT_ROOTS) -> List[Diagnostic]:
    """Lint every .py under the repo's source roots, and the root-level
    scripts among them (tests are exempt: they corrupt units on purpose)."""
    root = repo_root or find_repo_root()
    rules = load_rules(root) if rules is None else list(rules)
    out: List[Diagnostic] = []
    for sub in roots:
        base = root / sub
        for py in [base] if base.is_file() else sorted(base.rglob("*.py")):
            rel = py.relative_to(root).as_posix()
            out += lint_file(py, rel, rules)
    return out
