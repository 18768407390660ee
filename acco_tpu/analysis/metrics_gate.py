"""Metrics-gate: every telemetry call site names a declared metric/span.

The telemetry registry and tracer are closed-world at *runtime*
(``UndeclaredMetricError`` / ``UndeclaredSpanError``), but a runtime
check only fires on paths a test actually executes — an emit of a
misspelled name on the preemption path would ship silently. This gate
is the static mirror: an AST walk over the production sources resolving
every literal-named telemetry call against the declarations, the same
pairing the dtype policy has with the sharding rule tables.

Checked call shapes (receiver names are irrelevant — the method name +
a literal first argument is the contract):

- ``*.emit("name", …)`` / ``emit("name", …)`` and every literal key of
  ``*.emit_many({"name": …})`` → must be declared in
  :data:`acco_tpu.telemetry.metrics.DECLARED`;
- ``*.span("name", …)`` / ``*.complete_event("name", …)`` /
  ``*.instant("name", …)`` → must be declared in
  :data:`acco_tpu.telemetry.trace.SPAN_NAMES`, unless the call's
  ``cat`` is a :data:`~acco_tpu.telemetry.trace.FREE_CATEGORIES` member
  (the conftest's pytest-nodeid events);
- ``*.named_scope("name")`` (``jax.named_scope``) → must be one of
  :data:`acco_tpu.telemetry.trace.ALL_DEVICE_SCOPES`: the benchmark's device
  metrics select ops by these names, so a scope outside the list is time
  no metric owns. Here a non-literal argument is a finding too: there is
  no runtime check behind this one.

Dynamic names (a variable first argument) are left to the runtime
check — the closed world still catches them on first execution; this
gate exists so the *spelled-out* names, the overwhelmingly common case,
fail the PR instead of the run. Pure stdlib AST, no jax import (the
telemetry package itself is jax-free by contract).

The gate fires the other way too (:func:`check_repo`): a name in
``DECLARED`` that no call site under :data:`PRODUCTION_PATHS` emits is an
``orphaned-metric`` — a measurement nothing takes. A call site emits a
name when it spells it: as the literal, as either branch of a conditional
expression of literals, as a literal key of ``emit_many``'s dict, or as
``"prefix_" + key`` where the prefix is a literal and the rest of the
declared name is spelled as a dict literal's key somewhere in the walk
(the trainer's ``"train_" + name`` over ``LlamaModel._aux_terms``' keys).
An orphan is deleted from ``DECLARED``, never exempted here.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from acco_tpu.analysis.host_lint import DEFAULT_EXCLUDE_DIRS, Finding
from acco_tpu.telemetry.metrics import REGISTRY
from acco_tpu.telemetry.trace import ALL_DEVICE_SCOPES, FREE_CATEGORIES, SPAN_NAMES

METRIC_METHODS = {"emit"}
METRIC_MANY_METHODS = {"emit_many"}
SPAN_METHODS = {"span", "complete_event", "instant"}
SCOPE_METHODS = {"named_scope"}

# What "the tree emits a metric" means: the program, its tools and its
# entry points. Tests and fixtures emit to exercise the registry, the
# benchmark reads the registry; neither keeps a declaration alive.
PRODUCTION_PATHS = ("acco_tpu", "tools", "main.py", "serve.py", "chip_smoke.py")


@dataclass
class MetricsGateReport:
    findings: list[Finding] = field(default_factory=list)
    checked: int = 0  # literal-named call sites resolved
    emitted: set[str] = field(default_factory=set)  # metric names spelled at an emit
    prefixes: set[str] = field(default_factory=set)  # emit("prefix_" + expr, ...)
    dict_keys: set[str] = field(default_factory=set)  # every dict literal's str keys

    def orphaned(self, declared) -> list[str]:
        """Declared names no walked call site emits (module docstring)."""
        return sorted(
            name for name in declared
            if name not in self.emitted and not any(
                name.startswith(p) and name[len(p):] in self.dict_keys
                for p in self.prefixes
            )
        )

    @property
    def ok(self) -> bool:
        return not self.findings

    def summary(self) -> str:
        if self.ok:
            return (
                f"{self.checked} literal telemetry call sites, "
                "all names declared, every declared metric emitted"
            )
        return (
            f"{len(self.findings)} finding(s) across "
            f"{self.checked} literal call sites"
        )


def _method_name(node: ast.Call) -> str | None:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _literal_str(node: ast.AST | None) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _literal_names(node: ast.AST | None) -> list[str]:
    """The names an emit's first argument spells: a literal, or a
    conditional expression whose branches are (``"a" if c else "b"``)."""
    if isinstance(node, ast.IfExp):
        return _literal_names(node.body) + _literal_names(node.orelse)
    name = _literal_str(node)
    return [] if name is None else [name]


def _span_cat(node: ast.Call) -> str | None:
    """The call's ``cat`` value when given as a literal: the keyword, or
    span()/instant()'s second positional argument."""
    for kw in node.keywords:
        if kw.arg == "cat":
            return _literal_str(kw.value)
    if _method_name(node) in ("span", "instant") and len(node.args) >= 2:
        return _literal_str(node.args[1])
    return None


class _TelemetryCallVisitor(ast.NodeVisitor):
    def __init__(
        self, path: str, declared: frozenset, report: MetricsGateReport
    ) -> None:
        self.path = path
        self.declared = declared
        self.report = report

    def _check_metric(self, node: ast.Call, name: str) -> None:
        self.report.checked += 1
        self.report.emitted.add(name)
        if name not in self.declared:
            self.report.findings.append(Finding(
                self.path, node.lineno, "undeclared-metric",
                f"emit of {name!r}, which is not declared in "
                "acco_tpu/telemetry/metrics.py DECLARED (closed world: "
                "add a MetricSpec or fix the spelling)",
            ))

    def _check_span(self, node: ast.Call, name: str) -> None:
        self.report.checked += 1
        if name not in SPAN_NAMES:
            self.report.findings.append(Finding(
                self.path, node.lineno, "undeclared-span",
                f"span/event name {name!r} is not in telemetry.trace."
                "SPAN_NAMES (closed world: declare it there or fix the "
                "spelling)",
            ))

    def _check_scope(self, node: ast.Call, name: str | None) -> None:
        self.report.checked += 1
        if name not in ALL_DEVICE_SCOPES:
            self.report.findings.append(Finding(
                self.path, node.lineno, "undeclared-scope",
                f"named_scope of {name!r}, which is not a literal member of "
                "telemetry.trace.ALL_DEVICE_SCOPES (closed world: declare it "
                "there, and say in PERF.md which metric reads it)",
            ))

    def visit_Call(self, node: ast.Call) -> None:
        meth = _method_name(node)
        if meth in METRIC_METHODS and node.args:
            arg = node.args[0]
            for name in _literal_names(arg):
                self._check_metric(node, name)
            if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
                prefix = _literal_str(arg.left)
                if prefix:
                    self.report.prefixes.add(prefix)
        elif meth in METRIC_MANY_METHODS and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Dict):
                for key in arg.keys:
                    name = _literal_str(key)
                    if name is not None:
                        self._check_metric(node, name)
        elif meth in SPAN_METHODS and node.args:
            name = _literal_str(node.args[0])
            if name is not None:
                cat = _span_cat(node)
                if cat not in FREE_CATEGORIES:
                    self._check_span(node, name)
        elif meth in SCOPE_METHODS and node.args:
            self._check_scope(node, _literal_str(node.args[0]))
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict) -> None:
        for key in node.keys:
            name = _literal_str(key)
            if name is not None:
                self.report.dict_keys.add(name)
        self.generic_visit(node)


def check_file(
    path: str,
    source: str | None = None,
    report: MetricsGateReport | None = None,
) -> MetricsGateReport:
    report = report if report is not None else MetricsGateReport()
    if source is None:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        report.findings.append(Finding(
            path, exc.lineno or 0, "syntax-error", str(exc)
        ))
        return report
    declared = frozenset(REGISTRY.declared_names())
    _TelemetryCallVisitor(path, declared, report).visit(tree)
    return report


def check_paths(
    paths: list[str],
    exclude_dirs: tuple[str, ...] = DEFAULT_EXCLUDE_DIRS,
) -> MetricsGateReport:
    """Walk files/directories (``.py`` only) and resolve every
    literal-named telemetry call site."""
    report = MetricsGateReport()
    for root in paths:
        if os.path.isfile(root):
            check_file(root, report=report)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d not in exclude_dirs]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    check_file(os.path.join(dirpath, fn), report=report)
    return report


def check_repo(repo_root: str) -> MetricsGateReport:
    """Both directions over :data:`PRODUCTION_PATHS`: every spelled name is
    declared, and every declared name is spelled at some emit."""
    report = check_paths([
        os.path.join(repo_root, rel) for rel in PRODUCTION_PATHS
    ])
    for name in report.orphaned(REGISTRY.declared_names()):
        report.findings.append(Finding(
            "acco_tpu/telemetry/metrics.py", 0, "orphaned-metric",
            f"{name!r} is declared in DECLARED and no call site under "
            f"{', '.join(PRODUCTION_PATHS)} emits it: delete the declaration "
            "(or emit the metric where it is measured)",
        ))
    return report
