"""R2 check-rep-audit: every ``check_vma=False`` shard_map body carries an
explicit :func:`repro.analysis.audit.audit_check_rep` annotation.

``check_vma=False`` switches off the one JAX mechanism that would notice a
shard body producing non-replicated values where replication is claimed —
and this tree runs *every* kernel-backed shard body that way, because
``pallas_call`` has no replication rule.  The audit decorator records the
human argument for why that is safe (which collectives make the body's
outputs well-defined per member); R2 makes the annotation mandatory, so a
new ``check_vma=False`` site cannot ship with the argument still in the
author's head.

The check is a source scan (the jaxpr has no trace of where a body
function was defined): for each ``shard_map(...)`` call whose
``check_vma`` keyword is anything but a literal ``True`` (absent =
default True = fine), resolve the body argument to its ``def`` —

* a function defined in an enclosing scope, or
* the nearest preceding assignment ``body = _make_xyz(...)`` whose factory
  is a module-level function returning an inner ``def`` (the
  ``distributed/dpc.py`` phase-factory idiom)

— and require an ``audit_check_rep`` decorator on it.  Unresolvable bodies
are findings too (conservative: if the scanner cannot see the def, a
reviewer probably cannot either).

``src/repro/analysis`` itself is excluded: the analyzer builds throwaway
shard_map probes of *other* modules' bodies (the R1 gate, the sweep
targets); those are analysis inputs, not production shard bodies.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Any

from .rules import Finding, Rule, register_rule

RULE_NAME = "R2-check-rep-audit"
_DECORATOR = "audit_check_rep"


def _is_shard_map(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Name) and f.id == "shard_map") or \
           (isinstance(f, ast.Attribute) and f.attr == "shard_map")


def _check_rep_maybe_false(call: ast.Call) -> bool:
    """True when the call's check_vma could evaluate to False at runtime."""
    for kw in call.keywords:
        if kw.arg == "check_vma":
            v = kw.value
            return not (isinstance(v, ast.Constant) and v.value is True)
    return False               # absent -> default True


def _has_audit_decorator(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        node = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(node, ast.Name) and node.id == _DECORATOR:
            return True
        if isinstance(node, ast.Attribute) and node.attr == _DECORATOR:
            return True
    return False


@dataclass
class _ScopeInfo:
    node: object                       # Module | FunctionDef
    defs: dict                         # name -> FunctionDef (direct children)
    assigns: list                      # (lineno, name, value-expr)


def _scope_infos(tree: ast.Module) -> dict:
    """Map every FunctionDef/Module to its direct child defs + assigns."""
    parents: dict = {}

    def visit(node: Any, owner: Any) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                parents.setdefault(owner, []).append(("def", child))
                visit(child, child)
            else:
                if isinstance(child, ast.Assign) and len(child.targets) == 1 \
                        and isinstance(child.targets[0], ast.Name):
                    parents.setdefault(owner, []).append(
                        ("assign", (child.lineno, child.targets[0].id,
                                    child.value)))
                visit(child, owner)

    visit(tree, tree)
    infos: dict = {}
    for owner, items in parents.items():
        defs: dict[str, ast.FunctionDef] = {}
        assigns: list[tuple[int, str, Any]] = []
        for kind, payload in items:
            if kind == "def":
                defs.setdefault(payload.name, payload)
            else:
                assigns.append(payload)
        infos[owner] = _ScopeInfo(node=owner, defs=defs, assigns=assigns)
    return infos


def _factory_inner_def(factory: ast.FunctionDef) -> ast.FunctionDef | None:
    """The inner def a factory returns (``def f(): ... ; return f``)."""
    inner = {n.name: n for n in factory.body
             if isinstance(n, ast.FunctionDef)}
    for node in ast.walk(factory):
        if isinstance(node, ast.Return) and isinstance(node.value, ast.Name):
            if node.value.id in inner:
                return inner[node.value.id]
    return None


def _resolve_body(arg: Any, scope_stack: list, infos: dict,
                  call_lineno: int) -> ast.FunctionDef | None:
    """Resolve a shard_map body expression to its FunctionDef, or None."""
    if not isinstance(arg, ast.Name):
        return None
    name = arg.id
    # 1. a def visible in an enclosing scope
    for scope in reversed(scope_stack):
        info = infos.get(scope)
        if info and name in info.defs:
            return info.defs[name]
    # 2. nearest preceding `name = factory(...)` in an enclosing scope,
    #    where factory is a resolvable def returning an inner def
    for scope in reversed(scope_stack):
        info = infos.get(scope)
        if not info:
            continue
        cands = [(ln, val) for ln, nm, val in info.assigns
                 if nm == name and ln <= call_lineno]
        if not cands:
            continue
        _, val = max(cands, key=lambda c: c[0])
        if isinstance(val, ast.Call) and isinstance(val.func, ast.Name):
            for fscope in reversed(scope_stack):
                finfo = infos.get(fscope)
                if finfo and val.func.id in finfo.defs:
                    return _factory_inner_def(finfo.defs[val.func.id])
        return None
    return None


def scan_module(path: str, rel: str) -> list[Finding]:
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    infos = _scope_infos(tree)
    findings: list[Finding] = []

    def visit(node: Any, stack: list) -> None:
        for child in ast.iter_child_nodes(node):
            new_stack = stack + [child] \
                if isinstance(child, ast.FunctionDef) else stack
            if isinstance(child, ast.Call) and _is_shard_map(child) \
                    and _check_rep_maybe_false(child):
                where = f"{rel}:{child.lineno}"
                body = child.args[0] if child.args else None
                fn = _resolve_body(body, stack, infos, child.lineno)
                if fn is None:
                    findings.append(Finding(
                        rule=RULE_NAME, severity="error", target=rel,
                        message=("shard_map with check_vma that may be "
                                 "False has a body this scanner cannot "
                                 "resolve to a def — restructure so the "
                                 "body is a named local function (or a "
                                 "factory-returned one) and annotate it "
                                 "with @audit_check_rep"),
                        where=where))
                elif not _has_audit_decorator(fn):
                    findings.append(Finding(
                        rule=RULE_NAME, severity="error", target=rel,
                        message=(f"shard_map body `{fn.name}` runs with "
                                 f"check_vma=False but carries no "
                                 f"@audit_check_rep annotation — record "
                                 f"why the body is replication-safe "
                                 f"(see repro.analysis.audit)"),
                        where=where))
            visit(child, new_stack)

    visit(tree, [tree])
    return findings


@dataclass(frozen=True)
class CheckRepAuditRule(Rule):
    name: str = RULE_NAME
    description: str = ("every check_vma=False shard_map body must carry an "
                        "explicit @audit_check_rep replication-safety "
                        "annotation")
    kind: str = "project"

    def check_project(self, repo_root: str) -> list[Finding]:
        src = os.path.join(repo_root, "src", "repro")
        skip = os.path.join(src, "analysis")
        findings: list[Finding] = []
        for dirpath, _dirnames, filenames in os.walk(src):
            if dirpath.startswith(skip):
                continue
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, repo_root)
                findings.extend(scan_module(path, rel))
        return findings


register_rule(CheckRepAuditRule())
