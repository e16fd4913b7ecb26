"""The full analysis sweep: every ExecSpec combo x every subsystem target,
plus the project rules, folded into one JSON-able report.

``run_sweep`` is what ``python -m repro.analysis`` (and CI) runs.  Shape::

    {"ok": bool,                  # no *unsuppressed* error findings
     "findings": [Finding.to_dict(), ...],  # suppressed ones carry
                                            # severity "suppressed" + why
     "targets": ["<spec>:<target>", ...],   # every trace analyzed
     "skipped": ["<reason>", ...],          # impossible combos, with why
     "rules":   {name: {kind, description}, ...},
     "audits":  {key: {...}, ...},          # registered check_vma audits
     "determinism_audits": {key: {...}, ...}}

Plan-time analysis is suspended for the duration
(``REPRO_ANALYSIS=suspend`` — the internal value, not the ``0`` escape
hatch, which now still computes findings for telemetry): the sweep runs
the same rules itself over a superset of the plan-time targets, and a
plan-time :class:`AnalysisError` mid-sweep would surface as an
untraceable-target warning instead of the real findings.

**Baseline suppressions** (``analysis-baseline.json`` at the repo root,
or ``--baseline``): each entry matches findings by ``rule`` / ``target`` /
``where`` fnmatch globs and must carry a ``reason`` and an ``expires``
date (ISO ``YYYY-MM-DD``).  A matched error finding is downgraded to
severity ``"suppressed"`` (reported, not fatal); an entry past its expiry
is itself an error — suppressions are leases, not landfills.
"""
from __future__ import annotations

import datetime
import fnmatch
import json
import os
from typing import Any, Iterator

from .rules import Finding, project_rules


def _repo_root() -> str:
    # .../src/repro/analysis/report.py -> repo root
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


BASELINE_FILE = "analysis-baseline.json"


def load_baseline(path: str) -> list[dict]:
    """Suppression entries from a baseline file (missing file: none)."""
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    entries = doc.get("suppressions", []) if isinstance(doc, dict) else doc
    if not isinstance(entries, list):
        raise ValueError(f"{path}: 'suppressions' must be a list")
    return entries


def _baseline_findings(entries: list[dict], path: str,
                       today: datetime.date) -> list[Finding]:
    """Malformed / expired suppression entries, as error findings."""
    out: list[Finding] = []
    for i, e in enumerate(entries):
        where = f"{os.path.basename(path)}[{i}]"
        reason = str(e.get("reason", "")).strip()
        raw_exp = str(e.get("expires", "")).strip()
        if not reason:
            out.append(Finding(
                rule="baseline", severity="error", target=path,
                message="suppression entry carries no reason — a "
                        "suppression is an argued exception, not a mute "
                        "button", where=where))
        try:
            expires = datetime.date.fromisoformat(raw_exp)
        except ValueError:
            out.append(Finding(
                rule="baseline", severity="error", target=path,
                message=f"suppression entry has no parseable 'expires' "
                        f"date (got {raw_exp!r}; want YYYY-MM-DD) — "
                        f"suppressions are leases and leases end",
                where=where))
            continue
        if expires < today:
            out.append(Finding(
                rule="baseline", severity="error", target=path,
                message=f"suppression expired {expires.isoformat()} "
                        f"(rule={e.get('rule', '*')!r} "
                        f"target={e.get('target', '*')!r}): fix the "
                        f"finding or renew the lease with a fresh "
                        f"review", where=where))
    return out


def _matches(entry: dict, f: Finding) -> bool:
    return (fnmatch.fnmatch(f.rule, str(entry.get("rule", "*")))
            and fnmatch.fnmatch(f.target, str(entry.get("target", "*")))
            and fnmatch.fnmatch(f.where, str(entry.get("where", "*"))))


def apply_baseline(findings: list[Finding], entries: list[dict],
                   today: datetime.date | None = None) -> list[dict]:
    """Finding dicts with baseline-matched errors downgraded to
    ``"suppressed"`` (the suppression's reason attached).  Expired
    entries never match — their error finding keeps the pressure on."""
    today = today or datetime.date.today()

    def live(e: dict) -> bool:
        try:
            return datetime.date.fromisoformat(
                str(e.get("expires", ""))) >= today
        except ValueError:
            return False

    live_entries = [e for e in entries if live(e)]
    out: list[dict] = []
    for f in findings:
        d = f.to_dict()
        if f.severity == "error":
            hit = next((e for e in live_entries if _matches(e, f)), None)
            if hit is not None:
                d["severity"] = "suppressed"
                d["suppressed_reason"] = str(hit.get("reason", ""))
                d["suppressed_until"] = str(hit.get("expires", ""))
        out.append(d)
    return out


def run_sweep(repo_root: str | None = None,
              baseline_path: str | None = None) -> dict:
    from repro.engine.planner import plan

    from .audit import all_audits, all_determinism_audits
    from .rules import all_rules, analyze_jaxpr
    from .targets import (analyze_plan, distributed_targets, serve_targets,
                          stream_targets, sweep_specs)

    root = repo_root or _repo_root()
    baseline = baseline_path or os.path.join(root, BASELINE_FILE)
    findings: list[Finding] = []
    targets_run: list[str] = []
    skipped: list[str] = []

    prev = os.environ.get("REPRO_ANALYSIS")
    os.environ["REPRO_ANALYSIS"] = "suspend"
    try:
        for spec in sweep_specs():
            label = spec.describe()
            pl = plan(None, spec)

            plan_findings = analyze_plan(pl)
            findings.extend(plan_findings)
            targets_run.append(f"{label}:batch")

            for name, thunk in _collect(
                    (distributed_targets, pl), (stream_targets, pl),
                    skipped=skipped, label=label):
                target = f"{label}:{name}"
                targets_run.append(target)
                findings.extend(_analyze_one(target, thunk, analyze_jaxpr))

            serve_t, serve_skip = serve_targets(spec)
            skipped.extend(serve_skip)
            for name, thunk in serve_t:
                target = f"{label}:{name}"
                targets_run.append(target)
                findings.extend(_analyze_one(target, thunk, analyze_jaxpr))
    finally:
        if prev is None:
            os.environ.pop("REPRO_ANALYSIS", None)
        else:
            os.environ["REPRO_ANALYSIS"] = prev

    for rule in project_rules():
        findings.extend(rule.check_project(root))

    today = datetime.date.today()
    entries = load_baseline(baseline)
    findings.extend(_baseline_findings(entries, baseline, today))
    finding_dicts = apply_baseline(findings, entries, today)

    audits = {k: {"reason": a.reason, "collectives": list(a.collectives)}
              for k, a in sorted(all_audits().items())}
    det_audits = {k: {"reason": a.reason, "ops": list(a.ops),
                      "site": f"{a.file_name}:{a.function_name}"}
                  for k, a in sorted(all_determinism_audits().items())}
    rules_meta: dict[str, dict] = {}
    for r in all_rules():
        meta = rules_meta.setdefault(
            r.name, {"kind": r.kind, "description": r.description})
        if r.kind not in meta["kind"].split("+"):
            meta["kind"] += f"+{r.kind}"
    errors = [d for d in finding_dicts if d["severity"] == "error"]
    return {"ok": not errors,
            "findings": finding_dicts,
            "targets": sorted(set(targets_run)),
            "skipped": sorted(set(skipped)),
            "rules": rules_meta,
            "audits": audits,
            "determinism_audits": det_audits}


def _collect(*sources: tuple, skipped: list[str],
             label: str) -> Iterator[tuple[str, Any]]:
    for fn, pl in sources:
        tgts, skip = fn(pl)
        skipped.extend(f"{label}:{s}" for s in skip)
        yield from tgts


def _analyze_one(target: str, thunk: Any,
                 analyze_jaxpr: Any) -> list[Finding]:
    from .targets import _trace_failure

    try:
        closed = thunk()
    except Exception as exc:                 # noqa: BLE001
        return [_trace_failure(target, exc)]
    return analyze_jaxpr(target, closed)
