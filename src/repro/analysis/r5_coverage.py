"""R5 spec-coverage: the ExecSpec axes, the planner dispatch and the test
parametrizations stay mutually exhaustive.

The failure mode this rule exists for: someone adds an axis value (a new
backend, a new precision) and it ships reachable-but-untested — the spec
validation accepts it, the planner dispatches it somewhere, and no parity
test ever parametrizes over it.  R5 cross-checks four things and fails if
any drift:

1. **pinned axis snapshot** — the live ``available_backends()`` /
   ``LAYOUTS`` / ``PRECISIONS`` must equal the snapshot reviewed into this
   rule.  Adding an axis value therefore *requires* touching this file,
   which is the review hook for the other three checks.
2. **validation-table consistency** — for the full explicit cross product,
   ``ExecSpec`` construction and ``plan()`` resolution must succeed/fail
   exactly where the documented validity table says (bf16 needs an
   ``mxu_dense`` backend; everything else is legal).
3. **planner dispatch totality** — every valid plan must land on exactly
   the documented ``worklist_strategy`` (dense / traced / device from the
   backend's ``worklist_traceable`` flag) and ``grid_sort`` contract.
4. **parity-test coverage** — every axis value literal must appear in
   ``tests/`` at least once (the parametrized parity suites), so a new
   value cannot ship without a test naming it.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any

from .rules import Finding, Rule, register_rule

RULE_NAME = "R5-spec-coverage"

# The reviewed snapshot (check 1).  When an axis grows, update this tuple
# AND the validity logic below AND the parity-test parametrizations —
# that is the point.
KNOWN_BACKENDS = ("jnp", "pallas", "pallas-interpret")
KNOWN_LAYOUTS = ("dense", "block-sparse")
KNOWN_PRECISIONS = ("f32", "bf16")


def _expected_spec_valid(backend: str, layout: str,
                         precision: str) -> bool:
    """ExecSpec construction-time validity (backend-explicit combos)."""
    del layout
    return not (precision == "bf16" and backend == "jnp")


def _expected_plan_valid(be: Any, precision: str) -> bool:
    """plan()-time validity for a resolved backend instance."""
    return precision != "bf16" or be.mxu_dense


@dataclass(frozen=True)
class SpecCoverageRule(Rule):
    name: str = RULE_NAME
    description: str = ("ExecSpec axes, validation table, planner dispatch "
                        "and parity-test parametrizations cross-checked "
                        "for exhaustiveness")
    kind: str = "project"

    def check_project(self, repo_root: str) -> list[Finding]:
        from repro.engine.planner import plan
        from repro.engine.spec import ExecSpec, LAYOUTS, PRECISIONS
        from repro.kernels.backend import available_backends, get_backend

        out: list[Finding] = []

        def finding(msg: str, where: str = "") -> None:
            out.append(Finding(rule=RULE_NAME, severity="error",
                               target="spec-coverage", message=msg,
                               where=where))

        # 1. pinned snapshot
        for label, live, known in (
                ("backends", tuple(available_backends()), KNOWN_BACKENDS),
                ("layouts", tuple(LAYOUTS), KNOWN_LAYOUTS),
                ("precisions", tuple(PRECISIONS), KNOWN_PRECISIONS)):
            if set(live) != set(known):
                finding(f"{label} changed: live {sorted(live)} vs reviewed "
                        f"snapshot {sorted(known)} — update "
                        f"analysis/r5_coverage.py (validity table + "
                        f"snapshot) and the parity-test parametrizations "
                        f"together", where="r5_coverage.py")

        # 2 + 3. validation table and dispatch, over the explicit product.
        # Plan-time jaxpr analysis is suspended for these probe plans:
        # AnalysisError subclasses ValueError and would read as validity
        # drift here, and the sweep already analyzes every combo's traces.
        # ("suspend", not "0": the 0/off escape hatch now still computes
        # findings for telemetry — probe plans must skip entirely.)
        prev = os.environ.get("REPRO_ANALYSIS")
        os.environ["REPRO_ANALYSIS"] = "suspend"
        try:
            self._check_table(plan, ExecSpec, get_backend, finding)
        finally:
            if prev is None:
                os.environ.pop("REPRO_ANALYSIS", None)
            else:
                os.environ["REPRO_ANALYSIS"] = prev

        # 4. every axis value appears in the test suites
        tests_dir = os.path.join(repo_root, "tests")
        corpus = ""
        if os.path.isdir(tests_dir):
            for fname in sorted(os.listdir(tests_dir)):
                if fname.endswith(".py"):
                    with open(os.path.join(tests_dir, fname),
                              encoding="utf-8") as fh:
                        corpus += fh.read()
        for value in (*KNOWN_BACKENDS, *KNOWN_LAYOUTS, *KNOWN_PRECISIONS):
            if f'"{value}"' not in corpus and f"'{value}'" not in corpus:
                finding(f"axis value {value!r} appears in no test under "
                        f"tests/ — parametrize a parity test over it "
                        f"before shipping", where="tests/")
        return out

    @staticmethod
    def _check_table(plan: Any, ExecSpec: Any, get_backend: Any,
                     finding: Any) -> None:
        for backend in KNOWN_BACKENDS:
            for layout in KNOWN_LAYOUTS:
                for precision in KNOWN_PRECISIONS:
                    combo = f"{backend}:{layout}:{precision}"
                    try:
                        spec = ExecSpec(backend=backend, layout=layout,
                                        precision=precision)
                        spec_ok = True
                    except ValueError:
                        spec_ok = False
                    if spec_ok != _expected_spec_valid(backend, layout,
                                                       precision):
                        finding(f"ExecSpec validation drift for {combo}: "
                                f"construction "
                                f"{'succeeded' if spec_ok else 'failed'} "
                                f"but the documented table says otherwise",
                                where=combo)
                        continue
                    if not spec_ok:
                        continue
                    be = get_backend(backend)
                    try:
                        pl = plan(None, spec)
                        plan_ok = True
                    except ValueError:
                        plan_ok = False
                    if plan_ok != _expected_plan_valid(be, precision):
                        finding(f"plan() validity drift for {combo}: "
                                f"resolution "
                                f"{'succeeded' if plan_ok else 'failed'} "
                                f"but bf16-needs-mxu_dense says otherwise",
                                where=combo)
                        continue
                    if not plan_ok:
                        continue
                    want = "dense" if layout != "block-sparse" else (
                        "traced" if be.worklist_traceable else "device")
                    if pl.worklist_strategy != want:
                        finding(f"planner dispatch drift for {combo}: "
                                f"worklist_strategy="
                                f"{pl.worklist_strategy!r}, documented "
                                f"table says {want!r}", where=combo)
                    if pl.grid_sort != (layout == "block-sparse"):
                        finding(f"planner dispatch drift for {combo}: "
                                f"grid_sort={pl.grid_sort!r} but "
                                f"grid_sort contract is sparse-only",
                                where=combo)


register_rule(SpecCoverageRule())
