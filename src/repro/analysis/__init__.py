"""repro.analysis: jaxpr-level static analysis for the DPC engine.

Born from PR 4/5's silently-wrong distributed block-sparse results (the
pinned jax-0.4.37 XLA CPU SPMD pipeline miscompiles sort-derived gathers
inside multi-partition ``shard_map`` bodies): the class of bug that passes
every unit test on one device and corrupts results on four deserves a
static check, not a memory.  Nine rules walk traced computations, resolved
plans and the source tree:

=====================  =====================================================
R1-spmd-gather         sort-tainted dynamic indices feeding gather /
                       dynamic_slice inside multi-partition shard_map — the
                       miscompile class itself; also the re-enablement gate
                       for distributed block-sparse (``spmd_gather_safe``)
R2-check-rep-audit     every ``check_vma=False`` shard_map body carries an
                       ``@audit_check_rep`` replication-safety annotation
R3-precision-flow      bf16 dot_general accumulations reach the f32
                       direct-diff refinement epilogue
R4-pallas-legality     pallas_call grid/block divisibility, SMEM scalar
                       prefetch placement (budget from ``limits``),
                       host-static grids
R5-spec-coverage       ExecSpec axes x validation x dispatch x tests stay
                       mutually exhaustive
R6-pallas-race         abstract interpretation of every pallas_call's
                       output index maps over the symbolic grid: blocks
                       are visited once, or every revisit-path write is an
                       associative accumulate / guarded init; aliased
                       inputs are never read (``absint``)
R7-transfer-retrace    no host callbacks inside hot traces; equivalent
                       ``d_cut`` spellings hit one jit trace (stable
                       weak-type/dtype avals at every pjit boundary)
R8-determinism         non-associative float reductions (multi-device
                       psum, duplicate-index scatter-add) carry an
                       ``@audit_determinism`` blessing; unannotated sites
                       feeding user-visible outputs fail
R9-memory-budget       per-pallas_call VMEM/SMEM estimates and dense
                       live-buffer peaks stay under the per-platform
                       budget table (``limits``; surfaced in
                       ``DPCPlan.telemetry()``)
=====================  =====================================================

Rules run (a) at plan time — ``repro.engine.planner.plan`` analyzes each
fresh plan's canonical traces and the plan itself; ``REPRO_ANALYSIS=0``
bypasses the raise but still records findings on the
``analysis_findings_total`` obs counter — and (b) in the CLI sweep,
``python -m repro.analysis``, which CI gates on (``--sarif`` emits SARIF
2.1.0; ``analysis-baseline.json`` holds expiring suppression leases).

This top level stays jax-free (audit + rule vocabulary only); everything
that traces loads lazily via ``__getattr__``.
"""
from __future__ import annotations

from .audit import (CheckRepAudit, DeterminismAudit, all_audits,
                    all_determinism_audits, audit_check_rep,
                    audit_determinism, audit_of, determinism_audit_of)
from .limits import KernelLimits, limits_for_platform
from .rules import (AnalysisError, Finding, Rule, all_rules, analyze_jaxpr,
                    jaxpr_rules, plan_rules, project_rules, register_rule)

__all__ = [
    "AnalysisError", "CheckRepAudit", "DeterminismAudit", "Finding",
    "KernelLimits", "Rule",
    "all_audits", "all_determinism_audits", "all_rules", "analyze_jaxpr",
    "analyze_plan", "audit_check_rep", "audit_determinism", "audit_of",
    "determinism_audit_of", "jaxpr_rules", "limits_for_platform",
    "plan_memory", "plan_rules", "project_rules", "register_rule",
    "run_sweep", "spmd_gather_safe", "to_sarif",
]

_LAZY = {
    "spmd_gather_safe": ("r1_spmd_gather", "spmd_gather_safe"),
    "analyze_plan": ("targets", "analyze_plan"),
    "plan_targets": ("targets", "plan_targets"),
    "plan_memory": ("r9_memory_budget", "plan_memory"),
    "run_sweep": ("report", "run_sweep"),
    "to_sarif": ("sarif", "to_sarif"),
}


def __getattr__(name: str) -> object:
    if name in _LAZY:
        import importlib

        mod_name, attr = _LAZY[name]
        mod = importlib.import_module(f".{mod_name}", __name__)
        return getattr(mod, attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
