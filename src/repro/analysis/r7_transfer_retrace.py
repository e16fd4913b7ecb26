"""R7 transfer-retrace: no hidden host hops, no avoidable retrace churn.

Two halves share the rule name:

* **jaxpr half** — traced hot paths must not smuggle host transfers: a
  ``pure_callback`` / ``io_callback`` / ``debug_callback`` (or raw
  ``infeed`` / ``outfeed``) inside a canonical trace is a device->host
  round trip *per call*, serialized against the XLA stream.  The tree's
  deliberate host work (worklist builds) happens *outside* traces by
  construction; anything host-shaped that shows up inside one is a defect.
* **plan half** — the planner's jit caches must be spelling-stable.  The
  same plan called with equivalent ``d_cut`` spellings (python ``float``,
  ``np.float32``, ``np.float64``) must produce identical jit-boundary
  avals: a python float traces as a *weak-typed* f32 and a numpy scalar as
  a strong one, so an un-normalized scalar argument silently doubles the
  trace cache (one entry per spelling the caller happens to use — retrace
  churn, measured in whole-kernel recompiles).  The probe traces the
  plan's density primitive under each spelling and compares every ``jit``
  boundary's ``(dtype, shape, weak_type)`` signature.

The fix the probe enforces: ``DPCPlan.rho_delta`` and the ``tile_sweep``
host wrapper normalize ``d_cut`` to a strong ``f32`` before crossing any
jit boundary.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from .rules import Finding, Rule, register_rule

RULE_NAME = "R7-transfer-retrace"

# host-transfer primitives that must never appear inside a hot trace
_TRANSFER_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "infeed", "outfeed")

_DESCRIPTION = ("hot traced paths carry no host callbacks/transfers; "
                "equivalent d_cut spellings hit one jit trace (stable "
                "weak-type/dtype avals at every jit boundary)")


@dataclass(frozen=True)
class TransferRule(Rule):
    name: str = RULE_NAME
    description: str = _DESCRIPTION
    kind: str = "jaxpr"

    def check_jaxpr(self, target: str, closed_jaxpr: Any) -> list[Finding]:
        from .walker import iter_sites

        out: list[Finding] = []
        for site in iter_sites(closed_jaxpr):
            pname = site.eqn.primitive.name
            if pname in _TRANSFER_PRIMS:
                out.append(Finding(
                    rule=RULE_NAME, severity="error", target=target,
                    message=(f"{pname} inside a hot traced path: a "
                             f"device->host round trip per call, "
                             f"serialized against the XLA stream — hoist "
                             f"the host work out of the trace (worklist "
                             f"builds and callbacks belong on the host "
                             f"side of the dispatch seam)"),
                    where=site.where + f"/{pname}"))
        return out


# ----------------------------------------------------- retrace-churn probe
def _jit_signature(closed: Any) -> tuple:
    """Every ``jit`` boundary's aval signature, outermost to innermost."""
    from .walker import iter_sites

    sig: list[Any] = []
    for site in iter_sites(closed):
        eqn = site.eqn
        if eqn.primitive.name != "jit":
            continue
        avals = tuple(
            (str(v.aval.dtype), tuple(getattr(v.aval, "shape", ())),
             bool(getattr(v.aval, "weak_type", False)))
            for v in eqn.invars)
        sig.append((site.where, str(eqn.params.get("name", "")), avals))
    return tuple(sig)


def _spelling_probes(pl: Any) -> list[tuple[str, Any, Any]]:
    """(spelling label, d_cut value, trace thunk) triples for the plan's
    density primitive — the scalar argument every driver passes per call,
    in the spellings real call sites actually use."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .targets import D_CUT, canonical_points

    x_np = canonical_points()
    x = jnp.asarray(x_np)
    spellings = (("float", float(D_CUT)),
                 ("np.float32", np.float32(D_CUT)),
                 ("np.float64", np.float64(D_CUT)))
    be = pl.backend

    if be.fused_traceable:
        def make(d: Any) -> Any:
            return jax.make_jaxpr(lambda a, b: pl.rho_delta(a, b, d))(x, x)
    else:
        from repro.kernels import blocksparse, ops

        interpret = bool(getattr(be, "interpret", False))
        bn = pl.block or ops.DENSITY_BLOCK_N
        wl = None
        if pl.sparse:
            wl = blocksparse.fused_worklist(
                x, x, D_CUT, block_n=bn, block_m=ops.DENSITY_BLOCK_M,
                k=ops.FUSED_TOPK)

        def make(d: Any) -> Any:
            return jax.make_jaxpr(
                lambda a, b: ops.fused_sweep(
                    a, b, d, precision=pl.precision, block_n=bn,
                    interpret=interpret, worklist=wl))(x, x)

    return [(label, val, lambda v=val: make(v)) for label, val in spellings]


@dataclass(frozen=True)
class RetraceChurnRule(Rule):
    name: str = RULE_NAME
    description: str = _DESCRIPTION
    kind: str = "plan"

    def check_plan(self, pl: Any) -> list[Finding]:
        from repro.kernels import blocksparse
        from repro.resilience import faultinject

        target = f"plan[{pl.backend_name}:{pl.layout}:{pl.precision}]"
        out: list[Finding] = []

        # the plan cache key itself must be stable/hashable
        try:
            hash(pl.spec)
        except TypeError as exc:
            out.append(Finding(
                rule=RULE_NAME, severity="error", target=target,
                message=f"ExecSpec is unhashable ({exc}): every plan() "
                        f"call becomes a cache miss", where="<plan-cache>"))
            return out

        sigs: dict[str, tuple] = {}
        with faultinject.suspended(), blocksparse.suspend_counters():
            for label, _val, thunk in _spelling_probes(pl):
                try:
                    sigs[label] = _jit_signature(thunk())
                except Exception as exc:   # noqa: BLE001 — report, don't die
                    out.append(Finding(
                        rule="trace", severity="warn", target=target,
                        message=f"retrace probe [{label}] could not trace: "
                                f"{type(exc).__name__}: {exc}",
                        where="<retrace-probe>"))
                    return out

        base_label, base = next(iter(sigs.items()))
        for label, sig in sigs.items():
            if sig == base:
                continue
            boundary = "<jit count differs>"
            for a, b in zip(base, sig):
                if a != b:
                    boundary = f"{a[0]}/jit:{a[1] or '<anon>'}"
                    break
            out.append(Finding(
                rule=RULE_NAME, severity="error", target=target,
                message=(f"d_cut spelled as {label} traces different "
                         f"jit-boundary avals than {base_label} at "
                         f"{boundary} — each spelling lands its own trace "
                         f"cache entry (retrace churn: normalize the "
                         f"scalar to a strong f32 before the jit "
                         f"boundary)"),
                where="<retrace-probe>"))
        return out


register_rule(TransferRule())
register_rule(RetraceChurnRule())
