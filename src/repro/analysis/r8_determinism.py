"""R8 determinism: non-associative float reductions need a blessing.

Float addition is not associative; any reduction whose *operand order* is
not pinned by the program can move bits when the mesh, device count or
lowering changes.  Two shapes in this tree have that property:

* a float ``psum`` inside a shard_map mapped over a multi-partition axis —
  the all-reduce combines per-device partials in an order chosen by the
  runtime's reduction topology (ring vs tree, device count);
* a float ``scatter-add`` whose indices are not proven unique
  (``unique_indices=False``) — duplicate slots accumulate in an order the
  lowering picks, and XLA does not promise one.

Neither shape is a bug per se: counts summed in f32 are integer-exact
under any order, and some accumulations tolerate last-bit wobble by
design.  What *is* a bug is shipping one silently.  R8 therefore flags
every such site that is not lexically inside an
:func:`repro.analysis.audit.audit_determinism`-decorated function (matched
through the traced eqn's source frames, same mechanism as R2's check_vma
audits): **error** when the reduction's value flows to the trace's
outputs (user-visible labels / centers / densities), **warn** when it
stays internal.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from .rules import Finding, Rule, register_rule

RULE_NAME = "R8-determinism"


def _is_float(v: Any) -> bool:
    return "float" in str(getattr(getattr(v, "aval", None), "dtype", ""))


def _blessed(eqn: Any, index: dict) -> object | None:
    """The determinism audit covering this eqn's source site, if any."""
    from jax._src import source_info_util

    try:
        frames = list(source_info_util.user_frames(
            eqn.source_info.traceback))
    except Exception:       # noqa: BLE001 — source info is best-effort
        return None
    for fr in frames:
        # frames name the function by its qualified name
        # ("make_x.<locals>.f"); audits are keyed by the bare name
        name = fr.function_name.rsplit(".", 1)[-1]
        rec = index.get((fr.file_name, name))
        if rec is not None:
            return rec
    return None


def _src_of(eqn: Any) -> str:
    from jax._src import source_info_util

    try:
        fr = source_info_util.summarize(eqn.source_info)
        return str(fr)
    except Exception:       # noqa: BLE001
        return "<unknown source>"


def _feeds_outputs(jaxpr: Any, eqn: Any) -> bool:
    """Forward closure from ``eqn``'s outputs within its containing jaxpr:
    does the reduction's value reach the jaxpr's outvars?  Conservative —
    any consumer propagates (incl. opaque sub-jaxpr calls)."""
    from jax._src import core as jcore

    reached = set(map(id, eqn.outvars))
    seen = False
    for e in jaxpr.eqns:
        if not seen:
            seen = e is eqn
            continue
        if any(not isinstance(v, jcore.Literal) and id(v) in reached
               for v in e.invars):
            reached.update(map(id, e.outvars))
    return any(not isinstance(v, jcore.Literal) and id(v) in reached
               for v in jaxpr.outvars)


def _check(target: str, closed: Any) -> list[Finding]:
    from .audit import determinism_audit_index
    from .walker import shard_ctx_of, sub_jaxprs, unwrap

    index = determinism_audit_index()
    out: list[Finding] = []

    def visit(jaxpr: Any, path: tuple[str, ...], shard: Any) -> None:
        jaxpr = unwrap(jaxpr)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            hit = None
            # older jax traced lax.psum as "psum2" inside shard_map
            # bodies and "psum" under pmap — match both spellings
            if (name in ("psum", "psum2") and shard is not None
                    and shard.multi_partition
                    and any(_is_float(v) for v in eqn.invars)):
                hit = ("float psum over a multi-partition axis: the "
                       "all-reduce combines per-device partials in a "
                       "runtime-chosen order (ring vs tree varies with "
                       "device count)")
            elif (name == "scatter-add"
                    and eqn.params.get("unique_indices") is False
                    and any(_is_float(v) for v in eqn.invars)):
                hit = ("float scatter-add with possibly-duplicate indices: "
                       "colliding slots accumulate in a lowering-chosen "
                       "order XLA does not pin")
            if hit is not None and _blessed(eqn, index) is None:
                feeds = _feeds_outputs(jaxpr, eqn)
                sev = "error" if feeds else "warn"
                flow = ("feeds the trace's outputs" if feeds
                        else "stays internal to the trace")
                out.append(Finding(
                    rule=RULE_NAME, severity=sev, target=target,
                    message=(f"{hit}; the value {flow} and the site at "
                             f"{_src_of(eqn)} carries no "
                             f"@audit_determinism blessing — state why "
                             f"the order cannot move the result (or that "
                             f"the wobble is accepted) on the containing "
                             f"function"),
                    where="/".join(path + (name,)) or name))
            sub_shard = shard_ctx_of(eqn) if name == "shard_map" else shard
            for key, sub in sub_jaxprs(eqn):
                visit(sub, path + (f"{name}.{key}",), sub_shard)

    visit(closed, (), None)
    return out


@dataclass(frozen=True)
class DeterminismRule(Rule):
    name: str = RULE_NAME
    description: str = ("non-associative float reductions (multi-device "
                        "psum, duplicate-index scatter-add) carry an "
                        "@audit_determinism blessing; unannotated sites "
                        "feeding user-visible outputs are errors")
    kind: str = "jaxpr"

    def check_jaxpr(self, target: str, closed_jaxpr: Any) -> list[Finding]:
        return _check(target, closed_jaxpr)


register_rule(DeterminismRule())
