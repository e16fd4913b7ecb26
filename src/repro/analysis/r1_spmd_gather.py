"""R1 spmd-gather: sort-derived indices must not feed sliced reads inside
multi-partition shard_map bodies.

The pinned jax-0.4.37 XLA CPU SPMD pipeline miscompiles exactly this
pattern: PR 4's distributed block-sparse path sorted tile lower bounds
inside each shard (``jnp.argsort`` in the ring-worklist build) and then
walked the order with ``ord_i[p]`` — on multi-device meshes the compiled
module silently degraded the order-gather to the loop counter, skipping
kept tiles with *identical wrong answers on every device* (no check_vma,
no test failure).  PR 5 found it by accident and degraded the distributed
block-sparse phases to dense tiles behind a blunt ``S_data == 1`` guard.

R1 is the precise replacement for that guard: flag every ``gather`` /
``dynamic_slice`` whose index operand is tainted by a ``sort`` computed in
traced code, inside a shard_map body mapped over an axis of size > 1.
Narrowing to *sort-derived* indices is load-bearing — the clean stencil
phases gather with traced span-table indices inside the very same
shard_maps and compile correctly, so "any traced index" would drown the
tree in false positives.

:func:`spmd_gather_safe` is the re-enablement gate (ROADMAP item 2):
``distributed_dpc`` traces its candidate block-sparse shard phases through
it and re-enables them the day the pattern no longer appears (an XLA
unpin, a worklist rewrite to one-hot matmuls, ...).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from .rules import Finding, Rule, register_rule
from .walker import spmd_sort_tainted_slices

RULE_NAME = "R1-spmd-gather"


@dataclass(frozen=True)
class SpmdGatherRule(Rule):
    name: str = RULE_NAME
    description: str = ("sort-derived index operands must not feed gather/"
                        "dynamic_slice inside a multi-partition shard_map "
                        "body (jax-0.4.37 XLA CPU SPMD miscompiles it)")
    kind: str = "jaxpr"

    def check_jaxpr(self, target: str, closed_jaxpr: Any) -> list[Finding]:
        out: list[Finding] = []
        for hit in spmd_sort_tainted_slices(closed_jaxpr):
            axes = ", ".join(f"{a}={s}" for a, s in hit.shard.axis_sizes)
            out.append(Finding(
                rule=self.name, severity="error", target=target,
                message=(f"`{hit.primitive}` reads with a sort-derived "
                         f"index inside a shard_map body over a multi-"
                         f"partition axis ({axes}); the pinned XLA CPU "
                         f"SPMD pipeline miscompiles this (the PR 4 "
                         f"block-sparse ring-walk bug)"),
                where=hit.where))
        return out


register_rule(SpmdGatherRule())


def spmd_gather_safe(fn: Any, *example_args: Any) -> bool:
    """True iff tracing ``fn(*example_args)`` shows no R1 pattern.

    The guard ``distributed_dpc`` consults before running block-sparse
    per-shard phases on a multi-partition mesh: trace the candidate
    shard_map'd phase on representative (small) shapes and admit it only
    when the sort-tainted-gather pattern is absent.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    return not spmd_sort_tainted_slices(closed)
