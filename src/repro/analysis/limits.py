"""Per-platform kernel memory limits, shared by R4 and R9.

One table replaces R4's old hard-coded ``_SMEM_MAX_ELEMS = 1 << 20``
constant: budgets are looked up from the platform a ``pallas_call``
actually targets (its ``backend`` param when set; otherwise the kernels in
this tree are Mosaic TPU kernels — interpret mode runs them on CPU but
models the TPU memory hierarchy, so the TPU budgets apply there too).

Defaults are deliberately conservative fractions of real hardware (TPU v4
VMEM is 128 MiB; we budget 16 MiB so a kernel that fits here fits every
generation back to v2, double-buffering included).  The SMEM budget equals
the old R4 constant (2^20 four-byte scalars) so the R4 contract is
unchanged by the table refactor.

Environment overrides (operators raising/lowering the gate without a code
change)::

    REPRO_LIMIT_VMEM_BYTES      per-pallas_call VMEM budget
    REPRO_LIMIT_SMEM_BYTES      per-pallas_call SMEM budget
    REPRO_LIMIT_LIVE_BYTES      whole-trace live-buffer budget for dense
                                jnp paths (unset = report-only, no gate)

This module is jax-free (importable before backends initialize).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any

__all__ = ["KernelLimits", "limits_for_platform", "limits_for_eqn",
           "live_budget_bytes"]

_MIB = 1 << 20


@dataclass(frozen=True)
class KernelLimits:
    """Memory budgets for one target platform, in bytes."""

    platform: str
    vmem_bytes: int
    smem_bytes: int

    def to_dict(self) -> dict:
        return {"platform": self.platform,
                "vmem_bytes": self.vmem_bytes,
                "smem_bytes": self.smem_bytes}


# the R4-compatible SMEM budget: 2^20 four-byte scalars
_SMEM_DEFAULT = 4 * _MIB

_TABLE: dict[str, KernelLimits] = {
    "tpu": KernelLimits("tpu", vmem_bytes=16 * _MIB,
                        smem_bytes=_SMEM_DEFAULT),
    # Mosaic GPU shared memory is far smaller than TPU VMEM; nothing in
    # this tree targets it yet, so the budget is the Hopper 228 KiB smem
    # ceiling with VMEM modelling L1/register residency per block.
    "gpu": KernelLimits("gpu", vmem_bytes=228 * 1024,
                        smem_bytes=48 * 1024),
}


# backend spellings a pallas_call or a plan may carry -> table row
_ALIASES = {"cpu": "tpu", "interpret": "tpu", "mosaic": "tpu",
            "mosaic_tpu": "tpu", "triton": "gpu", "mosaic_gpu": "gpu"}


def _env_int(name: str) -> int | None:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def limits_for_platform(platform: str | None) -> KernelLimits:
    """Budget row for a resolved platform.  ``None`` is a ``pallas_call``
    that pins no backend: every kernel in this tree is written against
    ``pltpu``, so it takes the TPU row (interpret mode models the TPU memory
    hierarchy too).  A platform the table does not know is an error, not a
    default."""
    key = (platform or "tpu").lower()
    key = _ALIASES.get(key, key)
    if key not in _TABLE:
        raise ValueError(f"no kernel memory limits for platform {platform!r}"
                         f"; known: {sorted(set(_TABLE) | set(_ALIASES))}")
    base = _TABLE[key]
    vmem = _env_int("REPRO_LIMIT_VMEM_BYTES")
    smem = _env_int("REPRO_LIMIT_SMEM_BYTES")
    if vmem is None and smem is None:
        return base
    return KernelLimits(base.platform,
                        vmem_bytes=vmem if vmem is not None
                        else base.vmem_bytes,
                        smem_bytes=smem if smem is not None
                        else base.smem_bytes)


def limits_for_eqn(eqn: Any) -> KernelLimits:
    """Budget row for one ``pallas_call`` eqn: its ``backend`` param when
    the call pinned one, else the TPU row (Mosaic kernels under interpret
    mode still model the TPU memory hierarchy)."""
    backend = eqn.params.get("backend") if hasattr(eqn, "params") else None
    return limits_for_platform(str(backend) if backend else None)


def live_budget_bytes() -> int | None:
    """Whole-trace live-buffer budget for dense jnp paths, or None when the
    gate is report-only (the default: dense peaks scale with the caller's n,
    so a hard default would fail legitimate large fits)."""
    return _env_int("REPRO_LIMIT_LIVE_BYTES")
