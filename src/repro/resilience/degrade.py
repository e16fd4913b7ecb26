"""Graceful backend degradation: pallas -> pallas-interpret -> jnp.

The planner resolves an `ExecSpec.backend` string to a kernel backend
once per plan.  On hardware where the requested backend cannot actually
*compile* (no TPU for Mosaic lowering, a pallas regression, a driver
mismatch), the old behavior was to hand back a backend whose first
kernel launch explodes deep inside a jit trace.  This module inserts a
plan-time **compile probe** and walks a documented degradation chain
instead::

    pallas  ->  pallas-interpret  ->  jnp

mirroring how ``shard_blocksparse_layout`` already degrades off its R1
probe: probe once, warn once per edge, count every transition on
``resilience_degrade_total{src,dst,reason}``, and serve the strongest
backend that demonstrably works.  ``jnp`` is the chain's floor and is
never probed (pure jax.numpy always lowers on the host platform).

Probe results are memoized per backend name for the life of the
process; :func:`reset` clears the memo (tests).  Set ``REPRO_DEGRADE=0``
to disable degradation entirely and surface raw compile errors.

bf16 precision requires MXU-dense support which ``jnp`` lacks, so a
bf16 plan never silently lands on ``jnp`` — if the chain bottoms out
for a bf16 spec the degradation itself raises.

The chain is a CPU convenience only.  On a TPU a failed probe raises with
the compiler's message: falling back there would run the interpreter or
the reference path on the chip and report it as the kernels.
"""
from __future__ import annotations

import os
import warnings

from repro import obs
from repro.kernels.backend import default_backend_name, get_backend
from repro.resilience import faultinject

__all__ = ["DEGRADE_CHAIN", "probe_backend", "reset", "resolve_backend"]

# src -> next-weaker backend; jnp is the floor.
DEGRADE_CHAIN = {"pallas": "pallas-interpret", "pallas-interpret": "jnp"}

_M_DEGRADE = obs.counter(
    "resilience_degrade_total",
    "plan-time backend degradations, labeled by src/dst/reason")

# backend name -> None (probe passed) | str (failure reason)
_PROBED: dict[str, str | None] = {}
_WARNED: set[tuple[str, str]] = set()


def _platform() -> str:
    import jax

    return jax.default_backend()


def _enabled() -> bool:
    return os.environ.get("REPRO_DEGRADE", "1").lower() not in (
        "0", "off", "no", "false")


def probe_backend(name: str) -> str | None:
    """Compile-probe ``name``; return None if healthy, else the failure
    reason.  Memoized per process — one tiny compile per backend name."""
    if name in _PROBED:
        return _PROBED[name]
    if name == "jnp":
        _PROBED[name] = None
        return None
    reason: str | None = None
    try:
        faultinject.fire("degrade.probe")
        import jax
        import jax.numpy as jnp

        be = get_backend(name)
        pts = jnp.zeros((8, 2), jnp.float32)
        jax.jit(lambda a: be.range_count(a, a, 1.0)).lower(pts).compile()
    except Exception as exc:  # noqa: BLE001 - any compile failure degrades
        reason = f"{type(exc).__name__}: {exc}"
    _PROBED[name] = reason
    return reason


def resolve_backend(requested: str | None, *, precision: str = "f32") -> str:
    """Resolve a spec's backend request to a name whose compile probe
    passes, walking :data:`DEGRADE_CHAIN` with one-shot warnings."""
    name = requested
    if name in (None, "auto"):
        name = default_backend_name()
    if name == "jnp" or not _enabled():
        return name
    while True:
        reason = probe_backend(name)
        if reason is None:
            return name
        if _platform() == "tpu":
            raise RuntimeError(
                f"backend {name!r} failed its compile probe on the TPU "
                f"({reason}); no fallback is taken on a chip")
        nxt = DEGRADE_CHAIN.get(name)
        if nxt is None or (precision == "bf16" and nxt == "jnp"):
            raise RuntimeError(
                f"backend {name!r} failed its compile probe ({reason}) and "
                f"no admissible fallback remains"
                + (" for bf16 precision (jnp has no MXU-dense path)"
                   if precision == "bf16" else ""))
        _M_DEGRADE.inc(src=name, dst=nxt, reason=type_of(reason))
        if (name, nxt) not in _WARNED:
            _WARNED.add((name, nxt))
            warnings.warn(
                f"repro.resilience: backend {name!r} failed its compile "
                f"probe ({reason}); degrading to {nxt!r}. Set "
                f"REPRO_DEGRADE=0 to surface the raw error instead.",
                RuntimeWarning, stacklevel=3)
        name = nxt


def type_of(reason: str) -> str:
    """Label value for the degrade counter: the exception class name
    prefixing the probe's reason string."""
    return reason.split(":", 1)[0] if reason else "unknown"


def reset() -> None:
    """Forget probe results and warning history (test isolation)."""
    _PROBED.clear()
    _WARNED.clear()
