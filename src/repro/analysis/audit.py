"""Audit annotations: ``check_vma=False`` bodies and determinism blessings.

Two structured "the author thought about this" records, both attached to
functions with zero-wrapper decorators (one attribute set; decorated code
traces exactly as before):

* :func:`audit_check_rep` — ``shard_map(..., check_vma=False)`` switches
  off JAX's replication checking, the mechanism that would catch a body
  producing different values on different mesh members.  Every such body
  in this tree exists because a primitive inside it (``pallas_call``) has
  no replication rule, not because the body is replication-unsafe; the
  decorator records *why* it is safe and *which collectives* make it so.
  Rule R2 fails any unannotated ``check_vma=False`` body.
* :func:`audit_determinism` — a float ``psum`` whose operand order depends
  on the device count, or a float scatter-add with possibly-duplicate
  indices, is a non-associative reduction whose bit pattern can move when
  the mesh or lowering changes.  The decorator records why a specific site
  is nevertheless deterministic (integer-exact values, tolerated
  approximation, ...).  Rule R8 fails any unannotated site that feeds
  user-visible outputs; annotated sites are matched through the traced
  eqn's source frames (file + function name), so the blessing sits on the
  function that *contains* the reduction.

This module stays jax-free (kernel modules import it at definition time).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, TypeVar

_F = TypeVar("_F", bound=Callable[..., Any])


@dataclass(frozen=True)
class CheckRepAudit:
    """One audited ``check_vma=False`` body: the replication-safety argument."""

    qualname: str
    module: str
    reason: str
    collectives: tuple[str, ...] = field(default=())

    @property
    def key(self) -> str:
        return f"{self.module}.{self.qualname}"


_REGISTRY: dict[str, CheckRepAudit] = {}

_AUDIT_ATTR = "__check_rep_audit__"


def audit_check_rep(reason: str, *,
                    collectives: tuple[str, ...] | list[str] = ()
                    ) -> Callable[[_F], _F]:
    """Annotate a shard_map body as audited for ``check_vma=False``.

    ``reason`` states why the body is replication-safe; ``collectives``
    names the collective primitives (``all_gather``, ``psum``, ``ppermute``,
    ...) whose semantics the argument rests on.  The decorated function is
    returned unchanged.
    """
    if not reason or not reason.strip():
        raise ValueError("audit_check_rep needs a non-empty reason: the "
                         "annotation exists to record the safety argument")

    def deco(fn: _F) -> _F:
        rec = CheckRepAudit(qualname=fn.__qualname__, module=fn.__module__,
                            reason=" ".join(reason.split()),
                            collectives=tuple(collectives))
        setattr(fn, _AUDIT_ATTR, rec)
        _REGISTRY[rec.key] = rec
        return fn

    return deco


def audit_of(fn: Any) -> CheckRepAudit | None:
    """The audit record attached to ``fn``, or None."""
    return getattr(fn, _AUDIT_ATTR, None)


def all_audits() -> dict[str, CheckRepAudit]:
    """Every audit registered so far (importing a module registers its
    decorated bodies); keys are ``module.qualname``."""
    return dict(_REGISTRY)


# ------------------------------------------------------------- determinism
@dataclass(frozen=True)
class DeterminismAudit:
    """One blessed non-associative reduction site: the determinism argument.

    ``file_name`` / ``function_name`` are the match keys R8 compares
    against the traced eqn's ``source_info`` user frames — the blessing
    covers every flagged reduction *lexically inside* the decorated
    function, nothing else.
    """

    qualname: str
    module: str
    reason: str
    file_name: str
    function_name: str
    ops: tuple[str, ...] = field(default=())

    @property
    def key(self) -> str:
        return f"{self.module}.{self.qualname}"


_DET_REGISTRY: dict[str, DeterminismAudit] = {}

_DET_AUDIT_ATTR = "__determinism_audit__"


def audit_determinism(reason: str, *,
                      ops: tuple[str, ...] | list[str] = ()
                      ) -> Callable[[_F], _F]:
    """Annotate a function whose non-associative float reductions are
    deliberate and deterministic (or whose nondeterminism is accepted).

    ``reason`` states the argument — e.g. *counts are integer-exact in
    f32, so every summation order produces the same bits*; ``ops`` names
    the reduction primitives the argument covers (``psum``,
    ``scatter-add``, ...).  The decorated function is returned unchanged.
    """
    if not reason or not reason.strip():
        raise ValueError("audit_determinism needs a non-empty reason: the "
                         "annotation exists to record the determinism "
                         "argument")

    def deco(fn: _F) -> _F:
        code = fn.__code__
        rec = DeterminismAudit(qualname=fn.__qualname__,
                               module=fn.__module__,
                               reason=" ".join(reason.split()),
                               file_name=code.co_filename,
                               function_name=fn.__name__,
                               ops=tuple(ops))
        setattr(fn, _DET_AUDIT_ATTR, rec)
        _DET_REGISTRY[rec.key] = rec
        return fn

    return deco


def determinism_audit_of(fn: Any) -> DeterminismAudit | None:
    """The determinism audit attached to ``fn``, or None."""
    return getattr(fn, _DET_AUDIT_ATTR, None)


def all_determinism_audits() -> dict[str, DeterminismAudit]:
    """Every determinism audit registered so far, keyed
    ``module.qualname``."""
    return dict(_DET_REGISTRY)


def determinism_audit_index() -> dict[tuple[str, str], DeterminismAudit]:
    """The R8 match index: ``(file_name, function_name)`` -> audit."""
    return {(a.file_name, a.function_name): a
            for a in _DET_REGISTRY.values()}
