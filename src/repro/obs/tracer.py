"""Phase-scoped span tracer with host wall-time and device-time fencing.

``obs.span("rho")`` opens a nested span.  Spans are pure host-side
bookkeeping: a perf-counter pair plus a thread-local stack to record
parentage.  Device time is measured by *fencing*: call ``sp.sync(value)``
on the arrays a phase produced and, at ``level="trace"``, the span blocks
via ``jax.block_until_ready`` and records the span-start-to-fence window
as ``device_s`` (the synced compute portion of the phase; post-fence host
orchestration is what's left in ``host_s - device_s``).  Because the
fence happens *inside* the span, per-phase host times sum to roughly the
end-to-end wall time of a run instead of measuring only async dispatch.

Levels (``configure(level=...)``):

* ``"off"``     — default.  ``span()`` returns a shared null singleton
  (no allocation, no locking, no recording) and ``sync`` is the identity,
  so instrumented code paths keep JAX's async dispatch untouched.
* ``"metrics"`` — spans record host wall-time only; no device fencing.
* ``"trace"``   — spans record host + fenced device time, and are
  optionally appended to a JSON-lines trace file as they close.

At either instrumented level every span also opens a
``jax.profiler.TraceAnnotation`` of its name, so a profiler capture
started by the caller (``jax.profiler.trace(dir)``) shows the program's
phases on the device timeline's clock.  Each record carries ``root``, the
id of its outermost span: every span of one ``engine.fit`` shares it.

This module must stay a leaf: it may import jax/numpy/stdlib only, never
``repro.engine``/``repro.kernels`` — those import *us*.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
import warnings
from typing import Any

import jax

__all__ = ["LEVELS", "configure", "level", "enabled", "tracing", "span",
           "spans", "reset_spans", "flush"]

LEVELS = ("off", "metrics", "trace")

# Retention cap for the in-memory span list (streaming runs emit one span
# tree per tick; without a cap a long soak would grow unbounded).
_MAX_SPANS = 200_000

_LOCK = threading.RLock()
_TLS = threading.local()
_IDS = itertools.count(1)
_ORIGIN = time.perf_counter()


class _State:
    level: str = "off"
    trace_path: str | None = None
    file: Any = None  # lazily-opened JSONL handle


_STATE = _State()
_DONE: list[dict] = []

_KEEP = object()  # configure() sentinel: leave this setting unchanged


def configure(level: Any = _KEEP, trace_path: Any = _KEEP) -> None:
    """Set the global observability level and trace sink.

    ``level`` is one of ``LEVELS``.  ``trace_path`` names a JSON-lines file
    that closed spans are appended to (``None`` disables file emission;
    spans stay available in memory via :func:`spans`).  Arguments left
    unspecified keep their current value.
    """
    with _LOCK:
        if level is not _KEEP:
            if level not in LEVELS:
                raise ValueError(f"level must be one of {LEVELS}, got {level!r}")
            _STATE.level = level
        if trace_path is not _KEEP and trace_path != _STATE.trace_path:
            if _STATE.file is not None:
                try:
                    _STATE.file.close()
                except OSError:
                    pass
                _STATE.file = None
            _STATE.trace_path = trace_path


def level() -> str:
    return _STATE.level


def enabled() -> bool:
    """True when any instrumentation level is active."""
    return _STATE.level != "off"


def tracing() -> bool:
    """True when spans fence device work (``level="trace"``)."""
    return _STATE.level == "trace"


class _NullSpan:
    """Shared no-op span for the off path: entering, closing, ``sync`` and
    ``set`` all do nothing, so disabled instrumentation costs one dict
    lookup per ``span()`` call and zero allocation."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def sync(self, value: Any = None) -> Any:
        return value

    def set(self, **attrs: Any) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    __slots__ = ("name", "attrs", "id", "parent", "root", "depth", "path",
                 "_t0", "_mark", "_fence_s", "_ann")

    def __init__(self, name: str, attrs: dict) -> None:
        self.name = name
        self.attrs = attrs
        self.id = next(_IDS)
        self.parent = None
        self.root = self.id
        self.depth = 0
        self.path = name
        self._t0 = 0.0
        self._mark = 0.0
        self._fence_s = 0.0
        self._ann = None

    def set(self, **attrs: Any) -> None:
        """Attach attributes to an open span (e.g. sizes known mid-phase)."""
        self.attrs.update(attrs)

    def sync(self, value: Any = None) -> Any:
        """Fence device work attributed to this span.

        At trace level, blocks until ``value`` (any pytree of arrays) is
        ready and accumulates the *synced compute* duration — the time
        from the span's start (or its previous fence) until the fence
        completes — as ``device_s``.  On async backends the fence wait
        dominates this window; on CPU, where jnp executes synchronously
        inside the producing call, the window still covers the compute,
        which a fence-wait-only measurement would miss entirely.  Host
        orchestration after the last fence is excluded, so ``device_s <=
        host_s`` and per-phase device times sum to ~wall time for a
        compute-bound run.  Returns ``value`` so it can wrap an
        expression in place.  Tracer values (inside jit) cannot block and
        are passed through untouched.
        """
        if _STATE.level == "trace" and value is not None:
            try:
                jax.block_until_ready(value)
            except Exception:
                return value  # abstract values / non-arrays: nothing to fence
            now = time.perf_counter()
            self._fence_s += now - self._mark
            self._mark = now
        return value

    def __enter__(self) -> "Span":
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        if stack:
            top = stack[-1]
            self.parent = top.id
            self.root = top.root
            self.depth = top.depth + 1
            self.path = f"{top.path}/{self.name}"
        stack.append(self)
        # the profiler's host event of this span (a no-op unless a capture
        # is running); its name stays plain so trace readers match it exactly
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = self._mark = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        stack = getattr(_TLS, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        host_s = t1 - self._t0
        rec: dict[str, Any] = {
            "name": self.name,
            "path": self.path,
            "id": self.id,
            "parent": self.parent,
            "root": self.root,
            "depth": self.depth,
            "t0": self._t0 - _ORIGIN,
            "host_s": host_s,
            # device_s is a *component* of host_s: the start-to-last-fence
            # window; host_s adds the post-fence orchestration tail
            "device_s": self._fence_s if _STATE.level == "trace" else None,
        }
        if self.attrs:
            rec["attrs"] = self.attrs
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        with _LOCK:
            _DONE.append(rec)
            if len(_DONE) > _MAX_SPANS:
                del _DONE[: len(_DONE) - _MAX_SPANS]
            if _STATE.trace_path is not None:
                if _STATE.file is None:
                    _STATE.file = open(_STATE.trace_path, "a")
                json.dump(rec, _STATE.file, default=str)
                _STATE.file.write("\n")
        return False


def span(name: str, **attrs: Any) -> "Span | _NullSpan":
    """Open a named span context.  At ``level="off"`` returns the shared
    null singleton, keeping uninstrumented runs overhead-free."""
    if _STATE.level == "off":
        return NULL_SPAN
    return Span(name, attrs)


def spans() -> list[dict]:
    """Copy of all closed span records (insertion order = close order)."""
    with _LOCK:
        return [dict(r) for r in _DONE]


def reset_spans() -> None:
    with _LOCK:
        _DONE.clear()


def flush() -> None:
    """Flush the JSONL trace file (if one is open) to disk."""
    with _LOCK:
        if _STATE.file is not None:
            _STATE.file.flush()


# Environment activation, so benchmarks/CI can instrument without touching
# code: REPRO_OBS=metrics|trace [REPRO_OBS_TRACE=/path/to/trace.jsonl]
_env_level = os.environ.get("REPRO_OBS", "").strip().lower()
if _env_level:
    if _env_level in LEVELS:
        configure(level=_env_level,
                  trace_path=os.environ.get("REPRO_OBS_TRACE") or None)
    else:  # pragma: no cover - defensive
        warnings.warn(f"REPRO_OBS={_env_level!r} ignored (not in {LEVELS})",
                      stacklevel=1)
