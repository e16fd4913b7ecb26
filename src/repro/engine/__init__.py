"""repro.engine: one execution plan, one engine.

The public surface of the unified execution model:

* :class:`ExecSpec` — the frozen ``backend x layout x precision x block x
  data_axis`` execution spec accepted by every subsystem entry point
  (batch ``run_*`` / ``compute_dpc``, ``distributed_dpc``, ``StreamDPC``,
  DPC-KV ``compress_kv``).
* :func:`plan` / :class:`DPCPlan` — the planner: resolve a spec once
  (backend instance, worklist strategy, grid sort, pad shapes) and reuse
  the plan — with its jit traces — across repeated calls.
* :class:`DPCEngine` — the facade: ``fit(points)`` (batch or distributed
  when given a mesh), ``partial_fit(batch)`` (sliding-window streaming),
  ``predict(points)`` (read-only nearest-label queries with the serve
  layer's HIT / MISS_FALLBACK semantics), ``decision_graph()``.

The four legacy configs (``DPCConfig``, ``DistDPCConfig``,
``StreamDPCConfig``, ``DPCKVConfig``) remain as thin shims whose old
``backend=`` / ``layout=`` / ``block=`` kwargs fold into one ExecSpec with
a DeprecationWarning.
"""
from .planner import (DPCPlan, PointsSpec, as_plan, plan, plan_cache_clear,
                      plan_cache_info)
from .spec import ExecSpec

__all__ = ["ExecSpec", "DPCPlan", "PointsSpec", "plan", "as_plan",
           "plan_cache_info", "plan_cache_clear", "DPCEngine"]


def __getattr__(name):
    # DPCEngine imports the subsystem drivers, which themselves import the
    # planner above — loading it lazily keeps `repro.engine` importable
    # from inside those drivers without a cycle.
    if name == "DPCEngine":
        from .dpc_engine import DPCEngine
        return DPCEngine
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
