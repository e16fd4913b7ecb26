"""The planner: resolve an ExecSpec once, reuse it for every call.

``plan(points_spec, exec_spec) -> DPCPlan`` resolves the execution axes a
single time — the :class:`~repro.kernels.backend.KernelBackend` instance,
the layout (and with it the worklist strategy: none for dense, jit-built
for the jnp ring worklists, device-built scalar-prefetch tables for pallas),
the grid-sort requirement, the precision, and the sweep block size — and
hands back a plan object whose primitive wrappers inject all of that into
every kernel call.  Drivers stop re-threading ``backend=/layout=/block=``
kwargs; they take a plan (or an ExecSpec, via :func:`as_plan`) and call
``plan.rho_delta(...)``.

The **plan cache** makes repeated ``fit`` / ``partial_fit`` calls cheap:
``plan()`` memoizes on ``(PointsSpec, ExecSpec)`` (both frozen/hashable),
so a re-fit on same-shaped input gets the *same* plan object back — and
with it every jit trace keyed off the plan's resolved static arguments (no
re-trace; asserted in tests/test_engine.py).  Worklists are built on the
device at every call (``kernels.blocksparse``), so no plan caches one.

Block-size resolution (the one documented default): ``spec.block`` when
set; otherwise each backend's native tile default (jnp: 512, pallas: the
Mosaic tile constants in ``kernels.ops``).  This replaces the old silent
per-call-site defaults (``run_scan``'s 512 vs ``dpc_api``'s
``max(block, 256)``); results are block-independent on every backend, so
the resolution is a throughput knob only.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

from repro import obs
from repro.kernels import blocksparse
from repro.kernels.backend import KernelBackend, get_backend
from repro.obs import metrics as _obsm
from repro.resilience import faultinject
from repro.resilience.degrade import resolve_backend

from .spec import ExecSpec

__all__ = ["PointsSpec", "DPCPlan", "plan", "as_plan", "plan_cache_info",
           "plan_cache_clear"]

_PLAN = object()          # sentinel: "use the plan's resolved value"
_PLAN_CACHE_MAX = 64


@dataclass(frozen=True)
class PointsSpec:
    """Static shape of a point table: what the planner needs to size pads."""

    n: int
    d: int

    @classmethod
    def of(cls, points) -> "PointsSpec":
        return cls(n=int(points.shape[0]), d=int(points.shape[1]))


class DPCPlan:
    """A resolved execution plan: backend + layout + precision + block,
    with primitive wrappers that inject them into every kernel call.

    ``worklist_strategy``: ``"dense"`` (no worklists), ``"traced"``
    (jit-built jnp ring worklists — legal inside jit/shard_map), or
    ``"device"`` (device-built pallas scalar-prefetch tables, count lists
    sized on the host).
    ``grid_sort`` tells drivers the points must be laid out grid-sorted
    (block-sparse pruning quality depends on it).  ``resolved_block`` is
    the count-sweep row-tile size the wrappers actually pass.
    """

    def __init__(self, pspec: PointsSpec | None, spec: ExecSpec):
        self.spec = spec
        self.pspec = pspec
        # plan-time compile probe + graceful degradation chain
        # (pallas -> pallas-interpret -> jnp; see resilience.degrade)
        self.backend: KernelBackend = get_backend(resolve_backend(
            spec.backend, precision=spec.resolved_precision))
        self.backend_name: str = self.backend.name
        self.layout: str = spec.resolved_layout
        self.sparse: bool = spec.sparse
        self.precision: str = spec.resolved_precision
        self.data_axis: str = spec.data_axis
        if self.precision == "bf16" and not self.backend.mxu_dense:
            raise ValueError(
                f"precision='bf16' needs a pallas backend; resolved "
                f"backend is {self.backend_name!r} (the f32 reference)")
        self.block: int | None = spec.block
        # THE resolved sweep row-block (the satellite's one documented
        # default): spec.block when set, else the backend's native
        # count-sweep tile (jnp 512, pallas DENSITY_BLOCK_N).  The
        # count-sweep wrappers below pass exactly this value; the NN /
        # halo wrappers keep per-primitive native defaults when spec.block
        # is unset (their tiles are tuned separately).
        self.resolved_block: int = spec.block if spec.block is not None \
            else self._native_block()
        # drivers consult this to lay points out grid-sorted before the
        # sweep (block-sparse pruning quality depends on the layout)
        self.grid_sort: bool = self.sparse
        if not self.sparse:
            self.worklist_strategy = "dense"
        elif self.backend.worklist_traceable:
            self.worklist_strategy = "traced"
        else:
            self.worklist_strategy = "device"
        self._cost: dict | None = None          # hlo_cost estimate (lazy)
        self._memory: dict | None = None        # R9 memory block (lazy)

    def _native_block(self) -> int:
        if self.backend.mxu_dense:
            from repro.kernels import ops
            return ops.DENSITY_BLOCK_N
        return 512

    # ------------------------------------------------------- introspection
    def describe(self) -> str:
        shape = "" if self.pspec is None \
            else f" n={self.pspec.n} d={self.pspec.d}"
        return (f"DPCPlan[{self.backend_name}:{self.layout}:"
                f"{self.precision} block={self.block or 'native'} "
                f"worklists={self.worklist_strategy}{shape}]")

    __repr__ = describe

    # --------------------------------------------------- kernel telemetry
    def telemetry(self, include_cost: bool = False) -> dict:
        """What this plan resolved to and what its kernels will touch.

        Static fields (resolved axes, grid-sort, pad waste, the
        ``worklists`` strategy) are free.  ``include_cost=True`` adds a
        ``launch/hlo_cost`` flop/byte estimate from compiling the canonical
        fused sweep at the plan's shape; the estimate is computed once per
        plan and cached (compiles are not free), and pallas block-sparse
        plans are costed on the dense formulation — an upper bound —
        because a flat worklist's length cannot be read during an abstract
        trace.

        The ``memory`` block carries the R9 estimates the plan was gated
        against: per-``pallas_call`` VMEM/SMEM (block shapes
        double-buffered + scalar prefetch + scratch), the dense
        live-buffer peak over the canonical traces, and the platform
        budget table (``repro.analysis.limits``).  Computed once per plan
        and cached (it traces the canonical targets).
        """
        t: dict = {
            "backend": self.backend_name,
            "layout": self.layout,
            "precision": self.precision,
            "block": self.resolved_block,
            "worklist_strategy": self.worklist_strategy,
            "grid_sort": self.grid_sort,
            "data_axis": self.data_axis,
            "shape": None if self.pspec is None
            else {"n": self.pspec.n, "d": self.pspec.d},
            "pad": self._pad_telemetry(),
            "worklists": {"strategy": self.worklist_strategy},
            "memory": self._memory_estimate(),
        }
        if include_cost:
            t["hlo_cost"] = self._cost_estimate()
        return t

    def _pad_telemetry(self) -> dict | None:
        if self.pspec is None:
            return None
        n = self.pspec.n
        # the row tile the sweep actually pads to: block-sparse sweeps use
        # the ring-tile constants, dense sweeps the resolved block
        row_block = blocksparse.BS_BLOCK_N if self.sparse \
            else self.resolved_block
        padded = -(-n // row_block) * row_block
        return {"row_block": row_block, "n": n, "padded_n": padded,
                "pad_waste_frac": round(1.0 - n / padded, 6)}

    def _memory_estimate(self) -> dict:
        if self._memory is None:
            from repro.analysis.r9_memory_budget import plan_memory

            try:
                self._memory = plan_memory(self)
            except Exception as e:   # noqa: BLE001 — telemetry, not a gate
                self._memory = {"error": f"{type(e).__name__}: {e}"}
        return self._memory

    def _cost_estimate(self) -> dict:
        if self._cost is not None:
            return self._cost
        if self.pspec is None:
            return {"error": "plan has no bound shape"}
        import jax
        import jax.numpy as jnp

        from repro.launch import hlo_cost

        n, d = self.pspec.n, self.pspec.d
        layout = "block-sparse" if self.worklist_strategy == "traced" \
            else None
        formulation = ("block-sparse" if layout else
                       "dense-upper-bound" if self.sparse else "dense")

        def canonical(pts):
            return self.backend.rho_delta(
                pts, pts, 1.0, block=self.resolved_block,
                precision=self.precision, layout=layout)

        x = jax.ShapeDtypeStruct((n, d), jnp.float32)
        try:
            with blocksparse.suspend_counters():
                compiled = jax.jit(canonical).lower(x).compile()
            cost = dict(hlo_cost.analyze_compiled(compiled))
        except Exception as e:  # backend may not lower on this platform
            return {"error": f"{type(e).__name__}: {e}",
                    "formulation": formulation}
        cost["formulation"] = formulation
        self._cost = cost
        return cost

    # ------------------------------------------------------ value helpers
    def _layout(self, override):
        if override is _PLAN:
            return "block-sparse" if self.sparse else None
        return override

    def _block(self, override):
        return self.block if override is _PLAN else override

    # -------------------------------------------------- primitive wrappers
    # Thin forms of the two DRIVER-facing primitives with the plan's
    # resolved layout / precision / block injected (each overridable per
    # call for the few sites that intentionally diverge, e.g. dense
    # fallbacks).  Only the primitives the unified drivers actually route
    # through the plan live here; subsystems with bespoke orchestration —
    # the distributed halo phases, the stream repair primitives — consume
    # ``plan.backend`` directly with their own tuned parameters (their
    # call sites say so), rather than carrying dead wrapper surface.

    def _sweep_block(self, override):
        return self.resolved_block if override is _PLAN else override

    def denser_nn(self, x, x_key, y, y_key, *, block=_PLAN, layout=_PLAN):
        faultinject.fire("kernel.dispatch")
        return self.backend.denser_nn(x, x_key, y, y_key,
                                      block=self._block(block),
                                      layout=self._layout(layout))

    def rho_delta(self, x, y, d_cut, *, jitter=None, y_sel_slots=None,
                  fallback_interest=None, block=_PLAN, layout=_PLAN,
                  precision=_PLAN):
        faultinject.fire("kernel.dispatch")
        if d_cut is not None:
            # strong-f32 before any jit boundary: a python float traces
            # weak-typed, a numpy scalar strong — one cache entry per
            # spelling otherwise (R7's retrace-churn finding)
            import jax.numpy as jnp

            d_cut = jnp.asarray(d_cut, jnp.float32)
        return self.backend.rho_delta(
            x, y, d_cut, jitter=jitter, y_sel_slots=y_sel_slots,
            fallback_interest=fallback_interest,
            block=self._sweep_block(block),
            precision=self.precision if precision is _PLAN else precision,
            layout=self._layout(layout))


# ------------------------------------------------------------- plan cache
_PLANS: OrderedDict = OrderedDict()

# Cache traffic counts on the repro.obs registry; plan_cache_info() below
# stays the stable read surface.
_M_HITS = _obsm.counter("plan_cache_hits", "plan() memo hits")
_M_MISSES = _obsm.counter("plan_cache_misses", "plan() builds (memo misses)")
_M_EVICTIONS = _obsm.counter(
    "plan_cache_evictions", "plans LRU-evicted at _PLAN_CACHE_MAX")

# plan-time static analysis results, memoized per ExecSpec (the canonical
# traces depend only on the spec's resolved axes, not the point shape)
_ANALYZED: dict = {}

# every plan-time finding lands here, bypassed or not — the escape hatch
# silences the raise, never the telemetry
_M_FINDINGS = _obsm.counter(
    "analysis_findings_total",
    "plan-time static-analyzer findings, labeled by rule and level")

_BYPASS_WARNED = False


def _plan_check(pl: DPCPlan) -> None:
    """Run the static analyzer (``repro.analysis``) over the plan's
    canonical traces + the plan itself, once per spec; raise on
    error-severity findings so a spec that dispatches into a flagged
    kernel path fails at ``plan()``, before any data is touched.

    ``REPRO_ANALYSIS=0`` (also ``off``/``no``) is the debugging escape
    hatch: findings are still computed and recorded on the
    ``analysis_findings_total`` obs counter, and the first bypassed error
    logs one warning — the raise is suppressed, the evidence is not.  The
    internal value ``suspend`` (set by the analyzer's own sweep, which
    builds plans *in order to* analyze them) skips entirely."""
    import os

    global _BYPASS_WARNED

    mode = os.environ.get("REPRO_ANALYSIS", "1").lower()
    if mode == "suspend":
        return
    res = _ANALYZED.get(pl.spec)
    if res is None:
        from repro import analysis

        # tracing the canonical targets builds throwaway worklists; suspend
        # the worklist metrics so plan() stays neutral w.r.t. the
        # instrumentation tests assert on (worklist_build_count & co.)
        with blocksparse.suspend_counters():
            res = tuple(analysis.analyze_plan(pl))
        _ANALYZED[pl.spec] = res
        for f in res:
            _M_FINDINGS.inc(rule=f.rule, level=f.severity)
    errors = [f for f in res if f.severity == "error"]
    if not errors:
        return
    if mode in ("0", "off", "no"):
        if not _BYPASS_WARNED:
            import logging

            logging.getLogger("repro.analysis").warning(
                "REPRO_ANALYSIS=%s: bypassing %d error finding(s) for %s "
                "(recorded on analysis_findings_total; this warning is "
                "logged once per process)", mode, len(errors),
                pl.describe())
            _BYPASS_WARNED = True
        return
    from repro.analysis import AnalysisError

    raise AnalysisError(errors)


def plan(points_spec: PointsSpec | tuple | None,
         exec_spec: ExecSpec | None = None) -> DPCPlan:
    """Resolve (points_spec, exec_spec) -> DPCPlan, memoized.

    ``points_spec`` may be a PointsSpec, an ``(n, d)`` tuple, or ``None``
    for shape-independent plans (e.g. a stream driver before its window
    exists).  Same inputs return the *same object*, carrying its caches.
    """
    if isinstance(points_spec, tuple):
        points_spec = PointsSpec(*points_spec)
    spec = exec_spec if exec_spec is not None else ExecSpec()
    key = (points_spec, spec)
    with obs.span("planner.plan") as sp:
        hit = _PLANS.get(key)
        sp.set(hit=hit is not None)
        if hit is not None:
            _M_HITS.inc()
            _PLANS.move_to_end(key)
            return hit
        _M_MISSES.inc()
        pl = DPCPlan(points_spec, spec)
        with obs.span("planner.check"):
            _plan_check(pl)
        _PLANS[key] = pl
        while len(_PLANS) > _PLAN_CACHE_MAX:
            _PLANS.popitem(last=False)
            _M_EVICTIONS.inc()
        return pl


def as_plan(exec_spec, points=None) -> DPCPlan:
    """Coerce a driver's ``exec_spec`` argument (ExecSpec | DPCPlan | None)
    into a plan for ``points`` (re-planning a shape-mismatched plan's spec;
    the plan cache makes that free)."""
    pspec = None if points is None else PointsSpec.of(points)
    if isinstance(exec_spec, DPCPlan):
        if pspec is None or exec_spec.pspec == pspec:
            return exec_spec
        return plan(pspec, exec_spec.spec)
    if exec_spec is not None and not isinstance(exec_spec, ExecSpec):
        raise TypeError(
            f"exec_spec must be an ExecSpec, DPCPlan or None, got "
            f"{type(exec_spec).__name__} (legacy backend=/layout=/block= "
            f"kwargs moved onto repro.engine.ExecSpec)")
    return plan(pspec, exec_spec)


def plan_cache_info() -> dict:
    return {"hits": int(_M_HITS.value()),
            "misses": int(_M_MISSES.value()),
            "evictions": int(_M_EVICTIONS.value()),
            "entries": len(_PLANS)}


def plan_cache_clear() -> None:
    """Drop every cached plan and zero the cache counters (registry
    families included)."""
    _PLANS.clear()
    for m in (_M_HITS, _M_MISSES, _M_EVICTIONS):
        m._reset()
