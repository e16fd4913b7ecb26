"""DPCEngine: one facade over batch, distributed, streaming and serving DPC.

The subsystems share one lifecycle:

* ``fit(points)`` — batch clustering with the configured algorithm
  (``scan`` / ``exdpc`` / ``approxdpc`` / ``sapproxdpc`` / baselines), or
  the distributed shard_map phases when the engine holds a mesh.
* ``partial_fit(batch)`` — incremental sliding-window clustering
  (delegates to :class:`repro.stream.StreamDPC`; bit-identical to a
  from-scratch ``fit`` of the window contents, per the stream parity
  contract).  A batch ``fit`` of at most ``window_capacity`` points seeds
  the window.
* ``predict(points)`` — read-only nearest-label queries with the serve
  layer's semantics (``StreamService.query``): a query within ``d_cut`` of
  a fitted point adopts its label (``HIT``); out-of-coverage queries fall
  back to the nearest cluster center (``MISS_FALLBACK``); ``MISS`` only
  when no centers exist.
* ``decision_graph()`` — the paper's Fig. 1 (rho, delta) pairs for the
  current state.

Execution is one :class:`ExecSpec`, resolved once per input shape by the
planner and reused: repeated ``fit`` calls on same-shaped inputs get the
same :class:`DPCPlan` object back (same jit traces).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.resilience.sanitize import AdmissionConfig, admit

from .planner import DPCPlan, as_plan
from .spec import ExecSpec

__all__ = ["DPCEngine"]

# the canonical algorithm list lives with the dispatch table in dpc_api
from repro.core.dpc_api import _ALGORITHMS as _BATCH_ALGORITHMS

_DISTRIBUTED_OK = ("exdpc", "scan")     # distributed_dpc is exact DPC


class DPCEngine:
    """One engine, one plan: ``fit`` / ``partial_fit`` / ``predict`` /
    ``decision_graph`` over a single :class:`ExecSpec`.

    Domain parameters mirror :class:`repro.core.DPCConfig` (``d_cut``,
    ``algorithm``, ``rho_min`` / ``delta_min``, ``eps``, ``grid_dims``)
    plus the streaming window shape (``window_capacity`` / ``batch_cap``;
    extra :class:`repro.stream.StreamDPCConfig` fields ride in
    ``stream_options``) and an optional device ``mesh`` (distributed
    ``fit`` phases, sharded streaming rho repair).  Validation is
    fail-fast at construction (``stream_options`` contents are checked by
    ``StreamDPCConfig`` when the first ``partial_fit`` builds it).
    """

    def __init__(self, d_cut: float, *, algorithm: str = "approxdpc",
                 rho_min: float = 10.0, delta_min: float | None = None,
                 eps: float = 0.8, grid_dims: int | None = None,
                 exec_spec: ExecSpec | None = None, mesh=None,
                 strategy: str = "gather",
                 window_capacity: int = 4096, batch_cap: int = 256,
                 stream_options: dict | None = None,
                 admission: AdmissionConfig | None = AdmissionConfig()):
        if not d_cut > 0.0:
            raise ValueError(f"d_cut must be positive, got {d_cut!r}")
        if algorithm not in _BATCH_ALGORITHMS:
            raise ValueError(f"unknown algorithm {algorithm!r}; expected "
                             f"one of {_BATCH_ALGORITHMS}")
        if algorithm == "sapproxdpc" and eps <= 0.0:
            raise ValueError(f"S-Approx-DPC needs eps > 0, got {eps!r}")
        if delta_min is not None and delta_min <= d_cut:
            raise ValueError("delta_min must exceed d_cut (Def. 5)")
        if exec_spec is not None and not isinstance(exec_spec, ExecSpec):
            raise TypeError(f"exec_spec must be an ExecSpec, got "
                            f"{type(exec_spec).__name__}")
        if strategy not in ("gather", "halo"):
            raise ValueError(f"unknown strategy {strategy!r}; expected "
                             f"'gather' or 'halo'")
        if batch_cap > window_capacity:
            raise ValueError(f"batch_cap ({batch_cap}) cannot exceed "
                             f"window_capacity ({window_capacity})")
        if admission is not None and not isinstance(admission,
                                                    AdmissionConfig):
            raise TypeError(f"admission must be an AdmissionConfig or None, "
                            f"got {type(admission).__name__}")
        self.admission = admission
        self.d_cut = float(d_cut)
        self.algorithm = algorithm
        self.rho_min = float(rho_min)
        self.delta_min = delta_min
        self.eps = float(eps)
        self.grid_dims = grid_dims
        self.exec_spec = exec_spec if exec_spec is not None else ExecSpec()
        self.mesh = mesh
        self.strategy = strategy
        self.window_capacity = int(window_capacity)
        self.batch_cap = int(batch_cap)
        self.stream_options = dict(stream_options or {})
        self._plan: DPCPlan | None = None
        self._points = None             # fitted table (batch mode)
        self._result = None
        self._clustering = None
        self._stream = None             # StreamDPC (stream mode)
        self._mode: str | None = None

    # -------------------------------------------------------------- state
    @property
    def plan(self) -> DPCPlan | None:
        """The resolved plan of the most recent ``fit`` (or the stream's)."""
        return self._plan

    @property
    def result(self):
        """The current :class:`~repro.core.dpc_types.DPCResult`."""
        self._require_fitted()
        return self._result

    @property
    def clustering(self):
        self._require_fitted()
        return self._clustering

    @property
    def labels_(self) -> np.ndarray:
        """Current labels: cluster ids after ``fit``, the latest tick's
        *stable* ids after ``partial_fit``."""
        self._require_fitted()
        if self._mode == "stream":
            return np.asarray(self._stream._last.labels)
        return np.asarray(self._clustering.labels)

    def _require_fitted(self):
        if self._mode is None:
            raise ValueError("engine is unfitted: call fit() or "
                             "partial_fit() first")

    def resolved_delta_min(self) -> float:
        return 2.0 * self.d_cut if self.delta_min is None else self.delta_min

    # ---------------------------------------------------------------- fit
    def fit(self, points) -> "DPCEngine":
        """Batch (or distributed, when the engine holds a mesh) clustering
        of ``points``; re-fitting on a same-shaped input reuses the plan.
        A ``fit`` replaces any streaming state: the next ``partial_fit``
        starts a fresh window seeded from these points (when they fit)."""
        from repro.core.labels import assign_labels

        if self.admission is not None:
            admitted = admit(points, self.admission, where="engine.fit")
            if admitted.points.size == 0:
                raise ValueError(
                    "fit: no points survived admission control "
                    f"({admitted.quarantined} quarantined)")
            points = admitted.points
        with obs.span("engine.fit", algorithm=self.algorithm) as sp:
            host = not isinstance(points, jax.Array)
            points = jnp.asarray(points, jnp.float32)
            self._plan = as_plan(self.exec_spec, points)
            sp.set(n=int(points.shape[0]), plan=self._plan.describe(),
                   h2d_bytes=int(points.nbytes) if host else 0)
            if self.mesh is not None:
                if self.algorithm not in _DISTRIBUTED_OK:
                    raise ValueError(
                        f"distributed fit implements exact DPC "
                        f"({'/'.join(_DISTRIBUTED_OK)}); algorithm="
                        f"{self.algorithm!r} is not distributed — drop the "
                        f"mesh or pick an exact algorithm")
                from repro.distributed.dpc import distributed_dpc
                res = distributed_dpc(points, mesh=self.mesh,
                                      d_cut=self.d_cut,
                                      exec_spec=self._plan,
                                      strategy=self.strategy)
                cl = assign_labels(res, self.rho_min,
                                   self.resolved_delta_min())
            else:
                # one dispatch table: the engine IS dpc_api.cluster over the
                # resolved plan's spec (the driver re-resolves it through the
                # plan cache, so self._plan stays the object used)
                from repro.core.dpc_api import DPCConfig, cluster
                cl, res = cluster(points, DPCConfig(
                    d_cut=self.d_cut, rho_min=self.rho_min,
                    delta_min=self.delta_min, algorithm=self.algorithm,
                    eps=self.eps, grid_dims=self.grid_dims,
                    exec_spec=self._plan.spec))
            sp.sync((res.rho, res.delta, cl.labels))
        self._result = res
        self._clustering = cl
        self._points = points
        self._mode = "batch"
        self._stream = None     # fitted data supersedes any old window
        return self

    # -------------------------------------------------------- partial_fit
    def partial_fit(self, batch):
        """Sliding-window streaming ingest (micro-batched); returns the
        :class:`repro.stream.StreamTick`.  The first call builds the
        stream driver — seeded with the batch-fitted points when ``fit``
        ran first and they fit the window."""
        if self.algorithm != "approxdpc":
            raise ValueError(
                f"partial_fit maintains Approx-DPC state (the stream "
                f"parity contract); algorithm={self.algorithm!r} does not "
                f"stream")
        if self.admission is not None:
            batch = admit(batch, self.admission,
                          where="engine.partial_fit").points
        if np.asarray(batch).size == 0:
            # empty or fully-quarantined batch: a no-op, never a ghost tick
            return self._stream._last if self._stream is not None else None
        tick = None
        with obs.span("engine.partial_fit") as sp:
            if self._stream is None:
                from repro.stream.stream_dpc import StreamDPC, StreamDPCConfig
                cfg = StreamDPCConfig(
                    d_cut=self.d_cut, capacity=self.window_capacity,
                    batch_cap=self.batch_cap, rho_min=self.rho_min,
                    delta_min=self.delta_min, exec_spec=self.exec_spec,
                    **self.stream_options)
                self._stream = StreamDPC(cfg, mesh=self.mesh)
                self._plan = self._stream.plan
                if self._mode == "batch" \
                        and self._points.shape[0] <= self.window_capacity:
                    tick = self._stream.initialize(np.asarray(self._points))
            tick = self._stream.ingest(batch)
            sp.sync(tick.labels)
        self._result = self._stream.result
        self._clustering = self._stream.clustering
        self._mode = "stream"
        return tick

    @property
    def stream(self):
        """The underlying :class:`repro.stream.StreamDPC` (or None)."""
        return self._stream

    # ------------------------------------------------------------ predict
    def predict(self, points):
        """Read-only nearest-label queries over the fitted state, with
        ``StreamService.query`` semantics: returns a
        :class:`repro.stream.QueryResult` of (labels, status) — ``HIT``
        within d_cut of a fitted point, ``MISS_FALLBACK`` to the nearest
        center otherwise, ``MISS`` (-1) only with no centers at all."""
        self._require_fitted()
        from repro.stream.service import (QueryResult, QueryStatus,
                                          nearest_label_query)

        keep = None
        if self.admission is not None:
            admitted = admit(points, self.admission, where="engine.predict")
            points = admitted.points
            if admitted.quarantined:
                keep = admitted.keep
        with obs.span("engine.predict", mode=self._mode) as sp:
            if self._mode == "stream":
                s = self._stream
                ids, pos = s.center_positions()
                out = nearest_label_query(
                    s.be, points, self.d_cut, s.window.device,
                    s._last.labels, ids, pos, pad_multiple=self.batch_cap)
            else:
                labels = np.asarray(self._clustering.labels)
                centers = np.asarray(self._clustering.centers)
                pts_np = np.asarray(self._points)
                c_rows = np.nonzero(centers)[0]
                out = nearest_label_query(
                    self._plan.backend, points, self.d_cut, self._points,
                    labels, labels[c_rows].astype(np.int64), pts_np[c_rows],
                    pad_multiple=self.batch_cap)
            sp.sync(out.labels)
        if keep is not None:
            # re-expand to the caller's row alignment: dropped rows answer
            # (-1, QUARANTINED) instead of silently shifting every result
            labels = np.full(len(keep), -1, np.int64)
            status = np.full(len(keep), int(QueryStatus.QUARANTINED),
                             np.int8)
            labels[keep] = out.labels
            status[keep] = out.status
            out = QueryResult(labels=labels, status=status)
        return out

    # ----------------------------------------------------- decision graph
    def decision_graph(self):
        """(rho_i, delta_i) pairs of the current state (paper Fig. 1)."""
        from repro.core.labels import decision_graph as _dg
        return _dg(self.result)
