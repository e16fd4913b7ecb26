"""Multi-chip DPC: the paper's multicore parallelization as shard_map SPMD.

Mapping (DESIGN.md §2/§4):

* OpenMP ``schedule(dynamic)``  ->  *space-sorted equal-count partitioning*:
  points are globally sorted by grid cell id (the build_grid sort), then
  split into equal contiguous chunks over the ``data`` mesh axis.  Sorting
  groups dense cells together, so equal point counts imply similar candidate
  volumes — the paper's cost model (cost ∝ |P(c)|) baked into the layout.
* Shared-memory reads of P  ->  an explicit ``all_gather`` of the sorted
  point table (baseline) or a ring of ``ppermute`` block exchanges
  (optimized; see benchmarks/roofline notes).  DPC datasets are O(1e6-1e7)
  rows of 2-8 f32s, so a replicated table is ~100 MB — the standard
  time/space trade at pod scale.
* Ex-DPC's sequential kd-tree delta  ->  the stencil + masked-NN fallback
  (exact; parallel over rows), as in core/exdpc.py.
* Label propagation (DFS)  ->  pointer jumping: replicated parents
  (core/labels.py) for batch callers, or the sharded one-hot-matmul
  formulation (stream/sharded.py) when a mesh is in play.

Phases (each a shard_map over the ``data`` axis; fixed shapes throughout):

1. rho:    my rows x gathered table, grid-stencil range count.
2. delta:  my rows x gathered table, stencil NN among denser rows
           (resolves the paper's alpha fraction exactly).
3. fallback: stencil-unresolved rows (padded to a static cap) x gathered
           table, dense masked NN — the (1-alpha) remainder.

Everything is exact: output equals core.run_exdpc / run_scan (tested).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro import obs
from repro.analysis.audit import audit_check_rep
from repro.core.dpc_types import DPCResult, with_jitter
from repro.core.grid import build_grid, point_span_bounds
from repro.engine.planner import as_plan
from repro.engine.spec import ExecSpec, merge_legacy
from repro.launch.mesh import flatten_mesh
from repro.kernels.ref import sq_sum

_STRATEGIES = ("gather", "halo")


@dataclass(frozen=True)
class DistDPCConfig:
    """Distributed-phase parameters.

    Execution (backend / layout / precision / block / mesh axis) is one
    :class:`repro.engine.ExecSpec` on ``exec_spec``; the ``backend`` /
    ``layout`` / ``block`` / ``data_axis`` fields are the legacy spellings
    and fold into it with a ``DeprecationWarning`` (see ``repro.engine``).

    Execution-axis semantics here:

    * backend — per-shard kernel backend.  With a pallas backend +
      'gather', the rho/delta phases run the dense MXU kernels per shard
      (my rows x gathered table) and the delta phase is already globally
      exact, so the fallback phase is skipped.  With 'halo', both phases
      run the backend's span-masked halo primitives.
    * layout 'block-sparse' — grid-pruned worklists for the per-shard
      gather-strategy phases: each shard owns a contiguous chunk of the
      space-sorted table, so its row tiles have compact AABBs against the
      gathered table and most tile pairs prune away.  Requires a backend
      whose worklists are jit-built (``worklist_traceable`` — the jnp
      backend): pallas worklists are host-built and cannot be constructed
      inside shard_map, so pallas shards keep the dense MXU tiles.
      Honored on any mesh that passes the R1 probe
      (:func:`shard_blocksparse_layout`) — with the one-hot ring walk no
      sort-derived index reaches a gather inside the shard body, so
      multi-partition meshes run block-sparse shard phases too.  (Before
      the one-hot rewrite the order-gather walk tripped the pinned
      jax-0.4.37 XLA CPU SPMD miscompile and multi-device meshes degraded
      to dense per-shard tiles.)
    """

    d_cut: float
    fallback_cap_factor: float = 0.05   # static cap: fraction of n (padded)
    # 'gather': replicate the sorted table per shard (baseline; traffic =
    #   n*d per device).  'halo': ring-ppermute only the blocks that
    #   intersect each shard's stencil window (traffic = (W+m)*d — the
    #   space-sorted layout makes candidate windows narrow; §Perf).
    strategy: str = "gather"
    exec_spec: ExecSpec | None = None
    block: int | None = None            # deprecated -> ExecSpec.block
    data_axis: str = "data"             # deprecated -> ExecSpec.data_axis
    backend: str | None = None          # deprecated -> ExecSpec.backend
    layout: str | None = None           # deprecated -> ExecSpec.layout

    def __post_init__(self):
        if not self.d_cut > 0.0:
            raise ValueError(f"d_cut must be positive, got {self.d_cut!r}")
        if self.strategy not in _STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}; "
                             f"expected one of {_STRATEGIES}")
        ex = merge_legacy(self.exec_spec, owner="DistDPCConfig",
                          backend=self.backend, layout=self.layout,
                          block=self.block, data_axis=self.data_axis)
        object.__setattr__(self, "exec_spec", ex)

    def resolved_exec(self) -> ExecSpec:
        return self.exec_spec


def _pad_rows(x, m, value):
    pad = [(0, m - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=value)


def _blocked(n, block):
    return -(-n // block)


def _halo_window(tbl_my, lo_my, axis, n_shards: int, W: int,
                 hops_fwd: int, hops_bwd: int):
    """Assemble each shard's candidate window [lo, lo+W) via ppermute rings.

    tbl_my: (m, ...) my block of the sorted table; lo_my: (1,) my window
    start.  Two chains: pass-left delivers blocks AFTER mine (hop h sees
    block s+h), pass-right delivers blocks BEFORE mine (hop h sees s-h);
    rows whose global index falls inside my window are copied in.  Traffic
    per shard = (hops_fwd + hops_bwd) * m * rowbytes, vs n * rowbytes for
    the all-gather baseline — the space-sorted layout keeps windows narrow.
    """
    m = tbl_my.shape[0]
    my_id = jax.lax.axis_index(axis)
    lo = lo_my[0]
    wrow = lo + jnp.arange(W)                        # global row of window w
    wblock = wrow // m                               # owning block
    wpos = wrow % m
    left = [(i, (i - 1) % n_shards) for i in range(n_shards)]
    right = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def take_into(window, visiting, vid):
        take = wblock == vid
        rows = visiting[jnp.minimum(wpos, m - 1)]
        return jnp.where(take.reshape((W,) + (1,) * (tbl_my.ndim - 1)),
                         rows, window)

    window = jnp.zeros((W,) + tbl_my.shape[1:], tbl_my.dtype)
    visiting = tbl_my
    for h in range(hops_fwd + 1):                    # h=0: my own block
        window = take_into(window, visiting, (my_id + h) % n_shards)
        if h < hops_fwd:
            visiting = jax.lax.ppermute(visiting, axis, left)
    visiting = tbl_my
    for h in range(1, hops_bwd + 1):
        visiting = jax.lax.ppermute(visiting, axis, right)
        window = take_into(window, visiting, (my_id - h) % n_shards)
    return window


def _make_rho_halo(axis, d_cut, block, span_w, n_shards, W, hf, hb, be):
    @audit_check_rep(
        "window rows arrive via the ppermute ring and axis_index-gated "
        "selects; every output row is P(axis)-local (my rows' counts), "
        "nothing is claimed replicated",
        collectives=("ppermute", "axis_index"))
    def rho(my_pts, my_starts, my_ends, tbl_my, lo_my):
        """Halo rho phase: ring-assemble the window, then the backend's
        span-masked range-count primitive (pallas tiles when the backend is
        dense — the optimized distributed path exercises the Mosaic kernels,
        not the jnp reference)."""
        window = _halo_window(tbl_my, lo_my, axis, n_shards, W, hf, hb)
        lo = lo_my[0]
        return be.range_count_halo(my_pts, window, my_starts - lo,
                                   my_ends - lo, d_cut, span_cap=span_w,
                                   block=block)

    return rho


def _make_delta_halo(axis, d_cut, block, span_w, n_shards, W, hf, hb, be):
    @audit_check_rep(
        "same ppermute-ring window assembly as the rho phase; outputs "
        "(delta, parent, found) are all P(axis)-local per-row results",
        collectives=("ppermute", "axis_index"))
    def delta(my_pts, my_rk, my_starts, my_ends, tbl_my, rk_my, lo_my):
        """Halo delta phase: strictly-denser NN within d_cut over the halo
        window, through the backend's span-masked NN primitive."""
        both = jnp.concatenate([tbl_my, rk_my[:, None]], axis=1)
        wboth = _halo_window(both, lo_my, axis, n_shards, W, hf, hb)
        window, wrk = wboth[:, :-1], wboth[:, -1]
        lo = lo_my[0]
        dd, pp, ok = be.denser_nn_halo(my_pts, my_rk, window, wrk,
                                       my_starts - lo, my_ends - lo, d_cut,
                                       span_cap=span_w, block=block)
        # local window idx -> global sorted slot
        pp = jnp.where(ok, (pp + lo).astype(jnp.int32), -1)
        return dd, pp, ok

    return delta


def _make_rho(axis, d_cut, block, span_w):
    d2cut = jnp.float32(d_cut) ** 2

    def rho(my_pts, my_starts, my_ends, tbl_my):
        tbl = jax.lax.all_gather(tbl_my, axis, axis=0, tiled=True)
        n = tbl.shape[0]
        m = my_pts.shape[0]
        nb = _blocked(m, block)
        mp = nb * block
        pts_p = _pad_rows(my_pts, mp, 0.0)
        st_p = _pad_rows(my_starts, mp, 0)
        en_p = _pad_rows(my_ends, mp, 0)

        def chunk(i0):
            rows = jax.lax.dynamic_slice_in_dim(pts_p, i0, block, 0)
            st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)
            en = jax.lax.dynamic_slice_in_dim(en_p, i0, block, 0)
            idx = st[..., None] + jnp.arange(span_w, dtype=st.dtype)
            valid = idx < en[..., None]
            cand = tbl[jnp.minimum(idx, n - 1)]
            d2 = sq_sum(rows[:, None, None, :] - cand)
            return jnp.sum((d2 < d2cut) & valid, axis=(1, 2))

        cnt = jax.lax.map(chunk, jnp.arange(nb) * block).reshape(-1)[:m]
        return cnt.astype(jnp.float32)

    return rho


def _make_delta(axis, d_cut, block, span_w):
    d2cut = jnp.float32(d_cut) ** 2

    def delta(my_pts, my_rk, my_starts, my_ends, tbl_my, rk_my):
        tbl = jax.lax.all_gather(tbl_my, axis, axis=0, tiled=True)
        rk_all = jax.lax.all_gather(rk_my, axis, axis=0, tiled=True)
        n = tbl.shape[0]
        m = my_pts.shape[0]
        nb = _blocked(m, block)
        mp = nb * block
        pts_p = _pad_rows(my_pts, mp, 0.0)
        rk_p = _pad_rows(my_rk, mp, jnp.inf)
        st_p = _pad_rows(my_starts, mp, 0)
        en_p = _pad_rows(my_ends, mp, 0)

        def chunk(i0):
            rows = jax.lax.dynamic_slice_in_dim(pts_p, i0, block, 0)
            rk = jax.lax.dynamic_slice_in_dim(rk_p, i0, block, 0)
            st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)
            en = jax.lax.dynamic_slice_in_dim(en_p, i0, block, 0)
            idx = st[..., None] + jnp.arange(span_w, dtype=st.dtype)
            valid = idx < en[..., None]
            idx_c = jnp.minimum(idx, n - 1)
            cand = tbl[idx_c]
            cand_rk = rk_all[idx_c]
            d2 = sq_sum(rows[:, None, None, :] - cand)
            mask = valid & (cand_rk > rk[:, None, None]) & (d2 < d2cut)
            d2m = jnp.where(mask, d2, jnp.inf).reshape(block, -1)
            j = jnp.argmin(d2m, axis=1)
            best = d2m[jnp.arange(block), j]
            pidx = idx_c.reshape(block, -1)[jnp.arange(block), j]
            ok = jnp.isfinite(best)
            return (jnp.sqrt(best),
                    jnp.where(ok, pidx, -1).astype(jnp.int32), ok)

        dd, pp, ff = jax.lax.map(chunk, jnp.arange(nb) * block)
        return (dd.reshape(-1)[:m], pp.reshape(-1)[:m], ff.reshape(-1)[:m])

    return delta


def _make_fallback(axis, block, be, layout=None):
    @audit_check_rep(
        "the table and its keys are made identical on every member by "
        "all_gather(tiled) before use; outputs are P(axis)-local query "
        "rows", collectives=("all_gather",))
    def fallback(q_pts, q_rk, tbl_my, rk_my):
        """Dense denser-NN for unresolved rows (padded, rk=+inf rows inert):
        the backend's Def.-2 primitive over my queries x gathered table."""
        tbl = jax.lax.all_gather(tbl_my, axis, axis=0, tiled=True)
        rk_all = jax.lax.all_gather(rk_my, axis, axis=0, tiled=True)
        return be.denser_nn(q_pts, q_rk, tbl, rk_all, block=block,
                            layout=layout)

    return fallback


def _make_rho_dense(axis, d_cut, block, be, layout=None):
    @audit_check_rep(
        "the gathered table is replicated by all_gather(tiled); the range "
        "count reads it and writes P(axis)-local per-row counts only",
        collectives=("all_gather",))
    def rho(my_pts, tbl_my):
        """Engine tiles: my rows x gathered table (kernel range count;
        grid-pruned worklist when layout='block-sparse' — the shard rows
        are a contiguous chunk of the space-sorted table, so the jit-built
        AABB worklist prunes most of the gathered table's tiles)."""
        tbl = jax.lax.all_gather(tbl_my, axis, axis=0, tiled=True)
        return be.range_count(my_pts, tbl, d_cut, block=block, layout=layout)

    return rho


def _make_delta_dense(axis, block, be, layout=None):
    @audit_check_rep(
        "table and keys replicated by all_gather(tiled) before the NN "
        "kernel; outputs are P(axis)-local per-row (delta, parent, ok)",
        collectives=("all_gather",))
    def delta(my_pts, my_rk, tbl_my, rk_my):
        """Engine denser-NN kernel: globally exact, no fallback needed."""
        tbl = jax.lax.all_gather(tbl_my, axis, axis=0, tiled=True)
        rk_all = jax.lax.all_gather(rk_my, axis, axis=0, tiled=True)
        dd, pp = be.denser_nn(my_pts, my_rk, tbl, rk_all, block=block,
                              layout=layout)
        # the only infinite delta is the global peak (already final)
        return dd, pp, jnp.ones(dd.shape, bool)

    return delta


_BS_SAFE_CACHE: dict = {}


def _bs_shards_safe(flat_mesh, axis: str, be) -> bool:
    """R1 probe: trace the block-sparse shard phases this mesh would run
    and ask :func:`repro.analysis.spmd_gather_safe` whether any sort-
    derived value feeds a gather/dynamic-slice index inside the
    multi-partition body — the exact pattern the pinned jax-0.4.37 XLA CPU
    SPMD pipeline miscompiles (``ord_i[p]`` degrades to ``p``, silently
    skipping kept tiles).  Memoized per (shard count, axis, backend):
    the verdict depends only on the traced program, not on data."""
    S = int(flat_mesh.devices.size)
    key = (S, axis, be.name)
    hit = _BS_SAFE_CACHE.get(key)
    if hit is not None:
        return hit
    from repro.analysis import spmd_gather_safe

    rho_fn = _make_rho_dense(axis, 1.0, 256, be, layout="block-sparse")
    delta_fn = _make_delta_dense(axis, 256, be, layout="block-sparse")
    sm_rho = shard_map(rho_fn, mesh=flat_mesh,
                       in_specs=(P(axis), P(axis)), out_specs=P(axis),
                       check_vma=False)
    sm_delta = shard_map(delta_fn, mesh=flat_mesh, in_specs=(P(axis),) * 4,
                         out_specs=(P(axis), P(axis), P(axis)),
                         check_vma=False)
    pts = jnp.zeros((S * 8, 2), jnp.float32)
    rk = jnp.zeros((S * 8,), jnp.float32)
    ok = bool(spmd_gather_safe(sm_rho, pts, pts)
              and spmd_gather_safe(sm_delta, pts, rk, pts, rk))
    _BS_SAFE_CACHE[key] = ok
    return ok


# Shard-phase layout decisions, visible in ``python -m repro.obs report``:
# a future probe regression shows up as a dist_bs_degrade_total increment
# with reason=r1-probe-failed instead of only in timings.
_M_BS_ENABLED = obs.counter(
    "dist_bs_enabled",
    "shard-phase layout decisions that kept block-sparse worklists")
_M_BS_DEGRADE = obs.counter(
    "dist_bs_degrade_total",
    "shard-phase layout decisions that degraded block-sparse to dense "
    "per-shard tiles, by reason")
_G_BS_LAYOUT = obs.gauge(
    "dist_bs_layout",
    "last shard-phase layout decision (1 = block-sparse, 0 = dense "
    "degrade), by reason")


def shard_blocksparse_layout(pl, mesh) -> str | None:
    """The layout the per-shard gather-strategy phases run with:
    ``"block-sparse"`` when the plan asks for it AND the shards can honor
    it, else ``None`` (dense degrade — correct results always beat pruned
    tile counts).

    Per-shard block-sparse needs jit-built worklists (inside shard_map),
    so only ``worklist_traceable`` backends qualify.  On multi-partition
    meshes the phases must additionally pass the R1 probe
    (:func:`_bs_shards_safe`) against the pinned jax-0.4.37 XLA CPU SPMD
    miscompile.  The one-hot ring walk keeps every sort-derived value out
    of gather/dynamic-slice index position, so the probe passes and
    multi-device meshes run block-sparse shard phases
    (tests/test_distributed_dpc.py pins both the probe verdict and
    bit-parity with ``run_exdpc`` in a 4-device subprocess).

    Every decision on a sparse plan is recorded on the obs registry
    (``dist_bs_enabled`` / ``dist_bs_degrade_total`` with a reason label,
    plus the ``dist_bs_layout`` gauge) so a silent future degrade is
    visible in ``python -m repro.obs report``."""
    be = pl.backend
    if not pl.sparse:
        return None                     # dense plan: nothing to decide

    def decide(layout, reason):
        (_M_BS_DEGRADE if layout is None else _M_BS_ENABLED).inc(
            reason=reason)
        _G_BS_LAYOUT.set(0.0 if layout is None else 1.0, reason=reason)
        return layout

    if not be.worklist_traceable:
        return decide(None, "host-worklist-backend")
    flat_mesh = flatten_mesh(mesh, pl.data_axis)
    if flat_mesh.devices.size == 1:
        return decide("block-sparse", "single-partition")
    if _bs_shards_safe(flat_mesh, pl.data_axis, be):
        return decide("block-sparse", "r1-probe-passed")
    return decide(None, "r1-probe-failed")


def distributed_dpc(points, cfg: DistDPCConfig | None = None,
                    mesh: Mesh | None = None, *, d_cut: float | None = None,
                    exec_spec=None, strategy: str | None = None,
                    fallback_cap_factor: float | None = None) -> DPCResult:
    """Exact DPC (Ex-DPC semantics) on a device mesh.  Host-orchestrated
    phases, each an SPMD shard_map over the exec spec's data axis.

    Two spellings — mutually exclusive, never silently merged: the legacy
    ``distributed_dpc(points, cfg, mesh)`` with a :class:`DistDPCConfig`,
    or the unified-engine form ``distributed_dpc(points, mesh=mesh,
    d_cut=..., exec_spec=ExecSpec(...), strategy=...)``.
    """
    if cfg is None:
        if d_cut is None:
            raise ValueError("distributed_dpc needs a DistDPCConfig or an "
                             "explicit d_cut=")
        cfg = DistDPCConfig(d_cut=d_cut,
                            strategy=strategy or "gather",
                            fallback_cap_factor=0.05
                            if fallback_cap_factor is None
                            else fallback_cap_factor,
                            exec_spec=as_plan(exec_spec).spec
                            if exec_spec is not None else None)
    else:
        clashes = [n for n, v in (("d_cut", d_cut), ("exec_spec", exec_spec),
                                  ("strategy", strategy),
                                  ("fallback_cap_factor",
                                   fallback_cap_factor)) if v is not None]
        if clashes:
            raise ValueError(f"pass {clashes} either on the DistDPCConfig "
                             f"or as kwargs, not both")
    if mesh is None:
        raise ValueError("distributed_dpc needs a mesh")
    points = jnp.asarray(points, jnp.float32)
    pl = as_plan(cfg.resolved_exec(), points)
    be = pl.backend
    # one resolved row-block for every distributed phase (legacy default
    # 256 — the per-shard chunk loops and halo tiles were tuned to it)
    block = pl.block if pl.block is not None else 256
    n_orig, d = points.shape
    axis = pl.data_axis
    # flatten every mesh axis into the data dimension for DPC: a dedicated
    # 1-axis view keeps specs simple (launch.mesh.flatten_mesh).
    flat_mesh = flatten_mesh(mesh, axis)
    S_data = flat_mesh.devices.size

    with obs.span("dist.grid", n=n_orig) as sp:
        grid = sp.sync(build_grid(points, cfg.d_cut))
    n = grid.points.shape[0]
    # pad rows to a multiple of the shard count; padded rows are inert
    m = -(-n // S_data) * S_data
    pts_s = _pad_rows(grid.points, m, 1e9)

    halo = cfg.strategy == "halo"
    shard_layout = shard_blocksparse_layout(pl, flat_mesh)
    dense = (be.mxu_dense or shard_layout == "block-sparse") and not halo
    if halo or not dense:   # the dense kernel tiles never read the spans
        starts, ends = point_span_bounds(grid)      # (n, S_spans)
        span_w = grid.span_cap
        starts_p = _pad_rows(starts, m, 0).astype(jnp.int32)
        ends_p = _pad_rows(ends, m, 0).astype(jnp.int32)
    if halo:
        # per-shard window bounds from the span table (host: data statistic)
        rows_per = m // S_data
        st_np = np.asarray(starts_p).reshape(S_data, rows_per, -1)
        en_np = np.asarray(ends_p).reshape(S_data, rows_per, -1)
        nonempty = en_np > st_np
        lo_s = np.where(nonempty, st_np, np.iinfo(np.int64).max) \
                 .reshape(S_data, -1).min(axis=1)
        hi_s = en_np.reshape(S_data, -1).max(axis=1)
        starts_block = np.arange(S_data) * rows_per
        lo_s = np.minimum(lo_s, starts_block)
        hi_s = np.maximum(hi_s, starts_block + rows_per)
        W = int((hi_s - lo_s).max())
        # ring reach in blocks, forward and backward of each shard's own
        hf = int(min(S_data - 1,
                     -(-max(int((hi_s - starts_block - rows_per).max()), 0)
                       // rows_per)))
        hb = int(min(S_data - 1,
                     -(-max(int((starts_block - lo_s).max()), 0)
                       // rows_per)))
        lo_arr = jnp.asarray(lo_s[:, None].astype(np.int64))  # (S, 1)

        rho_fn = _make_rho_halo(axis, cfg.d_cut, block, span_w,
                                S_data, W, hf, hb, be)
        sm_rho = shard_map(rho_fn, mesh=flat_mesh,
                           in_specs=(P(axis),) * 5, out_specs=P(axis),
                           check_vma=not be.mxu_dense)  # pallas: no rep rule
        with obs.span("dist.rho", n=n, shards=S_data,
                      strategy=cfg.strategy) as sp:
            rho_sorted = sp.sync(jax.jit(sm_rho)(
                pts_s, starts_p, ends_p, pts_s, lo_arr)[:n])
    elif dense:
        rho_fn = _make_rho_dense(axis, cfg.d_cut, block, be,
                                 layout=shard_layout)
        sm_rho = shard_map(rho_fn, mesh=flat_mesh,
                           in_specs=(P(axis), P(axis)), out_specs=P(axis),
                           check_vma=False)   # pallas_call lacks a rep rule
        with obs.span("dist.rho", n=n, shards=S_data,
                      strategy=cfg.strategy) as sp:
            rho_sorted = sp.sync(jax.jit(sm_rho)(pts_s, pts_s)[:n])
    else:
        rho_fn = _make_rho(axis, cfg.d_cut, block, span_w)
        sm_rho = shard_map(rho_fn, mesh=flat_mesh,
                           in_specs=(P(axis), P(axis), P(axis), P(axis)),
                           out_specs=P(axis))
        with obs.span("dist.rho", n=n, shards=S_data,
                      strategy=cfg.strategy) as sp:
            rho_sorted = sp.sync(jax.jit(sm_rho)(
                pts_s, starts_p, ends_p, pts_s)[:n])

    rho = rho_sorted[grid.inv_order]
    rho_key = with_jitter(rho)
    rk_sorted_full = _pad_rows(rho_key[grid.order], m, -jnp.inf)
    # queries must carry +inf keys on padded rows so they never match
    rk_query = _pad_rows(rho_key[grid.order], m, jnp.inf)
    if halo:
        delta_fn = _make_delta_halo(axis, cfg.d_cut, block, span_w,
                                    S_data, W, hf, hb, be)
        sm_delta = shard_map(delta_fn, mesh=flat_mesh,
                             in_specs=(P(axis),) * 7,
                             out_specs=(P(axis), P(axis), P(axis)),
                             check_vma=not be.mxu_dense)  # pallas: no rep rule
        with obs.span("dist.delta", n=n, shards=S_data) as sp:
            dlt_s, par_s, ok_s = sp.sync(jax.jit(sm_delta)(
                pts_s, rk_query, starts_p, ends_p, pts_s, rk_sorted_full,
                lo_arr))
    elif dense:
        delta_fn = _make_delta_dense(axis, block, be,
                                     layout=shard_layout)
        sm_delta = shard_map(delta_fn, mesh=flat_mesh,
                             in_specs=(P(axis),) * 4,
                             out_specs=(P(axis), P(axis), P(axis)),
                             check_vma=False)  # pallas_call lacks a rep rule
        with obs.span("dist.delta", n=n, shards=S_data) as sp:
            dlt_s, par_s, ok_s = sp.sync(jax.jit(sm_delta)(
                pts_s, rk_query, pts_s, rk_sorted_full))
    else:
        delta_fn = _make_delta(axis, cfg.d_cut, block, span_w)
        sm_delta = shard_map(delta_fn, mesh=flat_mesh,
                             in_specs=(P(axis),) * 6,
                             out_specs=(P(axis), P(axis), P(axis)))
        with obs.span("dist.delta", n=n, shards=S_data) as sp:
            dlt_s, par_s, ok_s = sp.sync(jax.jit(sm_delta)(
                pts_s, rk_query, starts_p, ends_p, pts_s, rk_sorted_full))
    dlt_s, par_s, ok_s = dlt_s[:n], par_s[:n], ok_s[:n]

    # ---- fallback for stencil-unresolved rows (exact, the 1-alpha tail)
    unresolved = np.nonzero(~np.asarray(ok_s))[0]
    if unresolved.size:
        cap = max(S_data, int(-(-unresolved.size // S_data) * S_data))
        q_idx = np.pad(unresolved, (0, cap - unresolved.size),
                       constant_values=0)
        q_pts = grid.points[jnp.asarray(q_idx)]
        q_rk = jnp.asarray(np.where(
            np.arange(cap) < unresolved.size,
            np.asarray(rho_key[grid.order])[q_idx], np.inf))
        # the halo phases route through the configured backend's span-masked
        # kernels (winners direct-diff refined), so the fallback uses the
        # same backend — no silent jnp detour on the optimized path
        fb_be = be
        fb_fn = _make_fallback(axis, max(block, 1024), fb_be,
                               layout=shard_layout)
        sm_fb = shard_map(fb_fn, mesh=flat_mesh,
                          in_specs=(P(axis), P(axis), P(axis), P(axis)),
                          out_specs=(P(axis), P(axis)),
                          check_vma=not fb_be.mxu_dense)
        with obs.span("dist.fallback", unresolved=int(unresolved.size),
                      shards=S_data) as sp:
            fd, fp = sp.sync(jax.jit(sm_fb)(q_pts, q_rk, pts_s,
                                            rk_sorted_full))
        fd = np.asarray(fd)[: unresolved.size]
        fp = np.asarray(fp)[: unresolved.size]
        dlt = np.asarray(dlt_s).copy()
        par = np.asarray(par_s).copy()
        dlt[unresolved] = np.where(np.isfinite(fd), fd, np.inf)
        par[unresolved] = fp
        dlt_s, par_s = jnp.asarray(dlt), jnp.asarray(par)

    delta = dlt_s[grid.inv_order]
    parent_sorted = par_s[grid.inv_order]
    parent = jnp.where(parent_sorted >= 0, grid.order[parent_sorted],
                       -1).astype(jnp.int32)
    return DPCResult(rho=rho, rho_key=rho_key, delta=delta, parent=parent)
