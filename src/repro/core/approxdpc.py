"""Approx-DPC (§4): exact rho, O(1) approximate dependents, same centers.

Paper rules, realized with segment ops over the grouping grid G (side
d_cut/sqrt(d), in-cell diameter < d_cut):

1. p_i != p*(cell)  ->  parent = p*(cell), delta = d_cut.     [segment argmax]
2. p_i == p*(cell)  ->  nearest denser point within d_cut via the stencil
   (the paper's N(c)/min-rho test, evaluated directly in vector form);
   if found: parent = it, delta = d_cut.
3. otherwise (cell-max with no denser point within d_cut): exact global
   masked-NN fallback — these are the "stem" roots, |roots| << n.

rho is exact (joint per-cell range count), so Theorem 4 (identical cluster
centers to Ex-DPC for the same rho_min/delta_min) carries over: every point
resolved by rules 1-2 has true delta < d_cut < delta_min under Ex-DPC too, and
every root gets its exact delta.  Property-tested in tests/test_dpc_core.py.

With a pallas backend the grouping grid (rule 1) is unchanged but both hot
primitives come from ONE fused ``rho_delta`` tile sweep (kernels/sweep.py):
the same pass that counts every row's density also keeps its k nearest
candidates, so rules 2 and 3 read the per-row denser-NN for the cell maxima
with no second table sweep — the NN is within d_cut iff rule 2 fires, and
otherwise IS the rule-3 exact root distance.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.engine.planner import as_plan

from .dpc_types import DPCResult, density_jitter, with_jitter
from .exdpc import resolve_fallback
from .grid import build_grid, Grid, unsort_dpc
from .stencil import density_per_cell, dependent_stencil


def _group_segments(grid: Grid):
    """Contiguous grouping-cell segment id per sorted point (G is a refinement
    of the candidate grid on the leading dims, so one sort serves both)."""
    gk = grid.group_key
    is_first = jnp.concatenate([jnp.ones((1,), bool), gk[1:] != gk[:-1]])
    return (jnp.cumsum(is_first) - 1).astype(jnp.int32)


def run_approxdpc(points, d_cut: float, *, g: int | None = None,
                  cell_block: int = 32, fallback_block: int = 4096,
                  grid: Grid | None = None, exec_spec=None) -> DPCResult:
    points = jnp.asarray(points, jnp.float32)
    pl = as_plan(exec_spec, points)
    n = points.shape[0]
    block = pl.block or 256     # stencil row-tile default (jnp path)
    if grid is None:
        with obs.span("approxdpc.grid", n=n) as sp:
            grid = sp.sync(build_grid(points, d_cut, g=g))

    seg = _group_segments(grid)
    sparse = pl.sparse

    # --- exact local density: joint per-cell range count (§4.2) on the
    #     reference backend, fused rho+delta tile sweep on pallas (or any
    #     backend in the grid-pruned block-sparse layout) ---
    nn_delta_all = nn_parent_all = None
    use_engine = pl.backend.mxu_dense or sparse
    if sparse:
        def _maxima_mask_sorted(rk_s):
            # the engine ran on the grid-sorted table, so the interest
            # mask is directly the per-cell argmax in sorted space
            seg_max = jax.ops.segment_max(rk_s, seg, num_segments=n)
            return rk_s == seg_max[seg]

        with obs.span("approxdpc.rho_delta", n=n, layout=pl.layout) as sp:
            rho_s, rk_s, nnd_s, nnp_s = pl.rho_delta(
                grid.points, grid.points, d_cut,
                jitter=density_jitter(n)[grid.order],
                fallback_interest=_maxima_mask_sorted)
            rho, rho_key, nn_delta_all, nn_parent_all = sp.sync(unsort_dpc(
                grid, rho_s, rk_s, nnd_s, nnp_s))
    elif use_engine:
        def _maxima_mask(rho_key):
            # only cell maxima consume the Def.-2 answer (rules 2+3), so the
            # fused path's unresolved-row fallback is restricted to them —
            # the |G| << n rectangular pass the paper's cost model counts on
            rk_s = rho_key[grid.order]
            seg_max = jax.ops.segment_max(rk_s, seg, num_segments=n)
            return (rk_s == seg_max[seg])[grid.inv_order]

        # one engine invocation answers Def. 1 for every row AND Def. 2 for
        # the rows that will need it (the cell maxima, picked below)
        with obs.span("approxdpc.rho_delta", n=n, layout=pl.layout) as sp:
            rho, rho_key, nn_delta_all, nn_parent_all = sp.sync(pl.rho_delta(
                points, points, d_cut, jitter=density_jitter(n),
                fallback_interest=_maxima_mask))
    else:
        with obs.span("approxdpc.rho", n=n) as sp:
            rho = sp.sync(density_per_cell(grid,
                                           block=cell_block)[grid.inv_order])
        rho_key = with_jitter(rho)
    rk_sorted = rho_key[grid.order]

    # --- rule 1: in-cell O(1) dependents via segment argmax ---
    num_seg = n  # <= n segments; segment ops padded to n
    seg_max = jax.ops.segment_max(rk_sorted, seg, num_segments=num_seg)
    is_cellmax = rk_sorted == seg_max[seg]
    # index of each cell's max point (sorted order)
    slot = jnp.arange(n, dtype=jnp.int32)
    cellmax_slot = jax.ops.segment_max(jnp.where(is_cellmax, slot, -1), seg,
                                       num_segments=num_seg)
    parent_s = cellmax_slot[seg]                 # rule-1 parent (sorted idx)
    delta_s = jnp.full((n,), grid.d_cut, jnp.float32)

    if use_engine:
        # --- rules 2+3 from the fused sweep's per-row denser-NN: only the
        #     cell maxima consume it (every other row is rule 1).  NN within
        #     d_cut -> rule 2 (delta stamped d_cut); NN beyond d_cut ->
        #     rule 3 exact root delta (inf at the peak).
        with obs.span("approxdpc.rules", n=n) as sp:
            is_cm = np.asarray(is_cellmax[grid.inv_order])
            cm_rows = is_cm.nonzero()[0]
            # the mask comes down; cm_rows goes up once per use (two
            # gathers, two scatters)
            sp.set(d2h_bytes=int(is_cm.nbytes),
                   h2d_bytes=4 * int(cm_rows.nbytes))
            nn_delta = nn_delta_all[cm_rows]
            nn_parent = nn_parent_all[cm_rows]
            parent1 = jnp.where(parent_s >= 0, grid.order[parent_s], -1)
            parent1 = parent1[grid.inv_order]
            found2 = jnp.isfinite(nn_delta) & (nn_delta < d_cut)
            cm_delta = jnp.where(found2, jnp.float32(d_cut),
                                 jnp.where(jnp.isfinite(nn_delta), nn_delta,
                                           jnp.inf))
            delta = jnp.full((n,), d_cut,
                             jnp.float32).at[cm_rows].set(cm_delta)
            parent = parent1.at[cm_rows].set(nn_parent).astype(jnp.int32)
            sp.sync((delta, parent))
        return DPCResult(rho=rho, rho_key=rho_key, delta=delta,
                         parent=parent)

    resolved_s = ~is_cellmax

    # --- rule 2: cell maxima consult the d_cut stencil ---
    # (the stencil pass computes for every point; only cell maxima consume it.
    #  This is the vector-SPMD trade: lanes are cheaper than gather plumbing.)
    with obs.span("approxdpc.stencil", n=n) as sp:
        st_delta, st_parent, st_found = dependent_stencil(grid, rk_sorted,
                                                          block=block)
        use2 = is_cellmax & st_found
        parent_s = jnp.where(use2, st_parent, parent_s)
        delta_s = jnp.where(use2, jnp.float32(grid.d_cut), delta_s)  # paper: d_cut
        resolved_s = resolved_s | use2

        delta = delta_s[grid.inv_order]
        parent_sorted = parent_s[grid.inv_order]
        parent = jnp.where(parent_sorted >= 0, grid.order[parent_sorted],
                           -1).astype(jnp.int32)
        resolved = sp.sync(resolved_s[grid.inv_order])

    # --- rule 3: exact fallback for the stem roots ---
    with obs.span("approxdpc.fallback") as sp:
        delta, parent = sp.sync(resolve_fallback(
            points, rho_key, delta, parent, resolved,
            block=fallback_block, backend=pl.backend))
    return DPCResult(rho=rho, rho_key=rho_key, delta=delta,
                     parent=parent.astype(jnp.int32))
