"""Grid-stencil range counting and higher-density NN search.

These are the pure-jnp reference forms of the two compute hot spots the paper
optimizes (local density = range count; dependent point = constrained NN).
``repro.kernels`` provides the Pallas TPU versions; tests assert equality.

All functions operate in *sorted* (grid) order and are blocked with ``lax.map``
so memory stays O(block * stencil_window).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .grid import Grid, cell_span_bounds, point_span_bounds
from repro.kernels.ref import sq_sum


def _pad_to(x: jnp.ndarray, m: int, axis: int = 0, value=0):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - x.shape[axis])
    return jnp.pad(x, pad, constant_values=value)


@partial(jax.jit, static_argnames=("block",))
def density_per_point(grid: Grid, block: int = 256) -> jnp.ndarray:
    """Exact rho per *sorted* point via per-point stencil gathers.

    This is the Ex-DPC analogue of "one range search per point": every point
    gathers its own candidate spans.  O(n * S * W) with S = 3^(g-1) spans of
    padded width W = grid.span_cap.
    """
    n, d = grid.points.shape
    starts, ends = point_span_bounds(grid)                    # (n, S)
    S = starts.shape[1]
    W = grid.span_cap
    d2cut = jnp.float32(grid.d_cut) ** 2
    nb = -(-n // block)
    pts_p = _pad_to(grid.points, nb * block)
    st_p = _pad_to(starts, nb * block)
    en_p = _pad_to(ends, nb * block)

    def chunk(i0):
        rows = jax.lax.dynamic_slice_in_dim(pts_p, i0, block, 0)      # (B, d)
        st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)         # (B, S)
        en = jax.lax.dynamic_slice_in_dim(en_p, i0, block, 0)
        idx = st[..., None] + jnp.arange(W, dtype=st.dtype)           # (B, S, W)
        valid = idx < en[..., None]
        cand = grid.points[jnp.minimum(idx, n - 1)]                   # (B, S, W, d)
        d2 = sq_sum(rows[:, None, None, :] - cand)
        return jnp.sum((d2 < d2cut) & valid, axis=(1, 2))

    cnt = jax.lax.map(chunk, jnp.arange(nb) * block).reshape(-1)[:n]
    return cnt.astype(jnp.float32)


@partial(jax.jit, static_argnames=("block",))
def density_per_cell(grid: Grid, block: int = 32) -> jnp.ndarray:
    """Exact rho per sorted point via *joint* per-cell gathers (Approx-DPC §4.2).

    All members of a candidate cell share one gather of the cell's stencil
    spans — the TPU formulation of the paper's joint range search (one
    enlarged search serves the whole cell).  Returns rho in sorted order.
    """
    n, d = grid.points.shape
    starts, ends = cell_span_bounds(grid)                     # (n, S) padded cells
    S = starts.shape[1]
    W = grid.span_cap
    M = grid.cell_cap
    d2cut = jnp.float32(grid.d_cut) ** 2
    nc = grid.num_cells
    nb = -(-nc // block)
    st_p = _pad_to(starts[:nc], nb * block)
    en_p = _pad_to(ends[:nc], nb * block)
    cs_p = _pad_to(grid.cell_start[:nc], nb * block, value=n)
    cc_p = _pad_to(grid.cell_count[:nc], nb * block)

    def chunk(i0):
        cst = jax.lax.dynamic_slice_in_dim(cs_p, i0, block, 0)        # (B,)
        ccnt = jax.lax.dynamic_slice_in_dim(cc_p, i0, block, 0)
        st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)         # (B, S)
        en = jax.lax.dynamic_slice_in_dim(en_p, i0, block, 0)
        midx = cst[:, None] + jnp.arange(M, dtype=cst.dtype)          # (B, M)
        mvalid = jnp.arange(M) < ccnt[:, None]
        members = grid.points[jnp.minimum(midx, n - 1)]               # (B, M, d)
        cidx = st[..., None] + jnp.arange(W, dtype=st.dtype)          # (B, S, W)
        cvalid = cidx < en[..., None]
        cand = grid.points[jnp.minimum(cidx, n - 1)]                  # (B, S, W, d)
        cand = cand.reshape(block, S * W, d)
        cvalid = cvalid.reshape(block, S * W)
        d2 = sq_sum(members[:, :, None, :] - cand[:, None, :, :])
        cnt = jnp.sum((d2 < d2cut) & cvalid[:, None, :], axis=-1)     # (B, M)
        return cnt, midx, mvalid

    cnts, midxs, mvalids = jax.lax.map(chunk, jnp.arange(nb) * block)
    flat_idx = jnp.where(mvalids.reshape(-1), midxs.reshape(-1), n)
    rho = jnp.zeros((n,), jnp.float32).at[flat_idx].set(
        cnts.reshape(-1).astype(jnp.float32), mode="drop")
    return rho


@partial(jax.jit, static_argnames=("block",))
def dependent_stencil(grid: Grid, rho_key_sorted: jnp.ndarray, block: int = 256):
    """Nearest higher-density point within the d_cut stencil, per sorted point.

    Returns (delta, parent_sorted_idx, resolved).  Where ``resolved`` is True,
    delta/parent are *exact* (the true dependent point must lie within d_cut,
    hence inside the stencil — DESIGN.md §3).  Where False, no higher-density
    point exists within d_cut and the caller must run the global fallback.
    """
    n, d = grid.points.shape
    starts, ends = point_span_bounds(grid)
    S = starts.shape[1]
    W = grid.span_cap
    d2cut = jnp.float32(grid.d_cut) ** 2
    nb = -(-n // block)
    pts_p = _pad_to(grid.points, nb * block)
    rk_p = _pad_to(rho_key_sorted, nb * block, value=jnp.inf)
    st_p = _pad_to(starts, nb * block)
    en_p = _pad_to(ends, nb * block)

    def chunk(i0):
        rows = jax.lax.dynamic_slice_in_dim(pts_p, i0, block, 0)
        rk = jax.lax.dynamic_slice_in_dim(rk_p, i0, block, 0)
        st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)
        en = jax.lax.dynamic_slice_in_dim(en_p, i0, block, 0)
        idx = st[..., None] + jnp.arange(W, dtype=st.dtype)           # (B,S,W)
        valid = idx < en[..., None]
        idx_c = jnp.minimum(idx, n - 1)
        cand = grid.points[idx_c]                                     # (B,S,W,d)
        cand_rk = rho_key_sorted[idx_c]
        d2 = sq_sum(rows[:, None, None, :] - cand)
        mask = valid & (cand_rk > rk[:, None, None]) & (d2 < d2cut)
        d2m = jnp.where(mask, d2, jnp.inf).reshape(block, S * W)
        j = jnp.argmin(d2m, axis=1)
        best = d2m[jnp.arange(block), j]
        pidx = idx_c.reshape(block, S * W)[jnp.arange(block), j]
        resolved = jnp.isfinite(best)
        return (jnp.sqrt(best), jnp.where(resolved, pidx, -1).astype(jnp.int32),
                resolved)

    delta, parent, resolved = jax.lax.map(chunk, jnp.arange(nb) * block)
    return delta.reshape(-1)[:n], parent.reshape(-1)[:n], resolved.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("block",))
def density_for_slots(grid: Grid, slots: jnp.ndarray, block: int = 256) -> jnp.ndarray:
    """Exact rho for a subset of sorted slots (S-Approx-DPC representatives).

    ``slots`` is padded with n (out of range) — padded rows return 0.
    """
    n, d = grid.points.shape
    starts_all, ends_all = point_span_bounds(grid)
    S = starts_all.shape[1]
    W = grid.span_cap
    d2cut = jnp.float32(grid.d_cut) ** 2
    m = slots.shape[0]
    nb = -(-m // block)
    sl_p = _pad_to(slots, nb * block, value=n)

    def chunk(i0):
        sl = jax.lax.dynamic_slice_in_dim(sl_p, i0, block, 0)
        alive = sl < n
        slc = jnp.minimum(sl, n - 1)
        rows = grid.points[slc]
        st = starts_all[slc]
        en = ends_all[slc]
        idx = st[..., None] + jnp.arange(W, dtype=st.dtype)
        valid = idx < en[..., None]
        cand = grid.points[jnp.minimum(idx, n - 1)]
        d2 = sq_sum(rows[:, None, None, :] - cand)
        cnt = jnp.sum((d2 < d2cut) & valid, axis=(1, 2))
        return jnp.where(alive, cnt, 0)

    cnt = jax.lax.map(chunk, jnp.arange(nb) * block).reshape(-1)[:m]
    return cnt.astype(jnp.float32)


@partial(jax.jit, static_argnames=("block",))
def dependent_stencil_slots(grid: Grid, rho_key_sorted: jnp.ndarray,
                            slots: jnp.ndarray, block: int = 256):
    """dependent_stencil restricted to query rows ``slots`` (padded with n).

    Candidates whose rho_key is -inf never match, so callers can restrict the
    candidate set (e.g. to representatives) by masking rho_key_sorted.
    """
    n, d = grid.points.shape
    starts_all, ends_all = point_span_bounds(grid)
    S = starts_all.shape[1]
    W = grid.span_cap
    d2cut = jnp.float32(grid.d_cut) ** 2
    m = slots.shape[0]
    nb = -(-m // block)
    sl_p = _pad_to(slots, nb * block, value=n)

    def chunk(i0):
        sl = jax.lax.dynamic_slice_in_dim(sl_p, i0, block, 0)
        alive = sl < n
        slc = jnp.minimum(sl, n - 1)
        rows = grid.points[slc]
        rk = jnp.where(alive, rho_key_sorted[slc], jnp.inf)
        st = starts_all[slc]
        en = ends_all[slc]
        idx = st[..., None] + jnp.arange(W, dtype=st.dtype)
        valid = idx < en[..., None]
        idx_c = jnp.minimum(idx, n - 1)
        cand = grid.points[idx_c]
        cand_rk = rho_key_sorted[idx_c]
        d2 = sq_sum(rows[:, None, None, :] - cand)
        mask = valid & (cand_rk > rk[:, None, None]) & (d2 < d2cut)
        d2m = jnp.where(mask, d2, jnp.inf).reshape(block, S * W)
        j = jnp.argmin(d2m, axis=1)
        best = d2m[jnp.arange(block), j]
        pidx = idx_c.reshape(block, S * W)[jnp.arange(block), j]
        resolved = jnp.isfinite(best)
        return (jnp.sqrt(best), jnp.where(resolved, pidx, -1).astype(jnp.int32),
                resolved)

    delta, parent, resolved = jax.lax.map(chunk, jnp.arange(nb) * block)
    return delta.reshape(-1)[:m], parent.reshape(-1)[:m], resolved.reshape(-1)[:m]


@partial(jax.jit, static_argnames=("block",))
def masked_nn_rows(query_pts, query_rk, all_pts, all_rk, block: int = 4096):
    """Exact NN among strictly-denser points, query rows vs the full set.

    The global fallback for stencil-unresolved points (paper Lemma 2's
    (1-alpha) case). O(m * n), m = number of query rows.
    """
    m = query_pts.shape[0]
    n = all_pts.shape[0]
    nb = -(-n // block)
    pts_p = _pad_to(all_pts, nb * block)
    rk_p = _pad_to(all_rk, nb * block, value=-jnp.inf)

    def col_block(j0):
        cols = jax.lax.dynamic_slice_in_dim(pts_p, j0, block, 0)
        crk = jax.lax.dynamic_slice_in_dim(rk_p, j0, block, 0)
        d2 = sq_sum(query_pts[:, None, :] - cols[None, :, :])
        d2 = jnp.where(crk[None, :] > query_rk[:, None], d2, jnp.inf)
        j = jnp.argmin(d2, axis=1)
        return d2[jnp.arange(m), j], (j0 + j).astype(jnp.int32)

    d2s, js = jax.lax.map(col_block, jnp.arange(nb) * block)   # (nb, m)
    k = jnp.argmin(d2s, axis=0)
    best = d2s[k, jnp.arange(m)]
    parent = jnp.where(jnp.isfinite(best), js[k, jnp.arange(m)], -1)
    return jnp.sqrt(best), parent
