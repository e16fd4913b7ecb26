"""Oracles for the Pallas kernels (the ground truth in kernel tests): pure
jnp for their answers, numpy for their block-sparse worklists."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def sq_sum_parts(parts) -> jnp.ndarray:
    """``sum(c * c for c in parts)``, rounded the same way on every path.

    Every direct-difference distance in the package goes through here, so
    the paths that must agree bit for bit (kernel counts and re-ranks,
    their epilogues, the jnp reference, the stencils) do.  A plain sum
    leaves the compiler free to pick the reduction order and which multiply
    to contract into an add (FMA), and those choices differ between fusions,
    CPUs and the TPU.  Here each f32 component splits into a 12-bit head and
    its exact tail, so every product below is exact and contracting it
    cannot change a bit; the sum is a fixed left fold.
    """
    terms = []
    for c in parts:
        if c.dtype != jnp.float32:
            terms.append(c * c)
            continue
        h = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(c, jnp.int32) & jnp.int32(-4096),
            jnp.float32)
        t = jnp.where(h == c, jnp.float32(0.0), c - h)   # inf: no tail
        terms += [h * h, (h + h) * t, t * t]
    acc = terms[0]
    for t in terms[1:]:
        acc = acc + t
    return acc


def sq_sum(diff: jnp.ndarray, keepdims: bool = False) -> jnp.ndarray:
    """``sum(diff ** 2, axis=-1)`` through :func:`sq_sum_parts`."""
    if keepdims:
        return sq_sum_parts(diff[..., k:k + 1] for k in range(diff.shape[-1]))
    return sq_sum_parts(diff[..., k] for k in range(diff.shape[-1]))


def range_count_ref(x: jnp.ndarray, y: jnp.ndarray, d_cut: float) -> jnp.ndarray:
    """For each row of x: |{j : ||x_i - y_j|| < d_cut}| (direct-diff form)."""
    d2 = sq_sum(x[:, None, :] - y[None, :, :])
    return jnp.sum(d2 < jnp.float32(d_cut) ** 2, axis=1).astype(jnp.int32)


def prefix_min_dist_ref(pts: jnp.ndarray):
    """Prefix NN: for each i, min_j<i ||p_i - p_j|| and its argmin.

    Rows must be sorted by descending density key, so j < i == "j is denser"
    (Ex-DPC's incremental-tree invariant as a static iteration space).
    """
    n = pts.shape[0]
    d2 = sq_sum(pts[:, None, :] - pts[None, :, :])
    mask = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]
    d2 = jnp.where(mask, d2, jnp.inf)
    arg = jnp.argmin(d2, axis=1)
    best = d2[jnp.arange(n), arg]
    return jnp.sqrt(best), jnp.where(jnp.isfinite(best), arg, -1).astype(jnp.int32)


def masked_min_dist_ref(x, x_key, y, y_key):
    """For each row of x: nearest y with y_key strictly greater (+argmin)."""
    d2 = sq_sum(x[:, None, :] - y[None, :, :])
    d2 = jnp.where(y_key[None, :] > x_key[:, None], d2, jnp.inf)
    arg = jnp.argmin(d2, axis=1)
    best = d2[jnp.arange(x.shape[0]), arg]
    return jnp.sqrt(best), jnp.where(jnp.isfinite(best), arg, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The kept tile pairs of a block-sparse worklist, in numpy: the reference the
# device builders of ``kernels.blocksparse`` are tested against.
def _host_bounds(arr: np.ndarray, block: int):
    n, d = arr.shape
    nb = -(-n // block)
    pad = np.full((nb * block, d), np.inf, np.float32)
    pad[:n] = arr
    valid = (np.arange(nb * block) < n).reshape(nb, block)[..., None]
    xt = pad.reshape(nb, block, d)
    lo = np.where(valid, xt, np.inf).min(axis=1)
    hi = np.where(valid, xt, -np.inf).max(axis=1)
    return lo, hi


def host_pair_bounds(x: np.ndarray, y: np.ndarray, block_n: int,
                     block_m: int):
    """Numpy mirror of the device bound math: (lb, ub) matrices."""
    from .blocksparse import LB_SHRINK, UB_GROW

    rlo, rhi = _host_bounds(np.asarray(x, np.float32), block_n)
    clo, chi = _host_bounds(np.asarray(y, np.float32), block_m)
    gap = np.maximum(np.maximum(clo[None] - rhi[:, None],
                                rlo[:, None] - chi[None]), 0.0)
    lb = (gap * gap).sum(-1) * LB_SHRINK
    reach = np.maximum(np.maximum(chi[None] - rlo[:, None],
                                  rhi[:, None] - clo[None]), 0.0)
    ub = (reach * reach).sum(-1) * UB_GROW
    empty_r = (rlo > rhi).any(-1)
    empty_c = (clo > chi).any(-1)
    ub[empty_r[:, None] | empty_c[None, :]] = np.inf
    return lb.astype(np.float32), ub.astype(np.float32)


def _knn_radius(ub: np.ndarray, col_counts: np.ndarray, k: int) -> np.ndarray:
    """Numpy ``blocksparse.knn_radius``: per row tile the smallest upper
    bound v such that tiles with ub <= v hold at least k candidates."""
    nbr, _ = ub.shape
    ord_ub = np.argsort(ub, axis=1)
    ub_sorted = np.take_along_axis(ub, ord_ub, axis=1)
    cum = np.cumsum(col_counts[ord_ub], axis=1)
    reach = np.argmax(cum >= k, axis=1)           # first prefix with >= k
    radius = ub_sorted[np.arange(nbr), reach]
    return np.where(cum[:, -1] >= k, radius, np.inf).astype(np.float32)


def chunk_worklist(meta: np.ndarray, lb: np.ndarray,
                   chunk: int) -> tuple[np.ndarray, np.ndarray]:
    """Cut a (4, W) row-sorted worklist into (chunks, 4, C) / (chunks, C):
    whole row tiles greedily packed up to C entries, C a power of two no
    smaller than the longest row tile, each chunk padded with its last
    (row, col) pair, flag -1 and an infinite bound
    (``blocksparse.FlatWorklist``'s format)."""
    from .blocksparse import _pow2

    W = meta.shape[1]
    bounds = np.flatnonzero(np.diff(meta[0])) + 1
    row_start = np.concatenate([[0], bounds, [W]])
    C = _pow2(max(np.diff(row_start).max(), min(W, chunk), 8))
    cuts = [0]
    for a, b in zip(row_start[:-1], row_start[1:]):     # row tile [a, b)
        if b - cuts[-1] > C:
            cuts.append(int(a))
    cuts.append(W)
    metas, lbs = [], []
    for a, b in zip(cuts[:-1], cuts[1:]):
        pad = C - (b - a)
        fill = np.array([[meta[0, b - 1]], [meta[1, b - 1]], [0], [-1]],
                        np.int32)
        metas.append(np.concatenate([meta[:, a:b], np.repeat(fill, pad, 1)],
                                    axis=1))
        lbs.append(np.concatenate([lb[a:b], np.full(pad, np.inf,
                                                    np.float32)]))
    return np.stack(metas), np.stack(lbs)


def kept_pairs_ref(x, y, d_cut=None, *, block_n: int, block_m: int,
                   count: bool = True, nn: str | None = None, k: int = 0,
                   nn_col_counts=None):
    """The kept tile pairs of a pallas sweep's worklist, row-sorted and
    chunked in numpy: (meta chunks, lb chunks, kept, dense total).

    Kept pairs are the union of what each requested accumulator can touch:
    ``count`` keeps lb <= d_cut^2; ``nn='topk'`` adds the static k-NN ring
    (``nn_col_counts``: the admissible candidates of each column tile when
    a selection gate restricts the kept-k, every point otherwise);
    ``nn='best1'`` keeps every pair.  At least the min-lb pair of each row
    tile is kept, so output blocks always initialize.
    """
    from . import blocksparse

    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.float32)
    n, m = x.shape[0], y.shape[0]
    nbr, nbc = -(-n // block_n), -(-m // block_m)
    lb, ub = host_pair_bounds(x, y, block_n, block_m)
    in_cut = np.zeros((nbr, nbc), bool)
    if count:
        in_cut = lb <= float(d_cut) ** 2
    keep = in_cut.copy()
    if nn == "best1":
        keep[:] = True
    elif nn == "topk":
        col_counts = np.minimum(block_m, np.maximum(
            0, m - np.arange(nbc) * block_m)) if nn_col_counts is None \
            else np.asarray(nn_col_counts)
        keep |= lb <= _knn_radius(ub, col_counts, max(k, 1))[:, None]
    keep[np.arange(nbr), np.argmin(lb, axis=1)] = True

    wi, wj = np.nonzero(keep)
    wl = lb[wi, wj]
    sort = np.lexsort((wl, wi))
    wi, wj, wl = wi[sort], wj[sort], wl[sort]
    first = np.zeros(len(wi), np.int32)
    first[np.unique(wi, return_index=True)[1]] = 1
    meta = np.stack([wi, wj, first,
                     in_cut[wi, wj].astype(np.int64)]).astype(np.int32)
    meta_c, lb_c = chunk_worklist(meta, wl.astype(np.float32),
                                  blocksparse.WL_CHUNK)
    return meta_c, lb_c, len(wi), nbr * nbc
