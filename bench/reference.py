"""Plain reference of what the benchmark checks: Approx-DPC.

Written from the stated semantics, importing nothing of the program:

* rho_i: points j (i itself included) with squared distance < d_cut^2, the
  distance summed dimension by dimension in the configuration's precision;
* the density key rho_i + jitter_i, with the program's stated tie-break
  jitter (a Fibonacci hash of the row index, ranked into (0, 1));
* the grouping grid G of side d_cut / sqrt(d) against the origin; in each
  cell the points of the largest key are its maxima (several when keys
  tie), and the maximum of largest row index is the cell's peak;
* rule 1: every other point of a cell depends on its peak at d_cut;
* rules 2 and 3: a maximum depends on its nearest strictly denser point
  (smallest index on a tie), at d_cut when that lies within d_cut and at
  its distance otherwise (the global peak: none, delta = inf);
* centers: rho >= rho_min and delta >= delta_min; labels follow parents to
  a center, numbered by row order; noise and unreached points read -1.

Pair sweeps run on the device, a block of rows against the whole table at a
time, so the reference fits beside nothing else at the cell's own size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 512
_KNUTH = 2654435761
PAD = 1e9          # padding rows: far from every point and from each other


@functools.lru_cache(maxsize=2)
def jitter(n: int) -> np.ndarray:
    """All-distinct tie-break in (0, 1), one value per row index."""
    idx = np.arange(n, dtype=np.uint32)
    h = (idx * np.uint32(_KNUTH)) ^ (idx >> np.uint32(13))
    rank = np.argsort(np.argsort(h, kind="stable"), kind="stable")
    return (rank.astype(np.float32) + np.float32(0.5)) / np.float32(n)


def _blocks(x: np.ndarray, fill: float) -> jnp.ndarray:
    n, d = x.shape
    nb = -(-n // ROW_BLOCK)
    pad = np.full((nb * ROW_BLOCK, d), fill, x.dtype)
    pad[:n] = x
    return jnp.asarray(pad.reshape(nb, ROW_BLOCK, d))


def _d2(xb, yT, dtype):
    """(rows, n) squared distances, summed over dimensions in order."""
    acc = None
    for k in range(yT.shape[0]):
        c = xb[:, k:k + 1].astype(dtype) - yT[k][None, :].astype(dtype)
        acc = c * c if acc is None else acc + c * c
    return acc


@functools.partial(jax.jit, static_argnames="dtype")
def _count_rows(xb, yT, d2cut, dtype):
    return jax.lax.map(lambda b: jnp.sum(_d2(b, yT, dtype) < d2cut, axis=1,
                                         dtype=jnp.int32), xb)


def rho(points: np.ndarray, d_cut: float, dtype=jnp.float32) -> np.ndarray:
    n = len(points)
    d2cut = (jnp.asarray(d_cut, dtype) ** 2).astype(dtype)
    out = _count_rows(_blocks(points, PAD), jnp.asarray(points.T), d2cut,
                      dtype)
    return np.asarray(out).reshape(-1)[:n].astype(np.int64)


@functools.partial(jax.jit, static_argnames="dtype")
def _nearest_rows(xb, kb, yT, yk, dtype):
    def one(args):
        b, k = args
        d2 = _d2(b, yT, dtype).astype(jnp.float32)
        d2 = jnp.where(yk[None, :] > k[:, None], d2, jnp.inf)
        return jnp.min(d2, axis=1), jnp.argmin(d2, axis=1).astype(jnp.int32)
    return jax.lax.map(one, (xb, kb))


def nearest(q: np.ndarray, table: np.ndarray, q_key: np.ndarray,
            t_key: np.ndarray, dtype=jnp.float32):
    """Per query row: (distance, index) of the nearest table row among the
    rows whose key is strictly greater; (inf, -1) when there is none."""
    m = len(q)
    kb = np.full(-(-m // ROW_BLOCK) * ROW_BLOCK, np.inf, np.float32)
    kb[:m] = q_key
    best, arg = _nearest_rows(
        _blocks(q, PAD), jnp.asarray(kb.reshape(-1, ROW_BLOCK)),
        jnp.asarray(table.T), jnp.asarray(t_key), dtype)
    best = np.asarray(best).reshape(-1)[:m]
    arg = np.asarray(arg).reshape(-1)[:m].astype(np.int64)
    found = np.isfinite(best)
    return (np.where(found, np.sqrt(best), np.inf).astype(np.float32),
            np.where(found, arg, -1))


def propagate(parent: np.ndarray, centers: np.ndarray,
              noise: np.ndarray) -> np.ndarray:
    """Labels: each point follows parents to its root; a center root gives
    its rank among centers, anything else -1."""
    n = len(parent)
    idx = np.arange(n)
    p = np.where(centers | (parent < 0), idx, parent)
    while True:
        nxt = p[p]
        if np.array_equal(nxt, p):
            break
        p = nxt
    cid = np.cumsum(centers) - 1
    return np.where(noise | ~centers[p], -1, cid[p]).astype(np.int64)


def grid_cells(points: np.ndarray, d_cut: float) -> np.ndarray:
    """Each row's cell of the grouping grid, numbered densely."""
    side = np.float32(d_cut / math.sqrt(points.shape[1]))
    cells = np.floor(points / side).astype(np.int64)
    return np.unique(cells, axis=0, return_inverse=True)[1].reshape(-1)


def approx_dpc(points: np.ndarray, d_cut: float, rho_min: float,
               delta_min: float, dtype=jnp.float32, rho_=None,
               cell_=None) -> dict:
    """The clustering of ``points``; ``rho_`` and ``cell_`` reuse densities
    and grid cells already found for these rows."""
    points = np.asarray(points, np.float32)
    n = len(points)
    r = rho(points, d_cut, dtype) if rho_ is None else rho_
    cell = grid_cells(points, d_cut) if cell_ is None else cell_
    key = r.astype(np.float32) + jitter(n)
    top = np.full(cell.max() + 1, -np.inf, np.float32)
    np.maximum.at(top, cell, key)
    is_max = key == top[cell]
    peak = np.full(len(top), -1, np.int64)
    np.maximum.at(peak, cell[is_max], np.flatnonzero(is_max))
    parent = peak[cell]
    delta = np.full(n, d_cut, np.float32)
    rows = np.flatnonzero(is_max)
    nn_d, nn_p = nearest(points[rows], points, key[rows], key, dtype)
    parent[rows] = nn_p
    delta[rows] = np.where(nn_d < np.float32(d_cut), np.float32(d_cut), nn_d)
    noise = r < rho_min
    centers = ~noise & (delta >= np.float32(delta_min))
    return {"rho": r, "delta": delta, "parent": parent, "centers": centers,
            "labels": propagate(parent, centers, noise)}


def rows_off_partition(prog: np.ndarray, ref: np.ndarray) -> int:
    """Rows whose label disagrees once each program cluster is mapped to the
    reference cluster it shares most rows with (noise maps to noise)."""
    prog = np.asarray(prog, np.int64)
    ref = np.asarray(ref, np.int64)
    off = int(np.sum((prog < 0) != (ref < 0)))
    both = (prog >= 0) & (ref >= 0)
    if both.any():
        p, r = prog[both], ref[both]
        span = int(r.max()) + 1
        ids, pi = np.unique(p, return_inverse=True)
        pairs, counts = np.unique(pi.astype(np.int64) * span + r,
                                  return_counts=True)
        pc, rc = pairs // span, pairs % span
        # per program cluster, the reference cluster sharing most rows
        # (the smallest on a tie)
        first = np.lexsort((rc, -counts, pc))
        keep = first[np.r_[True, pc[first][1:] != pc[first][:-1]]]
        best = np.empty(len(ids), np.int64)
        best[pc[keep]] = rc[keep]
        off += int(np.sum(best[pi] != r))
    return off
