"""Plain reference of what the mesh cells check: exact DPC (Ex-DPC).

Written from the stated semantics, importing nothing of the program (from
``bench.reference``: the stated tie-break ``jitter``, the label propagation
``propagate`` and the two block sweeps behind ``rho`` and ``nearest``):

* rho_i: points j (i itself included) with squared distance < d_cut^2, the
  distance summed dimension by dimension in the configuration's precision;
  it does not depend on the order of the rows, so it is found once;
* the density key rho_i + jitter_i, the jitter by row index in the order
  the fit was given;
* every row depends on its nearest strictly denser point (smallest index on
  a tie), at that distance; the global peak has none (delta = inf,
  parent = -1);
* centers: rho >= rho_min and delta >= delta_min; labels follow parents to
  a center, numbered by row order; noise and unreached points read -1.

Pair sweeps run on the devices, each a block of ``ROW_BLOCK`` rows against
the whole table (a (rows x n) float32 block of 0.5 GB at n = 2,049,280),
the rows split evenly over the devices and dispatched to all of them before
any is waited for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (PAD, _count_rows, _nearest_rows, jitter,
                             propagate)

ROW_BLOCK = 64


def _spread(fn, rows: list[tuple], shared: list, devices) -> list:
    """``fn(row blocks..., *shared)`` on each device's share of the rows
    (``rows``: (array, pad value) pairs), all dispatched before any is read
    back; the results concatenated."""
    n = len(rows[0][0])
    per = -(-n // len(devices))
    per = -(-per // ROW_BLOCK) * ROW_BLOCK
    outs = []
    for i, dev in enumerate(devices):
        blocks = []
        for r, fill in rows:
            part = r[i * per:(i + 1) * per]
            pad = np.full((per,) + r.shape[1:], fill, r.dtype)
            pad[:len(part)] = part
            blocks.append(jax.device_put(
                pad.reshape((-1, ROW_BLOCK) + r.shape[1:]), dev))
        outs.append(fn(*blocks, *(jax.device_put(s, dev) for s in shared)))
    got = [[np.asarray(o).reshape(-1) for o in out]
           if isinstance(out, tuple) else [np.asarray(out).reshape(-1)]
           for out in outs]
    return [np.concatenate([g[k] for g in got])[:n]
            for k in range(len(got[0]))]


class Reference:
    """The Ex-DPC of one set of points in any order, on ``devices``."""

    def __init__(self, points: np.ndarray, d_cut: float, devices,
                 dtype=jnp.float32):
        self.points = np.asarray(points, np.float32)
        self.d_cut, self.devices, self.dtype = d_cut, list(devices), dtype
        self._rho = None

    def rho(self) -> np.ndarray:
        if self._rho is None:
            d2cut = (jnp.asarray(self.d_cut, self.dtype) ** 2).astype(
                self.dtype)
            (r,) = _spread(
                lambda xb, yT: _count_rows(xb, yT, d2cut, self.dtype),
                [(self.points, PAD)], [self.points.T], self.devices)
            self._rho = r.astype(np.int64)
        return self._rho

    def fit(self, order: np.ndarray, rho_min: float,
            delta_min: float) -> dict:
        """The clustering of ``points[order]``, rows in that order."""
        pts = self.points[order]
        r = self.rho()[order]
        key = r.astype(np.float32) + jitter(len(pts))
        best, arg = _spread(
            lambda xb, kb, yT, yk: _nearest_rows(xb, kb, yT, yk, self.dtype),
            [(pts, PAD), (key, np.inf)], [pts.T, key], self.devices)
        found = np.isfinite(best)
        delta = np.where(found, np.sqrt(best), np.inf).astype(np.float32)
        parent = np.where(found, arg.astype(np.int64), -1)
        noise = r < rho_min
        centers = ~noise & (delta >= np.float32(delta_min))
        return {"rho": r, "delta": delta, "parent": parent,
                "centers": centers,
                "labels": propagate(parent, centers, noise)}
