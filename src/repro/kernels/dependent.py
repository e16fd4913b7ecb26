"""Dependent-point (Def. 2) kernels — tile-sweep instantiations.

Ex-DPC's delta phase: with points sorted by *descending* density key, the
dependent point of row i is its nearest neighbor among rows j < i.  The
paper's incrementally-rebuilt kd-tree (provably sequential) becomes a static
lower-triangular tile sweep (``prefix_min_dist``); ``masked_min_dist`` is the
rectangular strictly-denser variant (global fallback, S-Approx phase 2); and
``masked_min_dist_halo`` is the same NN restricted to per-row ragged halo
windows (the distributed optimized path).  All three are instantiations of
``kernels.sweep`` — one ``SweepSpec`` each over the shared engine.

Every variant computes tile distances in the MXU expanded form and re-ranks
the top-k candidates per row in direct-difference form
(``sweep.refine_topk_d2``), so near-tie argmins survive ill-conditioned data
(NN distances << domain scale) and the consumed delta value is direct-diff
exact.
"""
from __future__ import annotations

import jax.numpy as jnp

from .sweep import (REFINE_TOPK, SweepSpec, tile_sweep,  # noqa: F401
                    worklist_args, refine_topk_d2 as _refine_topk_d2)

DEFAULT_BLOCK = 256


def prefix_min_dist(pts: jnp.ndarray, block: int = DEFAULT_BLOCK,
                    interpret: bool = False, refine_k: int = REFINE_TOPK,
                    precision: str = "f32"):
    """min_{j<i} ||p_i - p_j|| and argmin, rows sorted by descending key.

    pts must be padded to a multiple of block with PAD_COORD rows.
    Returns (delta (n,), parent (n,) int32, -1 where no prefix).
    """
    spec = SweepSpec(block_n=block, block_m=block, nn="best1", prefix=True,
                     refine_k=refine_k, precision=precision)
    best, arg = tile_sweep(spec, pts, pts, interpret=interpret)
    return jnp.sqrt(best), arg


def masked_min_dist(x, x_key, y, y_key, block_n: int = 128,
                    block_m: int = DEFAULT_BLOCK, interpret: bool = False,
                    refine_k: int = REFINE_TOPK, precision: str = "f32",
                    worklist=None):
    """NN among y-rows with y_key > x_key, per x-row (global fallback)."""
    spec = SweepSpec(block_n=block_n, block_m=block_m, nn="best1", key=True,
                     refine_k=refine_k, precision=precision)
    best, arg = tile_sweep(spec, x, y, x_key=x_key, y_key=y_key,
                           **worklist_args(worklist), interpret=interpret)
    return jnp.sqrt(best), arg


def masked_min_dist_halo(x, x_key, window, w_key, starts, ends, d_cut,
                         block_n: int = 128, block_m: int = DEFAULT_BLOCK,
                         interpret: bool = False,
                         refine_k: int = REFINE_TOPK,
                         precision: str = "f32"):
    """Strictly-denser NN within d_cut over per-row ragged halo windows.

    The distributed delta phase: candidates are the window columns inside the
    row's [start, end) spans that are strictly denser AND within d_cut
    (stencil semantics — beyond-d_cut rows fall to the global fallback).
    Returns (delta, parent_window_idx); parent -1 / delta inf when no
    candidate qualifies.
    """
    spec = SweepSpec(block_n=block_n, block_m=block_m, nn="best1", key=True,
                     span=True, span_s=starts.shape[1], nn_dcut=True,
                     refine_k=refine_k, precision=precision)
    best, arg = tile_sweep(spec, x, window, d_cut, x_key=x_key, y_key=w_key,
                           starts=starts, ends=ends, interpret=interpret)
    return jnp.sqrt(best), arg
