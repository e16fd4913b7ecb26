"""Range-count kernels (local density, Def. 1) — tile-sweep instantiations.

The compute hot spot of DPC's rho phase: an (n x m) pairwise-distance problem
tiled into (BLOCK_N x BLOCK_M) VMEM blocks, squared distances in direct-
difference form (rounded as the jnp reference rounds them), counts
accumulated across the column grid dimension.  Since
the unified engine landed, this module is the *instantiation* of
``kernels.sweep`` for the two count-only primitives; the kernel body itself
lives in ``sweep.tile_sweep`` (one ``SweepSpec`` per primitive).

The threshold d_cut^2 rides in SMEM as a runtime scalar (not baked into the
kernel), so jit-traced callers — DPC-KV estimates d_cut per head *inside*
jit — hit one compiled kernel for every threshold.

Padding contract: callers pad x/y rows with coordinates >= PAD_COORD, which
puts padded pairs far outside any realistic d_cut without overflowing f32
(see ops.pad_points).  Padded *rows* produce garbage counts that callers
slice off; padded *columns* are never counted.
"""
from __future__ import annotations

import jax.numpy as jnp

from .sweep import (DEFAULT_BLOCK_M, DEFAULT_BLOCK_N, PAD_COORD,  # noqa: F401
                    SweepSpec, tile_sweep, worklist_args)


def range_count(x: jnp.ndarray, y: jnp.ndarray, d_cut,
                block_n: int = DEFAULT_BLOCK_N, block_m: int = DEFAULT_BLOCK_M,
                interpret: bool = False, worklist=None) -> jnp.ndarray:
    """For each row of x (n, d): |{j : ||x_i - y_j|| < d_cut}| over y (m, d).

    x and y must already be padded to multiples of block_n/block_m with
    PAD_COORD rows (ops.pad_points does this).  ``d_cut`` may be a python
    float or a traced f32 scalar.
    """
    spec = SweepSpec(block_n=block_n, block_m=block_m, count=True)
    (cnt,) = tile_sweep(spec, x, y, d_cut, **worklist_args(worklist),
                        interpret=interpret)
    return cnt


def range_count_signed(x: jnp.ndarray, y: jnp.ndarray, signs: jnp.ndarray,
                       d_cut, block_n: int = DEFAULT_BLOCK_N,
                       block_m: int = DEFAULT_BLOCK_M,
                       interpret: bool = False,
                       worklist=None) -> jnp.ndarray:
    """For each row of x: sum_j signs[j] * [||x_i - y_j|| < d_cut], f32.

    The streaming rho-repair kernel — every surviving point's density changes
    by +1 per inserted / -1 per evicted neighbor, so one fused pass over the
    (insert + evict) delta batch with a per-column sign replaces two
    range-count sweeps.  Same padding contract as ``range_count``; padded y
    rows must carry sign 0 (and PAD_COORD keeps them outside any d_cut).
    """
    spec = SweepSpec(block_n=block_n, block_m=block_m, count=True,
                     signed=True)
    (cnt,) = tile_sweep(spec, x, y, d_cut, signs=signs,
                        **worklist_args(worklist), interpret=interpret)
    return cnt


def range_count_halo(x: jnp.ndarray, window: jnp.ndarray,
                     starts: jnp.ndarray, ends: jnp.ndarray, d_cut,
                     block_n: int = DEFAULT_BLOCK_N,
                     block_m: int = DEFAULT_BLOCK_M,
                     interpret: bool = False) -> jnp.ndarray:
    """Range count against per-row ragged [start, end) windows (halo tiles).

    The distributed halo layout: each x-row counts only the window columns
    inside its candidate spans (``starts``/``ends``: (n, S) window-local
    bounds; empty or negative spans contribute nothing).  Same padding
    contract; padded x rows must carry empty spans.
    """
    spec = SweepSpec(block_n=block_n, block_m=block_m, count=True, span=True,
                     span_s=starts.shape[1])
    (cnt,) = tile_sweep(spec, x, window, d_cut, starts=starts, ends=ends,
                        interpret=interpret)
    return cnt
