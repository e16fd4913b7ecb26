"""jit'd public wrappers around the Pallas tile-sweep kernels.

On CPU (this container) the kernels execute in interpret mode for
correctness; on TPU they compile to Mosaic.  ``pad_points`` implements the
padding contract shared by all kernels (rows padded at PAD_COORD, far outside
any d_cut; padded output rows sliced off).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .density import (PAD_COORD, range_count, range_count_halo,
                      range_count_signed)
from .dependent import masked_min_dist, masked_min_dist_halo, prefix_min_dist
from .sweep import (FUSED_TOPK, SweepSpec, gather_nn, tile_sweep,
                    worklist_args)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def pad_points(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    n = x.shape[0]
    npad = -(-n // multiple) * multiple
    return jnp.pad(x, ((0, npad - n), (0, 0)), constant_values=PAD_COORD)


def pad_vec(x: jnp.ndarray, multiple: int, value) -> jnp.ndarray:
    n = x.shape[0]
    npad = -(-n // multiple) * multiple
    return jnp.pad(x, (0, npad - n), constant_values=value)


DENSITY_BLOCK_N = 256
DENSITY_BLOCK_M = 512


def local_density_xy(x: jnp.ndarray, y: jnp.ndarray, d_cut, *,
                     block_n: int = DENSITY_BLOCK_N,
                     block_m: int = DENSITY_BLOCK_M,
                     interpret: bool | None = None,
                     worklist=None) -> jnp.ndarray:
    """Kernel-backed rectangular range count: per x-row count of y within
    d_cut (the backend-layer form of Def. 1; query != candidate set)."""
    if interpret is None:
        interpret = _on_cpu()
    n = x.shape[0]
    xp = pad_points(x.astype(jnp.float32), block_n)
    yp = pad_points(y.astype(jnp.float32), block_m)
    cnt = range_count(xp, yp, d_cut, block_n=block_n, block_m=block_m,
                      interpret=interpret, worklist=worklist)
    return cnt[:n].astype(jnp.float32)


def local_density(points: jnp.ndarray, d_cut, *,
                  block_n: int = DENSITY_BLOCK_N,
                  block_m: int = DENSITY_BLOCK_M,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Kernel-backed all-pairs local density (Scan's rho on TPU)."""
    return local_density_xy(points, points, d_cut, block_n=block_n,
                            block_m=block_m, interpret=interpret)


def local_density_delta(x: jnp.ndarray, batch: jnp.ndarray,
                        signs: jnp.ndarray, d_cut, *,
                        block_n: int = DENSITY_BLOCK_N,
                        interpret: bool | None = None,
                        worklist=None) -> jnp.ndarray:
    """Kernel-backed signed range count over a delta batch (streaming rho
    repair): per x-row, (+1 per inserted / -1 per evicted) batch neighbor
    within d_cut, fused in a single tile sweep."""
    if interpret is None:
        interpret = _on_cpu()
    n = x.shape[0]
    xp = pad_points(x.astype(jnp.float32), block_n)
    bp = pad_points(batch.astype(jnp.float32), DENSITY_BLOCK_M)
    sp = pad_vec(signs.astype(jnp.float32), DENSITY_BLOCK_M, 0.0)
    cnt = range_count_signed(xp, bp, sp, d_cut, block_n=block_n,
                             block_m=DENSITY_BLOCK_M, interpret=interpret,
                             worklist=worklist)
    return cnt[:n]


def dependent_prefix(points_sorted_desc: jnp.ndarray, *, block: int = 256,
                     interpret: bool | None = None):
    """Kernel-backed triangular dependent-point pass (rows pre-sorted)."""
    if interpret is None:
        interpret = _on_cpu()
    n = points_sorted_desc.shape[0]
    x = pad_points(points_sorted_desc.astype(jnp.float32), block)
    delta, parent = prefix_min_dist(x, block=block, interpret=interpret)
    return delta[:n], parent[:n]


def dependent_masked(x, x_key, y, y_key, *, block_n: int = 128,
                     block_m: int = 256, interpret: bool | None = None,
                     worklist=None):
    """Kernel-backed masked NN fallback (strictly-denser candidates)."""
    if interpret is None:
        interpret = _on_cpu()
    n = x.shape[0]
    xp = pad_points(x.astype(jnp.float32), block_n)
    xk = pad_vec(x_key.astype(jnp.float32), block_n, jnp.inf)
    yp = pad_points(y.astype(jnp.float32), block_m)
    yk = pad_vec(y_key.astype(jnp.float32), block_m, -jnp.inf)
    delta, parent = masked_min_dist(xp, xk, yp, yk, block_n=block_n,
                                    block_m=block_m, interpret=interpret,
                                    worklist=worklist)
    return delta[:n], parent[:n]


# ------------------------------------------------------ fused rho + delta
def fused_sweep(x, y, d_cut, *, nn_sel=None, k: int = FUSED_TOPK,
                block_n: int = DENSITY_BLOCK_N, block_m: int = DENSITY_BLOCK_M,
                precision: str = "f32", interpret: bool | None = None,
                worklist=None):
    """One tile sweep: per x-row range count over y AND the k nearest
    candidates (expanded-form d2 + global index, unmasked by density — the
    denser-mask resolves in the caller's epilogue once the counts are
    complete).  ``nn_sel`` (len(y) bool/int) optionally gates which columns
    may enter the kept-k (S-Approx representatives); the count ignores it.

    Returns (count (n,) f32, topv (n, k) f32 expanded d2, topi (n, k) int32
    y-row index, -1 when fewer than k candidates).
    """
    if interpret is None:
        interpret = _on_cpu()
    n = x.shape[0]
    xp = pad_points(x.astype(jnp.float32), block_n)
    yp = pad_points(y.astype(jnp.float32), block_m)
    sel = None
    if nn_sel is not None:
        sel = pad_vec(nn_sel.astype(jnp.float32), block_m, 0.0)
    spec = SweepSpec(block_n=block_n, block_m=block_m, count=True, nn="topk",
                     nn_sel=sel is not None, k=k, precision=precision)
    cnt, topv, topi = tile_sweep(spec, xp, yp, d_cut, nn_sel=sel,
                                 **worklist_args(worklist),
                                 interpret=interpret)
    return cnt[:n].astype(jnp.float32), topv[:n], topi[:n]


# --------------------------------------------------------- halo windows
def halo_density(x, window, starts, ends, d_cut, *,
                 block_n: int = DENSITY_BLOCK_N,
                 block_m: int = DENSITY_BLOCK_M,
                 interpret: bool | None = None):
    """Kernel-backed halo range count: per x-row count of window columns
    inside the row's [start, end) spans and within d_cut."""
    if interpret is None:
        interpret = _on_cpu()
    n = x.shape[0]
    xp = pad_points(x.astype(jnp.float32), block_n)
    wp = pad_points(window.astype(jnp.float32), block_m)
    st = _pad_spans(starts, block_n)
    en = _pad_spans(ends, block_n)
    cnt = range_count_halo(xp, wp, st, en, d_cut, block_n=block_n,
                           block_m=block_m, interpret=interpret)
    return cnt[:n].astype(jnp.float32)


def halo_dependent(x, x_key, window, w_key, starts, ends, d_cut, *,
                   block_n: int = 128, block_m: int = DENSITY_BLOCK_M,
                   interpret: bool | None = None):
    """Kernel-backed halo strictly-denser NN within d_cut.  Returns
    (delta, parent_window_idx, found)."""
    if interpret is None:
        interpret = _on_cpu()
    n = x.shape[0]
    xp = pad_points(x.astype(jnp.float32), block_n)
    xk = pad_vec(x_key.astype(jnp.float32), block_n, jnp.inf)
    wp = pad_points(window.astype(jnp.float32), block_m)
    wk = pad_vec(w_key.astype(jnp.float32), block_m, -jnp.inf)
    st = _pad_spans(starts, block_n)
    en = _pad_spans(ends, block_n)
    delta, parent = masked_min_dist_halo(xp, xk, wp, wk, st, en, d_cut,
                                         block_n=block_n, block_m=block_m,
                                         interpret=interpret)
    found = jnp.isfinite(delta[:n])
    return delta[:n], parent[:n], found


def _pad_spans(s, multiple: int):
    n = s.shape[0]
    npad = -(-n // multiple) * multiple
    return jnp.pad(s.astype(jnp.int32), ((0, npad - n), (0, 0)),
                   constant_values=0)


# ----------------------------------------------------- fused-gather NN
def dependent_masked_gather(table, keys, q_slots, *, block_n: int = 128,
                            block_m: int = DENSITY_BLOCK_M,
                            interpret: bool | None = None):
    """Strictly-denser NN for the row subset ``table[q_slots]``, with the
    gather fused into the kernel (the streaming maxima repair: the gathered
    query subset never materialises in HBM).  ``q_slots`` >= len(table) are
    padding and return (inf, -1).  Returns (delta, parent)."""
    if interpret is None:
        interpret = _on_cpu()
    q = q_slots.shape[0]
    m = table.shape[0]
    tp = pad_points(table.astype(jnp.float32), block_m)
    kp = pad_vec(keys.astype(jnp.float32), block_m, -jnp.inf)
    # padded slots point past the valid table: the kernel marks them inert
    sp = pad_vec(q_slots.astype(jnp.int32), block_n, m)
    best, parent = gather_nn(tp, kp, sp, m_valid=m, block_n=block_n,
                             block_m=block_m, interpret=interpret)
    return jnp.sqrt(best[:q]), parent[:q]
