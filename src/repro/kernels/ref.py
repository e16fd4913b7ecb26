"""Pure-jnp oracles for the Pallas kernels (the ground truth in kernel tests)."""
from __future__ import annotations

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
