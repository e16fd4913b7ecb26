"""CFSFDP-A baseline [Bai et al., Pattern Recognition'17] — the paper's
state-of-the-art exact competitor (§2.2, §6).

k-means pivots + triangle inequality filter candidate sets for the rho range
count; per the paper's own experimental setup, the dependent distances use the
Scan approach (Table 1 notes CFSFDP-A's own delta step is Omega(n^2) and
slower than Scan's).

TPU adaptation: the per-cluster triangle-inequality test
|dist(p, pivot_c)| - r_c >= d_cut  (skip cluster c entirely for p) becomes an
(n x k) mask; surviving (point, cluster) pairs are evaluated over padded
per-cluster windows.  k-means's noise sensitivity (weak filtering) is exactly
what the paper criticizes — reproduced by benchmarks/decomposed.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .dpc_types import DPCResult, with_jitter
from .grid import sq_dists
from .scan import dependent_scan
from repro.kernels.ref import sq_sum


@partial(jax.jit, static_argnames=("k", "iters"))
def kmeans_pivots(points, k: int, iters: int = 10, seed: int = 0):
    n, d = points.shape
    key = jax.random.PRNGKey(seed)
    init = points[jax.random.choice(key, n, (k,), replace=False)]

    def step(cents, _):
        d2 = sq_dists(points, cents)
        assign = jnp.argmin(d2, axis=1)
        sums = jax.ops.segment_sum(points, assign, num_segments=k)
        cnts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), assign, num_segments=k)
        cents = jnp.where(cnts[:, None] > 0, sums / jnp.maximum(cnts, 1)[:, None], cents)
        return cents, None

    cents, _ = jax.lax.scan(step, init, None, length=iters)
    assign = jnp.argmin(sq_dists(points, cents), axis=1)
    return cents, assign


def run_cfsfdp_a(points, d_cut: float, *, k: int = 32, block: int = 256,
                 scan_block: int = 1024) -> DPCResult:
    points = jnp.asarray(points, jnp.float32)
    n, d = points.shape
    k = min(k, n)
    cents, assign = kmeans_pivots(points, k)
    # sort by pivot-cluster id -> contiguous windows
    order = jnp.argsort(assign)
    inv = jnp.argsort(order)
    pts_s = points[order]
    as_s = assign[order]
    is_first = jnp.concatenate([jnp.ones((1,), bool), as_s[1:] != as_s[:-1]])
    seg = jnp.cumsum(is_first) - 1
    start_per_pt = jax.ops.segment_min(
        jnp.where(is_first, jnp.arange(n), n), seg, num_segments=k)
    count_per_cluster = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), as_s,
                                            num_segments=k)
    cap = int(jnp.max(count_per_cluster))
    # cluster radii for the triangle-inequality filter
    dist_to_own = jnp.sqrt(sq_sum(points - cents[assign]))
    radius = jax.ops.segment_max(dist_to_own, assign, num_segments=k)

    rho = _density(points, pts_s, cents, radius, start_per_pt,
                   count_per_cluster, d_cut, cap, block)
    rho_key = with_jitter(rho)
    delta, parent = dependent_scan(points, rho_key, block=scan_block)
    return DPCResult(rho=rho, rho_key=rho_key, delta=delta, parent=parent)


@partial(jax.jit, static_argnames=("cap", "block"))
def _density(points, pts_s, cents, radius, start, count, d_cut, cap: int, block: int):
    n, d = points.shape
    k = cents.shape[0]
    d2cut = jnp.float32(d_cut) ** 2
    nb = -(-n // block)
    npad = nb * block
    pts_p = jnp.pad(points, ((0, npad - n), (0, 0)))

    def chunk(i0):
        rows = jax.lax.dynamic_slice_in_dim(pts_p, i0, block, 0)   # (B, d)
        dp = jnp.sqrt(sq_dists(rows, cents))                       # (B, k)
        keep = dp - radius[None, :] < d_cut                        # triangle filter
        # evaluate every unpruned cluster window
        def per_cluster(c, acc):
            idx = start[c] + jnp.arange(cap)
            valid = jnp.arange(cap) < count[c]
            cand = pts_s[jnp.minimum(idx, n - 1)]
            d2 = sq_sum(rows[:, None, :] - cand[None, :, :])
            cnt = jnp.sum((d2 < d2cut) & valid[None, :], axis=1).astype(jnp.int32)
            return acc + jnp.where(keep[:, c], cnt, 0)

        cnt = jax.lax.fori_loop(0, k, per_cluster, jnp.zeros((block,), jnp.int32))
        return cnt

    cnt = jax.lax.map(chunk, jnp.arange(nb) * block)
    return cnt.reshape(-1)[:n].astype(jnp.float32)
