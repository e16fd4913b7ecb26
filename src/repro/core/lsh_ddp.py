"""LSH-DDP baseline [Zhang et al., TKDE'16] — the paper's state-of-the-art
approximate competitor (§2.2, §6).

p-stable compound LSH partitions P into buckets; rho and the dependent point
are approximated *within* the point's bucket; points that find no denser point
in any bucket fall back to a full scan.  M independent rounds refine the
estimates (rho: max over rounds — in-bucket counts only undercount; delta: min
over rounds).  As in the paper, both rho and delta are approximate, which is
exactly why its Rand index trails Approx-DPC (Tables 2-4).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from .dpc_types import DPCResult, with_jitter
from .exdpc import _pow2_pad
from .stencil import masked_nn_rows
from repro.kernels.ref import sq_sum


@partial(jax.jit, static_argnames=("L", "cap", "block"))
def _bucket_round(points, key, d_cut, L: int, cap: int, block: int = 64):
    """One compound-LSH partition round: in-bucket rho counts + denser-NN."""
    n, d = points.shape
    w = 2.0 * d_cut
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (d, L), jnp.float32)
    b = jax.random.uniform(kb, (L,), jnp.float32) * w
    h = jnp.floor((points @ a + b) / w).astype(jnp.int64)          # (n, L)
    # mix the L hash values into one bucket id
    bid = jnp.zeros((n,), jnp.int64)
    for l in range(L):
        bid = bid * jnp.int64(1000003) + h[:, l]
    order = jnp.argsort(bid)
    inv = jnp.argsort(order)
    bs = bid[order]
    pts_s = points[order]
    is_first = jnp.concatenate([jnp.ones((1,), bool), bs[1:] != bs[:-1]])
    seg = jnp.cumsum(is_first) - 1
    start = jax.ops.segment_min(jnp.where(is_first, jnp.arange(n), n), seg,
                                num_segments=n)[seg]               # (n,)
    d2cut = jnp.float32(d_cut) ** 2
    nb = -(-n // block)
    npad = nb * block
    pts_p = jnp.pad(pts_s, ((0, npad - n), (0, 0)))
    st_p = jnp.pad(start, (0, npad - n), constant_values=n)

    def chunk(i0):
        rows = jax.lax.dynamic_slice_in_dim(pts_p, i0, block, 0)
        st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)
        idx = st[:, None] + jnp.arange(cap)                        # (B, cap)
        valid = (idx < n) & (bs[jnp.minimum(idx, n - 1)] ==
                             bs[jnp.minimum(i0 + jnp.arange(block), n - 1)][:, None])
        cand = pts_s[jnp.minimum(idx, n - 1)]
        d2 = sq_sum(rows[:, None, :] - cand)
        cnt = jnp.sum((d2 < d2cut) & valid, axis=1)
        return cnt, idx, valid, d2

    counts = []
    mins = []
    arg = []
    # two passes: first rho (needs all counts), then denser-NN with rho known
    cnts = jax.lax.map(lambda i0: chunk(i0)[0], jnp.arange(nb) * block)
    rho_s = cnts.reshape(-1)[:n].astype(jnp.float32)
    rho = rho_s[inv]
    return rho, order, inv, bs, pts_s, st_p, npad


def run_lsh_ddp(points, d_cut: float, *, M: int = 4, L: int = 3,
                cap: int | None = None, block: int = 64, seed: int = 0,
                fallback_block: int = 4096) -> DPCResult:
    points = jnp.asarray(points, jnp.float32)
    n, d = points.shape
    key = jax.random.PRNGKey(seed)
    rho_best = jnp.zeros((n,), jnp.float32)
    rounds = []
    for r in range(M):
        key, sub = jax.random.split(key)
        # measure bucket capacity for this round on the host
        w = 2.0 * d_cut
        ka, kb = jax.random.split(sub)
        a = jax.random.normal(ka, (d, L), jnp.float32)
        b = jax.random.uniform(kb, (L,), jnp.float32) * w
        h = jnp.floor((points @ a + b) / w).astype(jnp.int64)
        bid = jnp.zeros((n,), jnp.int64)
        for l in range(L):
            bid = bid * jnp.int64(1000003) + h[:, l]
        _, counts = jnp.unique(bid, return_counts=True, size=n, fill_value=-1)
        cap_r = cap or int(jnp.max(counts))
        rho, order, inv, bs, pts_s, st_p, npad = _bucket_round(
            points, sub, d_cut, L, cap_r, block)
        rho_best = jnp.maximum(rho_best, rho)
        rounds.append((order, inv, bs, pts_s, st_p, cap_r))

    rho_key = with_jitter(rho_best)
    # dependent search within each round's buckets
    best_delta = jnp.full((n,), jnp.inf)
    best_parent = jnp.full((n,), -1, jnp.int32)
    for order, inv, bs, pts_s, st_p, cap_r in rounds:
        rk_s = rho_key[order]
        dlt, par = _bucket_dependent(pts_s, rk_s, bs, st_p, cap_r, block)
        dlt = dlt[inv]
        par_orig = jnp.where(par >= 0, order[jnp.maximum(par, 0)], -1)[inv]
        better = dlt < best_delta
        best_delta = jnp.where(better, dlt, best_delta)
        best_parent = jnp.where(better, par_orig, best_parent).astype(jnp.int32)

    # full-scan fallback for points with no denser point in any bucket
    unresolved = np.nonzero(~np.isfinite(np.asarray(best_delta)))[0]
    if unresolved.size:
        m = _pow2_pad(unresolved.size)
        qs = np.pad(unresolved, (0, m - unresolved.size))
        fd, fp = masked_nn_rows(points[qs], rho_key[qs], points, rho_key,
                                block=fallback_block)
        bd = np.asarray(best_delta).copy()
        bp = np.asarray(best_parent).copy()
        fdv = np.asarray(fd)[: unresolved.size]
        bd[unresolved] = np.where(np.isfinite(fdv), fdv, np.inf)
        bp[unresolved] = np.asarray(fp)[: unresolved.size]
        best_delta, best_parent = jnp.asarray(bd), jnp.asarray(bp)

    return DPCResult(rho=rho_best, rho_key=rho_key, delta=best_delta,
                     parent=best_parent.astype(jnp.int32))


@partial(jax.jit, static_argnames=("cap", "block"))
def _bucket_dependent(pts_s, rk_s, bs, st_p, cap: int, block: int):
    n = pts_s.shape[0]
    nb = -(-n // block)
    npad = nb * block
    pts_p = jnp.pad(pts_s, ((0, npad - n), (0, 0)))
    rk_p = jnp.pad(rk_s, (0, npad - n), constant_values=jnp.inf)

    def chunk(i0):
        rows = jax.lax.dynamic_slice_in_dim(pts_p, i0, block, 0)
        rks = jax.lax.dynamic_slice_in_dim(rk_p, i0, block, 0)
        st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)
        rowi = i0 + jnp.arange(block)
        idx = st[:, None] + jnp.arange(cap)
        same = (idx < n) & (bs[jnp.minimum(idx, n - 1)] ==
                            bs[jnp.minimum(rowi, n - 1)][:, None])
        cand = pts_s[jnp.minimum(idx, n - 1)]
        crk = rk_s[jnp.minimum(idx, n - 1)]
        d2 = sq_sum(rows[:, None, :] - cand)
        d2 = jnp.where(same & (crk > rks[:, None]), d2, jnp.inf)
        j = jnp.argmin(d2, axis=1)
        best = d2[jnp.arange(block), j]
        par = jnp.minimum(idx, n - 1)[jnp.arange(block), j]
        return jnp.sqrt(best), jnp.where(jnp.isfinite(best), par, -1).astype(jnp.int32)

    dlt, par = jax.lax.map(chunk, jnp.arange(nb) * block)
    return dlt.reshape(-1)[:n], par.reshape(-1)[:n]
