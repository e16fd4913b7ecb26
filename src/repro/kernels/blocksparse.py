"""Block-sparse tile worklists: grid-pruned sub-quadratic DPC sweeps.

The dense engine visits every (row-tile x col-tile) pair of the distance
grid — O(n^2) tile work regardless of d_cut.  Under the paper's d_cut
assumption (average rho in the tens) almost all of those pairs are provably
empty: when points are laid out in grid-sorted order (``core.grid``'s
(candidate-cell, grouping-cell) sort) each kernel tile covers a compact
region of space, so a per-tile axis-aligned bounding box gives a cheap lower
bound on every pairwise distance the tile pair could produce.  This module
owns that pruning logic once, in three forms:

* **pair bounds** — per-tile AABBs (pad rows masked) and the conservative
  min/max inter-tile squared distances.  Lower bounds are shrunk and upper
  bounds grown by a few ulps (``LB_SHRINK`` / ``UB_GROW``) so f32 rounding of
  the bound arithmetic can never out-round the kernels' own f32 distance
  evaluation — pruning decisions are exact, bit-parity with the dense sweep
  is preserved (tested on tie-heavy lattice data).

* **jit-built ring worklists** (the jnp backend) — the (nbr, nbc) bound
  matrix is *ranked* ascending per row tile (double argsort — pure sorts,
  no gather); count accumulators walk the prefix with ``lb <= d_cut^2``
  and NN accumulators walk the ring with a ``lax.while_loop`` that stops
  once the next lower bound exceeds the row tile's worst current candidate
  (the progressively-shrinking prune radius).  Each step selects its
  column tile by a one-hot ``(rank == p)`` matmul contraction — the same
  idiom ``sweep.gather_nn`` uses in-kernel — so **no sort-derived value
  ever feeds a gather/dynamic_slice index** and the walk is R1-clean
  (``analysis.spmd_gather_safe``): safe inside multi-partition shard_map
  bodies under the pinned jax-0.4.37 XLA CPU SPMD pipeline, which
  miscompiles sort-derived gather indices there.  Everything is traced —
  shapes depend only on tile counts — so the block-sparse jnp primitives
  stay jit/shard_map-safe (``rho_delta`` remains ``fused_traceable``) and
  the *work* is data-proportional because ``while_loop`` trip counts are
  runtime values.

* **host-built flat worklists** (the pallas backends) — the kept tile pairs
  flatten into a scalar-prefetched (wi, wj, first-visit, in-cut) table that
  drives a 1-D ``pallas_call`` grid (``sweep.tile_sweep``); the grid size IS
  the kept-pair count for count primitives, while NN primitives keep a
  ring-ordered list and skip tiles in-kernel against the live accumulator
  (``best1``: the current best; ``topk``: the worst kept kth — statically
  pre-pruned by the k-nearest upper-bound radius, which is exact because a
  tile whose lower bound clears k strictly-closer candidates can never
  contribute a kept entry).  A best-1 list that keeps every pair is a pure
  function of the tile bounds, so the fused pallas ``rho_delta``'s
  unresolved-row fallback builds it on the device (:func:`ring_worklist`)
  in the same chunked form.  So do the distributed shards, for their
  d_cut count lists as well (:func:`cut_prefix`, :func:`cut_worklist`: a
  static, bucketed length of which only the live chunks run).

Every builder force-keeps at least one pair per row tile so output blocks
are always initialized, and all-in-one-cell data degenerates to the dense
worklist (nothing prunes; the engine behaves exactly as ``worklist=None``).
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.obs import metrics as _obsm
from .ref import sq_sum

# Conservative slack on the f32 bound arithmetic: shrink lower bounds / grow
# upper bounds by ~10 ulp-equivalents so a bound can never out-round the
# kernel's own f32 distance (pruning stays exact; costs a few extra tiles).
LB_SHRINK = 1.0 - 1e-5
UB_GROW = 1.0 + 1e-5

# Default block-sparse tile shape (jnp ring sweeps).  Smaller row tiles than
# the dense engine: the ring early-exit is gated by the worst row in the
# tile and the AABB tightens with fewer points, which buys more pruning than
# the larger-tile dispatch amortization buys throughput (measured on the
# 64k acceptance shape: (128, 256) beats (256, 256) and (512, 512)).
BS_BLOCK_N = 128
BS_BLOCK_M = 256


def vary_like(init, *refs):
    """``init`` marked as varying over every mesh axis ``refs`` vary over.

    A loop whose body mixes per-shard data into its carry must start from a
    carry of the same type inside ``shard_map`` (jax's varying-axes check);
    outside ``shard_map`` nothing varies and ``init`` comes back as is.
    """
    axes = frozenset().union(*(jax.typeof(r).vma for r in refs))
    if not axes:
        return init
    return jax.tree.map(
        lambda a: jax.lax.pcast(a, tuple(sorted(axes)), to="varying"), init)


def _pad_inf(x: jnp.ndarray, block: int) -> jnp.ndarray:
    n = x.shape[0]
    npad = -(-n // block) * block
    return jnp.pad(x, ((0, npad - n), (0, 0)), constant_values=jnp.inf)


def tile_bounds(xp: jnp.ndarray, n_valid: int, block: int):
    """Per-tile AABB (lo, hi) of padded points, pad rows masked out.

    Empty (all-pad) tiles report (lo=+inf, hi=-inf), which makes every bound
    against them +inf — they prune away wherever pruning is legal and stay
    inert (infinite distances) wherever it is not.
    """
    N, d = xp.shape
    nb = N // block
    valid = (jnp.arange(N) < n_valid).reshape(nb, block)[..., None]
    xt = xp.reshape(nb, block, d)
    lo = jnp.min(jnp.where(valid, xt, jnp.inf), axis=1)
    hi = jnp.max(jnp.where(valid, xt, -jnp.inf), axis=1)
    return lo, hi


def pair_lower_bounds(rlo, rhi, clo, chi) -> jnp.ndarray:
    """(nbr, nbc) conservative min inter-AABB squared distance (shrunk)."""
    gap = jnp.maximum(jnp.maximum(clo[None, :, :] - rhi[:, None, :],
                                  rlo[:, None, :] - chi[None, :, :]), 0.0)
    return jnp.sum(gap * gap, axis=-1) * LB_SHRINK


def pair_upper_bounds(rlo, rhi, clo, chi) -> jnp.ndarray:
    """(nbr, nbc) conservative max inter-AABB squared distance (grown).

    +inf whenever either tile is empty (its degenerate box has lo > hi).
    """
    reach = jnp.maximum(jnp.maximum(chi[None, :, :] - rlo[:, None, :],
                                    rhi[:, None, :] - clo[None, :, :]), 0.0)
    ub = jnp.sum(reach * reach, axis=-1) * UB_GROW
    empty_r = jnp.any(rlo > rhi, axis=-1)
    empty_c = jnp.any(clo > chi, axis=-1)
    return jnp.where(empty_r[:, None] | empty_c[None, :], jnp.inf, ub)


def _ring(x_pad, nx, y_pad, ny, bn: int, bm: int):
    """Ascending-lb ring *ranks* per row tile: (rank, lb), both (nbr, nbc).

    ``rank[i, j]`` is column tile j's position in row tile i's ascending-lb
    visit order — a double argsort, so ties rank in tile-index order exactly
    like the stable ``argsort`` permutation the walk used to gather through.
    Pure traced math, and deliberately gather-free: both sorts return whole
    permutations that the walks consume only through ``rank == p`` one-hot
    comparisons, never as a gather/dynamic_slice index.  That keeps the jnp
    ring walk R1-clean (``spmd_gather_safe``) inside multi-partition
    shard_map bodies, where the pinned XLA CPU SPMD pipeline miscompiles
    sort-derived gather indices.
    """
    lb = _tile_pair_lb(x_pad, nx, y_pad, ny, bn, bm)
    rank = jnp.argsort(jnp.argsort(lb, axis=1), axis=1).astype(jnp.int32)
    return rank, lb


def _tile_pair_lb(x_pad, nx, y_pad, ny, bn: int, bm: int) -> jnp.ndarray:
    """(nbr, nbc) pair lower bounds of two padded tables (pad rows masked)."""
    rlo, rhi = tile_bounds(x_pad, nx, bn)
    clo, chi = tile_bounds(y_pad, ny, bm)
    return pair_lower_bounds(rlo, rhi, clo, chi)


# One-hot contractions never see ±inf pad values: 0 * inf = NaN would leak
# into selected tiles.  Clamped pads keep the walks exact — a clamped coord
# still squares past the f32 max (distance stays +inf), and a clamped -inf
# column key is restored below the admissibility mask (_RESTORE_NEG).
_FINITE_CAP = jnp.float32(3e38)


def _finitize(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.clip(a, -_FINITE_CAP, _FINITE_CAP)


def _onehot_pick(sel_f32: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Select one row of ``table`` (nbc, w) by a one-hot (nbc,) vector.

    A permutation-matrix contraction (MXU-friendly dot, no gather): the
    exact 0/1 weights make the picked row bitwise-equal to the stored row.
    """
    return jax.lax.dot_general(sel_f32[None, :], table,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)[0]


# =====================================================================
# jnp block-sparse primitives (direct-difference; bit-parity with the
# dense jnp engine — same per-tile float expressions, order-independent
# count sums, explicit lexicographic (d2, col) NN tie-break)
# =====================================================================
@partial(jax.jit, static_argnames=("bn", "bm", "signed"))
def _count_bs_jnp(x, y, weights, d_cut, bn: int = BS_BLOCK_N,
                  bm: int = BS_BLOCK_M, signed: bool = False):
    """Block-sparse (optionally signed) range count, x rows over y columns.

    Walks only the ascending-lb prefix with lb <= d_cut^2 per row tile
    (``while_loop``: work is proportional to the kept pairs, not the grid).
    Integer/sign sums are order-independent, so the result is bit-identical
    to the dense jnp range count.
    """
    n, d = x.shape
    m = y.shape[0]
    xp = _pad_inf(x, bn)
    yp = _pad_inf(y, bm)
    nbr, nbc = xp.shape[0] // bn, yp.shape[0] // bm
    rank, lb = _ring(xp, n, yp, m, bn, bm)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2
    kcut = jnp.sum(lb <= d2cut, axis=1).astype(jnp.int32)
    ypf = _finitize(yp).reshape(nbc, bm * d)
    if signed:
        wp = jnp.pad(weights.astype(jnp.float32), (0, nbc * bm - m),
                     constant_values=0.0).reshape(nbc, bm)

    def row_tile(i):
        rows = jax.lax.dynamic_slice_in_dim(xp, i * bn, bn, 0)
        rank_i, kc = rank[i], kcut[i]

        def body(c):
            p, acc = c
            sel = (rank_i == p).astype(jnp.float32)
            cols = _onehot_pick(sel, ypf).reshape(bm, d)
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            if signed:
                s = _onehot_pick(sel, wp)
                upd = jnp.sum(jnp.where(d2 < d2cut, s[None, :], 0.0), axis=1)
            else:
                upd = jnp.sum(d2 < d2cut, axis=1).astype(jnp.float32)
            return p + 1, acc + upd

        _, acc = jax.lax.while_loop(
            lambda c: c[0] < kc, body,
            (jnp.int32(0), vary_like(jnp.zeros((bn,), jnp.float32), xp, yp)))
        return acc

    cnt = jax.lax.map(row_tile, jnp.arange(nbr)).reshape(-1)[:n]
    return cnt


def _nn_ring_rows(xp, rkp, yp, ckp, n, rank, lb, bn: int, bm: int):
    """One block-sparse masked-NN row-tile sweep (the shared Def.-2 core).

    Ring order with a runtime early-exit: stop once the next tile's lower
    bound strictly exceeds the worst current best among the tile's valid
    rows (a bound can only be *conservative*, so every skipped pair is
    strictly worse for every row — exact, ties included).  Each step picks
    its column tile by one-hot ``rank == p`` contraction (never a
    sort-derived gather index) and tracks the winner *in-loop* as a global
    column id with a lexicographic (d2, col) tie-break — because global
    col = tile * bm + local col, this is exactly the dense sweep's
    lowest-index winner, bit for bit (same float ops on the same operands).
    """
    nbc, d = yp.shape[0] // bm, yp.shape[1]
    int_max = jnp.iinfo(jnp.int32).max
    # [coords | key] contraction table, pads finitized (gather_nn's idiom);
    # clamped -inf keys are restored after the pick so the strictly-denser
    # admissibility mask is untouched.
    ytab = jnp.concatenate([_finitize(yp), _finitize(ckp)[:, None]],
                           axis=1).reshape(nbc, bm * (d + 1))
    tile_ids = jnp.arange(nbc, dtype=jnp.int32)

    def row_tile(i):
        rows = jax.lax.dynamic_slice_in_dim(xp, i * bn, bn, 0)
        rrk = jax.lax.dynamic_slice_in_dim(rkp, i * bn, bn, 0)
        rvalid = (i * bn + jnp.arange(bn)) < n
        rank_i, lb_i = rank[i], lb[i]

        def cond(c):
            p, best, _ = c
            worst = jnp.max(jnp.where(rvalid, best, -jnp.inf))
            lb_p = jnp.sum(jnp.where(rank_i == jnp.minimum(p, nbc - 1),
                                     lb_i, 0.0))
            return (p < nbc) & (lb_p <= worst)

        def body(c):
            p, best, barg = c
            onehot = (rank_i == p)
            j = jnp.sum(jnp.where(onehot, tile_ids, 0)).astype(jnp.int32)
            picked = _onehot_pick(onehot.astype(jnp.float32),
                                  ytab).reshape(bm, d + 1)
            cols = picked[:, :d]
            crk = jnp.where(picked[:, d] <= -_FINITE_CAP, -jnp.inf,
                            picked[:, d])
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            d2m = jnp.where(crk[None, :] > rrk[:, None], d2, jnp.inf)
            cand = jnp.min(d2m, axis=1)
            carg = (j * bm + jnp.argmin(d2m, axis=1)).astype(jnp.int32)
            better = cand < best
            tie = (cand == best) & jnp.isfinite(cand) & (carg < barg)
            return (p + 1, jnp.where(better, cand, best),
                    jnp.where(better | tie, carg, barg))

        _, best, barg = jax.lax.while_loop(
            cond, body, (jnp.int32(0), *vary_like(
                (jnp.full((bn,), jnp.inf),
                 jnp.full((bn,), int_max, jnp.int32)), xp, rkp, yp, ckp)))
        parent = jnp.where(jnp.isfinite(best), barg, -1)
        return jnp.sqrt(best), parent

    return row_tile


@partial(jax.jit, static_argnames=("bn", "bm"))
def _denser_nn_bs_jnp(x, x_key, y, y_key, bn: int = BS_BLOCK_N,
                      bm: int = BS_BLOCK_M):
    """Block-sparse strictly-denser NN (Def. 2), ring-pruned."""
    n, d = x.shape
    m = y.shape[0]
    xp = _pad_inf(x, bn)
    yp = _pad_inf(y, bm)
    nbr = xp.shape[0] // bn
    rank, lb = _ring(xp, n, yp, m, bn, bm)
    rkp = jnp.pad(x_key.astype(jnp.float32), (0, xp.shape[0] - n),
                  constant_values=jnp.inf)
    ckp = jnp.pad(y_key.astype(jnp.float32), (0, yp.shape[0] - m),
                  constant_values=-jnp.inf)
    row_tile = _nn_ring_rows(xp, rkp, yp, ckp, n, rank, lb, bn, bm)
    delta, parent = jax.lax.map(row_tile, jnp.arange(nbr))
    return (delta.reshape(-1)[:n],
            parent.reshape(-1)[:n].astype(jnp.int32))


@partial(jax.jit, static_argnames=("bn", "bm"))
def _rho_delta_bs_jnp(x, y, jitter, d_cut, y_sel_slots=None,
                      bn: int = BS_BLOCK_N, bm: int = BS_BLOCK_M):
    """Block-sparse fused rho + delta, one jit (jit-built worklist).

    The count pass walks each row tile's lb <= d_cut^2 ring prefix; the NN
    pass walks the same ring with the runtime prune radius.  Bit-identical
    to the dense ``_rho_delta_jnp`` (order-independent counts; lexicographic
    NN winner recovery).
    """
    n, d = x.shape
    m = y.shape[0]
    xp = _pad_inf(x, bn)
    yp = _pad_inf(y, bm)
    nbr = xp.shape[0] // bn
    nbc = yp.shape[0] // bm
    rank, lb = _ring(xp, n, yp, m, bn, bm)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2
    kcut = jnp.sum(lb <= d2cut, axis=1).astype(jnp.int32)
    ypf = _finitize(yp).reshape(nbc, bm * d)

    def row_count(i):
        rows = jax.lax.dynamic_slice_in_dim(xp, i * bn, bn, 0)
        rank_i, kc = rank[i], kcut[i]

        def body(c):
            p, acc = c
            sel = (rank_i == p).astype(jnp.float32)
            cols = _onehot_pick(sel, ypf).reshape(bm, d)
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            return p + 1, acc + jnp.sum(d2 < d2cut, axis=1).astype(jnp.int32)

        _, acc = jax.lax.while_loop(
            lambda c: c[0] < kc, body,
            (jnp.int32(0), vary_like(jnp.zeros((bn,), jnp.int32), xp, yp)))
        return acc

    cnt = jax.lax.map(row_count, jnp.arange(nbr)).reshape(-1)[:n]
    rho = cnt.astype(jnp.float32)
    rho_key = rho + jitter
    if y_sel_slots is None:
        col_key = rho_key
    else:
        col_key = jnp.full((m,), -jnp.inf,
                           jnp.float32).at[y_sel_slots].set(rho_key)
    rkp = jnp.pad(rho_key, (0, xp.shape[0] - n), constant_values=jnp.inf)
    ckp = jnp.pad(col_key, (0, yp.shape[0] - m), constant_values=-jnp.inf)
    row_nn = _nn_ring_rows(xp, rkp, yp, ckp, n, rank, lb, bn, bm)
    delta, parent = jax.lax.map(row_nn, jnp.arange(nbr))
    return (rho, rho_key, delta.reshape(-1)[:n],
            parent.reshape(-1)[:n].astype(jnp.int32))


# =====================================================================
# host-built flat worklists (the pallas scalar-prefetch grid)
# =====================================================================
# Worklist entries per pallas_call.  Each call scalar-prefetches its chunk
# into SMEM — (4, C) int32 + (C,) f32, 160 KiB at this size — so the SMEM
# footprint stays fixed however many tile pairs a fit keeps.
WL_CHUNK = 8192


@dataclass(frozen=True)
class FlatWorklist:
    """A kept tile-pair list driving ``tile_sweep``'s worklist grid.

    ``meta`` rows: [row_tile, col_tile, first-visit flag, in-d_cut flag];
    entries sorted by (row_tile, lb) so output blocks are revisited
    consecutively (the Mosaic accumulation contract) in ring order.  The
    list is cut into chunks of whole row tiles, each padded to the same
    length C with inert entries (flag -1), one ``pallas_call`` per chunk.
    """

    meta: jnp.ndarray          # (chunks, 4, C) int32
    lb: jnp.ndarray            # (chunks, C) f32 — the in-kernel NN prune radius
    n_kept: int                # worklist entries (incl. forced keeps);
                               # -1 where only the device knows it
    n_total: int               # nbr * nbc dense pair count
    # () int32 on the device: the leading chunks the sweep runs, the rest
    # unused capacity of a list built at a static, bucketed length; None:
    # every chunk
    live_chunks: jnp.ndarray | None = None

    @property
    def pruned_frac(self) -> float:
        return 1.0 - self.n_kept / max(self.n_total, 1)


def _pow2(v: int) -> int:
    return 1 << max(int(v) - 1, 0).bit_length()


def chunk_worklist(meta: np.ndarray, lb: np.ndarray,
                   chunk: int) -> tuple[np.ndarray, np.ndarray]:
    """Cut a (4, W) row-sorted worklist into (chunks, 4, C) / (chunks, C).

    Chunks hold whole row tiles, greedily packed up to C entries, where C
    is a power of two (one compiled kernel serves every worklist of that
    bucket) no smaller than the longest row tile.  Padding entries repeat
    the chunk's last (row, col) pair — no new block DMA, no write-back of a
    block the chunk did not finish — with flag -1 and an infinite bound, so
    no accumulator reads them.
    """
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


def ring_worklist(x, y, *, block_n: int, block_m: int, ny=None,
                  live_rows=None) -> FlatWorklist:
    """Device-built best-1 ring worklist: every tile pair of ``x`` rows
    against ``y`` columns, each row tile's column tiles in ascending lower
    bound (stable: ties by tile index), chunked as :func:`chunk_worklist`
    chunks it.

    The list ``build_flat_worklist(nn="best1")`` builds on the host (its
    bounds equal up to f32 summation order), without the round trip: a
    best-1 list keeps every pair, so it is a pure function of the tile
    bounds, and its shapes depend only on the table shapes.  The kernel's
    ``lb <= max(best)`` test then skips every pair past the row tile's
    final radius.  Sets no worklist counter.  ``ny``: the leading rows of
    ``y`` that are points; ``live_rows`` (traceable): the leading rows of
    ``x`` to answer, so only the chunks that hold them run.
    """
    nbr, nbc = -(-x.shape[0] // block_n), -(-y.shape[0] // block_m)
    meta, lb = _ring_chunks(x, y, ny, bn=block_n, bm=block_m, chunk=WL_CHUNK)
    live = None
    if live_rows is not None:
        tiles = -(-live_rows // block_n)
        live = -(-tiles // ring_rows_per_chunk(nbr, nbc))
    return FlatWorklist(meta=meta, lb=lb, n_kept=nbr * nbc,
                        n_total=nbr * nbc, live_chunks=live)


def _chunk_len(nbr: int, nbc: int, chunk: int) -> int:
    """Entries per chunk of a device-built list: a power of two that holds
    a whole row tile of ``nbc`` entries."""
    return _pow2(max(nbc, min(nbr * nbc, chunk), 8))


def ring_rows_per_chunk(nbr: int, nbc: int) -> int:
    """Row tiles in each chunk of a :func:`ring_worklist` (every row tile
    lists all ``nbc`` column tiles)."""
    return _chunk_len(nbr, nbc, WL_CHUNK) // nbc


@partial(jax.jit, static_argnames=("bn", "bm", "chunk"))
def _ring_chunks(x, y, ny=None, *, bn: int, bm: int, chunk: int):
    """(chunks, 4, C) meta and (chunks, C) bounds of :func:`ring_worklist`.

    Every row tile holds nbc entries, so a chunk holds C // nbc whole row
    tiles; the entries past a chunk's last real one repeat it with flag -1
    and an infinite bound, as the host chunker pads.  ``ny``: the leading
    rows of ``y`` that are points (the rest bound nothing); all of them
    when None.
    """
    n, m = x.shape[0], y.shape[0] if ny is None else ny
    xp, yp = _pad_inf(x.astype(jnp.float32), bn), \
        _pad_inf(y.astype(jnp.float32), bm)
    nbr, nbc = xp.shape[0] // bn, yp.shape[0] // bm
    lb = _tile_pair_lb(xp, n, yp, m, bn, bm)
    # a stable int32 key-value sort: jnp.argsort's 64-bit iota (the package
    # enables x64) makes the TPU compile of a stable sort ~5x slower
    lb_sorted, order = jax.lax.sort(
        (lb, jax.lax.broadcasted_iota(jnp.int32, lb.shape, 1)),
        dimension=1, num_keys=1, is_stable=True)
    C = _chunk_len(nbr, nbc, chunk)
    per = C // nbc                                  # row tiles per chunk
    n_chunks = -(-nbr // per)
    c = jnp.arange(n_chunks)[:, None]
    q = jnp.arange(C)[None, :]
    real = jnp.minimum(nbr - c * per, per) * nbc    # entries before padding
    pad = q >= real
    qe = jnp.minimum(q, real - 1)
    row = c * per + qe // nbc
    r = qe % nbc
    meta = jnp.stack([row, order[row, r], (r == 0) & ~pad,
                      jnp.where(pad, -1, 0)], axis=1).astype(jnp.int32)
    return meta, jnp.where(pad, jnp.inf, lb_sorted[row, r])


@partial(jax.jit, static_argnames=("bn",))
def ring_live_pairs(meta, lb, delta, live_chunks=None, *, bn: int):
    """Entries of a best-1 ring worklist whose lower bound is within their
    row tile's final radius: the max over the tile's rows of ``delta``
    squared, where rows past ``delta`` (the kernel's pad rows, keyed +inf)
    hold it at +inf.  Visited in ascending bound, these are the pairs the
    kernel computes; it skips the rest.  Chunk padding is not counted, nor
    chunks past ``live_chunks`` (the sweep never runs them)."""
    nbr = -(-delta.shape[0] // bn)
    d2 = jnp.pad(jnp.square(delta.astype(jnp.float32)),
                 (0, nbr * bn - delta.shape[0]), constant_values=jnp.inf)
    radius = jnp.max(d2.reshape(nbr, bn), axis=1)
    live = (meta[:, 3] >= 0) & (lb <= radius[meta[:, 0]])
    if live_chunks is not None:
        live &= (jnp.arange(meta.shape[0]) < live_chunks)[:, None]
    return jnp.sum(live, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Device-built d_cut count lists (traceable: the shard phases of
# ``distributed.dpc`` build them inside shard_map).  Two stages with one host
# read between them: :func:`cut_prefix` finds each row tile's ascending-lb
# prefix within d_cut and packs whole row tiles greedily into chunks; the
# host takes the largest chunk count over the shards up to a power of two,
# the list's static length; :func:`cut_worklist` then lays the entries out
# in :class:`FlatWorklist` chunks, the host builder's format.
@dataclass(frozen=True)
class CutPrefix:
    """Stage one of a device-built count list (all on the device)."""

    lb_sorted: jnp.ndarray     # (nbr, nbc) f32, each row tile ascending
    order: jnp.ndarray         # (nbr, nbc) int32 column tile of each entry
    kept: jnp.ndarray          # (nbr,) int32 entries of each row tile
    start: jnp.ndarray         # (nbr,) int32 slot of each row tile's first
    chunks: jnp.ndarray        # () int32 chunks the packing fills


def cut_prefix(x, nx, y, ny, d_cut, *, bn: int, bm: int,
               chunk: int = WL_CHUNK) -> CutPrefix:
    """The kept pairs of ``build_flat_worklist(count=True)`` for ``x`` rows
    against ``y`` columns, on the device: per row tile the column tiles
    whose lower bound is within d_cut, in ascending bound (stable, ties by
    tile index), at least the nearest one (output blocks initialize).
    ``nx`` / ``ny``: the leading rows of each table that are points."""
    xp, yp = _pad_inf(x.astype(jnp.float32), bn), \
        _pad_inf(y.astype(jnp.float32), bm)
    nbr, nbc = xp.shape[0] // bn, yp.shape[0] // bm
    C = _chunk_len(nbr, nbc, chunk)
    lb = _tile_pair_lb(xp, nx, yp, ny, bn, bm)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2
    kept = jnp.maximum(jnp.sum(lb <= d2cut, axis=1, dtype=jnp.int32), 1)
    # the same stable int32 key-value sort as the ring's (_ring_chunks)
    lb_sorted, order = jax.lax.sort(
        (lb, jax.lax.broadcasted_iota(jnp.int32, lb.shape, 1)),
        dimension=1, num_keys=1, is_stable=True)

    def pack(carry, k):                 # whole row tiles, greedily
        c, fill = carry
        new = fill + k > C
        c = c + new.astype(jnp.int32)
        fill = jnp.where(new, 0, fill)
        return (c, fill + k), c * C + fill

    (last, _), start = jax.lax.scan(pack, (jnp.int32(0), jnp.int32(0)),
                                    kept)
    return CutPrefix(lb_sorted=lb_sorted, order=order, kept=kept,
                     start=start, chunks=last + 1)


def cut_worklist(cp: CutPrefix, d_cut, *, n_chunks: int,
                 chunk: int = WL_CHUNK) -> FlatWorklist:
    """Stage two: ``n_chunks`` chunks (at least ``cp.chunks``) in
    :func:`chunk_worklist`'s format.  Slot ``s`` belongs to the last row
    tile that starts at or before it; past that tile's entries it repeats
    the tile's last entry with flag -1 and an infinite bound, so a chunk's
    padding repeats its last pair, as the host chunker pads.  Chunks past
    ``cp.chunks`` hold only padding and are not run
    (``FlatWorklist.live_chunks``).  Every index here comes from the
    packing, none from the sort: no sort-derived gather index (R1)."""
    nbr, nbc = cp.lb_sorted.shape
    C = _chunk_len(nbr, nbc, chunk)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2
    s = jnp.arange(n_chunks * C, dtype=jnp.int32)
    row = (jnp.searchsorted(cp.start, s, side="right") - 1).astype(jnp.int32)
    r = s - cp.start[row]
    real = r < cp.kept[row]
    r = jnp.minimum(r, cp.kept[row] - 1)
    lb = cp.lb_sorted[row, r]
    flag = jnp.where(real, (lb <= d2cut).astype(jnp.int32), -1)
    meta = jnp.stack([row, cp.order[row, r], (real & (r == 0)), flag])
    meta = meta.astype(jnp.int32).reshape(4, n_chunks, C).transpose(1, 0, 2)
    return FlatWorklist(meta=meta,
                        lb=jnp.where(real, lb, jnp.inf).reshape(n_chunks, C),
                        n_kept=-1, n_total=nbr * nbc,
                        live_chunks=cp.chunks)


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
    """Host (numpy) mirror of the device bound math: (lb, ub) matrices."""
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
    """Per-row-tile static k-NN prune radius: the smallest upper bound v
    such that tiles with ub <= v hold at least k candidate points.  A pair
    with lb > v is provably outside every row's kept-k (k strictly closer
    candidates exist), so pruning by it preserves bit-parity."""
    nbr, nbc = ub.shape
    ord_ub = np.argsort(ub, axis=1)
    ub_sorted = np.take_along_axis(ub, ord_ub, axis=1)
    cnt_sorted = col_counts[ord_ub]
    cum = np.cumsum(cnt_sorted, axis=1)
    reach = np.argmax(cum >= k, axis=1)           # first prefix with >= k
    enough = cum[:, -1] >= k
    radius = ub_sorted[np.arange(nbr), reach]
    return np.where(enough, radius, np.inf).astype(np.float32)


# --------------------------------------------------------------------------
# Host-worklist caching.  Building a flat worklist is host work proportional
# to the tile-pair grid; a DPCPlan (repro.engine.planner) activates a small
# LRU here so repeated fits on the same data skip the rebuild.  Keys are
# content fingerprints (blake2b over the input bytes + every build knob), so
# same-shape-different-data inputs can never collide.  With no active cache
# (direct backend calls) every build runs, exactly as before.
_WL_CACHE_STACK: list[tuple[dict, int]] = []

# Instrumentation lives on the repro.obs metrics registry (the old
# ``_WL_BUILDS``/``_WL_CACHE_HITS`` module globals are gone); the functions
# below are the stable read surface tests and callers use.
_M_BUILDS = _obsm.counter(
    "worklist_builds", "host flat-worklist builds (cache misses included)")
_M_CACHE_HITS = _obsm.counter(
    "worklist_cache_hits", "fingerprint hits inside a worklist_cache scope")
_M_FP_MISSES = _obsm.counter(
    "worklist_fingerprint_misses",
    "cache was active but the content fingerprint was absent (true rebuild)")
_G_WL_LEN = _obsm.gauge(
    "worklist_len", "kept tile-pair count of the most recent build")


@contextmanager
def worklist_cache(cache, max_entries: int = 8,
                   max_bytes: int = 64 << 20):
    """Activate ``cache`` (a MutableMapping, LRU-trimmed to ``max_entries``
    AND to ``max_bytes`` of worklist table data — dense-degenerate
    worklists can reach tens of MB, so the cap is size-aware) for
    build_flat_worklist calls inside the context."""
    _WL_CACHE_STACK.append((cache, max_entries, max_bytes))
    try:
        yield cache
    finally:
        _WL_CACHE_STACK.pop()


@contextmanager
def suspend_counters():
    """Scope inside which worklist instrumentation is discarded.

    Plan-time static analysis (``engine.planner._plan_check``) builds
    throwaway worklists to probe kernel structure; those must not count as
    real builds or cache traffic.  On exit every worklist metric family is
    restored to its value at entry, atomically per family.
    """
    saved = [(m, m._state()) for m in
             (_M_BUILDS, _M_CACHE_HITS, _M_FP_MISSES, _G_WL_LEN)]
    try:
        yield
    finally:
        for m, state in saved:
            m._restore(state)


def _wl_nbytes(wl: "FlatWorklist") -> int:
    return int(wl.meta.nbytes) + int(wl.lb.nbytes)


def worklist_build_count() -> int:
    return int(_M_BUILDS.value())


def worklist_cache_hits() -> int:
    return int(_M_CACHE_HITS.value())


def worklist_fingerprint_misses() -> int:
    return int(_M_FP_MISSES.value())


def d2h_nbytes(*arrays) -> int:
    """Bytes that host copies of ``arrays`` fetch from the device: each
    distinct ``jax.Array`` once (jax keeps the host copy of an array it
    has fetched, so a second ``np.asarray`` of it moves nothing); host
    arrays and ``None`` nothing.  Shapes only: no device sync."""
    dev = {id(a): a for a in arrays if isinstance(a, jax.Array)}
    return sum(int(a.nbytes) for a in dev.values())


def _src_dtype_tag(arr) -> str:
    """The dtype the CALLER handed us, before any canonicalizing cast.

    Worklists are fingerprinted on the f32-converted coordinates, but two
    callers passing the same coordinates at different source precisions are
    different cache identities: the sweep kernels consume the *original*
    arrays, so a worklist built for one must not be served to the other
    (a f64 pad row that rounds onto a kept f32 point, say, has different
    pruning slack).  The tag rides the fingerprint alongside the bytes.
    """
    if arr is None:
        return "none"
    dt = getattr(arr, "dtype", None)
    return str(dt) if dt is not None else np.asarray(arr).dtype.name


def _wl_fingerprint(x, y, d_cut, block_n, block_m, count, nn, k, nn_dcut,
                    nn_col_counts, starts, ends,
                    src_dtypes: tuple = ()) -> bytes:
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for arr in (x, y, nn_col_counts, starts, ends):
        if arr is None:
            h.update(b"\x00none")
        else:
            a = np.ascontiguousarray(np.asarray(arr))
            h.update(str(a.shape).encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    h.update(repr((None if d_cut is None else float(d_cut), block_n,
                   block_m, bool(count), nn, int(k),
                   bool(nn_dcut), src_dtypes)).encode())
    return h.digest()


def build_flat_worklist(x, y, d_cut=None, *, block_n: int, block_m: int,
                        count: bool = True, nn: str | None = None,
                        k: int = 0, nn_dcut: bool = False,
                        nn_col_counts=None,
                        starts=None, ends=None) -> FlatWorklist:
    """Host-built kept-pair worklist for one pallas sweep.

    Kept pairs are the union of what each requested accumulator can touch:
    ``count`` keeps lb <= d_cut^2; ``nn='topk'`` adds the static k-NN ring
    (see :func:`_knn_radius`; ``nn_col_counts`` overrides the per-col-tile
    admissible-candidate counts when a selection gate restricts the kept-k,
    e.g. S-Approx representatives); ``nn='best1'`` keeps every pair unless
    ``nn_dcut`` bounds the search (halo semantics) — the in-kernel runtime
    radius does the remaining pruning.  ``starts``/``ends`` (halo spans)
    additionally drop col tiles no row span reaches.  At least one pair per
    row tile is force-kept so output blocks always initialize.

    Inside a :func:`worklist_cache` context (a DPCPlan primitive wrapper)
    results are memoized by content fingerprint — same data, same knobs,
    no rebuild.
    """
    with obs.span("worklist.build") as sp:
        src_dtypes = (_src_dtype_tag(x), _src_dtype_tag(y))
        with obs.span("worklist.fetch", d2h_bytes=d2h_nbytes(x, y)):
            x = np.asarray(x, np.float32)
            y = np.asarray(y, np.float32)
        key = None
        if _WL_CACHE_STACK:
            cache, max_entries, max_bytes = _WL_CACHE_STACK[-1]
            with obs.span("worklist.fingerprint"):
                key = _wl_fingerprint(x, y, d_cut, block_n, block_m, count,
                                      nn, k, nn_dcut, nn_col_counts, starts,
                                      ends, src_dtypes)
            hit = cache.get(key)
            if hit is not None:
                _M_CACHE_HITS.inc()
                if hasattr(cache, "move_to_end"):
                    cache.move_to_end(key)
                sp.set(kept=hit.n_kept, total=hit.n_total, hit=True,
                       chunks=int(hit.meta.shape[0]))
                return hit
            _M_FP_MISSES.inc()
        _M_BUILDS.inc()
        with obs.span("worklist.bounds"):
            meta_c, lb_c, n_kept, n_total = _kept_pairs(
                x, y, d_cut, block_n, block_m, count, nn, k, nn_dcut,
                nn_col_counts, starts, ends)
        with obs.span("worklist.upload",
                      h2d_bytes=int(meta_c.nbytes + lb_c.nbytes)):
            out = FlatWorklist(meta=jnp.asarray(meta_c), lb=jnp.asarray(lb_c),
                               n_kept=n_kept, n_total=n_total)
        _G_WL_LEN.set(out.n_kept)
        sp.set(kept=out.n_kept, total=out.n_total, hit=False,
               chunks=int(meta_c.shape[0]))
        if key is not None:
            cache[key] = out
            while len(cache) > 1 and (
                    len(cache) > max_entries
                    or sum(map(_wl_nbytes, cache.values())) > max_bytes):
                cache.pop(next(iter(cache)))    # oldest entry (insertion order)
        return out


def _kept_pairs(x, y, d_cut, block_n, block_m, count, nn, k, nn_dcut,
                nn_col_counts, starts, ends):
    """The kept tile pairs of :func:`build_flat_worklist`, row-sorted and
    chunked on the host: (meta chunks, lb chunks, kept, dense total)."""
    n, _ = x.shape
    m = y.shape[0]
    nbr, nbc = -(-n // block_n), -(-m // block_m)
    lb, ub = host_pair_bounds(x, y, block_n, block_m)
    d2cut = None if d_cut is None else float(d_cut) ** 2

    in_cut = np.zeros((nbr, nbc), bool)
    keep = np.zeros((nbr, nbc), bool)
    if count:
        assert d2cut is not None
        in_cut = lb <= d2cut
        keep |= in_cut
    if nn == "best1":
        if nn_dcut:
            assert d2cut is not None
            keep |= lb <= d2cut
        else:
            keep[:] = True
    elif nn == "topk":
        if nn_col_counts is None:
            col_counts = np.minimum(block_m, np.maximum(
                0, m - np.arange(nbc) * block_m))
        else:
            col_counts = np.asarray(nn_col_counts)
        radius = _knn_radius(ub, col_counts, max(k, 1))
        keep |= lb <= radius[:, None]

    if starts is not None:
        st = np.asarray(starts)
        en = np.asarray(ends)
        pad_rows = nbr * block_n - n
        if pad_rows:
            st = np.pad(st, ((0, pad_rows), (0, 0)))
            en = np.pad(en, ((0, pad_rows), (0, 0)))
        live = en > st
        smin = np.where(live, st, np.iinfo(np.int64).max) \
            .reshape(nbr, block_n, -1).min(axis=(1, 2))
        emax = np.where(live, en, np.iinfo(np.int64).min) \
            .reshape(nbr, block_n, -1).max(axis=(1, 2))
        jlo = np.arange(nbc) * block_m
        overlap = (smin[:, None] < jlo[None, :] + block_m) & \
                  (emax[:, None] > jlo[None, :])
        keep &= overlap
        in_cut &= overlap

    # force-keep the min-lb pair of every row tile (output block init)
    jmin = np.argmin(lb, axis=1)
    keep[np.arange(nbr), jmin] = True

    wi, wj = np.nonzero(keep)
    wl = lb[wi, wj]
    sort = np.lexsort((wl, wi))
    wi, wj, wl = wi[sort], wj[sort], wl[sort]
    first = np.zeros(len(wi), np.int32)
    first[np.unique(wi, return_index=True)[1]] = 1
    meta = np.stack([wi, wj, first,
                     in_cut[wi, wj].astype(np.int64)]).astype(np.int32)
    meta_c, lb_c = chunk_worklist(meta, wl.astype(np.float32), WL_CHUNK)
    return meta_c, lb_c, len(wi), nbr * nbc


def worklist_stats(x, y, d_cut, *, block_n: int = BS_BLOCK_N,
                   block_m: int = BS_BLOCK_M) -> dict:
    """Pruning statistics for benchmarks: how much of the dense tile grid
    the d_cut-bounded count sweep keeps (``benchmarks/scaling_dcut.py``
    records this next to runtime so the sensitivity plot shows *why*)."""
    wl = build_flat_worklist(x, y, d_cut, block_n=block_n, block_m=block_m,
                             count=True)
    return {"tiles_total": wl.n_total, "tiles_kept": wl.n_kept,
            "pruned_tile_frac": wl.pruned_frac}
