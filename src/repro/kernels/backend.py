"""Pluggable kernel backend for DPC's two primitives.

The paper's entire contribution is making two primitives fast on parallel
hardware: the range count behind local density (Def. 1) and the
nearest-strictly-denser-neighbor search behind the dependent point (Def. 2).
This module is the seam that lets every algorithm (core, distributed, serve)
pick where those primitives run:

* ``jnp``              — blocked pure-jnp direct-difference forms: the
                         reference implementation and the CPU default.  Bit-
                         identical to the historical ``core.scan`` oracle.
* ``pallas``           — the Mosaic TPU tile-sweep kernels in
                         ``kernels/sweep.py`` (MXU expanded-form tiles).
* ``pallas-interpret`` — the same kernels under the Pallas interpreter, so CI
                         containers without a TPU exercise the kernel code
                         paths (slow; correctness only).

Beyond the two static primitives (+ the triangular prefix variant), every
backend carries:

* the **fused** ``rho_delta`` primitive — Def. 1 and Def. 2 answered by one
  engine invocation instead of two back-to-back sweeps.  The jnp form shares
  one jit (a count pass plus a lean min-only NN pass whose argmin is
  recovered per winning tile); the pallas form is a genuinely single tile
  sweep (count + unmasked kept-k accumulator, the denser-mask resolved in a
  direct-diff epilogue, unresolved rows — the local-maxima tail — re-queried
  with one small masked-NN pass).  ``fused_traceable`` marks backends whose
  ``rho_delta`` is jit-safe end to end (the pallas epilogue's fallback is
  host-orchestrated).
* the **halo** primitives ``range_count_halo`` / ``denser_nn_halo`` — the
  same two definitions restricted to per-row ragged [start, end) windows of
  a halo-exchanged column table (the distributed optimized path).
* the two *streaming* batched primitives used by ``repro.stream``:
  ``range_count_delta`` (signed range count over an insert/evict delta batch
  — the sliding-window rho repair) and ``denser_nn_update`` (Def. 2
  re-queried for a row subset; the pallas backends fuse the row gather into
  the kernel).

Every dense primitive additionally accepts ``layout="block-sparse"``: the
grid-pruned execution mode (``kernels.blocksparse``).  Callers lay the
points out in grid-sorted order (``core.grid``'s sort — the drivers do
this), per-tile AABBs bound every tile pair's distances, and only pairs
that can matter are visited: count accumulators keep pairs with min
inter-AABB distance <= d_cut, NN accumulators walk an ascending-bound ring
with a progressively-shrinking prune radius.  The jnp worklists are
jit-built (``worklist_traceable``: block-sparse stays legal inside
jit/shard_map and ``rho_delta`` stays ``fused_traceable``).  The pallas
worklists are built on the device and drive a scalar-prefetched 1-D kernel
grid; a count list is sized on the host, by one 8-byte read of its length,
like the grid itself.  A caller may also build one inside a trace and hand
it in (``count_kept``, ``denser_nn(worklist=)``: the distributed shards).
f32 results are bit-identical to the dense layout of the same backend — pruning bounds carry conservative slack
covering f32 rounding of the bound arithmetic, NN tie-breaks are
explicitly lexicographic — which is property-tested on tie-heavy lattice
data (tests/test_blocksparse.py).  Under ``precision="bf16"`` the bounds
remain *true-distance* conservative (never prune a truly-relevant pair),
but the dense bf16 sweep evaluates tile distances with ~2^-8 relative
error, so on data where that error is material the two layouts can keep
different candidates — block-sparse == dense-bf16 exactly on
exactly-representable data (tested), and up to bf16 rounding elsewhere
(the same caveat bf16 itself carries).  Correctness never depends on the
input order; only the pruning rate does.

``get_backend(None)`` auto-detects: ``pallas`` on TPU, ``jnp`` elsewhere.
Numerical contract: every d_cut test (range counts, the halo NN's within-
d_cut gate) compares direct-difference distances rounded exactly as the jnp
reference rounds them (``ref.sq_sum_parts``), so counts agree bit for bit
across backends.  The pallas NN accumulators rank candidates in the MXU
expanded form |x|^2+|y|^2-2xy and re-rank the top-k direct-diff (see
sweep.refine_topk_d2), so only a near-tie whose true winner falls outside
the expanded top-k can pick a different neighbour than ``jnp``.
The pallas backends additionally accept ``precision="bf16"`` on ``rho_delta``
(bf16 inner product at twice the MXU rate, winners refined back to f32
direct-diff); the jnp backend is the f32 reference and rejects it.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs

from . import blocksparse, ops
from .blocksparse import vary_like
from .ref import sq_sum

__all__ = ["KernelBackend", "available_backends", "default_backend_name",
           "get_backend", "register_backend", "rho_delta_sequential"]


def _default_jitter(n: int):
    from repro.core.dpc_types import density_jitter  # lazy: avoids a cycle
    return density_jitter(n)


def _pow2_pad(m: int) -> int:
    p = 1
    while p < m:
        p *= 2
    return p


def _sparse(layout: str | None) -> bool:
    """Resolve a layout name: None/'dense' -> False, 'block-sparse' -> True."""
    if layout in (None, "dense"):
        return False
    if layout == "block-sparse":
        return True
    raise ValueError(f"unknown layout {layout!r}; "
                     "expected 'dense' or 'block-sparse'")


def _require_host(name: str, *arrays) -> None:
    """Pallas count lists are sized on the host (like the grid index
    itself)."""
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        raise ValueError(
            f"{name}(layout='block-sparse') on a pallas backend sizes its "
            "tile worklist on the host; call it outside jit/shard_map, or "
            "use the jnp backend (worklist_traceable) for traced callers")


def rho_delta_sequential(be: "KernelBackend", x, y, d_cut, *, jitter=None,
                         y_sel_slots=None, block: int | None = None,
                         layout: str | None = None):
    """The two-pass reference formulation of the fused primitive.

    Def. 1 then Def. 2 as separate backend calls — the parity oracle the
    fused ``rho_delta`` implementations are tested against, and the default
    for backends that do not override it.  ``y_sel_slots`` (len(x) int,
    y-row of query i) restricts the NN candidate set to the query rows
    themselves mapped into y space (S-Approx representatives); ``None``
    means y *is* the query set (identity correspondence).
    """
    rho = be.range_count(x, y, d_cut, block=block, layout=layout)
    if jitter is None:
        jitter = _default_jitter(x.shape[0])
    rho_key = rho + jitter
    if y_sel_slots is None:
        assert x.shape[0] == y.shape[0], \
            "identity rho_delta needs y rows == query rows"
        col_key = rho_key
    else:
        col_key = jnp.full((y.shape[0],), -jnp.inf,
                           jnp.float32).at[y_sel_slots].set(rho_key)
    delta, parent = be.denser_nn(x, rho_key, y, col_key, block=block,
                                 layout=layout)
    return rho, rho_key, delta, parent


# --------------------------------------------------------------- interface
class KernelBackend:
    """The DPC primitives (Def. 1 / Def. 2 + fused, halo and streaming forms).

    ``mxu_dense`` tells algorithm drivers this backend wants the dense tiled
    formulation (all-pairs MXU tiles) rather than the grid-stencil gathers;
    the stencil IS the jnp reference, so only the pallas backends set it.
    ``fused_traceable`` marks a ``rho_delta`` that is safe to call inside
    jit/vmap (no host-orchestrated fallback step).  ``worklist_traceable``
    marks a backend whose block-sparse worklists are jit-built — its
    ``layout="block-sparse"`` primitives stay legal inside jit/shard_map
    (the pallas count lists are sized on the host, like the grid index).
    """

    name: str = "abstract"
    mxu_dense: bool = False
    fused_traceable: bool = False
    worklist_traceable: bool = False

    def range_count(self, x, y, d_cut, *, block: int | None = None,
                    layout: str | None = None):
        """(n,) f32: |{j : ||x_i - y_j|| < d_cut}| per row of x (Def. 1)."""
        raise NotImplementedError

    def denser_nn(self, x, x_key, y, y_key, *, block: int | None = None,
                  layout: str | None = None):
        """(delta, parent): NN among y rows with y_key strictly greater
        (Def. 2).  delta = +inf, parent = -1 where no such row exists."""
        raise NotImplementedError

    def prefix_nn(self, pts_sorted_desc, *, block: int | None = None):
        """(delta, parent): NN among strict-prefix rows, input pre-sorted by
        descending density key — Def. 2 as a triangular sweep (Ex-DPC)."""
        raise NotImplementedError

    def count_kept(self, x, y, d_cut, *, worklist, block: int | None = None,
                   precision: str | None = None):
        """The first half of the fused ``rho_delta`` over a given worklist
        (one built on the device: traceable): (rho (n,) f32, the k nearest
        visited candidates' expanded d2 (n, k) and y-rows (n, k)), the
        denser-mask left to the caller once every count is known."""
        raise NotImplementedError

    # ---- fused rho + delta (the unified-engine primitive) ----

    def rho_delta(self, x, y, d_cut, *, jitter=None, y_sel_slots=None,
                  block: int | None = None, precision: str | None = None,
                  fallback_interest=None, layout: str | None = None):
        """Fused Def. 1 + Def. 2: per x-row range count over y AND the
        nearest strictly-denser neighbor, one engine invocation.

        Returns (rho, rho_key, delta, parent); rho_key = rho + jitter
        (all-distinct comparison key), parent in y-row index space.
        ``y_sel_slots``: see :func:`rho_delta_sequential`.  ``precision``:
        pallas backends accept ``"bf16"`` for the tile inner product (winners
        refined back to f32 direct-diff); default f32.  ``layout``:
        ``"block-sparse"`` selects the grid-pruned worklist mode (callers
        should pass grid-sorted points — pruning quality, not correctness,
        depends on the layout).

        ``fallback_interest``: optional ``rho_key -> (nx,) bool`` callable
        naming the rows whose Def.-2 answer the caller will actually consume
        (e.g. Approx-DPC reads it only for the cell maxima).  Backends whose
        fused path re-queries unresolved rows may restrict that pass to the
        interest set — rows outside it can come back as (inf, -1) when the
        kept-k did not resolve them.  Exact backends ignore it.
        """
        if precision not in (None, "f32"):
            raise ValueError(f"{self.name} backend computes f32 only")
        del fallback_interest  # every row exact: nothing to restrict
        return rho_delta_sequential(self, x, y, d_cut, jitter=jitter,
                                    y_sel_slots=y_sel_slots, block=block,
                                    layout=layout)

    # ---- halo-window primitives (distributed optimized path) ----

    def range_count_halo(self, x, window, starts, ends, d_cut, *,
                         span_cap: int, block: int | None = None,
                         layout: str | None = None):
        """Def. 1 restricted to per-row ragged [start, end) windows into a
        halo-exchanged column table.  ``starts``/``ends``: (n, S)
        window-local span bounds (empty or negative spans count nothing;
        a row's spans must be pairwise disjoint, as the grid's candidate-cell
        spans are); ``span_cap``: static max span length (gather-form
        backends)."""
        raise NotImplementedError

    def denser_nn_halo(self, x, x_key, window, w_key, starts, ends, d_cut, *,
                       span_cap: int, block: int | None = None,
                       layout: str | None = None):
        """Def. 2 restricted to the row's halo spans AND to d_cut (stencil
        semantics).  Returns (delta, parent_window_idx, found); rows with no
        strictly-denser candidate within d_cut inside their spans report
        found = False (the caller's global fallback handles them)."""
        raise NotImplementedError

    # ---- streaming (repro.stream) batched primitives ----

    def range_count_delta(self, x, batch, signs, d_cut, *,
                          block: int | None = None,
                          layout: str | None = None):
        """(n,) f32 signed count: sum_b signs[b] * [||x_i - batch_b|| < d_cut].

        The sliding-window rho repair (each surviving point's density changes
        by +1 per inserted / -1 per evicted neighbor): signs are +1 for
        inserted rows, -1 for evicted rows, 0 for padding.  With
        ``layout="block-sparse"`` the window's row tiles outside d_cut of
        the batch AABB are pruned — pays when batches are spatially
        localized (drifting streams)."""
        raise NotImplementedError

    def denser_nn_update(self, points, rho_key, q_slots, *,
                         block: int | None = None,
                         layout: str | None = None):
        """Def. 2 recomputed for the row subset ``q_slots`` of ``points``.

        The streaming delta repair: only rows whose dependent point may have
        changed (cell maxima / dirty rows) are re-queried against the full
        window.  ``q_slots`` entries >= len(points) are padding and return
        (inf, -1).  Rides each backend's denser-NN kernel; the pallas
        backends override with the fused-gather kernel (the gathered subset
        never materialises)."""
        n = points.shape[0]
        slot_c = jnp.clip(q_slots, 0, n - 1)
        valid = q_slots < n
        q = points[slot_c]
        qk = jnp.where(valid, rho_key[slot_c], jnp.inf)  # +inf key: inert row
        return self.denser_nn(q, qk, points, rho_key, block=block,
                              layout=layout)


# ------------------------------------------------------------ jnp reference
@partial(jax.jit, static_argnames=("block",))
def _range_count_jnp(x, y, d_cut, block: int = 512):
    """Blocked direct-difference range count (row blocks x column loop)."""
    n, d = x.shape
    m = y.shape[0]
    nbr, nbc = -(-n // block), -(-m // block)
    xp = jnp.pad(x, ((0, nbr * block - n), (0, 0)), constant_values=jnp.inf)
    yp = jnp.pad(y, ((0, nbc * block - m), (0, 0)), constant_values=jnp.inf)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2

    def row_block(i0):
        rows = jax.lax.dynamic_slice_in_dim(xp, i0, block, 0)

        def col_block(j, acc):
            cols = jax.lax.dynamic_slice_in_dim(yp, j * block, block, 0)
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            return acc + jnp.sum(d2 < d2cut, axis=1).astype(jnp.int32)

        return jax.lax.fori_loop(
            0, nbc, col_block, vary_like(jnp.zeros((block,), jnp.int32),
                                         xp, yp))

    cnt = jax.lax.map(row_block, jnp.arange(nbr) * block).reshape(-1)[:n]
    return cnt.astype(jnp.float32)


@partial(jax.jit, static_argnames=("block",))
def _denser_nn_jnp(x, x_key, y, y_key, block: int = 512):
    """Blocked direct-difference masked NN with a running (min, argmin)."""
    n, d = x.shape
    m = y.shape[0]
    nbr, nbc = -(-n // block), -(-m // block)
    xp = jnp.pad(x, ((0, nbr * block - n), (0, 0)), constant_values=jnp.inf)
    xk = jnp.pad(x_key, (0, nbr * block - n), constant_values=jnp.inf)
    yp = jnp.pad(y, ((0, nbc * block - m), (0, 0)), constant_values=jnp.inf)
    yk = jnp.pad(y_key, (0, nbc * block - m), constant_values=-jnp.inf)

    def row_block(i0):
        rows = jax.lax.dynamic_slice_in_dim(xp, i0, block, 0)
        rrk = jax.lax.dynamic_slice_in_dim(xk, i0, block, 0)

        def col_block(j, carry):
            best, arg = carry
            cols = jax.lax.dynamic_slice_in_dim(yp, j * block, block, 0)
            crk = jax.lax.dynamic_slice_in_dim(yk, j * block, block, 0)
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            d2 = jnp.where(crk[None, :] > rrk[:, None], d2, jnp.inf)
            jj = jnp.argmin(d2, axis=1)
            cand = d2[jnp.arange(block), jj]
            better = cand < best
            return (jnp.where(better, cand, best),
                    jnp.where(better, j * block + jj, arg))

        best, arg = jax.lax.fori_loop(
            0, nbc, col_block, vary_like(
                (jnp.full((block,), jnp.inf),
                 jnp.full((block,), -1, jnp.int64)), xp, xk, yp, yk))
        return jnp.sqrt(best), jnp.where(jnp.isfinite(best), arg, -1)

    delta, parent = jax.lax.map(row_block, jnp.arange(nbr) * block)
    return delta.reshape(-1)[:n], parent.reshape(-1)[:n].astype(jnp.int32)


@partial(jax.jit, static_argnames=("block",))
def _range_count_delta_jnp(x, batch, signs, d_cut, block: int = 512):
    """Blocked direct-difference *signed* range count (streaming rho repair).

    One fused pass over the delta batch: each batch column contributes its
    sign (+1 inserted / -1 evicted / 0 pad) to every x-row within d_cut."""
    n, d = x.shape
    m = batch.shape[0]
    nbr, nbc = -(-n // block), -(-m // block)
    xp = jnp.pad(x, ((0, nbr * block - n), (0, 0)), constant_values=jnp.inf)
    bp = jnp.pad(batch, ((0, nbc * block - m), (0, 0)),
                 constant_values=jnp.inf)
    sp = jnp.pad(signs.astype(jnp.float32), (0, nbc * block - m),
                 constant_values=0.0)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2

    def row_block(i0):
        rows = jax.lax.dynamic_slice_in_dim(xp, i0, block, 0)

        def col_block(j, acc):
            cols = jax.lax.dynamic_slice_in_dim(bp, j * block, block, 0)
            s = jax.lax.dynamic_slice_in_dim(sp, j * block, block, 0)
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            return acc + jnp.sum(jnp.where(d2 < d2cut, s[None, :], 0.0),
                                 axis=1)

        return jax.lax.fori_loop(
            0, nbc, col_block, vary_like(jnp.zeros((block,), jnp.float32),
                                         xp, bp, sp))

    cnt = jax.lax.map(row_block, jnp.arange(nbr) * block).reshape(-1)[:n]
    return cnt


@partial(jax.jit, static_argnames=("block",))
def _rho_delta_jnp(x, y, jitter, d_cut, y_sel_slots=None, block: int = 512):
    """Fused rho + delta, direct-difference, one jit.

    Pass 1 is the blocked range count; pass 2 is a *lean* masked NN that
    keeps only (min d2, winning column tile) per row — no per-tile argmin or
    gathers on the hot loop; the argmin is recovered afterwards by
    recomputing the single winning tile per row block (bit-identical floats,
    so the recovered winner equals the sequential formulation's exactly).
    """
    n, d = x.shape
    m = y.shape[0]
    nbr, nbc = -(-n // block), -(-m // block)
    xp = jnp.pad(x, ((0, nbr * block - n), (0, 0)), constant_values=jnp.inf)
    yp = jnp.pad(y, ((0, nbc * block - m), (0, 0)), constant_values=jnp.inf)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2

    # ---- pass 1: range count (Def. 1) ----
    def row_count(i0):
        rows = jax.lax.dynamic_slice_in_dim(xp, i0, block, 0)

        def col(j, acc):
            cols = jax.lax.dynamic_slice_in_dim(yp, j * block, block, 0)
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            return acc + jnp.sum(d2 < d2cut, axis=1).astype(jnp.int32)

        return jax.lax.fori_loop(
            0, nbc, col, vary_like(jnp.zeros((block,), jnp.int32), xp, yp))

    cnt = jax.lax.map(row_count, jnp.arange(nbr) * block).reshape(-1)[:n]
    rho = cnt.astype(jnp.float32)
    rho_key = rho + jitter
    if y_sel_slots is None:
        col_key = rho_key
    else:
        col_key = jnp.full((m,), -jnp.inf,
                           jnp.float32).at[y_sel_slots].set(rho_key)
    rkp = jnp.pad(rho_key, (0, nbr * block - n), constant_values=jnp.inf)
    ckp = jnp.pad(col_key, (0, nbc * block - m), constant_values=-jnp.inf)

    # ---- pass 2 + epilogue: lean masked NN (Def. 2) ----
    def row_nn(i0):
        rows = jax.lax.dynamic_slice_in_dim(xp, i0, block, 0)
        rrk = jax.lax.dynamic_slice_in_dim(rkp, i0, block, 0)

        def col(j, carry):
            best, jwin = carry
            cols = jax.lax.dynamic_slice_in_dim(yp, j * block, block, 0)
            crk = jax.lax.dynamic_slice_in_dim(ckp, j * block, block, 0)
            d2 = sq_sum(rows[:, None, :] - cols[None, :, :])
            cand = jnp.min(jnp.where(crk[None, :] > rrk[:, None], d2,
                                     jnp.inf), axis=1)
            better = cand < best
            return (jnp.where(better, cand, best), jnp.where(better, j, jwin))

        best, jwin = jax.lax.fori_loop(
            0, nbc, col, vary_like((jnp.full((block,), jnp.inf),
                                    jnp.zeros((block,), jnp.int32)),
                                   xp, yp, rkp, ckp))
        # recover the argmin inside each row's winning tile (same float ops
        # on the same operands -> bitwise-equal d2 -> the sequential winner)
        cidx = jwin[:, None] * block + jnp.arange(block)[None, :]
        cols = yp[cidx]                              # (block, block, d)
        crk = ckp[cidx]
        d2r = sq_sum(rows[:, None, :] - cols)
        d2m = jnp.where(crk > rrk[:, None], d2r, jnp.inf)
        jloc = jnp.argmin(d2m, axis=1)
        parent = jnp.where(jnp.isfinite(best),
                           cidx[jnp.arange(block), jloc], -1)
        return jnp.sqrt(best), parent

    delta, parent = jax.lax.map(row_nn, jnp.arange(nbr) * block)
    return (rho, rho_key, delta.reshape(-1)[:n],
            parent.reshape(-1)[:n].astype(jnp.int32))


@partial(jax.jit, static_argnames=("span_w", "block"))
def _range_count_halo_jnp(x, window, starts, ends, d_cut, span_w: int,
                          block: int = 256):
    """Gather-form halo range count: per-row candidate spans into a window."""
    W = window.shape[0]
    m, d = x.shape
    nb = -(-m // block)
    mp = nb * block
    xp = jnp.pad(x, ((0, mp - m), (0, 0)), constant_values=jnp.inf)
    st_p = jnp.pad(starts, ((0, mp - m), (0, 0)), constant_values=0)
    en_p = jnp.pad(ends, ((0, mp - m), (0, 0)), constant_values=0)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2

    def chunk(i0):
        rows = jax.lax.dynamic_slice_in_dim(xp, i0, block, 0)
        st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)
        en = jax.lax.dynamic_slice_in_dim(en_p, i0, block, 0)
        idx = st[..., None] + jnp.arange(span_w, dtype=st.dtype)
        valid = (idx < en[..., None]) & (idx >= 0)
        cand = window[jnp.clip(idx, 0, W - 1)]
        d2 = sq_sum(rows[:, None, None, :] - cand)
        return jnp.sum((d2 < d2cut) & valid, axis=(1, 2))

    cnt = jax.lax.map(chunk, jnp.arange(nb) * block).reshape(-1)[:m]
    return cnt.astype(jnp.float32)


@partial(jax.jit, static_argnames=("span_w", "block"))
def _denser_nn_halo_jnp(x, x_key, window, w_key, starts, ends, d_cut,
                        span_w: int, block: int = 256):
    """Gather-form halo strictly-denser NN within d_cut (window-local
    parents; found = a qualifying candidate exists inside the spans)."""
    W = window.shape[0]
    m, d = x.shape
    nb = -(-m // block)
    mp = nb * block
    xp = jnp.pad(x, ((0, mp - m), (0, 0)), constant_values=jnp.inf)
    rk_p = jnp.pad(x_key, (0, mp - m), constant_values=jnp.inf)
    st_p = jnp.pad(starts, ((0, mp - m), (0, 0)), constant_values=0)
    en_p = jnp.pad(ends, ((0, mp - m), (0, 0)), constant_values=0)
    d2cut = jnp.asarray(d_cut, jnp.float32) ** 2

    def chunk(i0):
        rows = jax.lax.dynamic_slice_in_dim(xp, i0, block, 0)
        rk = jax.lax.dynamic_slice_in_dim(rk_p, i0, block, 0)
        st = jax.lax.dynamic_slice_in_dim(st_p, i0, block, 0)
        en = jax.lax.dynamic_slice_in_dim(en_p, i0, block, 0)
        idx = st[..., None] + jnp.arange(span_w, dtype=st.dtype)
        valid = (idx < en[..., None]) & (idx >= 0)
        idx_c = jnp.clip(idx, 0, W - 1)
        cand = window[idx_c]
        cand_rk = w_key[idx_c]
        d2 = sq_sum(rows[:, None, None, :] - cand)
        mask = valid & (cand_rk > rk[:, None, None]) & (d2 < d2cut)
        d2m = jnp.where(mask, d2, jnp.inf).reshape(block, -1)
        j = jnp.argmin(d2m, axis=1)
        best = d2m[jnp.arange(block), j]
        pidx = idx_c.reshape(block, -1)[jnp.arange(block), j].astype(jnp.int32)
        ok = jnp.isfinite(best)
        return (jnp.sqrt(best), jnp.where(ok, pidx, -1).astype(jnp.int32), ok)

    dd, pp, ff = jax.lax.map(chunk, jnp.arange(nb) * block)
    return (dd.reshape(-1)[:m], pp.reshape(-1)[:m], ff.reshape(-1)[:m])


class JnpBackend(KernelBackend):
    """Reference backend: the direct-difference math of the Scan oracle.

    Block-sparse routes (``layout="block-sparse"``) run the jit-built ring
    worklists of ``kernels.blocksparse`` — bit-identical outputs (same
    per-tile float expressions, order-independent count sums, lexicographic
    NN winner), sub-quadratic work under the paper's d_cut assumption.
    The halo primitives are gather-form — the candidate spans already ARE
    the grid pruning — so they accept and ignore ``layout``.
    """

    name = "jnp"
    mxu_dense = False
    fused_traceable = True
    worklist_traceable = True

    def range_count(self, x, y, d_cut, *, block=None, layout=None):
        if _sparse(layout):
            return blocksparse._count_bs_jnp(x, y, None, d_cut)
        return _range_count_jnp(x, y, d_cut, block=block or 512)

    def range_count_delta(self, x, batch, signs, d_cut, *, block=None,
                          layout=None):
        if _sparse(layout):
            return blocksparse._count_bs_jnp(x, batch, signs, d_cut,
                                             signed=True)
        return _range_count_delta_jnp(x, batch, signs, d_cut,
                                      block=block or 512)

    def denser_nn(self, x, x_key, y, y_key, *, block=None, layout=None):
        if _sparse(layout):
            return blocksparse._denser_nn_bs_jnp(x, x_key, y, y_key)
        return _denser_nn_jnp(x, x_key, y, y_key, block=block or 512)

    def prefix_nn(self, pts_sorted_desc, *, block=None):
        # strict prefix == strictly greater key when keyed by -row_index
        n = pts_sorted_desc.shape[0]
        kdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        key = -jnp.arange(n, dtype=kdt)
        return _denser_nn_jnp(pts_sorted_desc, key, pts_sorted_desc, key,
                              block=block or 512)

    def rho_delta(self, x, y, d_cut, *, jitter=None, y_sel_slots=None,
                  block=None, precision=None, fallback_interest=None,
                  layout=None):
        if precision not in (None, "f32"):
            raise ValueError("the jnp backend is the f32 direct-difference "
                             "reference; use a pallas backend for bf16")
        del fallback_interest  # every row answered exactly on both layouts
        if jitter is None:
            jitter = _default_jitter(x.shape[0])
        if _sparse(layout):
            return blocksparse._rho_delta_bs_jnp(x, y, jitter, d_cut,
                                                 y_sel_slots)
        return _rho_delta_jnp(x, y, jitter, d_cut, y_sel_slots,
                              block=block or 512)

    def range_count_halo(self, x, window, starts, ends, d_cut, *,
                         span_cap, block=None, layout=None):
        del layout  # gather form: the spans already prune the candidates
        return _range_count_halo_jnp(x, window, starts, ends, d_cut,
                                     span_cap, block=block or 256)

    def denser_nn_halo(self, x, x_key, window, w_key, starts, ends, d_cut, *,
                       span_cap, block=None, layout=None):
        del layout  # gather form: the spans already prune the candidates
        return _denser_nn_halo_jnp(x, x_key, window, w_key, starts, ends,
                                   d_cut, span_cap, block=block or 256)


# --------------------------------------------------------------- pallas
@jax.jit
def _fused_resolve(x, y, rho_key, col_key, topv, topi):
    """Direct-diff refine + denser-mask resolution of the kept-k candidates.

    Re-evaluates every kept candidate in direct-difference f32 (extending the
    refine_topk_d2 contract to the fused path: both the winner and its value
    are direct-diff exact whenever the true denser-NN sits within the kept
    k), then picks the nearest strictly-denser one — lexicographic
    (d2, y-index), matching the sequential sweep's tie-break.  Rows with no
    denser kept candidate report resolved = False.
    """
    n, k = topi.shape
    ti = jnp.maximum(topi, 0)
    y_sel = y[ti]                                      # (n, k, d)
    d2d = sq_sum(x[:, None, :] - y_sel)
    ok = (topi >= 0) & (col_key[ti] > rho_key[:, None])
    cand = jnp.where(ok, d2d, jnp.inf)
    best = jnp.min(cand, axis=1)
    tied = jnp.where(cand == best[:, None], topi, jnp.iinfo(jnp.int32).max)
    parent = jnp.min(tied, axis=1)
    resolved = jnp.isfinite(best)
    parent = jnp.where(resolved, parent, -1).astype(jnp.int32)
    return jnp.sqrt(best), parent, resolved


class PallasBackend(KernelBackend):
    """MXU tiled kernels; ``interpret=True`` is the CPU-CI variant.

    Block-sparse routes build a :class:`blocksparse.FlatWorklist` on the
    device and hand it to the scalar-prefetched 1-D sweep grid: count
    primitives get a genuinely pruned grid (kept pairs only), NN
    primitives a ring-ordered list whose pairs the kernel skips against
    its live prune radius, and the fused ``rho_delta`` the union of the
    d_cut prefix and the static k-NN ring.  A count list
    (:func:`blocksparse.fused_worklist`) is sized on the host, so the
    count routes are not jit-callable (``worklist_traceable`` stays False)
    — the same contract as the grid build itself.  A best-1 ring
    (:func:`blocksparse.ring_worklist`) depends only on shapes, so
    ``denser_nn`` and the fused ``rho_delta``'s unresolved-row fallback
    build theirs under a trace too, as the distributed shard phases build
    the lists they hand to ``count_kept`` and ``denser_nn`` inside
    ``shard_map``.  The halo primitives sweep dense span-masked tiles:
    the spans already prune them.
    """

    mxu_dense = True

    def __init__(self, interpret: bool):
        self.interpret = interpret
        self.name = "pallas-interpret" if interpret else "pallas"

    def range_count(self, x, y, d_cut, *, block=None, layout=None):
        bn = block or ops.DENSITY_BLOCK_N
        wl = None
        if _sparse(layout):
            _require_host("range_count", x, y)
            wl = blocksparse.fused_worklist(x, y, d_cut, block_n=bn,
                                            block_m=ops.DENSITY_BLOCK_M)
        return ops.local_density_xy(x, y, d_cut, block_n=bn,
                                    interpret=self.interpret, worklist=wl)

    def range_count_delta(self, x, batch, signs, d_cut, *, block=None,
                          layout=None):
        bn = block or ops.DENSITY_BLOCK_N
        wl = None
        if _sparse(layout):
            _require_host("range_count_delta", x, batch)
            wl = blocksparse.fused_worklist(x, batch, d_cut, block_n=bn,
                                            block_m=ops.DENSITY_BLOCK_M)
        return ops.local_density_delta(x, batch, signs, d_cut, block_n=bn,
                                       interpret=self.interpret, worklist=wl)

    def denser_nn(self, x, x_key, y, y_key, *, block=None, layout=None,
                  worklist=None):
        """``worklist``: a best-1 ring built on the device at the sweep's
        tiles (``block`` rows, ``blocksparse.BS_BLOCK_M`` columns), swept
        as given; otherwise ``layout`` decides, and the block-sparse layout
        builds that ring here.  Traceable either way."""
        bn, bm = min(block or 128, 1024), blocksparse.BS_BLOCK_M
        wl = worklist
        if wl is None and _sparse(layout):
            wl = blocksparse.ring_worklist(x, y, block_n=bn, block_m=bm)
        return ops.dependent_masked(x, x_key, y, y_key, block_n=bn,
                                    block_m=bm, interpret=self.interpret,
                                    worklist=wl)

    def count_kept(self, x, y, d_cut, *, worklist, block=None,
                   precision=None):
        """The fused sweep (count + unmasked kept-k) over ``worklist``, at
        ``block`` x ``ops.DENSITY_BLOCK_M`` tiles."""
        return ops.fused_sweep(x, y, d_cut, precision=precision or "f32",
                               block_n=block or ops.DENSITY_BLOCK_N,
                               interpret=self.interpret, worklist=worklist)

    def prefix_nn(self, pts_sorted_desc, *, block=None):
        return ops.dependent_prefix(pts_sorted_desc, block=block or 256,
                                    interpret=self.interpret)

    def rho_delta(self, x, y, d_cut, *, jitter=None, y_sel_slots=None,
                  block=None, precision=None, fallback_interest=None,
                  layout=None):
        """One tile sweep (count + unmasked kept-k), direct-diff epilogue,
        then one small masked-NN pass for the unresolved tail.

        The kept-k resolution is exact: if any kept candidate is strictly
        denser, every candidate nearer than it would also have been kept, so
        the nearest denser kept candidate IS the dependent point.  Rows
        whose k nearest neighbors are all less dense (the local-maxima /
        jitter-tail fraction) fall through to the fallback —
        ``fallback_interest`` restricts that pass to the rows the caller
        will read (Approx-DPC: the |G| << n cell maxima).  Under the
        block-sparse layout it walks a device-built ring worklist, so it
        computes only the tile pairs within each row tile's final radius
        (span ``rho_delta.fallback``: ``pairs`` listed, ``live`` computed);
        under the dense layout it is the dense sweep.  The fallback is
        host-orchestrated, so this path is not jit-safe (fused_traceable is
        False); jitted consumers use the two-pass formulation instead.
        """
        if precision is None:
            precision = "f32"
        if jitter is None:
            jitter = _default_jitter(x.shape[0])
        nn_sel = None
        if y_sel_slots is not None:
            nn_sel = jnp.zeros((y.shape[0],),
                               jnp.float32).at[y_sel_slots].set(1.0)
        bn = block or ops.DENSITY_BLOCK_N
        wl = None
        if _sparse(layout):
            _require_host("rho_delta", x, y)
            # the d_cut prefix (count) union the static kept-k ring (NN):
            # a pair whose lower bound clears k strictly-closer candidates
            # can never contribute a kept entry, so pruning it preserves
            # the kept set bit-for-bit; rows whose true denser-NN lies
            # beyond the kept-k fall to the existing unresolved fallback.
            # Built on the device; a selection gate counts only the
            # admissible (representative) columns per tile
            wl = blocksparse.fused_worklist(
                x, y, d_cut, block_n=bn, block_m=ops.DENSITY_BLOCK_M,
                k=ops.FUSED_TOPK, sel_slots=y_sel_slots)
        cnt, topv, topi = ops.fused_sweep(x, y, d_cut, nn_sel=nn_sel,
                                          precision=precision,
                                          block_n=bn,
                                          interpret=self.interpret,
                                          worklist=wl)
        rho = cnt
        rho_key = rho + jitter
        if y_sel_slots is None:
            col_key = rho_key
        else:
            col_key = jnp.full((y.shape[0],), -jnp.inf,
                               jnp.float32).at[y_sel_slots].set(rho_key)
        delta, parent, resolved = _fused_resolve(
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            rho_key, col_key, topv, topi)
        with obs.span("rho_delta.fallback") as sp:
            d2h = blocksparse.d2h_nbytes(resolved)
            unres_mask = ~np.asarray(resolved)
            if fallback_interest is not None:
                interest = fallback_interest(rho_key)
                d2h += blocksparse.d2h_nbytes(interest)
                unres_mask &= np.asarray(interest, bool)
            unresolved = np.nonzero(unres_mask)[0]
            cap = h2d = 0
            if unresolved.size:
                cap = _pow2_pad(unresolved.size)
                # pad rows repeat row 0, real key included: a pad row keyed
                # +inf would hold its row tile's ring radius at +inf
                rows = np.pad(unresolved, (0, cap - unresolved.size))
                xr, kr = jnp.asarray(x)[rows], rho_key[rows]
                if _sparse(layout):
                    # a ring-ordered walk over every column tile, built on
                    # the device: the kernel skips each pair whose lower
                    # bound clears its row tile's live radius
                    bn, bm = min(block or 128, 1024), blocksparse.BS_BLOCK_M
                    wl = blocksparse.ring_worklist(xr, y, block_n=bn,
                                                   block_m=bm)
                    fd, fp = ops.dependent_masked(
                        xr, kr, y, col_key, block_n=bn, block_m=bm,
                        interpret=self.interpret, worklist=wl)
                    live = blocksparse.ring_live_pairs(wl.meta, wl.lb, fd,
                                                       bn=bn)
                    d2h += blocksparse.d2h_nbytes(live)
                    sp.set(pairs=wl.n_kept, live=int(live))
                else:
                    fd, fp = self.denser_nn(xr, kr, y, col_key, block=block)
                d2h += blocksparse.d2h_nbytes(delta, parent, fd, fp)
                dd = np.asarray(delta).copy()
                pp = np.asarray(parent).copy()
                dd[unresolved] = np.asarray(fd)[: unresolved.size]
                pp[unresolved] = np.asarray(fp)[: unresolved.size]
                delta, parent = jnp.asarray(dd), jnp.asarray(pp)
                # rows goes up once per gather (x and rho_key)
                h2d = 2 * rows.nbytes + dd.nbytes + pp.nbytes
            sp.set(rows=int(unresolved.size), padded=int(cap),
                   d2h_bytes=int(d2h), h2d_bytes=int(h2d))
        return rho, rho_key, delta, parent

    def range_count_halo(self, x, window, starts, ends, d_cut, *,
                         span_cap, block=None, layout=None):
        # dense span-masked tiles: no gather width needed, and the spans
        # already prune the candidates
        del span_cap, layout
        return ops.halo_density(x, window, starts, ends, d_cut,
                                block_n=block or ops.DENSITY_BLOCK_N,
                                interpret=self.interpret)

    def denser_nn_halo(self, x, x_key, window, w_key, starts, ends, d_cut, *,
                       span_cap, block=None, layout=None):
        del span_cap, layout
        return ops.halo_dependent(x, x_key, window, w_key, starts, ends,
                                  d_cut, block_n=min(block or 128, 1024),
                                  interpret=self.interpret)

    def denser_nn_update(self, points, rho_key, q_slots, *, block=None,
                         layout=None):
        del layout  # the fused-gather kernel is already subset-shaped
        return ops.dependent_masked_gather(points, rho_key, q_slots,
                                           block_n=min(block or 128, 1024),
                                           interpret=self.interpret)


# --------------------------------------------------------------- registry
_REGISTRY: dict = {}
_INSTANCES: dict = {}


def register_backend(name: str, factory) -> None:
    """Register a backend factory under ``name`` (instantiated lazily)."""
    _REGISTRY[name] = factory
    _INSTANCES.pop(name, None)


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def default_backend_name() -> str:
    """Platform auto-detection: kernels on TPU, reference elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def get_backend(backend: str | KernelBackend | None = None) -> KernelBackend:
    """Resolve a backend name (or None/'auto' for platform default)."""
    if isinstance(backend, KernelBackend):
        return backend
    name = backend if backend not in (None, "auto") else default_backend_name()
    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"available: {available_backends()}")
    if name not in _INSTANCES:
        _INSTANCES[name] = _REGISTRY[name]()
    return _INSTANCES[name]


register_backend("jnp", JnpBackend)
register_backend("pallas", lambda: PallasBackend(interpret=False))
register_backend("pallas-interpret", lambda: PallasBackend(interpret=True))
