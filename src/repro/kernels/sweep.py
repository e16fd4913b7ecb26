"""The unified tile-sweep engine behind every DPC Pallas kernel.

Every hot primitive in this repo is the same computation wearing different
masks: sweep a grid of (row-tile x col-tile) squared-distance blocks and
reduce each tile into per-row accumulators.  This module owns that sweep
once — a :class:`SweepSpec` declares *which* accumulators and *which* masks a
primitive needs, and ``tile_sweep`` builds the corresponding Mosaic kernel:

==================  =======================================================
accumulators        ``count`` — |{j : d2 < d_cut^2}| per row (Def. 1), d2
                    in direct-difference form rounded as the jnp reference
                    rounds it, with optional per-column ``signed`` weights
                    (streaming rho repair); ``nn`` — running masked nearest neighbor, either
                    ``'best1'`` (min + argmin, per-tile direct-diff re-rank
                    of the top-``refine_k`` candidates) or ``'topk'`` (the
                    ``k`` nearest candidates kept for a direct-diff epilogue
                    — the fused rho+delta path, where the denser-mask is not
                    known until the counts are complete).
masks               ``key`` — strictly-denser candidates only (Def. 2);
                    ``prefix`` — strict lower-triangular tiles (Ex-DPC's
                    density-sorted invariant; upper tiles never touch the
                    MXU); ``span`` — per-row ragged [start, end) windows into
                    the column table (the distributed halo layout); ``nn_dcut``
                    — NN candidates must also sit within d_cut (stencil
                    semantics); ``nn_sel`` — per-column candidate gate for
                    the NN accumulator only (S-Approx representatives).
precision           (NN ranking only) ``'f32'`` — expanded-form distances
                    with an f32 MXU matmul; ``'bf16'`` — bf16 inner product (MXU at twice the
                    f32 rate), f32 accumulation and norms.  Winners are
                    restored to direct-difference f32 by the re-rank
                    (``'best1'``) or the caller's epilogue (``'topk'``), so
                    mixed precision costs nothing on well-separated data.
==================  =======================================================

``kernels/density.py`` and ``kernels/dependent.py`` keep their public
signatures as thin instantiations, and ``kernels/ops.py`` adds the padding
wrappers for the new fused / halo / gathered entry points.

Two grids drive the same kernel body.  A dense sweep (``worklist=None``)
is a plain (row tile, col tile) grid; triangular specs skip the upper tiles
in-kernel.  A block-sparse sweep is **worklist-driven**: a 1-D grid iterates
a scalar-prefetched (row-tile, col-tile, first-visit, in-cut) table plus a
per-pair lower-bound vector (``kernels.blocksparse.FlatWorklist``), so a
grid-pruned worklist visits only the tile pairs that can matter — the count
accumulators honour the ``in_cut`` flag (pairs within d_cut of the tile
AABBs) and the NN accumulators skip pairs in-kernel whenever the pair's
lower bound exceeds the accumulator's current prune radius (best-1: the
worst current best; kept-k: the worst current kth candidate).  The table is
cut into fixed-size chunks of whole row tiles, one ``pallas_call`` each, so
its SMEM footprint does not grow with n.

Mosaic constraints the layout follows: kernels trace with 64-bit types off
(int32 grid indices, 32-bit operands); per-row vectors travel as (n, 1)
columns and per-column vectors as (1, m) rows, never as rank-1 blocks.

Also here: ``gather_nn`` — the fused-gather variant of the masked NN for the
streaming repair path.  The query rows are gathered *inside* the kernel from
the (VMEM-resident) window table via one-hot matmuls over a doubled column
grid (first ``nbc`` steps assemble the queries into scratch, the next ``nbc``
steps run the masked-NN sweep), so the gathered row subset is never
materialised in HBM.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import sq_sum, sq_sum_parts

PAD_COORD = 1e9  # >> any data domain; 3*PAD^2 ~ 3e18 << f32 max

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_M = 512

# How many expanded-form candidates are re-ranked in direct-difference form
# per row tile ('best1') or kept for the epilogue ('topk').
REFINE_TOPK = 4
FUSED_TOPK = 8


@dataclass(frozen=True)
class SweepSpec:
    """Static description of one tile-sweep primitive (hashable: jit key)."""

    block_n: int = DEFAULT_BLOCK_N
    block_m: int = DEFAULT_BLOCK_M
    count: bool = False          # emit (n,) range count
    signed: bool = False         # count weighted by per-column signs (f32)
    nn: str | None = None        # None | 'best1' | 'topk'
    key: bool = False            # strictly-denser mask (xk / yk inputs)
    prefix: bool = False         # strict lower-triangular sweep
    span: bool = False           # per-row ragged [start, end) column windows
    span_s: int = 0              # spans per row (span mask)
    nn_dcut: bool = False        # NN candidates must satisfy d2 < d_cut^2
    nn_sel: bool = False         # per-column NN candidate gate (f32 mask)
    k: int = FUSED_TOPK          # kept candidates ('topk')
    refine_k: int = REFINE_TOPK  # re-rank rounds ('best1')
    precision: str = "f32"       # 'f32' | 'bf16' tile-distance inner product

    @property
    def needs_dcut(self) -> bool:
        return self.count or self.nn_dcut


def tile_d2(x, y, precision: str = "f32"):
    """Expanded-form squared distances |x|^2 + |y|^2 - 2 x.y for one tile.

    The inner product feeds the MXU; ``'bf16'`` casts the operands of the
    matmul only (norms and accumulation stay f32), trading ~8 mantissa bits
    on the cross term for twice the MXU rate.  Both operands are first
    shifted by the row tile's first point: the form's absolute error scales
    with |x|^2 + |y|^2, so measured from an origin far from the data it
    swamps nearest-neighbour gaps (ranks only; winners are re-ranked in
    direct form).
    """
    anchor = x[:1, :]
    x, y = x - anchor, y - anchor
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    y2 = jnp.sum(y * y, axis=-1, keepdims=True).T
    if precision == "bf16":
        xm, ym = x.astype(jnp.bfloat16), y.astype(jnp.bfloat16)
        prec = None
    else:
        # full f32 contraction on the MXU (Mosaic's default may not be)
        xm, ym, prec = x, y, jax.lax.Precision.HIGHEST
    xy = jax.lax.dot_general(xm, ym, (((1,), (1,)), ((), ())),
                             precision=prec,
                             preferred_element_type=jnp.float32)
    return x2 + y2 - 2.0 * xy


def _first_argmin(work, cols, bm: int):
    """(min, first index of the min) along lanes, both (bn, 1).

    Spelled as min + masked index-min so the tie rule (lowest column) is
    explicit and identical in Mosaic and in the interpreter."""
    cand = jnp.min(work, axis=1, keepdims=True)
    loc = jnp.min(jnp.where(work == cand, cols, bm), axis=1, keepdims=True)
    return cand, loc


def _pick(onehot, table):
    """Rows of ``table`` selected by a one-hot matrix, bit-exact (fp32 MXU
    contraction: exactly one unit weight per row)."""
    return jax.lax.dot_general(onehot, table, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def refine_topk_d2(x, y, d2, k: int):
    """Re-rank the k smallest expanded-form candidates in direct-diff form.

    The expanded form has absolute error ~eps*(|x|^2+|y|^2) — a large
    *relative* error for small distances, big enough to flip near-tie argmins
    when NN distances are far below the domain scale.  k rounds of extract-
    argmin / re-evaluate-direct-diff (one-hot matmul: MXU-friendly, no
    gather) / retire make both the winner *and* its value direct-diff exact
    whenever the true NN sits within the top-k expanded candidates.

    Masked candidates carry d2 = inf and stay inert.  Returns
    (best_d2_direct, local_argmin), both (bn, 1); (inf, -1) where no finite
    candidate.
    """
    bn, bm = d2.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
    best = jnp.full((bn, 1), jnp.inf, jnp.float32)
    arg = jnp.full((bn, 1), -1, jnp.int32)
    work = d2
    for _ in range(max(k, 1)):
        cand, loc = _first_argmin(work, cols, bm)
        y_sel = _pick((loc == cols).astype(jnp.float32), y)
        d2d = sq_sum(x - y_sel, keepdims=True)
        d2d = jnp.where(jnp.isfinite(cand), d2d, jnp.inf)     # keep masked inert
        better = d2d < best
        best = jnp.where(better, d2d, best)
        arg = jnp.where(better, loc, arg)
        work = jnp.where(cols == loc, jnp.inf, work)           # retire winner
    return best, arg


def _extract_topk(d2, base_col, k: int):
    """k smallest (d2, global col) of a tile, ascending by (d2, idx)."""
    bn, bm = d2.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bn, k), 1)
    vals = jnp.full((bn, k), jnp.inf, jnp.float32)
    idxs = jnp.full((bn, k), -1, jnp.int32)
    work = d2
    for t in range(k):
        m, loc = _first_argmin(work, cols, bm)
        vals = jnp.where(lane == t, m, vals)
        idxs = jnp.where(lane == t, base_col + loc, idxs)
        work = jnp.where(cols == loc, jnp.inf, work)
    return vals, idxs


def _merge_topk(av, ai, bv, bi, k: int):
    """Merge two (bn, k) candidate lists, keeping the k smallest by (d2, idx).

    The tie-break is *explicitly* lexicographic on the global index: each
    round extracts the minimum value and, among equal-valued entries, the
    lowest index.  For the dense sweep (tiles arriving in ascending column
    order) this reproduces the historical first-position behaviour exactly;
    for a block-sparse worklist (tiles arriving in ring order) it makes the
    kept set independent of the visit order — the bit-parity contract.
    Each round retires exactly one entry: the first position of ``a ++ b``
    carrying the extracted (d2, idx) pair.
    """
    bn = av.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (bn, k), 1)
    int_max = jnp.iinfo(jnp.int32).max
    vals = jnp.full((bn, k), jnp.inf, jnp.float32)
    idxs = jnp.full((bn, k), -1, jnp.int32)
    for t in range(k):
        m = jnp.minimum(jnp.min(av, axis=1, keepdims=True),
                        jnp.min(bv, axis=1, keepdims=True))
        hit_a, hit_b = av == m, bv == m
        sel = jnp.minimum(
            jnp.min(jnp.where(hit_a, ai, int_max), axis=1, keepdims=True),
            jnp.min(jnp.where(hit_b, bi, int_max), axis=1, keepdims=True))
        vals = jnp.where(pos == t, m, vals)
        idxs = jnp.where(pos == t, sel, idxs)
        first_a = jnp.min(jnp.where(hit_a & (ai == sel), pos, int_max),
                          axis=1, keepdims=True)
        first_b = jnp.min(jnp.where(hit_b & (bi == sel), pos, int_max),
                          axis=1, keepdims=True)
        in_a = first_a < int_max
        av = jnp.where(in_a & (pos == first_a), jnp.inf, av)
        bv = jnp.where(~in_a & (pos == first_b), jnp.inf, bv)
    return vals, idxs


def _span_mask(st_ref, en_ref, base_col, bn: int, bm: int):
    """(bn, bm) bool: column j in any of the row's [start, end) windows."""
    col = base_col + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
    mask = jnp.zeros((bn, bm), bool)
    for s in range(st_ref.shape[1]):
        mask |= (col >= st_ref[:, s:s + 1]) & (col < en_ref[:, s:s + 1])
    return mask


def _make_sweep_kernel(spec: SweepSpec, sparse: bool, n_out: int):
    """Kernel body for one sweep.  ``sparse``: a 1-D grid over a scalar-
    prefetched worklist chunk (its prior outputs ride as aliased, untouched
    HBM operands); otherwise a plain (row tile, col tile) grid."""
    bn, bm = spec.block_n, spec.block_m

    def kernel(*refs):
        it = iter(refs)
        if sparse:
            meta_ref = next(it)           # (4, C) worklist chunk
            lb_ref = next(it)             # (C,) per-pair lower bounds
        d2s_ref = next(it) if spec.needs_dcut else None
        x_ref = next(it)
        xk_ref = next(it) if spec.key else None
        y_ref = next(it)
        yt_ref = next(it) if spec.needs_dcut else None
        yk_ref = next(it) if spec.key else None
        s_ref = next(it) if spec.signed else None
        sel_ref = next(it) if spec.nn_sel else None
        st_ref = next(it) if spec.span else None
        en_ref = next(it) if spec.span else None
        if sparse:
            for _ in range(n_out):        # aliased prior outputs (HBM)
                next(it)
        cnt_ref = next(it) if spec.count else None
        if spec.nn == "best1":
            best_ref, arg_ref = next(it), next(it)
        elif spec.nn == "topk":
            topv_ref, topi_ref = next(it), next(it)

        if sparse:
            p = pl.program_id(0)
            i, j = meta_ref[0, p], meta_ref[1, p]
            first = meta_ref[2, p] == 1
            # meta row 3: 1 = in d_cut, 0 = NN-only pair, -1 = chunk padding
            flag = meta_ref[3, p]
        else:
            i, j = pl.program_id(0), pl.program_id(1)
            first = j == 0

        @pl.when(first)
        def _init():
            if spec.count:
                cnt_ref[...] = jnp.zeros_like(cnt_ref)
            if spec.nn == "best1":
                best_ref[...] = jnp.full_like(best_ref, jnp.inf)
                arg_ref[...] = jnp.full_like(arg_ref, -1)
            elif spec.nn == "topk":
                topv_ref[...] = jnp.full_like(topv_ref, jnp.inf)
                topi_ref[...] = jnp.full_like(topi_ref, -1)

        # per-accumulator liveness.  Worklist chunks: the count honours the
        # in-cut flag; the NN accumulators compare the pair's lower bound
        # against their current prune radius.  Dense grids visit every pair
        # (triangular specs skip the upper tiles).
        if sparse:
            cnt_live = (flag == 1) if spec.count else False
            if spec.nn == "best1":
                nn_live = (flag >= 0) & (lb_ref[p] <= jnp.max(best_ref[...]))
            elif spec.nn == "topk":
                nn_live = (flag >= 0) & (lb_ref[p] <= jnp.max(topv_ref[...]))
            else:
                nn_live = False
            live = cnt_live | nn_live if spec.count and spec.nn else \
                (cnt_live if spec.count else nn_live)
        else:
            cnt_live = nn_live = True
            live = (j * bm < (i + 1) * bn) if spec.prefix else True

        def _direct_d2():
            # d_cut tests compare direct-difference distances, rounded as
            # the jnp reference rounds them (ref.sq_sum_parts): a count
            # never depends on the expanded form's cancellation error
            x, yt = x_ref[...], yt_ref[...]
            return sq_sum_parts(x[:, k:k + 1] - yt[k:k + 1, :]
                                for k in range(x.shape[1]))

        def _count():
            cmask = _direct_d2() < d2s_ref[0]
            if spec.span:
                cmask &= _span_mask(st_ref, en_ref, j * bm, bn, bm)
            if spec.signed:
                cnt = jnp.sum(jnp.where(cmask, s_ref[...], 0.0),
                              axis=1, keepdims=True)
            else:
                cnt = jnp.sum(cmask.astype(jnp.int32), axis=1,
                              keepdims=True)
            cnt_ref[...] += cnt

        def _compute():
            if spec.count:
                _when(cnt_live, _count)
            if spec.nn is None:
                return
            x = x_ref[...]
            y = y_ref[...]
            d2 = tile_d2(x, y, spec.precision)
            smask = (_span_mask(st_ref, en_ref, j * bm, bn, bm)
                     if spec.span else None)
            d2m = d2
            if spec.key:
                d2m = jnp.where(yk_ref[...] > xk_ref[...], d2m, jnp.inf)
            if spec.prefix:
                row = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 0)
                col = j * bm + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
                d2m = jnp.where(col < row, d2m, jnp.inf)
            if smask is not None:
                d2m = jnp.where(smask, d2m, jnp.inf)
            if spec.nn_dcut:
                d2m = jnp.where(_direct_d2() < d2s_ref[0], d2m, jnp.inf)
            if spec.nn_sel:
                d2m = jnp.where(sel_ref[...] > 0, d2m, jnp.inf)

            if spec.nn == "best1":
                cand, loc = refine_topk_d2(x, y, d2m, spec.refine_k)
                cand = jnp.where(nn_live, cand, jnp.inf)
                gidx = j * bm + loc
                # lexicographic (d2, col) update: ring-ordered worklists
                # visit tiles out of column order, and on exact distance
                # ties the dense sweep's winner is the lowest column
                best = best_ref[...]
                arg = arg_ref[...]
                better = cand < best
                tie = (cand == best) & jnp.isfinite(cand) & (gidx < arg)
                upd = better | tie
                best_ref[...] = jnp.where(upd, cand, best)
                arg_ref[...] = jnp.where(upd, gidx, arg)
            else:
                tv, ti = _extract_topk(d2m, j * bm, spec.k)
                tv = jnp.where(nn_live, tv, jnp.inf)
                ti = jnp.where(nn_live, ti, -1)
                mv, mi = _merge_topk(topv_ref[...], topi_ref[...], tv, ti,
                                     spec.k)
                topv_ref[...] = mv
                topi_ref[...] = mi

        _when(live, _compute)

    return kernel


def _when(cond, fn):
    """``pl.when`` that also takes a static ``True`` (dense grids)."""
    if cond is True:
        fn()
    else:
        pl.when(cond)(fn)


def tile_sweep(spec: SweepSpec, x, y, d_cut=None, x_key=None, y_key=None,
               signs=None, nn_sel=None, starts=None, ends=None,
               wl_meta=None, wl_lb=None, *, interpret: bool = False):
    """Run the sweep described by ``spec`` over padded inputs.

    Shape contract (as for every kernel here): ``x`` is (n, d) padded to a
    multiple of ``spec.block_n`` with PAD_COORD rows, ``y`` (m, d) padded to
    ``spec.block_m``; per-row/per-column vectors padded to match (keys +inf
    on padded queries / -inf on padded candidates; signs 0; spans empty).
    ``wl_meta``/``wl_lb`` (``blocksparse.FlatWorklist`` chunk arrays, shapes
    (chunks, 4, C) and (chunks, C)) select the block-sparse tile-pair
    worklist; ``None`` runs the dense all-pairs sweep.  Returns the tuple of
    requested accumulators, in order: ``count`` (n,), then ``nn`` —
    (best_d2, arg) of shape (n,) or (topv, topi) of shape (n, k).

    Host wrapper: ``d_cut`` is normalized to a strong ``f32`` *before* the
    jit boundary — a python float traces weak-typed and a numpy scalar
    strong, so an un-normalized scalar would land one trace-cache entry
    per spelling the caller uses (R7's retrace-churn finding).
    """
    if d_cut is not None:
        d_cut = jnp.asarray(d_cut, jnp.float32)
    return _tile_sweep_jit(spec, x, y, d_cut, x_key, y_key, signs, nn_sel,
                           starts, ends, wl_meta, wl_lb,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def _tile_sweep_jit(spec: SweepSpec, x, y, d_cut=None, x_key=None,
                    y_key=None, signs=None, nn_sel=None, starts=None,
                    ends=None, wl_meta=None, wl_lb=None, *,
                    interpret: bool = False):
    n, d = x.shape
    m, _ = y.shape
    assert n % spec.block_n == 0 and m % spec.block_m == 0
    bn, bm = spec.block_n, spec.block_m
    sparse = wl_meta is not None

    # Operands in kernel order, each with its block kind.  Per-row vectors
    # travel as (n, 1) columns and per-column vectors as (1, m) rows: the
    # 2-D layouts Mosaic tiles (rank-1 blocks it refuses).
    args, kinds = [], []
    if spec.needs_dcut:
        args.append((jnp.asarray(d_cut, jnp.float32) ** 2).reshape((1,)))
        kinds.append(("smem", None))
    args.append(x)
    kinds.append(("row", (bn, d)))
    if spec.key:
        args.append(x_key.astype(jnp.float32).reshape(n, 1))
        kinds.append(("row", (bn, 1)))
    args.append(y)
    kinds.append(("col", (bm, d)))
    if spec.needs_dcut:                  # coordinates as rows: direct form
        args.append(y.T)
        kinds.append(("colvec", (d, bm)))
    if spec.key:
        args.append(y_key.astype(jnp.float32).reshape(1, m))
        kinds.append(("colvec", (1, bm)))
    if spec.signed:
        args.append(signs.astype(jnp.float32).reshape(1, m))
        kinds.append(("colvec", (1, bm)))
    if spec.nn_sel:
        args.append(nn_sel.astype(jnp.float32).reshape(1, m))
        kinds.append(("colvec", (1, bm)))
    if spec.span:
        S = spec.span_s
        args += [starts.astype(jnp.int32), ends.astype(jnp.int32)]
        kinds += [("row", (bn, S))] * 2

    out_shape, out_kinds = [], []
    if spec.count:
        out_shape.append(jax.ShapeDtypeStruct(
            (n, 1), jnp.float32 if spec.signed else jnp.int32))
        out_kinds.append(("row", (bn, 1)))
    if spec.nn == "best1":
        out_shape += [jax.ShapeDtypeStruct((n, 1), jnp.float32),
                      jax.ShapeDtypeStruct((n, 1), jnp.int32)]
        out_kinds += [("row", (bn, 1))] * 2
    elif spec.nn == "topk":
        out_shape += [jax.ShapeDtypeStruct((n, spec.k), jnp.float32),
                      jax.ShapeDtypeStruct((n, spec.k), jnp.int32)]
        out_kinds += [("row", (bn, spec.k))] * 2

    # Kernels are traced with 64-bit types off: Mosaic takes int32 grid
    # indices and 32-bit operands only (the package enables x64 for the
    # grid's cell keys, which never reach a kernel).
    with jax.enable_x64(False):
        if sparse:
            outs = _sweep_worklist(spec, args, kinds, out_shape, out_kinds,
                                   wl_meta, wl_lb, interpret)
        else:
            outs = pl.pallas_call(
                _make_sweep_kernel(spec, False, len(out_shape)),
                grid=(n // bn, m // bm),
                in_specs=[_block_spec(k, b, False) for k, b in kinds],
                out_specs=[_block_spec(k, b, False) for k, b in out_kinds],
                out_shape=out_shape,
                interpret=interpret,
                name="tile_sweep_dense",
            )(*args)
    # per-row vector outputs go back to (n,); kept-k lists stay (n, k)
    n_vec = len(outs) - 2 if spec.nn == "topk" else len(outs)
    return tuple(o.reshape(n) if idx < n_vec else o
                 for idx, o in enumerate(outs))


def _block_spec(kind: str, block, sparse: bool):
    """BlockSpec for one operand kind.  Dense grids index (row tile i, col
    tile j) directly; worklist grids read (i, j) from the prefetched chunk."""
    if kind == "smem":
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    if sparse:
        tiles = lambda p, mt, lb: (mt[0, p], mt[1, p])      # noqa: E731
    else:
        tiles = lambda i, j: (i, j)                          # noqa: E731
    if kind == "row":
        return pl.BlockSpec(block, lambda *g: (tiles(*g)[0], 0))
    if kind == "col":
        return pl.BlockSpec(block, lambda *g: (tiles(*g)[1], 0))
    assert kind == "colvec", kind
    return pl.BlockSpec(block, lambda *g: (0, tiles(*g)[1]))


def _sweep_worklist(spec, args, kinds, out_shape, out_kinds, wl_meta, wl_lb,
                    interpret):
    """Block-sparse sweep: one ``pallas_call`` per worklist chunk.

    Chunks hold whole row tiles (``blocksparse`` packs them so), so each
    output block is finished inside one call; the outputs thread through
    the chunks as aliased operands and every call writes back only the row
    tiles it visits.  The scalar-prefetch table per call is bounded by the
    chunk size, whatever n is.
    """
    n_out = len(out_shape)
    n_chunks, _, C = wl_meta.shape
    call = pl.pallas_call(
        _make_sweep_kernel(spec, True, n_out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(C,),
            in_specs=[_block_spec(k, b, True) for k, b in kinds]
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_out,
            out_specs=[_block_spec(k, b, True) for k, b in out_kinds],
        ),
        out_shape=out_shape,
        input_output_aliases={2 + len(args) + o: o for o in range(n_out)},
        interpret=interpret,
        name="tile_sweep_worklist",
    )
    outs = tuple(jnp.zeros(s.shape, s.dtype) for s in out_shape)
    if n_chunks == 1:
        return call(wl_meta[0], wl_lb[0], *args, *outs)
    return jax.lax.fori_loop(
        0, n_chunks,
        lambda c, o: tuple(call(wl_meta[c], wl_lb[c], *args, *o)), outs)


# ------------------------------------------------------- fused-gather NN
def _gather_nn_kernel(slots_ref, y_ref, yk_ref, best_ref, arg_ref, qx_ref,
                      qk_ref, *, block_n: int, block_m: int, nbc: int,
                      m_valid: int, refine_k: int):
    """Masked NN whose query rows are gathered in-kernel from the table.

    Doubled column grid: steps j < nbc assemble the gathered queries
    (coords, key) into VMEM scratch via one-hot contractions (MXU-friendly,
    no dynamic gather); steps j >= nbc run the standard strictly-denser NN
    sweep against column tile (j - nbc).  Slots >= ``m_valid`` are padding
    and produce (inf, -1).
    """
    j = pl.program_id(1)
    bn, bm = block_n, block_m

    @pl.when(j == 0)
    def _init():
        qx_ref[...] = jnp.zeros_like(qx_ref)
        qk_ref[...] = jnp.zeros_like(qk_ref)
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        arg_ref[...] = jnp.full_like(arg_ref, -1)

    @pl.when(j < nbc)
    def _gather():
        col = j * bm + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
        onehot = (slots_ref[...] == col).astype(jnp.float32)
        # padded table rows carry -inf keys; finitize them so the one-hot
        # contraction never forms 0 * inf = NaN (such slots are masked
        # inert below)
        yk = jnp.maximum(yk_ref[...], jnp.float32(-3e38))
        qx_ref[...] += _pick(onehot, y_ref[...])
        qk_ref[...] += jnp.sum(onehot * yk, axis=1, keepdims=True)

    @pl.when(j >= nbc)
    def _sweep():
        x = qx_ref[...]
        xk = jnp.where(slots_ref[...] < m_valid, qk_ref[...], jnp.inf)
        d2 = tile_d2(x, y_ref[...])
        d2m = jnp.where(yk_ref[...] > xk, d2, jnp.inf)
        cand, loc = refine_topk_d2(x, y_ref[...], d2m, refine_k)
        best = best_ref[...]
        better = cand < best
        best_ref[...] = jnp.where(better, cand, best)
        arg_ref[...] = jnp.where(better, (j - nbc) * bm + loc, arg_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "m_valid",
                                             "refine_k", "interpret"))
def gather_nn(table, keys, q_slots, *, m_valid: int,
              block_n: int = 128, block_m: int = DEFAULT_BLOCK_M,
              refine_k: int = REFINE_TOPK, interpret: bool = False):
    """Strictly-denser NN for ``table[q_slots]`` rows, gather fused in-kernel.

    ``table`` (m, d) / ``keys`` (m,) padded to ``block_m`` multiples
    (PAD_COORD rows, -inf keys); ``q_slots`` (q,) int32 padded to ``block_n``
    with values >= ``m_valid`` (padding queries return (inf, -1)).  Returns
    (best_d2, parent) of shape (q,) — best_d2 is the squared distance.
    """
    q = q_slots.shape[0]
    m, d = table.shape
    assert q % block_n == 0 and m % block_m == 0
    nbc = m // block_m
    kernel = functools.partial(_gather_nn_kernel, block_n=block_n,
                               block_m=block_m, nbc=nbc, m_valid=m_valid,
                               refine_k=refine_k)
    slots = q_slots.astype(jnp.int32).reshape(q, 1)
    keys = keys.astype(jnp.float32).reshape(1, m)
    with jax.enable_x64(False):       # Mosaic: int32 grid indices only
        col = lambda j: jax.lax.rem(j, jnp.int32(nbc))       # noqa: E731
        best, arg = pl.pallas_call(
            kernel,
            grid=(q // block_n, 2 * nbc),
            in_specs=[
                pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((block_m, d), lambda i, j: (col(j), 0)),
                pl.BlockSpec((1, block_m), lambda i, j: (0, col(j))),
            ],
            out_specs=[
                pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((q, 1), jnp.float32),
                jax.ShapeDtypeStruct((q, 1), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32),
                            pltpu.VMEM((block_n, 1), jnp.float32)],
            interpret=interpret,
            name="gather_nn",
        )(slots, table, keys)
    return best.reshape(q), arg.reshape(q)
