"""Block-sparse tile-sweep parity: grid-pruned == dense, bit for bit.

The acceptance contract of the block-sparse execution mode (ISSUE 4): for
every accumulator x mask x backend x precision combination the worklist-
driven sweep must reproduce the dense sweep of the same backend exactly.
Lattice data (integer coords x power-of-two scale) makes every distance
exact in f32 *and* makes duplicate points — exact distance ties — frequent,
so the pruning bounds' conservative slack and the explicit lexicographic NN
tie-breaks are both exercised where they can actually flip answers.

Also here: the adversarial all-in-one-cell case (nothing prunes — the
worklist degenerates to the dense pair set and must still be correct), the
worklist statistics sanity checks, driver/distributed-level layout parity
on tie-free data, and the streaming dirty-tracking contract (queries
actually skipped, parity preserved).
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import DPCConfig, compute_dpc
from repro.core.dpc_types import density_jitter
from repro.core.grid import build_grid
from repro.kernels import get_backend
from repro.kernels.blocksparse import FlatWorklist, worklist_stats
from repro.kernels.ref import kept_pairs_ref

BACKENDS = ["jnp", "pallas-interpret"]
SEED_MATRIX = [(17, 2, 0, 0), (96, 3, 3, 1), (64, 4, 6, 2), (2, 2, 0, 3),
               (33, 2, 1, 4), (300, 3, 2, 7)]


def _lattice(n, d, sexp, seed):
    """Integer-lattice data (see tests/test_sweep_fused.py): distances are
    exact in every arithmetic, ties are frequent, and the grid sort gives
    the worklist real structure to prune."""
    rng = np.random.default_rng(seed)
    pts = rng.integers(0, 13, (n, d)).astype(np.float32) * (2.0 ** sexp)
    d2cut = (float(rng.integers(1, 3 * 13 ** 2)) + 0.5) * (2.0 ** (2 * sexp))
    d_cut = float(np.sqrt(d2cut))
    grid = build_grid(jnp.asarray(pts), d_cut)
    return grid.points, d_cut


def _eq(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype.kind == "f":
        return bool(np.all((a == b) | (np.isinf(a) & np.isinf(b))))
    return bool(np.all(a == b))


class TestEngineParity:
    """backend primitive x layout: block-sparse == dense, bit for bit."""

    @pytest.mark.parametrize("n,d,sexp,seed", SEED_MATRIX)
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_range_count(self, backend, n, d, sexp, seed):
        pts, d_cut = _lattice(n, d, sexp, seed)
        be = get_backend(backend)
        dense = be.range_count(pts, pts, d_cut)
        bs = be.range_count(pts, pts, d_cut, layout="block-sparse")
        assert _eq(dense, bs)

    @pytest.mark.parametrize("n,d,sexp,seed", SEED_MATRIX[:4])
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_range_count_delta_signed(self, backend, n, d, sexp, seed):
        pts, d_cut = _lattice(n, d, sexp, seed)
        rng = np.random.default_rng(seed)
        signs = jnp.asarray(rng.choice([-1.0, 0.0, 1.0], n)
                            .astype(np.float32))
        be = get_backend(backend)
        dense = be.range_count_delta(pts, pts, signs, d_cut)
        bs = be.range_count_delta(pts, pts, signs, d_cut,
                                  layout="block-sparse")
        assert _eq(dense, bs)

    @pytest.mark.parametrize("n,d,sexp,seed", SEED_MATRIX)
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_denser_nn(self, backend, n, d, sexp, seed):
        """best-1 + strictly-denser key mask, runtime ring pruning; the
        lattice duplicates force the lexicographic (d2, col) tie-break."""
        pts, d_cut = _lattice(n, d, sexp, seed)
        rng = np.random.default_rng(seed + 1)
        rk = jnp.asarray(rng.permutation(n).astype(np.float32))
        be = get_backend(backend)
        dd, dp = be.denser_nn(pts, rk, pts, rk)
        sd, sp = be.denser_nn(pts, rk, pts, rk, layout="block-sparse")
        assert _eq(dp, sp)
        assert _eq(dd, sd)

    @pytest.mark.parametrize("n,d,sexp,seed", SEED_MATRIX)
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_rho_delta_fused(self, backend, n, d, sexp, seed):
        pts, d_cut = _lattice(n, d, sexp, seed)
        be = get_backend(backend)
        jit_ = density_jitter(n)
        dense = be.rho_delta(pts, pts, d_cut, jitter=jit_)
        bs = be.rho_delta(pts, pts, d_cut, jitter=jit_,
                          layout="block-sparse")
        for a, b, name in zip(dense, bs, ("rho", "rho_key", "delta",
                                          "parent")):
            assert _eq(a, b), f"fused {name} differs under block-sparse"

    @pytest.mark.parametrize("primitive", ["count", "fused"])
    def test_multi_chunk_worklist(self, primitive, monkeypatch):
        """Worklists longer than one chunk run as several pallas_calls
        threading their outputs through; each chunk holds whole row tiles,
        so the result equals the dense sweep bit for bit."""
        from repro.kernels import blocksparse, ops

        monkeypatch.setattr(blocksparse, "WL_CHUNK", 4)
        pts, d_cut = _lattice(1500, 2, 0, 5)
        be = get_backend("pallas-interpret")
        wl = blocksparse.fused_worklist(
            pts, pts, d_cut, block_n=ops.DENSITY_BLOCK_N,
            block_m=ops.DENSITY_BLOCK_M,
            k=None if primitive == "count" else ops.FUSED_TOPK)
        assert int(wl.live_chunks) > 1, "expected several chunks"
        if primitive == "count":
            pairs = [(be.range_count(pts, pts, d_cut),
                      be.range_count(pts, pts, d_cut,
                                     layout="block-sparse"))]
        else:
            jit_ = density_jitter(len(pts))
            pairs = zip(be.rho_delta(pts, pts, d_cut, jitter=jit_),
                        be.rho_delta(pts, pts, d_cut, jitter=jit_,
                                     layout="block-sparse"))
        for dense, bs in pairs:
            assert _eq(dense, bs)

    @pytest.mark.parametrize("n,d,sexp,seed", SEED_MATRIX[:3])
    def test_rho_delta_fused_bf16(self, n, d, sexp, seed):
        """precision axis: the bf16 inner-product path prunes identically
        (bounds compare against f32 values; winners are f32-refined)."""
        pts, d_cut = _lattice(n, d, sexp, seed)
        be = get_backend("pallas-interpret")
        jit_ = density_jitter(n)
        dense = be.rho_delta(pts, pts, d_cut, jitter=jit_, precision="bf16")
        bs = be.rho_delta(pts, pts, d_cut, jitter=jit_, precision="bf16",
                          layout="block-sparse")
        for a, b in zip(dense, bs):
            assert _eq(a, b)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_rho_delta_rep_subset(self, backend):
        """nn_sel mask (S-Approx representatives): the static kept-k ring
        must count only admissible columns, or it would over-prune."""
        rng = np.random.default_rng(7)
        n, m = 60, 200
        y_np = rng.integers(0, 13, (m, 3)).astype(np.float32) * 8
        d_cut = float(np.sqrt(100.5)) * 8
        y = build_grid(jnp.asarray(y_np), d_cut).points
        slots = jnp.asarray(np.sort(rng.choice(m, n, replace=False)))
        x = y[slots]
        be = get_backend(backend)
        jit_ = density_jitter(n)
        dense = be.rho_delta(x, y, d_cut, jitter=jit_, y_sel_slots=slots)
        bs = be.rho_delta(x, y, d_cut, jitter=jit_, y_sel_slots=slots,
                          layout="block-sparse")
        for a, b in zip(dense, bs):
            assert _eq(a, b)


class TestHaloParity:
    """span-masked primitives: the pallas kernels' dense span-masked tiles
    (any layout) against the jnp gather-form reference, bit for bit on
    lattice data (exact distances, frequent ties)."""

    @staticmethod
    def _case(seed, W=256, m=300, S=3, d=3):
        rng = np.random.default_rng(seed)
        window = jnp.asarray(rng.integers(0, 50, (W, d))
                             .astype(np.float32) * 64)
        x = jnp.asarray(rng.integers(0, 50, (m, d)).astype(np.float32) * 64)
        xk = jnp.asarray(rng.uniform(0, W, m).astype(np.float32))
        wk = jnp.asarray(rng.permutation(W).astype(np.float32))
        cuts = np.sort(rng.integers(0, W, (m, 2 * S)), axis=1)
        st = cuts[:, 0::2].astype(np.int32)
        en = cuts[:, 1::2].astype(np.int32)
        st[:3] = en[:3] = 0
        st[3] = en[3] = -9
        return (window, x, xk, wk, jnp.asarray(st), jnp.asarray(en),
                int(max((en - st).max(), 1)))

    @pytest.mark.parametrize("seed", [0, 1])
    def test_halo_count(self, seed):
        window, x, _, _, st, en, cap = self._case(seed)
        d_cut = 900.0
        ref = get_backend("jnp").range_count_halo(x, window, st, en, d_cut,
                                                  span_cap=cap)
        bs = get_backend("pallas-interpret").range_count_halo(
            x, window, st, en, d_cut, span_cap=cap, layout="block-sparse")
        assert _eq(ref, bs)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_halo_nn(self, seed):
        window, x, xk, wk, st, en, cap = self._case(seed + 10)
        d_cut = 900.0
        ref = get_backend("jnp").denser_nn_halo(x, xk, window, wk, st, en,
                                                d_cut, span_cap=cap)
        bs = get_backend("pallas-interpret").denser_nn_halo(
            x, xk, window, wk, st, en, d_cut, span_cap=cap,
            layout="block-sparse")
        for a, b in zip(ref, bs):
            assert _eq(a, b)


def _clusters(n, d, seed, k=12, spread=20.0):
    """Gaussian blobs, grid-sorted: many cell maxima, so the fused sweep
    leaves a real unresolved tail for its fallback."""
    rng = np.random.default_rng(seed)
    c = rng.uniform(0, 1000, (k, d))
    pts = c[rng.integers(0, k, n)] + rng.normal(0, spread, (n, d))
    d_cut = 8.0
    return build_grid(jnp.asarray(pts.astype(np.float32)), d_cut).points, \
        d_cut


class TestFallbackRing:
    """The fused pallas ``rho_delta``'s unresolved-row fallback: under the
    block-sparse layout a device-built ring worklist, under the dense
    layout the dense sweep, with the same answers bit for bit."""

    @pytest.mark.parametrize("n_rows, m, bn, bm, chunk", [
        (700, 3000, 128, 256, 8192),     # one chunk
        (700, 3000, 8, 16, 8192),        # several chunks, C = 8192
        (300, 1000, 16, 64, 4),          # C = the column-tile count
    ])
    def test_device_ring_equals_host_best1(self, n_rows, m, bn, bm, chunk,
                                           monkeypatch):
        from repro.kernels import blocksparse

        monkeypatch.setattr(blocksparse, "WL_CHUNK", chunk)
        rng = np.random.default_rng(n_rows + m)
        y = np.asarray(_clusters(m, 3, 0)[0])
        x = y[np.sort(rng.choice(m, n_rows, replace=False))]
        dev = blocksparse.ring_worklist(jnp.asarray(x), jnp.asarray(y),
                                        block_n=bn, block_m=bm)
        hm, hl, hkept, htotal = kept_pairs_ref(
            x, y, None, block_n=bn, block_m=bm, count=False, nn="best1")
        assert dev.meta.shape == hm.shape
        assert (dev.n_kept, dev.n_total) == (hkept, htotal)
        dm, dl = np.asarray(dev.meta), np.asarray(dev.lb)
        # chunk padding in the same places, flagged -1 with infinite bounds
        assert np.array_equal(dm[:, 3], hm[:, 3])
        real = dm[:, 3] >= 0
        assert np.all(np.isinf(dl[~real])) and np.all(dm[:, 2][~real] == 0)
        # padding repeats its chunk's last real pair
        for c in range(dm.shape[0]):
            last = np.flatnonzero(real[c])[-1]
            assert np.all(dm[c, :2, last + 1:] == dm[c, :2, last:last + 1])
        rows, cols, lbs = dm[:, 0][real], dm[:, 1][real], dl[real]
        hrows, hcols = hm[:, 0][real], hm[:, 1][real]
        assert np.array_equal(rows, hrows)
        for i in np.unique(rows):
            mine = rows == i
            assert sorted(cols[mine]) == sorted(hcols[hrows == i])
            assert np.all(np.diff(lbs[mine]) >= 0), "not in ring order"
            assert np.array_equal(np.flatnonzero(dm[:, 2][real] & mine),
                                  np.flatnonzero(mine)[:1])
        np.testing.assert_allclose(lbs, hl[real], rtol=1e-6)

    @staticmethod
    def _record_fallback(monkeypatch):
        """Wrap ``ops.dependent_masked``: each call's worklist and answers."""
        from repro.kernels import ops

        calls = []
        inner = ops.dependent_masked

        def spy(*a, **kw):
            out = inner(*a, **kw)
            calls.append((kw.get("worklist"), out))
            return out
        monkeypatch.setattr(ops, "dependent_masked", spy)
        return calls

    @staticmethod
    def _fallback_span():
        from repro import obs

        (fb,) = [s for s in obs.spans() if s["name"] == "rho_delta.fallback"]
        return fb["attrs"]

    @pytest.mark.parametrize("case", ["interest", "rep_subset", "lattice"])
    def test_ring_fallback_parity_and_live_count(self, case, monkeypatch):
        from repro import obs

        rng = np.random.default_rng(11)
        kw = {}
        if case == "lattice":
            x, d_cut = _lattice(600, 3, 1, 21)
            y = x
        else:
            y, d_cut = _clusters(1500, 3, 4)
            x = y
        if case == "interest":
            keep = jnp.asarray(rng.random(len(x)) < 0.6)
            kw["fallback_interest"] = lambda rk: keep
        elif case == "rep_subset":
            slots = jnp.asarray(np.sort(rng.choice(len(y), 800,
                                                   replace=False)))
            x, kw["y_sel_slots"] = y[slots], slots
        be = get_backend("pallas-interpret")
        jit_ = density_jitter(len(x))
        calls = self._record_fallback(monkeypatch)
        obs.configure(level="metrics")
        try:
            dense = be.rho_delta(x, y, d_cut, jitter=jit_, block=8, **kw)
            obs.reset_spans()
            bs = be.rho_delta(x, y, d_cut, jitter=jit_, block=8,
                              layout="block-sparse", **kw)
            f = self._fallback_span()
        finally:
            obs.configure(level="off")
            obs.reset_spans()
        for a, b, name in zip(dense[2:], bs[2:], ("delta", "parent")):
            assert _eq(a, b), f"fallback {name} differs under block-sparse"
        rows = f["rows"]
        assert rows & (rows - 1), f"{rows} unresolved rows: a power of two"
        # the dense call took no worklist; the block-sparse one the ring
        (wl_dense, _), (wl, (fd, _)) = calls
        assert wl_dense is None
        nbr, nbc = f["padded"] // 8, -(-len(y) // 256)
        assert f["pairs"] == wl.n_kept == nbr * nbc
        assert 0 < f["live"] <= f["pairs"]
        if case != "lattice":           # compact tiles: the ring prunes
            assert f["live"] < f["pairs"]
        # recount on the host from the answers: pairs within their row
        # tile's final radius (max over its rows of the squared answer)
        meta, lb = np.asarray(wl.meta), np.asarray(wl.lb)
        d2 = np.square(np.asarray(fd, np.float32))
        radius = d2.reshape(nbr, 8).max(axis=1)
        real = meta[:, 3] >= 0
        assert f["live"] == int(np.sum(
            real & (lb <= radius[meta[:, 0]])))

    def test_dense_layout_fallback_is_the_dense_call(self, monkeypatch):
        """The dense layout sweeps every column with no worklist and records
        no ring counters; the block-sparse fallback builds nothing on the
        host, so the worklist counters keep counting the fused sweep's."""
        from repro import obs
        from repro.kernels import blocksparse

        pts, d_cut = _clusters(600, 3, 4)
        be = get_backend("pallas-interpret")
        calls = self._record_fallback(monkeypatch)
        obs.configure(level="metrics")
        try:
            be.rho_delta(pts, pts, d_cut)
            f = self._fallback_span()
            obs.reset_spans()
            builds = blocksparse.worklist_build_count()
            be.rho_delta(pts, pts, d_cut, layout="block-sparse")
            (build,) = [s for s in obs.spans()
                        if s["name"] == "worklist.build"]
        finally:
            obs.configure(level="off")
            obs.reset_spans()
        assert f["rows"] > 0 and "pairs" not in f and "live" not in f
        assert [wl is None for wl, _ in calls] == [True, False]
        assert blocksparse.worklist_build_count() == builds + 1
        assert obs.get_metric("worklist_len").value() \
            == build["attrs"]["kept"]


class TestDeviceCountList:
    """The d_cut count list a distributed shard builds on the device
    (``cut_prefix`` then ``cut_worklist``): the reference count list's
    pairs in its chunk format, swept with only its live chunks run."""

    @staticmethod
    def _shard(m, bn, bm, seed, lattice=False):
        """A contiguous block of the sorted table (a shard's rows), the
        table, and d_cut."""
        if lattice:
            y, d_cut = _lattice(m, 3, 1, seed)
        else:
            y, d_cut = _clusters(m, 3, seed)
        y = np.asarray(y)
        lo = m // 3
        return y[lo:lo + m // 4], y, d_cut

    @staticmethod
    def _device(x, y, d_cut, bn, bm, chunk, nx=None, spare=1):
        from repro.kernels import blocksparse

        cp = blocksparse.cut_prefix(
            jnp.asarray(x), len(x) if nx is None else nx, jnp.asarray(y),
            len(y), d_cut, bn=bn, bm=bm, chunk=chunk)
        n_chunks = blocksparse._pow2(int(cp.chunks)) * spare
        return cp, blocksparse.cut_worklist(cp, d_cut, n_chunks=n_chunks,
                                            chunk=chunk)

    @pytest.mark.parametrize("m, bn, bm, chunk, lattice", [
        (3000, 128, 256, 8192, False),   # one chunk
        (3000, 8, 16, 64, False),        # many chunks of whole row tiles
        (1000, 16, 64, 4, False),        # C = the column-tile count
        (1200, 8, 16, 64, True),         # ties on the lattice
    ])
    def test_pairs_equal_host_count_builder(self, m, bn, bm, chunk, lattice):
        x, y, d_cut = self._shard(m, bn, bm, 5, lattice)
        cp, wl = self._device(x, y, d_cut, bn, bm, chunk, spare=2)
        hm, hl, hkept, _ = kept_pairs_ref(x, y, d_cut, block_n=bn,
                                          block_m=bm)
        dm, dl = np.asarray(wl.meta), np.asarray(wl.lb)
        live = int(wl.live_chunks)
        assert live == int(cp.chunks) and dm.shape[0] == 2 * (
            1 << max(live - 1, 0).bit_length())
        real = dm[:, 3] >= 0
        hreal = hm[:, 3] >= 0
        assert not real[live:].any(), "entries past the live chunks"
        assert int(real.sum()) == int(hreal.sum()) == hkept \
            == int(np.sum(np.asarray(cp.kept)))
        rows, cols = dm[:, 0][real], dm[:, 1][real]
        # the same (row, col, in-cut) set, row tiles in order, each row
        # tile's entries in ascending bound, its first flagged first
        assert sorted(zip(rows, cols, dm[:, 3][real])) == sorted(
            zip(hm[:, 0][hreal], hm[:, 1][hreal], hm[:, 3][hreal]))
        assert np.all(np.diff(rows) >= 0)
        for i in np.unique(rows):
            mine = rows == i
            assert np.all(np.diff(dl[real][mine]) >= 0)
            firsts = np.flatnonzero(dm[:, 2][real] == 1)
            assert np.flatnonzero(mine)[0] in firsts
        assert int(np.sum(dm[:, 2][real])) == len(np.unique(rows))
        np.testing.assert_allclose(np.sort(dl[real]), np.sort(hl[hreal]),
                                   rtol=1e-6)
        # whole row tiles per chunk; padding repeats the chunk's last pair
        chunk_of = np.repeat(np.arange(dm.shape[0]), dm.shape[2]).reshape(
            dm.shape[0], -1)[real]
        for i in np.unique(rows):
            assert len(np.unique(chunk_of[rows == i])) == 1
        for c in range(live):
            last = np.flatnonzero(real[c])[-1]
            assert np.all(dm[c, :2, last + 1:] == dm[c, :2, last:last + 1])
            assert np.all(np.isinf(dl[c, last + 1:]))

    def test_masked_rows_bound_nothing(self):
        """Rows past ``nx`` (a shard's padding) are not points: their tile
        keeps one forced, out-of-cut pair and no other."""
        x, y, d_cut = self._shard(2000, 16, 64, 2)
        x = np.concatenate([x[:100], np.full((28, 3), 1e9, np.float32)])
        _, wl = self._device(x, y, d_cut, 16, 64, 64, nx=100)
        dm = np.asarray(wl.meta)
        real = dm[:, 3] >= 0
        rows, flags = dm[:, 0][real], dm[:, 3][real]
        hm = kept_pairs_ref(x[:100], y, d_cut, block_n=16, block_m=64)[0]
        hreal = hm[:, 3] >= 0
        # tiles 0-6 hold the 100 points (tile 6 four of them): as the host
        # builds them from the points alone; tile 7 is all padding
        assert sorted(zip(rows[rows < 7], dm[:, 1][real][rows < 7])) == \
            sorted(zip(hm[:, 0][hreal], hm[:, 1][hreal]))
        assert np.sum(rows == 7) == 1 and flags[rows == 7][0] == 0

    @pytest.mark.parametrize("precision", ["f32", "bf16"])
    def test_sweep_equals_host_list_sweep(self, precision):
        """The fused sweep over the device list, spare chunks left unrun,
        equals the sweep over the reference list: counts and kept-k."""
        from repro.kernels import ops

        x, y, d_cut = self._shard(3000, 16, 64, 9)
        hm, hl, hkept, htotal = kept_pairs_ref(x, y, d_cut, block_n=16,
                                               block_m=64)
        host = FlatWorklist(meta=jnp.asarray(hm), lb=jnp.asarray(hl),
                            n_kept=hkept, n_total=htotal)
        x, y = jnp.asarray(x), jnp.asarray(y)
        _, wl = self._device(x, y, d_cut, 16, 64, 64, spare=2)
        kw = dict(block_n=16, block_m=64, precision=precision,
                  interpret=True)
        for a, b in zip(ops.fused_sweep(x, y, d_cut, worklist=wl, **kw),
                        ops.fused_sweep(x, y, d_cut, worklist=host, **kw)):
            assert _eq(a, b)
        assert _eq(ops.fused_sweep(x, y, d_cut, worklist=wl, **kw)[0],
                   get_backend("jnp").range_count(x, y, d_cut))

    def test_ring_live_rows_run_only_their_chunks(self, monkeypatch):
        """``ring_worklist(live_rows=r)``: the chunks that hold the first r
        rows run, their answers equal the whole ring's, and the live count
        covers only them."""
        from repro.kernels import blocksparse, ops

        monkeypatch.setattr(blocksparse, "WL_CHUNK", 16)   # 2 tiles a chunk

        y, _ = _clusters(2000, 3, 3)
        y = np.asarray(y)
        rng = np.random.default_rng(4)
        x = jnp.asarray(y[np.sort(rng.choice(len(y), 96, replace=False))])
        y = jnp.asarray(y)
        xk = jnp.asarray(rng.random(96).astype(np.float32))
        yk = jnp.asarray(rng.random(len(y)).astype(np.float32))
        full = blocksparse.ring_worklist(x, y, block_n=8, block_m=256)
        part = blocksparse.ring_worklist(x, y, block_n=8, block_m=256,
                                         live_rows=jnp.int32(21))
        nbc = -(-len(y) // 256)
        per = blocksparse.ring_rows_per_chunk(12, nbc)
        assert per == 2 and int(part.live_chunks) == 2
        kw = dict(block_n=8, block_m=256, interpret=True)
        fd, fp = ops.dependent_masked(x, xk, y, yk, worklist=full, **kw)
        pd, pp = ops.dependent_masked(x, xk, y, yk, worklist=part, **kw)
        rows = int(part.live_chunks) * per * 8
        assert _eq(fd[:rows], pd[:rows]) and _eq(fp[:rows], pp[:rows])
        live = blocksparse.ring_live_pairs(part.meta, part.lb, fd,
                                           part.live_chunks, bn=8)
        meta, lb = np.asarray(full.meta), np.asarray(full.lb)
        radius = np.square(np.asarray(fd)).reshape(12, 8).max(axis=1)
        hit = (meta[:, 3] >= 0) & (lb <= radius[meta[:, 0]])
        assert int(live) == int(hit[:int(part.live_chunks)].sum())
        assert int(live) < int(blocksparse.ring_live_pairs(
            full.meta, full.lb, fd, bn=8))



class TestDeviceFusedList:
    """The fused pallas ``rho_delta``'s count + kept-k list, built on the
    device (``fused_worklist``: ``cut_prefix`` with the k-NN radius), equals
    the numpy reference's (``kept_pairs_ref(nn="topk", k=8)``) entry for
    entry up to the live chunks; the chunks past them are padding."""

    BN, BM, CHUNK = 16, 64, 64

    @staticmethod
    def _far_rows(y, lo):
        """``y``'s first ``lo`` rows, then a last row tile (13 points and
        pad rows) far from every column: none of its pairs lies within
        d_cut."""
        far = y[:13] + np.float32(5000.0)
        return np.concatenate([y[:lo], far])

    def _device(self, case, x, y, d_cut, sel):
        """(meta, lb, live chunks, kept) of the device list for ``case``."""
        from repro.kernels import blocksparse

        if case in ("forced", "no_threshold"):
            cp = blocksparse.cut_prefix(
                jnp.asarray(x), len(x), jnp.asarray(y), len(y), d_cut,
                bn=self.BN, bm=self.BM, chunk=self.CHUNK,
                r2=(jnp.zeros((-(-len(x) // self.BN),), jnp.float32)
                    if case == "forced" else None))
            live = int(cp.chunks)
            meta, lb = blocksparse._cut_list(
                cp, d_cut, n_chunks=blocksparse._pow2(live), chunk=self.CHUNK)
            return np.asarray(meta), np.asarray(lb), live, int(
                np.sum(np.asarray(cp.kept)))
        wl = blocksparse.fused_worklist(
            jnp.asarray(x), jnp.asarray(y), d_cut, block_n=self.BN,
            block_m=self.BM, k=8,
            sel_slots=None if sel is None else jnp.asarray(sel))
        assert wl.n_total == -(-len(x) // self.BN) * -(-len(y) // self.BM)
        return np.asarray(wl.meta), np.asarray(wl.lb), int(
            wl.live_chunks), wl.n_kept

    @pytest.mark.parametrize("case", ["uneven", "far_row_tile", "forced",
                                      "sel_gate", "non_pow2",
                                      "no_threshold"])
    def test_list_equals_host_builder(self, case, monkeypatch):
        from repro.kernels import blocksparse

        monkeypatch.setattr(blocksparse, "WL_CHUNK", self.CHUNK)
        y, d_cut = _clusters(3000 if case == "non_pow2" else 1003, 3, 6)
        y = np.asarray(y)
        x, sel, counts, nn = y, None, None, "topk"
        if case in ("far_row_tile", "forced"):
            x = self._far_rows(y, 208)
        if case in ("forced", "no_threshold"):
            # the count-only list (the mesh's), long enough for whole chunks
            nn, d_cut = None, 5 * d_cut
        if case == "no_threshold":
            x = y[300:550]              # a shard's rows against the table
        if case == "sel_gate":
            rng = np.random.default_rng(7)
            sel = np.sort(rng.choice(len(y), 60, replace=False))
            counts = np.bincount(sel // self.BM,
                                 minlength=-(-len(y) // self.BM))
        dm, dl, live, kept = self._device(case, x, y, d_cut, sel)
        hm, hl, hkept, _ = kept_pairs_ref(
            x, y, d_cut, block_n=self.BN, block_m=self.BM, nn=nn, k=8,
            nn_col_counts=counts)
        assert len(x) % self.BN and len(y) % self.BM, "pad rows expected"
        assert kept == hkept
        assert dm.shape == (blocksparse._pow2(live),) + hm.shape[1:]
        assert hm.shape[0] == live
        np.testing.assert_array_equal(dm[:live], hm)
        # bounds in the same places; equal up to the f32 summation order
        # of the host and device bound arithmetic
        assert np.array_equal(np.isinf(dl[:live]), np.isinf(hl))
        fin = np.isfinite(hl)
        np.testing.assert_allclose(dl[:live][fin], hl[fin], rtol=1e-6)
        assert np.all(dm[live:, 3] == -1) and np.all(np.isinf(dl[live:]))
        real = hm[:, 3] >= 0
        rows, flags = hm[:, 0][real], hm[:, 3][real]
        if case == "far_row_tile":
            # nothing within d_cut: the k-NN ring alone keeps its pairs
            far = rows == 13
            assert far.sum() > 1 and not flags[far].any()
        if case == "forced":
            # past both thresholds: the force-kept nearest pair alone
            assert np.sum(rows == 13) == 1 and flags[rows == 13][0] == 0
        if case == "sel_gate":
            # the gate widens the ring: fewer candidates per column tile
            assert kept > self._device("uneven", x, y, d_cut, None)[3]
        if case == "non_pow2":
            assert live & (live - 1), f"{live} chunks: a power of two"

    @pytest.mark.parametrize("gate", [False, True])
    def test_rho_delta_equals_dense_layout(self, gate):
        """rho, delta and parent of the block-sparse fused ``rho_delta``
        (device-built list, then the ring fallback) equal the dense
        layout's bit for bit."""
        y, d_cut = _clusters(1500, 3, 8)
        x, kw = y, {}
        if gate:
            rng = np.random.default_rng(5)
            slots = jnp.asarray(np.sort(rng.choice(len(y), 700,
                                                   replace=False)))
            x, kw["y_sel_slots"] = y[slots], slots
        be = get_backend("pallas-interpret")
        jit_ = density_jitter(len(x))
        dense = be.rho_delta(x, y, d_cut, jitter=jit_, **kw)
        bs = be.rho_delta(x, y, d_cut, jitter=jit_, layout="block-sparse",
                          **kw)
        for a, b, name in zip(dense, bs, ("rho", "rho_key", "delta",
                                          "parent")):
            assert _eq(a, b), f"{name} differs under block-sparse"

class TestDegenerateWorklists:
    """All points in one grouping cell: nothing prunes, the worklist is the
    dense pair set, and the engine must behave exactly as worklist=None."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_single_cell_parity(self, backend):
        rng = np.random.default_rng(2)
        n = 400
        # spread << cell side: one grouping cell, every tile pair kept
        pts_np = rng.integers(0, 4, (n, 3)).astype(np.float32)
        d_cut = float(np.sqrt(3 * 16 + 0.5)) * 4
        grid = build_grid(jnp.asarray(pts_np), d_cut)
        assert grid.num_cells == 1
        pts = grid.points
        be = get_backend(backend)
        jit_ = density_jitter(n)
        dense = be.rho_delta(pts, pts, d_cut, jitter=jit_)
        bs = be.rho_delta(pts, pts, d_cut, jitter=jit_,
                          layout="block-sparse")
        for a, b in zip(dense, bs):
            assert _eq(a, b)

    def test_single_cell_worklist_is_dense(self):
        from repro.kernels import blocksparse

        rng = np.random.default_rng(2)
        pts = rng.integers(0, 4, (400, 3)).astype(np.float32)
        wl = blocksparse.fused_worklist(pts, pts, 1e6, block_n=128,
                                        block_m=128)
        assert wl.n_kept == wl.n_total
        assert worklist_stats(pts, pts, 1e6, block_n=128,
                              block_m=128)["pruned_tile_frac"] == 0.0
        assert wl.pruned_frac == 0.0

    def test_separated_clusters_prune(self):
        """Far-apart clusters with a small d_cut: the count worklist must
        actually drop cross-cluster tile pairs."""
        rng = np.random.default_rng(3)
        a = rng.normal(0, 1, (512, 3)).astype(np.float32)
        b = rng.normal(0, 1, (512, 3)).astype(np.float32) + 1000.0
        pts = np.concatenate([a, b])
        stats = worklist_stats(pts, pts, 5.0, block_n=128, block_m=128)
        assert stats["pruned_tile_frac"] >= 0.4
        # and the pruned sweep still counts correctly
        be = get_backend("jnp")
        dense = be.range_count(jnp.asarray(pts), jnp.asarray(pts), 5.0)
        bs = be.range_count(jnp.asarray(pts), jnp.asarray(pts), 5.0,
                            layout="block-sparse")
        assert _eq(dense, bs)

    def test_worklist_always_initializes_rows(self):
        """Row tiles with nothing in range still appear once (their output
        blocks must initialize): counts are exact zeros, not garbage."""
        pts = np.zeros((300, 2), np.float32)
        pts[200:] = 1e6                 # far tile: nothing within d_cut
        be = get_backend("pallas-interpret")
        dense = be.range_count(jnp.asarray(pts), jnp.asarray(pts[:200]), 1.0)
        bs = be.range_count(jnp.asarray(pts), jnp.asarray(pts[:200]), 1.0,
                            layout="block-sparse")
        assert _eq(dense, bs)


class TestTraceability:
    def test_jnp_worklists_are_jit_safe(self):
        import jax
        be = get_backend("jnp")
        assert be.worklist_traceable
        pts = jnp.asarray(np.random.default_rng(0)
                          .uniform(0, 100, (200, 3)).astype(np.float32))
        f = jax.jit(lambda p: be.range_count(p, p, 10.0,
                                             layout="block-sparse"))
        assert _eq(f(pts), be.range_count(pts, pts, 10.0))

    @pytest.mark.parametrize("primitive", ["range_count",
                                           "range_count_delta"])
    def test_pallas_worklists_require_host(self, primitive):
        """A count list is sized on the host: its routes refuse a trace."""
        import jax
        be = get_backend("pallas-interpret")
        assert not be.worklist_traceable
        pts = jnp.zeros((64, 2), jnp.float32)
        if primitive == "range_count":
            def f(p):
                return be.range_count(p, p, 1.0, layout="block-sparse")
        else:
            def f(p):
                return be.range_count_delta(p, p, jnp.ones(64), 1.0,
                                            layout="block-sparse")
        with pytest.raises(ValueError, match="host"):
            jax.jit(f)(pts)

    def test_pallas_best1_denser_nn_is_jit_safe(self):
        """The best-1 ring depends only on shapes: the block-sparse pallas
        ``denser_nn`` runs under ``jax.jit`` and equals the eager call."""
        import jax
        be = get_backend("pallas-interpret")
        pts, _ = _clusters(700, 3, 2)
        rk = jnp.asarray(np.random.default_rng(2).permutation(700)
                         .astype(np.float32))
        eager = be.denser_nn(pts, rk, pts, rk, layout="block-sparse")
        jitted = jax.jit(lambda p, k: be.denser_nn(
            p, k, p, k, layout="block-sparse"))(pts, rk)
        for a, b in zip(eager, jitted):
            assert _eq(a, b)

    def test_unknown_layout_rejected(self):
        pts = jnp.zeros((8, 2), jnp.float32)
        with pytest.raises(ValueError, match="layout"):
            get_backend("jnp").range_count(pts, pts, 1.0, layout="sparse")


class TestDriverParity:
    """Driver-level layout parity on tie-free data (random floats: parents
    are unique, so original-order vs sorted-order tie-breaks coincide)."""

    @pytest.mark.parametrize("algo", ["scan", "exdpc", "approxdpc",
                                      "sapproxdpc"])
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_algorithms(self, algo, backend):
        rng = np.random.default_rng(3)
        pts = rng.uniform(0, 50 * 900.0, (500, 3)).astype(np.float32)
        base = DPCConfig(d_cut=4000.0, algorithm=algo, backend=backend)
        a = compute_dpc(pts, base)
        b = compute_dpc(pts, DPCConfig(d_cut=4000.0, algorithm=algo,
                                       backend=backend,
                                       layout="block-sparse"))
        assert _eq(a.rho, b.rho)
        assert _eq(a.parent, b.parent)
        assert _eq(a.delta, b.delta)

    def test_distributed(self):
        from jax.sharding import Mesh
        import jax
        from repro.distributed.dpc import DistDPCConfig, distributed_dpc
        rng = np.random.default_rng(5)
        pts = rng.uniform(0, 20000.0, (400, 3)).astype(np.float32)
        mesh = Mesh(np.array(jax.devices()), ("data",))
        a = distributed_dpc(pts, DistDPCConfig(d_cut=2500.0, backend="jnp"),
                            mesh)
        b = distributed_dpc(pts, DistDPCConfig(d_cut=2500.0, backend="jnp",
                                               layout="block-sparse"), mesh)
        assert _eq(a.rho, b.rho)
        assert _eq(a.parent, b.parent)
        assert _eq(a.delta, b.delta)


class TestStreamDirtyTracking:
    """Per-cell dirty tracking: clean-cell maxima reuse cached NN answers —
    parity must hold AND queries must actually be skipped."""

    @staticmethod
    def _drive(dirty_tracking, rng):
        from repro.stream import StreamDPC, StreamDPCConfig
        cfg = StreamDPCConfig(d_cut=2.0, capacity=512, batch_cap=16,
                              dirty_tracking=dirty_tracking)
        s = StreamDPC(cfg)
        centers = rng.uniform(0, 120, (12, 2))
        pts = (centers[rng.integers(0, 12, 512)]
               + rng.normal(0, 0.5, (512, 2))).astype(np.float32)
        s.initialize(pts)
        for t in range(8):
            c = centers[t % 12]
            batch = (c + rng.normal(0, 0.5, (16, 2))).astype(np.float32)
            s.ingest(batch)
        return s

    def test_parity_and_savings(self):
        rng = np.random.default_rng(0)
        s = self._drive(True, rng)
        # parity vs a from-scratch solve of the final window
        from repro.core.approxdpc import run_approxdpc
        ref = run_approxdpc(jnp.asarray(s.window_points()), s.cfg.d_cut,
                            exec_spec=s.plan.spec)
        assert _eq(s.result.rho, ref.rho)
        assert _eq(s.result.parent, ref.parent)
        assert _eq(s.result.delta, ref.delta)
        st = s.stats()
        assert st["nn_queries"] < st["nn_maxima_total"], \
            "dirty tracking never skipped a maxima query"

    def test_matches_undirtied_stream(self):
        """Tick-for-tick label equality with tracking off."""
        a = self._drive(True, np.random.default_rng(1))
        b = self._drive(False, np.random.default_rng(1))
        assert np.array_equal(a._last.labels, b._last.labels)
        assert _eq(a.result.delta, b.result.delta)
        assert _eq(a.result.parent, b.result.parent)
