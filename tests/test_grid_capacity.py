"""Grid capacity handling: build-time measurement under skew, canonical
partitions, and the overflow -> rebuild path the streaming subsystem
relies on."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.grid import (build_grid, canonical_group_coords,
                             cell_span_bounds, point_span_bounds)
from repro.data.points import gaussian_mixture, real_proxy
from repro.stream.incremental import CellOverflow, IncrementalGrid

D_CUT = 5000.0


class TestBuildTimeCapacities:
    """Measured capacities are exact data statistics, even under skew."""

    @pytest.mark.parametrize("name", ["airline", "household"])
    def test_cell_cap_is_max_occupancy(self, name):
        pts, _ = real_proxy(name, 1500, seed=0)     # pareto-skewed densities
        grid = build_grid(jnp.asarray(pts), D_CUT)
        counts = np.asarray(grid.cell_count)[: grid.num_cells]
        assert counts.sum() == len(pts)
        assert counts.max() == grid.cell_cap        # measured, not padded
        assert counts.min() >= 1                    # only occupied cells

    def test_span_cap_bounds_every_span(self):
        pts, _ = real_proxy("pamap2", 1200, seed=1)
        grid = build_grid(jnp.asarray(pts), D_CUT)
        starts, ends = point_span_bounds(grid)
        widths = np.asarray(ends - starts)
        assert widths.max() == grid.span_cap        # tight measurement
        cs, ce = cell_span_bounds(grid)
        assert int(jnp.max(ce - cs)) <= grid.span_cap

    def test_stencil_covers_dcut_ball(self):
        """Every point within d_cut of p lies inside p's candidate spans —
        the invariant that makes stencil rho/delta exact."""
        pts, _ = gaussian_mixture(600, k=4, d=3, overlap=0.06, seed=2)
        grid = build_grid(jnp.asarray(pts), D_CUT)
        sorted_pts = np.asarray(grid.points)
        starts, ends = map(np.asarray, point_span_bounds(grid))
        d2 = ((sorted_pts[:, None, :].astype(np.float64)
               - sorted_pts[None]) ** 2).sum(-1)
        for i in range(0, len(pts), 37):
            nbrs = set(np.nonzero(d2[i] < D_CUT ** 2)[0])
            covered = set()
            for s, e in zip(starts[i], ends[i]):
                covered.update(range(s, e))
            assert nbrs <= covered


def test_real_proxy_same_in_every_process():
    """The dataset a seed names must not depend on the process: two
    interpreters with different string-hash salts draw equal arrays."""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    code = ("import sys, hashlib; from repro.data.points import real_proxy; "
            "x, y = real_proxy('household', 512, seed=3); "
            "sys.stdout.write(hashlib.sha256(x.tobytes() + y.tobytes())"
            ".hexdigest())")
    digests = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, PYTHONPATH=src,
                   JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        digests.append(out.stdout.strip())
    assert digests[0] == digests[1]


class TestCanonicalPartition:
    """floor(p/side) quantization: the partition is origin-independent."""

    def test_shared_points_group_identically(self):
        pts, _ = gaussian_mixture(400, k=3, d=2, overlap=0.05, seed=3)
        extra = np.array([[1.0, 1.0]], np.float32)   # shifts the data min
        a = canonical_group_coords(jnp.asarray(pts), D_CUT)
        b = canonical_group_coords(jnp.asarray(np.concatenate([extra, pts])),
                                   D_CUT)[1:]
        assert bool(jnp.all(a == b))
        # and through build_grid: same pairs share grouping cells
        ga = build_grid(jnp.asarray(pts), D_CUT)
        gb = build_grid(jnp.asarray(np.concatenate([extra, pts])), D_CUT)
        key_a = np.asarray(ga.group_key)[np.asarray(ga.inv_order)]
        key_b = np.asarray(gb.group_key)[np.asarray(gb.inv_order)][1:]
        same_a = key_a[:, None] == key_a[None, :]
        same_b = key_b[:, None] == key_b[None, :]
        assert (same_a == same_b).all()


class TestIncrementalOverflow:
    """The streaming grid's measured budgets: overflow raises, rebuild
    restores an exact partition."""

    def _grid(self, pts, **kw):
        g = IncrementalGrid(D_CUT, capacity=len(pts), dim=pts.shape[1], **kw)
        g.rebuild(pts, len(pts))
        return g

    def test_rebuild_matches_canonical_coords(self):
        pts, _ = gaussian_mixture(300, k=3, d=2, overlap=0.05, seed=4)
        g = self._grid(pts)
        coords = np.asarray(canonical_group_coords(jnp.asarray(pts), D_CUT))
        keys = g._pack(coords)
        seg = np.asarray(g.seg_dev)[: len(pts)]
        # same packed key <-> same segment id
        for k in np.unique(keys):
            ids = np.unique(seg[keys == k])
            assert len(ids) == 1
        assert g.live_cells == len(np.unique(keys))

    def test_out_of_box_raises(self):
        pts = np.random.default_rng(0).normal(5e4, 800.0, (64, 2)) \
            .astype(np.float32)
        g = self._grid(pts, extent_margin=1)
        far = np.array([[9.9e4, 9.9e4]], np.float32)
        with pytest.raises(CellOverflow):
            g.apply(np.array([0], np.int32), far, pts[:1], 1)

    def test_live_cell_budget_raises(self):
        rng = np.random.default_rng(1)
        pts = rng.normal(5e4, 500.0, (128, 2)).astype(np.float32)
        g = self._grid(pts, cell_slack=1.0, extent_margin=16)
        budget = g.maxima_cap
        side = D_CUT / np.sqrt(2.0)
        # one new singleton cell per insert, marching along a grid row
        with pytest.raises(CellOverflow):
            for i in range(budget + 1):
                p = np.array([[3e4 + (2 * i + 1) * side, 2e4]], np.float32)
                g.apply(np.array([i % 64], np.int32), p, pts[i % 64: i % 64 + 1],
                        1)
                pts[i % 64] = p[0]

    def test_eviction_recycles_cell_ids(self):
        pts = np.array([[0., 0.], [1e4, 1e4], [2e4, 2e4], [3e4, 3e4]],
                       np.float32)
        g = IncrementalGrid(100.0, capacity=4, dim=2, extent_margin=500)
        g.rebuild(pts, 4)
        assert g.live_cells == 4
        # replace a singleton with a point in an existing cell: id freed
        g.apply(np.array([3], np.int32), pts[:1].copy(), pts[3:4], 1)
        assert g.live_cells == 3 and len(g.free_ids) == 1
        # replacing a singleton with a new singleton: the evicted cell's id
        # frees and the new cell reuses a recycled id — ids stay < capacity
        old_seg2 = int(g.seg_np[2])
        g.apply(np.array([2], np.int32), np.array([[4e4, 4e4]], np.float32),
                pts[2:3], 1)
        assert g.live_cells == 3 and len(g.free_ids) == 1
        assert int(g.seg_np[2]) in (old_seg2, 3)   # recycled, never a new id
        assert g.next_id == 4
