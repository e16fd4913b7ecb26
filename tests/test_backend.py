"""Kernel-backend parity: the pallas kernels (interpret mode on CPU) must
reproduce the jnp reference through every algorithm driver.

Threshold contract (kernels/backend.py): the pallas backends compute d2 in
the MXU expanded form, so data is drawn away from the d_cut boundary and
with NN distances comparable to the domain scale (uniform), where the
expanded form is exact to the same f32 ulps as the direct difference —
equality is then *bit*-equality, not a tolerance.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import DPCConfig, cluster, compute_dpc
from repro.core.scan import run_scan
from repro.kernels import available_backends, get_backend
from repro.kernels.backend import JnpBackend, KernelBackend, PallasBackend
from repro.kernels.ref import masked_min_dist_ref, range_count_ref

D_CUT = 900.0


def _safe_points(n, d, d_cut, seed):
    """Uniform points with no pairwise distance near the d_cut threshold."""
    rng = np.random.default_rng(seed)
    pts = rng.uniform(0, 50 * d_cut, size=(n, d)).astype(np.float32)
    d2 = ((pts[:, None, :].astype(np.float64) - pts[None, :, :]) ** 2).sum(-1)
    bad = np.abs(np.sqrt(d2) - d_cut) < 1e-3 * d_cut
    np.fill_diagonal(bad, False)
    return pts[~bad.any(1)]


def _assert_equal_results(a, b):
    assert bool(jnp.all(a.rho == b.rho)), "rho mismatch"
    assert bool(jnp.all(a.parent == b.parent)), "parent mismatch"
    both_inf = jnp.isinf(a.delta) & jnp.isinf(b.delta)
    assert bool(jnp.all((a.delta == b.delta) | both_inf)), "delta mismatch"


class TestRegistry:
    def test_all_backends_registered(self):
        assert {"jnp", "pallas", "pallas-interpret"} <= set(
            available_backends())

    def test_cpu_default_is_jnp(self):
        # conftest pins JAX_PLATFORMS=cpu, so auto-detection must pick the
        # reference (interpret mode is a CI opt-in, not a default)
        assert isinstance(get_backend(None), JnpBackend)
        assert get_backend("auto").name == get_backend(None).name

    def test_instance_passthrough_and_flags(self):
        be = get_backend("pallas-interpret")
        assert get_backend(be) is be
        assert isinstance(be, PallasBackend) and be.mxu_dense
        assert not get_backend("jnp").mxu_dense

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown kernel backend"):
            get_backend("cuda")

    def test_custom_registration(self):
        from repro.kernels.backend import register_backend, _REGISTRY

        class _Probe(KernelBackend):
            name = "probe"

        register_backend("probe", _Probe)
        try:
            assert isinstance(get_backend("probe"), _Probe)
        finally:
            _REGISTRY.pop("probe", None)


class TestPrimitiveParity:
    """Both backends against the dense jnp oracles, rectangular shapes."""

    @pytest.mark.parametrize("name", ["jnp", "pallas-interpret"])
    def test_range_count(self, name):
        be = get_backend(name)
        x = jnp.asarray(_safe_points(300, 3, D_CUT, 0))
        y = jnp.asarray(_safe_points(500, 3, D_CUT, 1))
        got = be.range_count(x, y, D_CUT)
        ref = range_count_ref(x, y, D_CUT).astype(jnp.float32)
        assert bool(jnp.all(got == ref))

    @pytest.mark.parametrize("name", ["jnp", "pallas-interpret"])
    def test_denser_nn(self, name):
        be = get_backend(name)
        rng = np.random.default_rng(2)
        x = jnp.asarray(_safe_points(300, 3, D_CUT, 3))
        y = jnp.asarray(_safe_points(500, 3, D_CUT, 4))
        xk = jnp.asarray(rng.uniform(0, 10, x.shape[0]), jnp.float32)
        yk = jnp.asarray(rng.uniform(0, 10, y.shape[0]), jnp.float32)
        dd, pp = be.denser_nn(x, xk, y, yk)
        rd, rp = masked_min_dist_ref(x, xk, y, yk)
        assert bool(jnp.all(pp == rp))
        both_inf = jnp.isinf(dd) & jnp.isinf(rd)
        assert bool(jnp.allclose(jnp.where(both_inf, 0, dd),
                                 jnp.where(both_inf, 0, rd),
                                 rtol=1e-6, atol=1e-4))

    def test_prefix_nn_matches_denser_nn_semantics(self):
        # prefix NN == denser NN keyed by descending position
        pts = jnp.asarray(_safe_points(300, 2, D_CUT, 5))
        for name in ("jnp", "pallas-interpret"):
            be = get_backend(name)
            dd, pp = be.prefix_nn(pts)
            n = pts.shape[0]
            key = -jnp.arange(n, dtype=jnp.float32)
            rd, rp = masked_min_dist_ref(pts, key, pts, key)
            assert bool(jnp.all(pp == rp)), name
            assert bool(jnp.all(jnp.isinf(dd) == jnp.isinf(rd))), name


class TestStreamingPrimitives:
    """The two streaming batched primitives (repro.stream) per backend."""

    @pytest.mark.parametrize("name", ["jnp", "pallas-interpret"])
    def test_range_count_delta(self, name):
        be = get_backend(name)
        rng = np.random.default_rng(5)
        x = jnp.asarray(_safe_points(400, 3, D_CUT, 6))
        batch = jnp.asarray(_safe_points(96, 3, D_CUT, 7))
        signs = jnp.asarray(rng.choice([-1.0, 0.0, 1.0], batch.shape[0]),
                            jnp.float32)
        got = be.range_count_delta(x, batch, signs, D_CUT)
        d2 = ((np.asarray(x)[:, None, :].astype(np.float64)
               - np.asarray(batch)[None]) ** 2).sum(-1)
        ref = ((d2 < D_CUT ** 2) * np.asarray(signs)[None, :]).sum(1)
        assert np.array_equal(np.asarray(got), ref.astype(np.float32))

    @pytest.mark.parametrize("name", ["jnp", "pallas-interpret"])
    def test_delta_of_counts_composes(self, name):
        """rho(after) == rho(before) + delta(batch): the exact-integer
        repair identity the sliding window relies on."""
        be = get_backend(name)
        pts = _safe_points(500, 2, D_CUT, 8)
        survivors, ins = pts[:400], pts[400:432]
        evi = survivors[:32]          # pretend these leave the window
        after = np.concatenate([survivors[32:], ins])
        batch = jnp.asarray(np.concatenate([ins, evi]))
        signs = jnp.asarray(np.concatenate([np.ones(len(ins)),
                                            -np.ones(len(evi))]), jnp.float32)
        q = jnp.asarray(survivors[32:])
        before = be.range_count(q, jnp.asarray(survivors), D_CUT)
        repaired = before + be.range_count_delta(q, batch, signs, D_CUT)
        fresh = be.range_count(q, jnp.asarray(after), D_CUT)
        assert bool(jnp.all(repaired == fresh))

    @pytest.mark.parametrize("name", ["jnp", "pallas-interpret"])
    def test_denser_nn_update_subset(self, name):
        be = get_backend(name)
        rng = np.random.default_rng(9)
        pts = jnp.asarray(_safe_points(400, 3, D_CUT, 10))
        n = pts.shape[0]
        rk = jnp.asarray(rng.permutation(n).astype(np.float32))
        rows = np.sort(rng.choice(n, 48, replace=False))
        q_slots = jnp.asarray(np.concatenate([rows, [n, n + 3]]))  # + padding
        dd, pp = be.denser_nn_update(pts, rk, q_slots)
        rd, rp = be.denser_nn(pts[jnp.asarray(rows)],
                              rk[jnp.asarray(rows)], pts, rk)
        assert bool(jnp.all(pp[:48] == rp))
        both_inf = jnp.isinf(dd[:48]) & jnp.isinf(rd)
        assert bool(jnp.all((dd[:48] == rd) | both_inf))
        assert bool(jnp.all(jnp.isinf(dd[48:])))     # padding rows inert
        assert bool(jnp.all(pp[48:] == -1))


class TestArgminRefinement:
    """ROADMAP item: expanded-form d2 can flip near-tie argmins when NN
    distances << domain scale; the kernels re-rank the top-k candidates in
    direct-diff form so the winner survives ill conditioning."""

    @staticmethod
    def _adversarial(offset=5e4, seed=0):
        """Query at a large offset with a planted near-tie: true NN at
        r=30, decoy at r=30.07 — a gap far below the expanded form's
        absolute error (~eps * |x|^2 ~ 1e2 at this offset), with fillers
        far enough to stay out of every top-k."""
        rng = np.random.default_rng(seed)
        q = np.array([offset, offset], np.float32)
        nn = q + np.array([30.0, 0.0], np.float32)
        decoy = q + np.array([0.0, 30.07], np.float32)
        fillers = q + (rng.uniform(300.0, 2000.0, (61, 2)).astype(np.float32)
                       * rng.choice([-1, 1], (61, 2)))
        y = np.concatenate([[nn], [decoy], fillers]).astype(np.float32)
        return (jnp.asarray(q[None]), jnp.zeros(1, jnp.float32),
                jnp.asarray(y), jnp.ones(len(y), jnp.float32))

    def test_topk_rerank_recovers_true_nn(self):
        from repro.kernels import ops

        x, xk, y, yk = self._adversarial()
        ref_d, ref_p = get_backend("jnp").denser_nn(x, xk, y, yk)
        assert int(ref_p[0]) == 0                    # direct diff: true NN
        got_d, got_p = ops.dependent_masked(x, xk, y, yk, interpret=True)
        assert int(got_p[0]) == int(ref_p[0])
        assert float(got_d[0]) == float(ref_d[0])    # winner value direct-diff

    def test_k1_reproduces_the_bug(self):
        """refine_k=1 is the historical refine-the-winner-only behavior;
        the adversarial data must flip it (guards the test's potency)."""
        from repro.kernels.dependent import masked_min_dist
        from repro.kernels.ops import pad_points, pad_vec

        x, xk, y, yk = self._adversarial()
        # the kernels anchor the expanded form at the row tile's first
        # point; a first row at the origin keeps the query's form as ill
        # conditioned as the unanchored one
        x = jnp.concatenate([jnp.zeros_like(x), x])
        xk = jnp.concatenate([xk, xk])
        xp, xkp = pad_points(x, 128), pad_vec(xk, 128, jnp.inf)
        yp, ykp = pad_points(y, 256), pad_vec(yk, 256, -jnp.inf)
        _, p1 = masked_min_dist(xp, xkp, yp, ykp, interpret=True, refine_k=1)
        assert int(p1[1]) == 1, "expanded-form flip no longer reproduces"

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_scaled_dataset_parent_parity(self, seed):
        """Whole-dataset regression: a blob at a 50x offset (NN distances
        << coordinate scale; expanded-form noise spans several near-ties,
        flipping refine_k=1 on every seed) keeps exact parent parity
        between the jnp reference and the re-ranking kernels."""
        rng = np.random.default_rng(seed)
        pts = (rng.normal(0, 200.0, (384, 2)) + 1e4).astype(np.float32)
        x = jnp.asarray(pts)
        rk = jnp.asarray(rng.permutation(len(pts)).astype(np.float32))
        rd, rp = get_backend("jnp").denser_nn(x, rk, x, rk)
        gd, gp = get_backend("pallas-interpret").denser_nn(x, rk, x, rk)
        assert bool(jnp.all(rp == gp))
        both_inf = jnp.isinf(rd) & jnp.isinf(gd)
        assert bool(jnp.all((rd == gd) | both_inf))


class TestAlgorithmParity:
    """Acceptance: compute_dpc(..., backend="pallas-interpret") equals the
    jnp backend (and, for the exact algorithms, the run_scan oracle)."""

    @pytest.fixture(scope="class")
    def pts(self):
        return _safe_points(800, 3, D_CUT, 0)

    @pytest.mark.parametrize("alg", ["scan", "exdpc", "approxdpc",
                                     "sapproxdpc"])
    def test_matches_jnp_backend(self, pts, alg):
        rj = compute_dpc(pts, DPCConfig(d_cut=D_CUT, algorithm=alg,
                                        backend="jnp"))
        rp = compute_dpc(pts, DPCConfig(d_cut=D_CUT, algorithm=alg,
                                        backend="pallas-interpret"))
        _assert_equal_results(rj, rp)

    @pytest.mark.parametrize("alg", ["scan", "exdpc"])
    def test_exact_algorithms_match_scan_oracle(self, pts, alg):
        oracle = run_scan(jnp.asarray(pts), D_CUT)   # jnp reference oracle
        rp = compute_dpc(pts, DPCConfig(d_cut=D_CUT, algorithm=alg,
                                        backend="pallas-interpret"))
        _assert_equal_results(oracle, rp)

    def test_approxdpc_centers_equal(self, pts):
        cfg = dict(d_cut=D_CUT, algorithm="approxdpc", rho_min=3.0)
        cj, _ = cluster(pts, DPCConfig(backend="jnp", **cfg))
        cp, _ = cluster(pts, DPCConfig(backend="pallas-interpret", **cfg))
        assert bool(jnp.all(cj.centers == cp.centers))
        assert bool(jnp.all(cj.labels == cp.labels))

    def test_dense_path_engages(self, pts):
        # the pallas run must actually take the dense branch (guard against
        # silently falling back to the stencil)
        assert get_backend("pallas-interpret").mxu_dense
