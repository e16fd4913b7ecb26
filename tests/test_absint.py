"""Property tests for the abstract-interpretation layer (repro.analysis.absint)
and the walker taint engine.

Two tiers, same properties:

* **seeded-random sweeps** — always run, no extra deps: a fixed
  ``numpy`` RNG drives a few hundred random affine index maps / traced
  programs per property, so local runs exercise the domain even where
  hypothesis is absent;
* **hypothesis** — the same properties under minimizing search, guarded
  with the repo's ``requirements-dev`` convention (degrade to skips when
  hypothesis is not installed; CI installs it).

The core soundness property: for any affine index map over a concrete
grid small enough to enumerate, :func:`absint.visit_verdict` must agree
*exactly* with brute-force enumeration — ``"once"`` iff no two grid
points produce the same output block tuple.  Above the enumeration cap
the check is one-sided (a ``"once"`` claim must still be true; the
analyzer may say ``"unknown"``).
"""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import absint
from repro.analysis.absint import Affine

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


# ------------------------------------------------------------ ground truth
def brute_force_verdict(dims, grid):
    """Exact uniqueness of the output tuples over the concrete grid."""
    seen = set()
    for point in itertools.product(*[range(s) for s in grid]):
        key = tuple(d.eval(point) for d in dims)
        if key in seen:
            return "revisit"
        seen.add(key)
    return "once"


def random_case(rng):
    """One random (dims, grid): <= 3 grid axes of size 1..6, <= 3 output
    dims, coefficients in [-3, 3], constants in [-4, 4]."""
    n_axes = int(rng.integers(1, 4))
    grid = tuple(int(rng.integers(1, 7)) for _ in range(n_axes))
    n_dims = int(rng.integers(1, 4))
    dims = []
    for _ in range(n_dims):
        coeffs = tuple(
            (a, int(c)) for a in range(n_axes)
            if (c := rng.integers(-3, 4)) != 0)
        dims.append(Affine(int(rng.integers(-4, 5)), coeffs))
    return dims, grid


def check_exact_agreement(dims, grid):
    verdict = absint.visit_verdict(dims, grid)
    truth = brute_force_verdict(dims, grid)
    vol = 1
    for s in grid:
        vol *= s
    if vol <= absint.ENUM_CAP:
        assert verdict == truth, (dims, grid, verdict, truth)
    elif verdict == "once":                           # pragma: no cover
        assert truth == "once", (dims, grid)


# ------------------------------------------------- seeded-random fallback
class TestAffineDomainSeeded:
    def test_visit_verdict_matches_enumeration(self):
        rng = np.random.default_rng(0)
        for _ in range(300):
            dims, grid = random_case(rng)
            check_exact_agreement(dims, grid)

    def test_eval_index_map_matches_python_semantics(self):
        """Random affine lambdas traced with make_jaxpr: the abstract
        evaluation of the index-map jaxpr reproduces the concrete map at
        every grid point."""
        rng = np.random.default_rng(1)
        for _ in range(60):
            c0, c1, k = (int(rng.integers(-3, 4)) for _ in range(3))

            def f(i, j, c0=c0, c1=c1, k=k):
                return c0 * i + k, c1 * j - k, i + j

            closed = jax.make_jaxpr(f)(jnp.int32(0), jnp.int32(0))
            dims = absint.eval_index_map(closed, n_grid=2)
            assert all(isinstance(d, Affine) for d in dims), dims
            for point in itertools.product(range(4), range(4)):
                concrete = f(*point)
                assert tuple(d.eval(point) for d in dims) == concrete

    def test_unit_ownership_once_claims_are_sound_above_cap(self):
        """Big grids (enumeration impossible) only get "once" through the
        unit-coefficient ownership condition — spot-check its claims
        against sampled collisions."""
        grid = (512, 512)                  # vol > ENUM_CAP
        dims = [Affine(0, ((0, 1),)), Affine(3, ((1, 1),))]
        assert absint.visit_verdict(dims, grid) == "once"
        rng = np.random.default_rng(2)
        seen = {}
        for _ in range(5000):
            p = (int(rng.integers(512)), int(rng.integers(512)))
            key = tuple(d.eval(p) for d in dims)
            assert seen.setdefault(key, p) == p
        # and a genuinely colliding big-grid map must not claim "once"
        dims_bad = [Affine(0, ((0, 1),))]  # axis 1 unused -> revisit
        assert absint.visit_verdict(dims_bad, grid) == "revisit"

    def test_data_and_top_degrade(self):
        assert absint.visit_verdict([absint.DATA], (4,)) == "data"
        assert absint.visit_verdict([absint.TOP], (4,)) == "unknown"
        assert absint.visit_verdict([Affine(0, ((0, 1),))], (0.5,)) \
            == "unknown"


# ----------------------------------------------------- hypothesis mirror
if HAVE_HYPOTHESIS:
    coeff = st.integers(min_value=-3, max_value=3)

    @st.composite
    def affine_case(draw):
        n_axes = draw(st.integers(1, 3))
        grid = tuple(draw(st.lists(st.integers(1, 6), min_size=n_axes,
                                   max_size=n_axes)))
        n_dims = draw(st.integers(1, 3))
        dims = []
        for _ in range(n_dims):
            coeffs = tuple((a, c) for a in range(n_axes)
                           if (c := draw(coeff)) != 0)
            dims.append(Affine(draw(st.integers(-4, 4)), coeffs))
        return dims, grid

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis absent")
    class TestAffineDomainHypothesis:
        @settings(max_examples=200, deadline=None)
        @given(case=affine_case())
        def test_visit_verdict_matches_enumeration(self, case):
            dims, grid = case
            check_exact_agreement(dims, grid)

        @settings(max_examples=100, deadline=None)
        @given(axis_sizes=st.lists(st.integers(1, 5), min_size=1,
                                   max_size=3),
               consts=st.lists(st.integers(-4, 4), min_size=1,
                               max_size=3))
        def test_identity_maps_visit_once(self, axis_sizes, consts):
            """Each live axis owning its own unit-coefficient dim is the
            BlockSpec common case — always "once", any grid size."""
            grid = tuple(axis_sizes)
            dims = [Affine(consts[min(a, len(consts) - 1)], ((a, 1),))
                    for a in range(len(grid))]
            assert absint.visit_verdict(dims, grid) == "once"


# ------------------------------------------------- walker taint properties
def _taint_hits(fn, *args, require_multi_partition=False):
    from repro.analysis.walker import spmd_sort_tainted_slices

    closed = jax.make_jaxpr(fn)(*args)
    return spmd_sort_tainted_slices(
        closed, require_multi_partition=require_multi_partition)


def _in_shard_map(body):
    """Wrap body in a 1-device shard_map (single-partition: only visible
    with require_multi_partition=False)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("i",))
    return shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                     check_vma=False)


class TestWalkerTaintProperties:
    def test_sort_derived_gather_is_hit(self):
        def body(x):
            order = jnp.argsort(x)
            return x[order]

        hits = _taint_hits(_in_shard_map(body), jnp.arange(8.0))
        assert hits and all(h.primitive in ("gather", "dynamic_slice")
                            for h in hits)

    def test_span_derived_gather_is_clean(self):
        """Indices computed arithmetically (no sort ancestry) never hit —
        the property that keeps the stencil paths out of R1."""
        def body(x):
            idx = (jnp.arange(8) * 3 + 1) % 8
            return x[idx]

        assert _taint_hits(_in_shard_map(body), jnp.arange(8.0)) == []

    def test_taint_survives_while_carry_fixpoint(self):
        def body(x):
            order = jnp.argsort(x)

            def cond(state):
                i, _ = state
                return i < 2

            def step(state):
                i, o = state
                return i + 1, o[o]          # keeps sort ancestry

            _, o = jax.lax.while_loop(cond, step, (0, order))
            return x[o]

        hits = _taint_hits(_in_shard_map(body), jnp.arange(8.0))
        assert hits, "carry fixpoint must preserve sort taint"

    def test_outside_shard_map_never_hits(self):
        def body(x):
            return x[jnp.argsort(x)]

        assert _taint_hits(body, jnp.arange(8.0)) == []

    def test_default_requires_multi_partition(self):
        def body(x):
            return x[jnp.argsort(x)]

        assert _taint_hits(_in_shard_map(body), jnp.arange(8.0),
                           require_multi_partition=True) == []

    def test_random_index_chains_agree_with_ancestry(self):
        """Seeded sweep: random chains of index ops either include a sort
        ancestor or not; hits mirror that exactly."""
        rng = np.random.default_rng(3)
        ops_pool = ("add", "mul", "mod")
        for _ in range(40):
            use_sort = bool(rng.integers(2))
            chain = [ops_pool[int(rng.integers(len(ops_pool)))]
                     for _ in range(int(rng.integers(1, 4)))]

            def body(x, use_sort=use_sort, chain=tuple(chain)):
                idx = jnp.argsort(x) if use_sort \
                    else jnp.arange(x.shape[0])
                for op in chain:
                    if op == "add":
                        idx = idx + 1
                    elif op == "mul":
                        idx = idx * 2
                    idx = idx % x.shape[0]
                return x[idx]

            hits = _taint_hits(_in_shard_map(body), jnp.arange(8.0))
            assert bool(hits) == use_sort, (use_sort, chain, hits)


# ----------------------------------------------------- memory estimators
class TestMemoryEstimators:
    def test_pallas_memory_counts_blocks_and_prefetch(self):
        from repro.analysis.walker import iter_sites
        from repro.kernels import sweep as S

        x = jnp.zeros((128, 2), jnp.float32)
        spec = S.SweepSpec(block_n=64, block_m=128, count=True)
        closed = jax.make_jaxpr(
            lambda a, b: S.tile_sweep(spec, a, b, 0.35,
                                      interpret=True))(x, x)
        eqns = [s.eqn for s in iter_sites(closed)
                if s.eqn.primitive.name == "pallas_call"]
        assert eqns
        est = absint.pallas_memory(eqns[0])
        assert est["vmem_bytes"] > 0
        assert est["smem_bytes"] > 0          # the d_cut^2 scalar in SMEM
        assert list(est["grid"]) == [2, 1]    # 2 row-blocks x 1 col-block

    def test_live_buffer_peak_scales_with_intermediates(self):
        small = jax.make_jaxpr(
            lambda x: (x * 2).sum())(jnp.ones((8, 8), jnp.float32))
        big = jax.make_jaxpr(
            lambda x: (x[:, None, :] - x[None, :, :]).sum())(
                jnp.ones((64, 8), jnp.float32))
        assert absint.live_buffer_peak(big) > \
            absint.live_buffer_peak(small) > 0
