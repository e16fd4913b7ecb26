"""repro.engine acceptance: one ExecSpec, one engine, one plan.

The ISSUE 5 contract, in four parts:

* **Parity** — the legacy-kwarg config shims (``DPCConfig(backend=...)``,
  ``DistDPCConfig``, ``StreamDPCConfig``, ``DPCKVConfig``) and the unified
  ``ExecSpec`` / ``DPCEngine`` paths produce bit-identical results per
  backend (including ``pallas-interpret``) and per layout.
* **Plan reuse** — a re-``fit`` on a same-shaped input returns the *same*
  plan object and adds no new jit trace entries; block-sparse pallas
  builds its worklists on the device, fetching no point table.
* **Deprecation** — constructing any of the four shims through its legacy
  exec kwargs emits a ``DeprecationWarning`` pointing at ``repro.engine``.
* **Fail-fast validation** — unknown backend/layout/precision names and
  impossible combos raise ``ValueError`` at construction / plan time, not
  inside the kernel layer.
"""
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import DPCConfig, compute_dpc
from repro.core.approxdpc import run_approxdpc
from repro.core.sapproxdpc import run_sapproxdpc
from repro.engine import DPCEngine, ExecSpec, PointsSpec, as_plan, plan
from repro.kernels import blocksparse
from repro.stream import QueryStatus, StreamDPC, StreamDPCConfig

BACKENDS = ["jnp", "pallas-interpret"]


def _mix(n=384, d=2, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, 6000.0, (4, d))
    pts = (centers[rng.integers(0, 4, n)]
           + rng.normal(0, 150.0, (n, d))).astype(np.float32)
    return pts


def _eq(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype.kind == "f":
        return bool(np.all((a == b) | (np.isinf(a) & np.isinf(b))))
    return bool(np.all(a == b))


def _assert_same_result(a, b):
    assert _eq(a.rho, b.rho), "rho diverged"
    assert _eq(a.rho_key, b.rho_key), "rho_key diverged"
    assert _eq(a.delta, b.delta), "delta diverged"
    assert _eq(a.parent, b.parent), "parent diverged"


class TestLegacyShimParity:
    """Legacy-kwarg configs == ExecSpec/DPCEngine, bit for bit."""

    @pytest.mark.parametrize("algo", ["scan", "exdpc", "approxdpc",
                                      "sapproxdpc"])
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_batch_backend_parity(self, backend, algo):
        pts = _mix(256 if backend == "jnp" else 160)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = compute_dpc(pts, DPCConfig(d_cut=900.0, algorithm=algo,
                                                backend=backend))
        spec = ExecSpec(backend=backend)
        unified = compute_dpc(pts, DPCConfig(d_cut=900.0, algorithm=algo,
                                             exec_spec=spec))
        engine = DPCEngine(d_cut=900.0, algorithm=algo,
                           exec_spec=spec).fit(pts).result
        _assert_same_result(legacy, unified)
        _assert_same_result(unified, engine)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_batch_layout_parity(self, backend):
        pts = _mix(256 if backend == "jnp" else 160, seed=3)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = compute_dpc(pts, DPCConfig(
                d_cut=900.0, backend=backend, layout="block-sparse"))
        spec = ExecSpec(backend=backend, layout="block-sparse")
        engine = DPCEngine(d_cut=900.0, exec_spec=spec).fit(pts).result
        _assert_same_result(legacy, engine)

    def test_block_kwarg_parity(self):
        """The resolved-block satellite: an explicit legacy block and the
        plan's native default give identical bits (block is a throughput
        knob only), and the shim threads it to the same place ExecSpec
        does."""
        pts = _mix(300, seed=5)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = compute_dpc(pts, DPCConfig(d_cut=900.0,
                                                algorithm="scan", block=96))
        via_spec = compute_dpc(pts, DPCConfig(
            d_cut=900.0, algorithm="scan",
            exec_spec=ExecSpec(block=96)))
        native = compute_dpc(pts, DPCConfig(d_cut=900.0, algorithm="scan"))
        _assert_same_result(legacy, via_spec)
        _assert_same_result(legacy, native)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_stream_parity(self, backend):
        pts = _mix(320, seed=7)
        cap, B = 256, 32

        def drive(cfg):
            s = StreamDPC(cfg)
            s.initialize(pts[:cap])
            for i in range(cap, len(pts), B):
                tick = s.ingest(pts[i: i + B])
            return s, tick

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            s1, t1 = drive(StreamDPCConfig(d_cut=900.0, capacity=cap,
                                           batch_cap=B, rho_min=3.0,
                                           backend=backend))
        s2, t2 = drive(StreamDPCConfig(
            d_cut=900.0, capacity=cap, batch_cap=B, rho_min=3.0,
            exec_spec=ExecSpec(backend=backend)))
        _assert_same_result(s1.result, s2.result)
        assert np.array_equal(t1.labels, t2.labels)
        # and the engine facade drives the same stream
        eng = DPCEngine(d_cut=900.0, rho_min=3.0, window_capacity=cap,
                        batch_cap=B, exec_spec=ExecSpec(backend=backend))
        eng.partial_fit(pts[:cap])
        for i in range(cap, len(pts), B):
            eng.partial_fit(pts[i: i + B])
        _assert_same_result(eng.result, s2.result)

    def test_distributed_parity(self):
        from repro.distributed import DistDPCConfig, distributed_dpc

        pts = _mix(256, seed=9)
        mesh = jax.make_mesh((1,), ("data",))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = distributed_dpc(pts, DistDPCConfig(d_cut=900.0,
                                                        backend="jnp"),
                                     mesh)
        unified = distributed_dpc(pts, mesh=mesh, d_cut=900.0,
                                  exec_spec=ExecSpec(backend="jnp"))
        engine = DPCEngine(d_cut=900.0, algorithm="exdpc", mesh=mesh,
                           exec_spec=ExecSpec(backend="jnp")).fit(pts)
        _assert_same_result(legacy, unified)
        _assert_same_result(unified, engine.result)

    def test_dpc_kv_parity(self):
        from repro.serve.dpc_kv import DPCKVConfig, compress_kv

        rng = np.random.default_rng(2)
        k = jnp.asarray(rng.normal(size=(2, 96, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 96, 2, 32)), jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = compress_kv(k, v, 80, DPCKVConfig(budget=16,
                                                       backend="jnp"))
        unified = compress_kv(k, v, 80, DPCKVConfig(
            budget=16, exec_spec=ExecSpec(backend="jnp")))
        for a, b in zip(legacy, unified):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_dpc_kv_block_sparse_traceable(self):
        """Newly-reachable capability: jnp jit-built worklists let DPC-KV
        run block-sparse under its jit+vmap, bit-equal to dense."""
        from repro.serve.dpc_kv import DPCKVConfig, compress_kv

        rng = np.random.default_rng(4)
        k = jnp.asarray(rng.normal(size=(1, 96, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 96, 2, 32)), jnp.float32)
        dense = compress_kv(k, v, 90, DPCKVConfig(
            budget=12, exec_spec=ExecSpec(backend="jnp")))
        sparse = compress_kv(k, v, 90, DPCKVConfig(
            budget=12, exec_spec=ExecSpec(backend="jnp",
                                          layout="block-sparse")))
        for a, b in zip(dense, sparse):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestPlanReuse:
    """Re-fit on same-shaped input: cached plan, no retrace."""

    def test_plan_object_identity_and_no_retrace(self):
        from repro.kernels.backend import _rho_delta_jnp

        pts = _mix(288, seed=11)
        eng = DPCEngine(d_cut=900.0, algorithm="scan",
                        exec_spec=ExecSpec(backend="jnp"))
        eng.fit(pts)
        first_plan = eng.plan
        traces_after_first = _rho_delta_jnp._cache_size()
        eng.fit(pts)                                # same shape, same data
        eng.fit(_mix(288, seed=12))                 # same shape, new data
        assert eng.plan is first_plan, "same-shaped re-fit built a new plan"
        assert _rho_delta_jnp._cache_size() == traces_after_first, \
            "same-shaped re-fit re-traced the fused sweep"
        # a different shape re-plans (and re-traces) as it must
        eng.fit(_mix(290, seed=12))
        assert eng.plan is not first_plan

    def test_plan_cache_function(self):
        ps = PointsSpec(n=128, d=3)
        spec = ExecSpec(backend="jnp", layout="block-sparse")
        assert plan(ps, spec) is plan(ps, spec)
        assert plan(ps, spec) is not plan(PointsSpec(n=129, d=3), spec)
        assert as_plan(spec).spec == spec

    def test_fused_worklist_fetches_nothing(self):
        """pallas block-sparse: the fused rho/delta list is built on the
        device at every fit, same data included.  Its one host copy is the
        8-byte (chunks, kept) read."""
        from repro import obs

        pts = _mix(160, seed=13)
        eng = DPCEngine(d_cut=900.0, algorithm="scan",
                        exec_spec=ExecSpec(backend="pallas-interpret",
                                           layout="block-sparse"))
        eng.fit(pts)                    # plans (and analyzes) the spec
        obs.configure(level="metrics")
        try:
            for _ in range(2):          # the same data twice
                obs.reset_spans()
                before = blocksparse.worklist_build_count()
                eng.fit(pts)
                assert blocksparse.worklist_build_count() == before + 1
                recs = obs.spans()
                (build,) = [r for r in recs if r["name"] == "worklist.build"]
                sub = [r.get("attrs", {}) for r in recs
                       if r["parent"] == build["id"]]
                assert sum(a.get("d2h_bytes", 0) for a in sub) == 8
                assert not any("h2d_bytes" in a for a in sub)
        finally:
            obs.configure(level="off")
            obs.reset_spans()

    @pytest.mark.parametrize("primitive", ["range_count",
                                           "range_count_delta",
                                           "denser_nn"])
    def test_pallas_sparse_routes_fetch_no_table(self, primitive):
        """pallas block-sparse primitives build their lists on the device:
        a call's spans copy at most the count list's 8-byte read down and
        no list up, and its answer equals the dense layout's."""
        from repro import obs
        from repro.kernels import get_backend

        pts = jnp.asarray(_mix(160, seed=13))
        key = jnp.asarray(np.random.default_rng(13).permutation(160)
                          .astype(np.float32))
        signs = jnp.asarray(np.random.default_rng(14)
                            .choice([-1.0, 0.0, 1.0], 160).astype(np.float32))
        be = get_backend("pallas-interpret")
        call = {
            "range_count": lambda **kw: be.range_count(pts, pts, 900.0,
                                                       **kw),
            "range_count_delta": lambda **kw: be.range_count_delta(
                pts, pts, signs, 900.0, **kw),
            "denser_nn": lambda **kw: be.denser_nn(pts, key, pts, key, **kw),
        }[primitive]
        dense = call()
        obs.configure(level="metrics")
        try:
            obs.reset_spans()
            sparse = call(layout="block-sparse")
            attrs = [r.get("attrs", {}) for r in obs.spans()]
        finally:
            obs.configure(level="off")
            obs.reset_spans()
        assert sum(a.get("d2h_bytes", 0) for a in attrs) <= 8
        assert not any(a.get("h2d_bytes", 0) for a in attrs)
        for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(sparse)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestDeprecationShims:
    def test_dpc_config_warns(self):
        with pytest.warns(DeprecationWarning, match="repro.engine"):
            DPCConfig(d_cut=100.0, backend="jnp")
        with pytest.warns(DeprecationWarning, match="repro.engine"):
            DPCConfig(d_cut=100.0, layout="block-sparse")
        with pytest.warns(DeprecationWarning, match="repro.engine"):
            DPCConfig(d_cut=100.0, block=128)

    def test_dist_config_warns(self):
        from repro.distributed import DistDPCConfig
        with pytest.warns(DeprecationWarning, match="repro.engine"):
            DistDPCConfig(d_cut=100.0, backend="jnp")

    def test_stream_config_warns(self):
        with pytest.warns(DeprecationWarning, match="repro.engine"):
            StreamDPCConfig(d_cut=100.0, layout="block-sparse")

    def test_data_axis_legacy_kwarg(self):
        from repro.distributed import DistDPCConfig
        with pytest.warns(DeprecationWarning, match="data_axis"):
            cfg = DistDPCConfig(d_cut=100.0, data_axis="dp")
        assert cfg.resolved_exec().data_axis == "dp"
        with pytest.raises(ValueError, match="legacy"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                DistDPCConfig(d_cut=100.0, data_axis="dp",
                              exec_spec=ExecSpec(data_axis="mp"))
        with pytest.warns(DeprecationWarning, match="data_axis"):
            scfg = StreamDPCConfig(d_cut=100.0, data_axis="dp")
        assert scfg.resolved_exec().data_axis == "dp"

    def test_kv_config_warns(self):
        from repro.serve.dpc_kv import DPCKVConfig
        with pytest.warns(DeprecationWarning, match="repro.engine"):
            DPCKVConfig(budget=8, backend="jnp")

    def test_no_warning_on_unified_path(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            DPCConfig(d_cut=100.0, exec_spec=ExecSpec(backend="jnp"))
            StreamDPCConfig(d_cut=100.0)

    def test_conflicting_legacy_and_spec_raise(self):
        with pytest.raises(ValueError, match="legacy"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                DPCConfig(d_cut=100.0, backend="jnp",
                          exec_spec=ExecSpec(backend="pallas-interpret"))


class TestFailFastValidation:
    def test_unknown_backend(self):
        with pytest.raises(ValueError, match="unknown kernel backend"):
            ExecSpec(backend="cuda")

    def test_unknown_layout(self):
        with pytest.raises(ValueError, match="unknown layout"):
            ExecSpec(layout="sparse")

    def test_unknown_precision(self):
        with pytest.raises(ValueError, match="unknown precision"):
            ExecSpec(precision="fp8")

    def test_bf16_on_jnp(self):
        with pytest.raises(ValueError, match="bf16"):
            ExecSpec(backend="jnp", precision="bf16")

    def test_bf16_auto_resolving_to_jnp(self):
        # on a CPU container auto-detection lands on jnp: plan() must
        # reject bf16 with a clear message, not fail inside the kernels
        spec = ExecSpec(precision="bf16")
        with pytest.raises(ValueError, match="pallas"):
            as_plan(spec, np.zeros((8, 2), np.float32))

    def test_bad_block(self):
        with pytest.raises(ValueError, match="block"):
            ExecSpec(block=0)

    def test_bad_eps_sapprox(self):
        with pytest.raises(ValueError, match="eps > 0"):
            DPCConfig(d_cut=10.0, algorithm="sapproxdpc", eps=0.0)
        with pytest.raises(ValueError, match="eps > 0"):
            run_sapproxdpc(np.zeros((4, 2), np.float32), 1.0, eps=-1.0)
        with pytest.raises(ValueError, match="eps > 0"):
            DPCEngine(d_cut=10.0, algorithm="sapproxdpc", eps=0.0)

    def test_bad_dcut(self):
        with pytest.raises(ValueError, match="d_cut"):
            DPCConfig(d_cut=0.0)
        with pytest.raises(ValueError, match="d_cut"):
            StreamDPCConfig(d_cut=-1.0)

    def test_unknown_algorithm(self):
        with pytest.raises(ValueError, match="algorithm"):
            DPCConfig(d_cut=10.0, algorithm="dbscan")
        with pytest.raises(ValueError, match="algorithm"):
            DPCEngine(d_cut=10.0, algorithm="dbscan")

    def test_pallas_block_sparse_under_jit_config(self):
        from repro.serve.dpc_kv import DPCKVConfig
        with pytest.raises(ValueError, match="jit"):
            DPCKVConfig(budget=8, exec_spec=ExecSpec(
                backend="pallas", layout="block-sparse"))

    def test_legacy_kwargs_rejected_on_runners(self):
        with pytest.raises(TypeError):
            run_approxdpc(np.zeros((4, 2), np.float32), 1.0, backend="jnp")

    def test_runners_accept_array_likes(self):
        """Plain lists keep working on the public run_* API (the planner
        reads shapes only after jnp.asarray coercion)."""
        from repro.core.scan import run_scan
        res = run_scan([[0.0, 0.0], [1.0, 1.0], [2.0, 0.0]], 1.5)
        assert res.rho.shape == (3,)

    def test_distributed_cfg_kwarg_conflict(self):
        from repro.distributed import DistDPCConfig, distributed_dpc
        mesh = jax.make_mesh((1,), ("data",))
        pts = np.zeros((8, 2), np.float32)
        with pytest.raises(ValueError, match="not both"):
            distributed_dpc(pts, DistDPCConfig(d_cut=1.0), mesh,
                            strategy="halo")
        with pytest.raises(ValueError, match="not both"):
            distributed_dpc(pts, DistDPCConfig(d_cut=1.0), mesh, d_cut=2.0)

    def test_exec_parse(self):
        assert ExecSpec.parse("jnp:block-sparse") == \
            ExecSpec(backend="jnp", layout="block-sparse")
        assert ExecSpec.parse("::") == ExecSpec()
        assert ExecSpec.parse("pallas::bf16").precision == "bf16"
        with pytest.raises(ValueError):
            ExecSpec.parse("a:b:c:d")


class TestExecParseErrors:
    """Each malformed --exec form fails with the offending segment named
    and that axis's valid values enumerated (ISSUE 6 satellite)."""

    def test_too_many_segments(self):
        with pytest.raises(ValueError) as ei:
            ExecSpec.parse("jnp:dense:f32:extra")
        msg = str(ei.value)
        assert "at most 3" in msg and "got 4" in msg
        # the recovery path: every axis's valid values are in the message
        for value in ("jnp", "pallas", "pallas-interpret", "dense",
                      "block-sparse", "f32", "bf16"):
            assert value in msg

    def test_unknown_backend_segment(self):
        with pytest.raises(ValueError) as ei:
            ExecSpec.parse("cuda:dense")
        msg = str(ei.value)
        assert "segment 1 (backend)" in msg and "'cuda'" in msg
        assert "jnp" in msg and "pallas-interpret" in msg
        assert "empty/'-'/'auto'" in msg

    def test_unknown_layout_segment(self):
        with pytest.raises(ValueError) as ei:
            ExecSpec.parse("jnp:sparse")
        msg = str(ei.value)
        assert "segment 2 (layout)" in msg and "'sparse'" in msg
        assert "dense" in msg and "block-sparse" in msg

    def test_unknown_precision_segment(self):
        with pytest.raises(ValueError) as ei:
            ExecSpec.parse("jnp:dense:fp8")
        msg = str(ei.value)
        assert "segment 3 (precision)" in msg and "'fp8'" in msg
        assert "f32" in msg and "bf16" in msg

    def test_misordered_value_hint(self):
        # a precision in the layout slot: the error says which axis the
        # value actually belongs to and restates the segment order
        with pytest.raises(ValueError) as ei:
            ExecSpec.parse("jnp:bf16")
        msg = str(ei.value)
        assert "segment 2 (layout)" in msg
        assert "'bf16' is a precision" in msg
        assert "backend:layout:precision" in msg

    def test_backend_in_precision_slot_hint(self):
        with pytest.raises(ValueError) as ei:
            ExecSpec.parse("::jnp")
        assert "'jnp' is a backend" in str(ei.value)

    def test_valid_combos_still_parse(self):
        assert ExecSpec.parse("") == ExecSpec()
        assert ExecSpec.parse("-:block-sparse:-").layout == "block-sparse"
        assert ExecSpec.parse("auto:dense").layout == "dense"
        # combo validation still happens (in the constructor, post-parse)
        with pytest.raises(ValueError, match="bf16"):
            ExecSpec.parse("jnp::bf16")


class TestEnginePredict:
    """predict == the serve layer's query semantics, on batch state."""

    def test_hit_and_fallback(self):
        pts = _mix(256, seed=21)
        eng = DPCEngine(d_cut=900.0, rho_min=3.0,
                        exec_spec=ExecSpec(backend="jnp")).fit(pts)
        q = eng.predict(pts[:16])
        assert (q.status == int(QueryStatus.HIT)).all()
        assert np.array_equal(q.labels, eng.labels_[:16])
        far = eng.predict(np.full((1, 2), 1e7, np.float32))
        assert far.status[0] == int(QueryStatus.MISS_FALLBACK)
        assert far.labels[0] in set(eng.labels_[eng.labels_ >= 0])

    def test_stream_predict_matches_service_query(self):
        from repro.stream import StreamServeConfig, StreamService

        pts = _mix(320, seed=22)
        cap, B = 256, 32
        spec = ExecSpec(backend="jnp")
        eng = DPCEngine(d_cut=900.0, rho_min=3.0, window_capacity=cap,
                        batch_cap=B, exec_spec=spec)
        svc = StreamService(StreamServeConfig(stream=StreamDPCConfig(
            d_cut=900.0, capacity=cap, batch_cap=B, rho_min=3.0,
            exec_spec=spec)))
        # drive both through the same warm-up ticks so the stable-id
        # assignment order (and with it the label values) matches
        eng.partial_fit(pts[:cap])
        svc.engine.ingest(pts[:cap])
        for i in range(cap, len(pts), B):
            eng.partial_fit(pts[i: i + B])
            svc.engine.ingest(pts[i: i + B])
        qe = eng.predict(pts[:40])
        qs = svc.query(pts[:40])
        assert np.array_equal(qe.labels, qs.labels)
        assert np.array_equal(qe.status, qs.status)

    def test_unfitted_raises(self):
        with pytest.raises(ValueError, match="unfitted"):
            DPCEngine(d_cut=10.0).predict(np.zeros((1, 2), np.float32))

    def test_refit_resets_stream(self):
        """A fit after streaming replaces the window: the next partial_fit
        seeds from the newly fitted points, not the stale stream."""
        a = _mix(128, seed=30)
        c = _mix(128, seed=31) + 50000.0      # disjoint data
        eng = DPCEngine(d_cut=900.0, rho_min=3.0, window_capacity=128,
                        batch_cap=32, exec_spec=ExecSpec(backend="jnp"))
        eng.partial_fit(a)
        eng.fit(c)
        assert eng.stream is None
        eng.partial_fit(c[:32])               # re-seeds from c, ingests
        w = eng.stream.window_points()
        assert np.abs(w).min() >= 40000.0, "stale pre-fit window survived"

    def test_engine_ctor_validation(self):
        with pytest.raises(ValueError, match="strategy"):
            DPCEngine(d_cut=10.0, strategy="ring")
        with pytest.raises(ValueError, match="batch_cap"):
            DPCEngine(d_cut=10.0, window_capacity=64, batch_cap=128)
