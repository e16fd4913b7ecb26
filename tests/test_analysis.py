"""repro.analysis: the jaxpr-level static analyzer (rules R1-R9 + audits).

The R1 positive control reconstructs the PR 4 distributed block-sparse
miscompile shape — a sort-derived order gather inside a multi-partition
shard_map body — which needs >1 device, so it runs in a subprocess with 4
fake host devices (the test_distributed_dpc.py pattern).  Everything else
(R2 source scans, R3/R4 hand-built traces, R5 cross-checks, the audit
registry, the plan-time gate) runs in-process.
"""
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from repro.analysis import AnalysisError, all_audits, audit_check_rep, audit_of
from repro.analysis import r2_check_rep, r3_precision, r4_pallas, \
    r5_coverage
from repro.analysis.rules import Finding, analyze_jaxpr
from repro.engine import ExecSpec
from repro.engine import planner

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------- audits
class TestAuditRegistry:
    def test_decorator_attaches_and_registers(self):
        @audit_check_rep("outputs are psum-reduced, identical per member",
                         collectives=("psum",))
        def body(x):
            return x

        rec = audit_of(body)
        assert rec is not None
        assert rec.collectives == ("psum",)
        assert "psum-reduced" in rec.reason
        assert body(3) == 3, "decorator must return the function unchanged"
        assert rec.key in all_audits()

    def test_empty_reason_rejected(self):
        with pytest.raises(ValueError, match="reason"):
            audit_check_rep("")
        with pytest.raises(ValueError, match="reason"):
            audit_check_rep("   ")

    def test_production_bodies_are_audited(self):
        """R2 on the real tree: every check_vma=False shard_map body in
        src/repro resolves to a def carrying @audit_check_rep."""
        findings = r2_check_rep.CheckRepAuditRule().check_project(_REPO_ROOT)
        assert findings == [], [f.to_dict() for f in findings]


# ------------------------------------------------------------------- R2
_R2_BAD = """\
from jax import shard_map

def build(mesh, spec):
    def body(x):
        return x
    return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)
"""

_R2_GOOD = """\
from jax import shard_map
from repro.analysis.audit import audit_check_rep

def build(mesh, spec):
    @audit_check_rep("P(axis)-local rows only; no replicated outputs")
    def body(x):
        return x
    return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)
"""

_R2_FACTORY = """\
from jax import shard_map

def _make_body(scale):
    def body(x):
        return x * scale
    return body

def build(mesh, spec):
    body = _make_body(2.0)
    return shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)
"""

_R2_LAMBDA = """\
from jax import shard_map

def build(mesh, spec):
    return shard_map(lambda x: x, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)
"""


class TestR2CheckRepAudit:
    def _scan(self, tmp_path, src):
        p = tmp_path / "mod.py"
        p.write_text(src)
        return r2_check_rep.scan_module(str(p), "mod.py")

    def test_unaudited_body_flagged(self, tmp_path):
        findings = self._scan(tmp_path, _R2_BAD)
        assert len(findings) == 1
        assert "no @audit_check_rep" in findings[0].message
        assert findings[0].severity == "error"

    def test_audited_body_clean(self, tmp_path):
        assert self._scan(tmp_path, _R2_GOOD) == []

    def test_factory_returned_body_resolved(self, tmp_path):
        """The distributed/dpc.py idiom: body = _make_xyz(...) resolves
        through the factory's returned inner def."""
        findings = self._scan(tmp_path, _R2_FACTORY)
        assert len(findings) == 1
        assert "`body`" in findings[0].message

    def test_unresolvable_body_flagged(self, tmp_path):
        findings = self._scan(tmp_path, _R2_LAMBDA)
        assert len(findings) == 1
        assert "cannot" in findings[0].message

    def test_default_check_rep_ignored(self, tmp_path):
        src = _R2_BAD.replace(",\n                     check_vma=False", "")
        assert self._scan(tmp_path, src) == []


# ------------------------------------------------------------------- R3
def _bf16_expanded_argmin(x, y):
    """The mixed-precision sweep shape: expanded-form d2 with a bf16 dot."""
    g = jnp.dot(x.astype(jnp.bfloat16),
                y.astype(jnp.bfloat16).T).astype(jnp.float32)
    d2 = (x * x).sum(-1)[:, None] + (y * y).sum(-1)[None, :] - 2.0 * g
    return jnp.argmin(d2, axis=1)


def _r3_findings(fn):
    x = jnp.zeros((8, 2), jnp.float32)
    y = jnp.zeros((5, 2), jnp.float32)
    closed = jax.make_jaxpr(fn)(x, y)
    return [f for f in analyze_jaxpr("r3-control", closed)
            if f.rule == r3_precision.RULE_NAME]


class TestR3PrecisionFlow:
    def test_bf16_dot_without_refinement_fires(self):
        assert len(_r3_findings(_bf16_expanded_argmin)) == 1

    def test_refinement_epilogue_passes(self):
        def refined(x, y):
            idx = _bf16_expanded_argmin(x, y)
            y_sel = y[idx]
            # the refine_topk_d2 / _fused_resolve contract: direct-diff
            # square-sum in full precision over the kept winners
            return jnp.sum((x - y_sel) ** 2, axis=-1)

        assert _r3_findings(refined) == []

    def test_pure_f32_never_fires(self):
        def f32_only(x, y):
            d2 = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
            return jnp.argmin(d2, axis=1)

        assert _r3_findings(f32_only) == []


# ------------------------------------------------------------------- R4
def _pallas_identity(block_rows):
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + jnp.float32(1.0)

    n = 96
    grid = -(-n // block_rows)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, 2), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 2), jnp.float32),
        interpret=True)


def _r4_findings(block_rows):
    x = jnp.zeros((96, 2), jnp.float32)
    closed = jax.make_jaxpr(_pallas_identity(block_rows))(x)
    return [f for f in analyze_jaxpr("r4-control", closed)
            if f.rule == r4_pallas.RULE_NAME]


class TestR4PallasLegality:
    def test_nondivisible_block_fires(self):
        findings = _r4_findings(40)          # 96 % 40 != 0
        assert findings, "96-row array with 40-row blocks must be flagged"
        assert all(f.severity == "error" for f in findings)

    def test_divisible_block_passes(self):
        assert _r4_findings(32) == []        # 96 % 32 == 0


# ------------------------------------------------------------------- R5
class TestR5SpecCoverage:
    def test_clean_on_tree(self):
        findings = r5_coverage.SpecCoverageRule().check_project(_REPO_ROOT)
        assert findings == [], [f.to_dict() for f in findings]

    def test_snapshot_drift_detected(self, monkeypatch):
        monkeypatch.setattr(r5_coverage, "KNOWN_BACKENDS", ("jnp", "pallas"))
        findings = r5_coverage.SpecCoverageRule().check_project(_REPO_ROOT)
        assert any("backends changed" in f.message for f in findings)

    @pytest.mark.parametrize("backend", ["jnp", "pallas", "pallas-interpret"])
    @pytest.mark.parametrize("layout", ["dense", "block-sparse"])
    @pytest.mark.parametrize("precision", ["f32", "bf16"])
    def test_axis_product_matches_validity_table(self, backend, layout,
                                                 precision):
        """Every axis value, by literal name (R5's corpus check counts on
        exactly this parametrization): ExecSpec accepts/rejects the full
        cross product where the documented table says."""
        if r5_coverage._expected_spec_valid(backend, layout, precision):
            spec = ExecSpec(backend=backend, layout=layout,
                            precision=precision)
            assert spec.describe() == f"{backend}:{layout}:{precision}"
        else:
            with pytest.raises(ValueError):
                ExecSpec(backend=backend, layout=layout, precision=precision)


# ------------------------------------------------ plan-time gate (planner)
class TestPlanTimeGate:
    def test_error_findings_fail_plan(self, monkeypatch):
        from repro import analysis

        bad = Finding(rule="X-test", severity="error", target="t",
                      message="injected failure")
        monkeypatch.setattr(analysis, "analyze_plan", lambda pl: [bad])
        spec = ExecSpec(backend="jnp", block=137)   # unique -> memo miss
        monkeypatch.delenv("REPRO_ANALYSIS", raising=False)
        planner._ANALYZED.pop(spec, None)
        planner._PLANS.pop((None, spec), None)
        try:
            with pytest.raises(AnalysisError, match="REPRO_ANALYSIS=0"):
                planner.plan(None, spec)
            # the documented escape hatch bypasses without re-analyzing
            monkeypatch.setenv("REPRO_ANALYSIS", "0")
            assert planner.plan(None, spec) is not None
        finally:
            planner._ANALYZED.pop(spec, None)
            planner._PLANS.pop((None, spec), None)

    def test_warnings_do_not_fail_plan(self, monkeypatch):
        from repro import analysis

        warn = Finding(rule="X-test", severity="warn", target="t",
                       message="advisory only")
        monkeypatch.setattr(analysis, "analyze_plan", lambda pl: [warn])
        spec = ExecSpec(backend="jnp", block=139)
        monkeypatch.delenv("REPRO_ANALYSIS", raising=False)
        planner._ANALYZED.pop(spec, None)
        planner._PLANS.pop((None, spec), None)
        try:
            assert planner.plan(None, spec) is not None
        finally:
            planner._ANALYZED.pop(spec, None)
            planner._PLANS.pop((None, spec), None)

    def test_real_plans_analyze_clean(self):
        """The canonical plan-time targets of the shipping specs carry no
        findings at all (error or warn) on this tree."""
        from repro.analysis import analyze_plan

        for spec in (ExecSpec(),
                     ExecSpec(backend="jnp", layout="block-sparse"),
                     ExecSpec(backend="pallas-interpret",
                              layout="block-sparse")):
            pl = planner.plan(None, spec)
            assert list(analyze_plan(pl)) == []


# ----------------------------------------------- R1 + the distributed gate
def test_single_device_blocksparse_layout():
    """shard_blocksparse_layout: single-partition meshes never hit the
    miscompile (no SPMD partitioning), so block-sparse plans keep
    block-sparse: traceable-worklist backends, and the pallas backends,
    whose shards build their worklists on the device; dense plans never
    do."""
    from repro.distributed import dpc as ddpc

    mesh = jax.make_mesh((1,), ("data",))
    bs = planner.plan(None, ExecSpec(backend="jnp", layout="block-sparse"))
    assert ddpc.shard_blocksparse_layout(bs, mesh) == "block-sparse"
    dense = planner.plan(None, ExecSpec(backend="jnp"))
    assert ddpc.shard_blocksparse_layout(dense, mesh) is None
    host = planner.plan(None, ExecSpec(backend="pallas-interpret",
                                       layout="block-sparse"))
    assert ddpc.shard_blocksparse_layout(host, mesh) == "block-sparse"


_R1_SCRIPT = r"""
import warnings, json, os
warnings.filterwarnings("ignore")
os.environ["REPRO_ANALYSIS"] = "suspend"   # probe plans, not production fits
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.analysis import spmd_gather_safe, r1_spmd_gather
from repro.analysis.rules import analyze_jaxpr
from repro.analysis.targets import distributed_targets, stream_targets
from repro.distributed import dpc as ddpc
from repro.engine import ExecSpec
from repro.engine.planner import plan
from repro.kernels.backend import get_backend

mesh = jax.make_mesh((4,), ("data",))
be = get_backend("jnp")
pts = jnp.zeros((32, 2), jnp.float32)
rk = jnp.zeros((32,), jnp.float32)

# (a) positive control: a frozen copy of the pre-one-hot order-gather ring
# walk (argsort visit order, tile id read from the sorted permutation
# inside the walk, feeding a dynamic_slice) -- the exact shape the pinned
# XLA CPU SPMD pipeline miscompiles.  Deleted from production by the
# one-hot rewrite; kept here so R1's detection of the pattern stays pinned.
BM = 8
def frozen_order_gather_walk(x_my, y):
    nbc = y.shape[0] // BM
    lo = jnp.min(y.reshape(nbc, BM, -1), axis=1)
    lb = jnp.sum((jnp.mean(x_my, axis=0)[None, :] - lo) ** 2, axis=1)
    order = jnp.argsort(lb).astype(jnp.int32)     # sort-derived visit order
    lbs = jnp.take_along_axis(lb, order, axis=0)  # the old order-gather

    def cond(c):
        p, _ = c
        return (p < nbc) & (lbs[jnp.minimum(p, nbc - 1)] < jnp.inf)

    def body(c):
        p, acc = c
        j = order[p]                              # tainted tile id ...
        tile = jax.lax.dynamic_slice_in_dim(y, j * BM, BM, 0)  # ... -> R1
        d2 = jnp.sum((x_my[:, None, :] - tile[None, :, :]) ** 2, -1)
        return p + 1, acc + jnp.sum(d2 < 1.0, axis=1).astype(jnp.float32)

    _, acc = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros(x_my.shape[0], jnp.float32)))
    return acc

sm_old = shard_map(frozen_order_gather_walk, mesh=mesh,
                   in_specs=(P("data"), P(None)), out_specs=P("data"),
                   check_vma=False)
safe_old = spmd_gather_safe(sm_old, pts, pts)
closed = jax.make_jaxpr(sm_old)(pts, pts)
r1 = [f for f in analyze_jaxpr("frozen-order-gather", closed)
      if f.rule == r1_spmd_gather.RULE_NAME]

# (b) the production one-hot walk: both block-sparse shard phases trace
# clean over 4 partitions, so the guard keeps block-sparse on this mesh
rho_fn = ddpc._make_rho_dense("data", 1.0, 256, be, layout="block-sparse")
delta_fn = ddpc._make_delta_dense("data", 256, be, layout="block-sparse")
sm_rho = shard_map(rho_fn, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=P("data"), check_vma=False)
sm_delta = shard_map(delta_fn, mesh=mesh, in_specs=(P("data"),) * 4,
                     out_specs=(P("data"),) * 3, check_vma=False)
safe_rho = spmd_gather_safe(sm_rho, pts, pts)
safe_delta = spmd_gather_safe(sm_delta, pts, rk, pts, rk)
pl_bs = plan(None, ExecSpec(backend="jnp", layout="block-sparse"))
pl_dense = plan(None, ExecSpec(backend="jnp"))
lay_bs = ddpc.shard_blocksparse_layout(pl_bs, mesh)
lay_dense = ddpc.shard_blocksparse_layout(pl_dense, mesh)

# (c) the clean tree: every distributed/stream target these plans run
# today -- now including the block-sparse shard phases and the sharded
# stream tail (NN re-query, label propagation, center distances) --
# analyzes with zero error findings
errors = []
for pl in (pl_bs, pl_dense):
    tgts = list(distributed_targets(pl)[0]) + list(stream_targets(pl)[0])
    for name, thunk in tgts:
        for f in analyze_jaxpr(name, thunk()):
            if f.severity == "error":
                errors.append([name, f.rule])

out = {"safe_old": bool(safe_old), "n_r1": len(r1),
       "messages": [f.message for f in r1],
       "safe_rho": bool(safe_rho), "safe_delta": bool(safe_delta),
       "layout_bs": lay_bs, "layout_dense": lay_dense,
       "clean_errors": errors}
print("RESULT" + json.dumps(out))
"""


def _run_subprocess(script):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_REPO_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_r1_positive_control_and_production_tree_is_clean():
    """All three R1 halves in one 4-device subprocess: the frozen copy of
    the old order-gather walk is still flagged (the rule's detection of
    the miscompile pattern stays pinned after the production rewrite),
    both production block-sparse shard phases trace clean so the probe
    keeps block-sparse on a multi-partition mesh (ISSUE 8 acceptance),
    and every shipping distributed/stream trace analyzes clean."""
    out = _run_subprocess(_R1_SCRIPT)
    assert out["safe_old"] is False
    assert out["n_r1"] >= 1
    assert any("sort-derived" in m for m in out["messages"])
    assert out["safe_rho"] is True and out["safe_delta"] is True, \
        "production one-hot shard phases must pass spmd_gather_safe"
    assert out["layout_bs"] == "block-sparse", \
        "the probe must re-enable multi-partition block-sparse"
    assert out["layout_dense"] is None
    assert out["clean_errors"] == []


# ------------------------------------------------------ R6 pallas-race
class TestR6PallasRace:
    """The race detector over real kernel traces: the shipping merges are
    proved associative-or-guarded, and the seeded lost-update mutation
    (kept-k merge -> passthrough overwrite) fires."""

    def _trace(self, spec):
        from repro.kernels import sweep as S

        # two column tiles: each row block is revisited across the grid
        x = jnp.zeros((256, 2), jnp.float32)
        return jax.make_jaxpr(
            lambda a, b: S.tile_sweep(spec, a, b, 0.35, interpret=True))(x, x)

    def _findings(self, closed):
        from repro.analysis.r6_pallas_race import PallasRaceRule

        return PallasRaceRule().check_jaxpr("t", closed)

    def test_shipping_topk_merge_clean(self):
        from repro.kernels import sweep as S

        spec = S.SweepSpec(block_n=64, block_m=128, count=True,
                           nn="topk", k=4)
        assert self._findings(self._trace(spec)) == []

    def test_shipping_best1_merge_clean(self):
        from repro.kernels import sweep as S

        spec = S.SweepSpec(block_n=64, block_m=128, nn="best1")
        assert self._findings(self._trace(spec)) == []

    def test_overwrite_mutation_fires(self, monkeypatch):
        """Positive control: _merge_topk mutated into last-tile-wins.  A
        unique SweepSpec forces a fresh trace (the jit cache would
        otherwise replay the unmutated kernel)."""
        from repro.kernels import sweep as S

        monkeypatch.setattr(S, "_merge_topk",
                            lambda ov, oi, nv, ni, k: (nv, ni))
        spec = S.SweepSpec(block_n=64, block_m=128, count=True,
                           nn="topk", k=3)
        findings = self._findings(self._trace(spec))
        assert len(findings) == 2, findings       # topv and topi outputs
        assert all(f.severity == "error" for f in findings)
        assert all("overwrite" in f.message for f in findings)
        assert all("revisited" in f.message for f in findings)


# ------------------------------------------------- R7 transfer / retrace
class TestR7TransferRetrace:
    def test_callback_in_trace_fires(self):
        from repro.analysis.r7_transfer_retrace import TransferRule

        def f(x):
            y = jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y * 2.0

        closed = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
        fs = TransferRule().check_jaxpr("t", closed)
        assert [f.severity for f in fs] == ["error"]
        assert "round trip" in fs[0].message

    def test_clean_trace_passes(self):
        from repro.analysis.r7_transfer_retrace import TransferRule

        closed = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((4,)))
        assert TransferRule().check_jaxpr("t", closed) == []

    def test_raw_jit_spellings_diverge_and_wrapper_normalizes(self):
        """The detection mechanism end-to-end: the un-normalized jit
        boundary shows weak-vs-strong aval drift across d_cut spellings;
        the public tile_sweep wrapper erases it."""
        import numpy as np

        from repro.analysis.r7_transfer_retrace import _jit_signature
        from repro.kernels import sweep as S

        x = jnp.zeros((128, 2), jnp.float32)
        spec = S.SweepSpec(block_n=64, block_m=128, count=True)

        def sig(fn, d):
            return _jit_signature(jax.make_jaxpr(
                lambda a, b: fn(spec, a, b, d, interpret=True))(x, x))

        assert sig(S._tile_sweep_jit, 0.35) != \
            sig(S._tile_sweep_jit, np.float32(0.35))
        assert sig(S.tile_sweep, 0.35) == sig(S.tile_sweep,
                                              np.float32(0.35))

    def test_plan_probe_clean_on_shipping_specs(self):
        from repro.analysis.r7_transfer_retrace import RetraceChurnRule

        for spec in (ExecSpec(backend="jnp"),
                     ExecSpec(backend="pallas-interpret",
                              layout="block-sparse")):
            pl = planner.plan(None, spec)
            assert RetraceChurnRule().check_plan(pl) == []

    def test_plan_probe_fires_on_unnormalized_plan(self):
        """Positive control: a plan whose rho_delta forwards d_cut raw
        into a jit boundary produces one trace-cache entry per spelling —
        the probe must call that out."""
        from repro.analysis.r7_transfer_retrace import RetraceChurnRule

        inner = jax.jit(lambda a, b, d: (a * d).sum() + b.sum())

        class _BE:
            fused_traceable = True

        class _FakePlan:
            backend = _BE()
            backend_name = "fake"
            layout = "dense"
            precision = "f32"
            spec = ("fake-spec",)
            sparse = False
            block = None

            def rho_delta(self, a, b, d):
                return inner(a, b, d)       # no normalization: the defect

        fs = RetraceChurnRule().check_plan(_FakePlan())
        assert any(f.severity == "error" and "retrace churn" in f.message
                   for f in fs), fs


# ------------------------------------------------------ R8 determinism
class TestR8Determinism:
    def _mesh(self):
        import numpy as np

        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()), ("i",))

    def _psum_trace(self, body, out_spec):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        sm = shard_map(body, mesh=self._mesh(), in_specs=(P("i"),),
                       out_specs=out_spec)
        return jax.make_jaxpr(sm)(jnp.ones((8,), jnp.float32))

    def _findings(self, closed):
        from repro.analysis.r8_determinism import DeterminismRule

        return DeterminismRule().check_jaxpr("t", closed)

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs a multi-partition mesh")
    def test_unannotated_float_psum_feeding_outputs_is_error(self):
        from jax.sharding import PartitionSpec as P

        def body(x):
            return jax.lax.psum(jnp.sum(x * 1.5), "i")

        fs = self._findings(self._psum_trace(body, P(None)))
        assert [f.severity for f in fs] == ["error"]
        assert "audit_determinism" in fs[0].message

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs a multi-partition mesh")
    def test_internal_only_psum_is_warn(self):
        from jax.sharding import PartitionSpec as P

        def body(x):
            _ = jax.lax.psum(jnp.sum(x * 1.5), "i")
            return jnp.ones_like(x)

        fs = self._findings(self._psum_trace(body, P("i")))
        assert [f.severity for f in fs] == ["warn"]

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs a multi-partition mesh")
    def test_blessed_psum_is_clean(self):
        from jax.sharding import PartitionSpec as P

        from repro.analysis import audit_determinism

        @audit_determinism("test blessing: values are integer-exact",
                           ops=("psum",))
        def body(x):
            return jax.lax.psum(jnp.sum(x * 1.5), "i")

        assert self._findings(self._psum_trace(body, P(None))) == []

    def test_duplicate_index_scatter_add_fires(self):
        def scat(x, idx):
            return jnp.zeros((4,), jnp.float32).at[idx].add(x)

        closed = jax.make_jaxpr(scat)(jnp.ones((8,), jnp.float32),
                                      jnp.zeros((8,), jnp.int32))
        fs = self._findings(closed)
        assert [f.severity for f in fs] == ["error"]
        assert "scatter-add" in fs[0].message

    def test_unique_index_scatter_add_clean(self):
        def scat(x):
            idx = jnp.arange(8)
            return jnp.zeros((8,), jnp.float32).at[idx].add(
                x, unique_indices=True)

        closed = jax.make_jaxpr(scat)(jnp.ones((8,), jnp.float32))
        assert self._findings(closed) == []

    def test_integer_scatter_add_clean(self):
        def scat(x, idx):
            return jnp.zeros((4,), jnp.int32).at[idx].add(x)

        closed = jax.make_jaxpr(scat)(jnp.ones((8,), jnp.int32),
                                      jnp.zeros((8,), jnp.int32))
        assert self._findings(closed) == []

    def test_production_blessings_registered(self):
        """The two shipping non-associative sites carry their audits.
        ``_compress_head``'s registers at import; the sharded repair's
        rides its factory (decorators on the inner def run per build)."""
        import repro.serve.dpc_kv                  # noqa: F401
        from repro.analysis import all_determinism_audits
        from repro.kernels.backend import get_backend
        from repro.stream.incremental import make_sharded_repair

        make_sharded_repair(jax.make_mesh((1,), ("i",)), "i",
                            get_backend("jnp"), 0.35)
        keys = set(all_determinism_audits())
        assert "repro.serve.dpc_kv._compress_head" in keys
        assert any(k.startswith("repro.stream.incremental."
                                "make_sharded_repair") for k in keys)


# --------------------------------------------------- R9 memory budget
class TestR9MemoryBudget:
    def _trace(self):
        from repro.kernels import sweep as S

        x = jnp.zeros((128, 2), jnp.float32)
        spec = S.SweepSpec(block_n=64, block_m=128, count=True)
        return jax.make_jaxpr(
            lambda a, b: S.tile_sweep(spec, a, b, 0.35, interpret=True))(x, x)

    def test_default_budget_passes(self):
        from repro.analysis.r9_memory_budget import MemoryBudgetRule

        assert MemoryBudgetRule().check_jaxpr("t", self._trace()) == []

    def test_tiny_vmem_budget_fires(self, monkeypatch):
        from repro.analysis.r9_memory_budget import MemoryBudgetRule

        monkeypatch.setenv("REPRO_LIMIT_VMEM_BYTES", "1024")
        fs = MemoryBudgetRule().check_jaxpr("t", self._trace())
        assert fs and all(f.severity == "error" for f in fs)
        assert any("VMEM" in f.message for f in fs)

    def test_live_buffer_gate_arms_only_with_env(self, monkeypatch):
        from repro.analysis.r9_memory_budget import MemoryBudgetRule

        closed = jax.make_jaxpr(
            lambda x: (x @ x.T).sum())(jnp.ones((64, 64), jnp.float32))
        assert MemoryBudgetRule().check_jaxpr("t", closed) == []
        monkeypatch.setenv("REPRO_LIMIT_LIVE_BYTES", "64")
        fs = MemoryBudgetRule().check_jaxpr("t", closed)
        assert [f.severity for f in fs] == ["error"]
        assert "live-buffer" in fs[0].message

    def test_limits_table_and_env_override(self, monkeypatch):
        from repro.analysis import limits

        base = limits.limits_for_platform(None)
        assert base.platform == "tpu"
        assert base.smem_bytes == 4 * (1 << 20)    # the R4-era contract
        monkeypatch.setenv("REPRO_LIMIT_SMEM_BYTES", "17")
        assert limits.limits_for_platform("tpu").smem_bytes == 17
        assert limits.limits_for_platform("tpu").vmem_bytes == \
            base.vmem_bytes
        with pytest.raises(ValueError, match="no kernel memory limits"):
            limits.limits_for_platform("quantum")

    def test_plan_telemetry_reports_memory(self):
        pl = planner.plan(None, ExecSpec(backend="pallas-interpret"))
        mem = pl.telemetry()["memory"]
        assert mem["kernels"], "pallas plan must report kernel estimates"
        for k in mem["kernels"]:
            assert k["vmem_bytes"] > 0
            assert k["vmem_bytes"] <= mem["limits"]["vmem_bytes"]
        assert mem["live_peak_bytes"] > 0
        assert mem["limits"]["platform"] == "tpu"
        # memoized: second call returns the same object, no re-trace
        assert pl.telemetry()["memory"] is mem


# ------------------------------------------- escape hatch + obs counter
class TestEscapeHatch:
    def test_bypass_records_findings_and_warns_once(self, monkeypatch,
                                                    caplog):
        import logging

        from repro import analysis

        bad = Finding(rule="X-hatch", severity="error", target="t",
                      message="injected failure")
        monkeypatch.setattr(analysis, "analyze_plan", lambda pl: [bad])
        monkeypatch.setenv("REPRO_ANALYSIS", "0")
        monkeypatch.setattr(planner, "_BYPASS_WARNED", False)
        spec = ExecSpec(backend="jnp", block=141)   # unique -> memo miss
        planner._ANALYZED.pop(spec, None)
        planner._PLANS.pop((None, spec), None)
        planner._M_FINDINGS._reset()
        try:
            with caplog.at_level(logging.WARNING, logger="repro.analysis"):
                assert planner.plan(None, spec) is not None
            assert any("bypassing" in r.message for r in caplog.records)
            vals = planner._M_FINDINGS._vals
            assert vals.get("level=error,rule=X-hatch") == 1, vals
            # second bypassed plan: counted via memo? new spec -> counted,
            # but the warning stays once-per-process
            caplog.clear()
            spec2 = ExecSpec(backend="jnp", block=143)
            planner._ANALYZED.pop(spec2, None)
            planner._PLANS.pop((None, spec2), None)
            with caplog.at_level(logging.WARNING, logger="repro.analysis"):
                assert planner.plan(None, spec2) is not None
            assert not any("bypassing" in r.message
                           for r in caplog.records)
        finally:
            for s in (spec, ExecSpec(backend="jnp", block=143)):
                planner._ANALYZED.pop(s, None)
                planner._PLANS.pop((None, s), None)
            planner._M_FINDINGS._reset()
            planner._BYPASS_WARNED = False

    def test_suspend_skips_entirely(self, monkeypatch):
        from repro import analysis

        calls = []
        monkeypatch.setattr(analysis, "analyze_plan",
                            lambda pl: calls.append(pl) or [])
        monkeypatch.setenv("REPRO_ANALYSIS", "suspend")
        spec = ExecSpec(backend="jnp", block=145)
        planner._ANALYZED.pop(spec, None)
        planner._PLANS.pop((None, spec), None)
        try:
            assert planner.plan(None, spec) is not None
            assert calls == []
        finally:
            planner._ANALYZED.pop(spec, None)
            planner._PLANS.pop((None, spec), None)


# --------------------------------------------------- SARIF + baseline
class TestSarifAndBaseline:
    def _report(self, findings):
        return {"ok": not any(f["severity"] == "error" for f in findings),
                "findings": findings, "targets": ["a"], "skipped": [],
                "rules": {"R6-pallas-race":
                          {"kind": "jaxpr", "description": "races"}}}

    def test_sarif_levels_and_locations(self):
        from repro.analysis.sarif import to_sarif

        findings = [
            {"rule": "R6-pallas-race", "severity": "error", "target": "t",
             "message": "m", "where": "pjit.jaxpr/pallas_call"},
            {"rule": "R2-check-rep-audit", "severity": "warn",
             "target": "t2", "message": "m2",
             "where": "src/repro/stream/incremental.py:271"},
        ]
        doc = to_sarif(self._report(findings))
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
        assert "R6-pallas-race" in ids and "baseline" in ids
        res = run["results"]
        assert res[0]["level"] == "error"
        fq = res[0]["locations"][0]["logicalLocations"][0]
        assert fq["fullyQualifiedName"] == "t::pjit.jaxpr/pallas_call"
        assert res[1]["level"] == "warning"
        phys = res[1]["locations"][0]["physicalLocation"]
        assert phys["artifactLocation"]["uri"].endswith("incremental.py")
        assert phys["region"]["startLine"] == 271

    def test_sarif_suppressed_findings_carry_justification(self):
        from repro.analysis.sarif import to_sarif

        findings = [{"rule": "R6-pallas-race", "severity": "suppressed",
                     "target": "t", "message": "m", "where": "w",
                     "suppressed_reason": "leased until fix lands",
                     "suppressed_until": "2099-01-01"}]
        res = to_sarif(self._report(findings))["runs"][0]["results"][0]
        assert res["suppressions"][0]["justification"] == \
            "leased until fix lands"

    def test_baseline_downgrades_matching_errors(self):
        import datetime

        from repro.analysis import report as R

        f = Finding(rule="R6-pallas-race", severity="error",
                    target="plan[x]:fused", message="m", where="p/q")
        entries = [{"rule": "R6-*", "target": "plan*", "reason": "leased",
                    "expires": "2099-01-01"}]
        out = R.apply_baseline([f], entries,
                               today=datetime.date(2026, 1, 1))
        assert out[0]["severity"] == "suppressed"
        assert out[0]["suppressed_reason"] == "leased"

    def test_expired_baseline_entry_fails(self):
        import datetime

        from repro.analysis import report as R

        today = datetime.date(2026, 8, 7)
        entries = [{"rule": "R6-*", "reason": "old lease",
                    "expires": "2025-01-01"}]
        errs = R._baseline_findings(entries, "analysis-baseline.json",
                                    today)
        assert [e.severity for e in errs] == ["error"]
        assert "expired" in errs[0].message
        # and an expired entry no longer suppresses anything
        f = Finding(rule="R6-pallas-race", severity="error", target="t",
                    message="m", where="w")
        out = R.apply_baseline([f], entries, today=today)
        assert out[0]["severity"] == "error"

    def test_entry_without_reason_or_date_fails(self):
        import datetime

        from repro.analysis import report as R

        errs = R._baseline_findings([{"rule": "*"}], "b.json",
                                    datetime.date(2026, 8, 7))
        kinds = " | ".join(e.message for e in errs)
        assert "no reason" in kinds and "expires" in kinds

    def test_checked_in_baseline_is_well_formed(self):
        from repro.analysis import report as R

        path = os.path.join(_REPO_ROOT, R.BASELINE_FILE)
        entries = R.load_baseline(path)
        assert R._baseline_findings(
            entries, path, __import__("datetime").date.today()) == []
