"""Distributed DPC (shard_map) equals the single-device exact algorithms.

Multi-device CPU requires XLA_FLAGS set before jax initializes, so the
actual comparison runs in a subprocess with 4 fake host devices.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import warnings, json
warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.distributed import distributed_dpc, DistDPCConfig
from repro.core.exdpc import run_exdpc
from repro.core.scan import run_scan
from repro.data.points import gaussian_mixture, with_noise

out = {}
for seed, d, k in ((0, 2, 6), (1, 3, 4)):
    pts, labels = gaussian_mixture(1200, k=k, d=d, overlap=0.03, seed=seed)
    pts, labels = with_noise(pts, labels, 0.05, seed=seed)
    d_cut = 3000.0
    mesh = jax.make_mesh((4,), ("data",))
    res_d = distributed_dpc(pts, DistDPCConfig(d_cut=d_cut), mesh)
    res_e = run_exdpc(pts, d_cut)
    res_s = run_scan(pts, d_cut)
    key = f"{seed}_{d}"
    out[key] = {
        "rho_eq_ex": bool(jnp.all(res_d.rho == res_e.rho)),
        "rho_eq_scan": bool(jnp.all(res_d.rho == res_s.rho)),
        "delta_close": bool(jnp.allclose(res_d.delta, res_e.delta,
                                         rtol=1e-5, atol=1e-4)),
        "parent_eq": float((np.asarray(res_d.parent)
                            == np.asarray(res_e.parent)).mean()),
    }

# pallas backend parity: the per-shard dense MXU phases (interpret mode on
# CPU) must reproduce the single-device exact result.  Uniform data keeps
# the expanded-form d2 well conditioned, so equality is exact.
rng = np.random.default_rng(5)
d_cut = 900.0
pts = rng.uniform(0, 30 * d_cut, size=(1200, 3)).astype(np.float32)
res_p = distributed_dpc(pts, DistDPCConfig(d_cut=d_cut,
                                           backend="pallas-interpret"), mesh)
res_r = run_exdpc(pts, d_cut)
res_o = run_scan(jnp.asarray(pts), d_cut)
both_inf = jnp.isinf(res_p.delta) & jnp.isinf(res_r.delta)
out["pallas"] = {
    "rho_eq_ex": bool(jnp.all(res_p.rho == res_r.rho)),
    "rho_eq_scan": bool(jnp.all(res_p.rho == res_o.rho)),
    "delta_close": bool(jnp.all((res_p.delta == res_r.delta) | both_inf)),
    "parent_eq": float((np.asarray(res_p.parent)
                        == np.asarray(res_r.parent)).mean()),
}
print("RESULT" + json.dumps(out))
"""

# Regression pin (runs in CI — deliberately NOT slow-marked): block-sparse
# exec on a MULTI-device mesh must stay exact AND stay *enabled*.  With
# the one-hot ring walk, shard_blocksparse_layout's R1 probe passes on
# multi-partition meshes, so the shard phases run block-sparse worklists —
# this check fails both if the probe silently degrades again (layout flips
# to None) and if the enabled phases ever stop bit-matching run_exdpc
# (which is how the pinned jax-0.4.37 XLA SPMD miscompile manifested).
_BS_GUARD_SCRIPT = r"""
import warnings, json
warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.distributed import distributed_dpc
from repro.distributed.dpc import shard_blocksparse_layout
from repro.core.exdpc import run_exdpc
from repro.data.points import gaussian_mixture
from repro.engine import ExecSpec
from repro.engine.planner import plan

mesh = jax.make_mesh((4,), ("data",))
pl = plan(None, ExecSpec(backend="jnp", layout="block-sparse"))
layout = shard_blocksparse_layout(pl, mesh)
pts, _ = gaussian_mixture(1024, k=5, d=2, overlap=0.03, seed=3)
res = distributed_dpc(pts, mesh=mesh, d_cut=2500.0,
                      exec_spec=ExecSpec(backend="jnp",
                                         layout="block-sparse"))
ref = run_exdpc(pts, 2500.0, exec_spec=ExecSpec(backend="jnp"))
binf = jnp.isinf(res.delta) & jnp.isinf(ref.delta)
out = {"bs_multidev": {
    "layout": layout,
    "rho_eq_ex": bool(jnp.all(res.rho == ref.rho)),
    "rho_eq_scan": True,
    "delta_close": bool(jnp.all((res.delta == ref.delta) | binf)),
    "parent_eq": float((np.asarray(res.parent)
                        == np.asarray(ref.parent)).mean()),
}}
print("RESULT" + json.dumps(out))
"""


def _run_subprocess(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


@pytest.mark.slow
def test_distributed_matches_exact():
    out = _run_subprocess(_SCRIPT)
    for key, r in out.items():
        assert r["rho_eq_ex"], (key, r)
        assert r["rho_eq_scan"], (key, r)
        assert r["delta_close"], (key, r)
        assert r["parent_eq"] == 1.0, (key, r)


def test_multidev_block_sparse_enabled_and_exact():
    """ISSUE 8 acceptance: per-shard block-sparse on a 4-device mesh is
    *enabled* (the R1 probe passes on the one-hot ring walk, so
    shard_blocksparse_layout returns "block-sparse") and bit-matches
    run_exdpc.  Not slow-marked on purpose: CI must catch both a silent
    probe degrade and a miscompile-shaped divergence."""
    out = _run_subprocess(_BS_GUARD_SCRIPT)
    r = out["bs_multidev"]
    assert r["layout"] == "block-sparse", r
    assert r["rho_eq_ex"] and r["delta_close"] and r["parent_eq"] == 1.0, r


_HALO_SCRIPT = r"""
import warnings, json
warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.distributed import distributed_dpc, DistDPCConfig
from repro.core.exdpc import run_exdpc
from repro.kernels import get_backend

rng = np.random.default_rng(5)
d_cut = 900.0
pts = rng.uniform(0, 10 * d_cut, size=(800, 3)).astype(np.float32)
mesh = jax.make_mesh((4,), ("data",))
res_e = run_exdpc(pts, d_cut)
out = {}

# --- pallas-interpret halo: the optimized path must exercise the kernel
#     backend — count the halo-primitive invocations to prove there is no
#     silent jnp fallback ---
be = get_backend("pallas-interpret")
calls = {"rho": 0, "nn": 0}
orig_rc, orig_nn = be.range_count_halo, be.denser_nn_halo
def _rc(*a, **k):
    calls["rho"] += 1
    return orig_rc(*a, **k)
def _nn(*a, **k):
    calls["nn"] += 1
    return orig_nn(*a, **k)
be.range_count_halo, be.denser_nn_halo = _rc, _nn
try:
    res_h = distributed_dpc(pts, DistDPCConfig(
        d_cut=d_cut, strategy="halo", backend="pallas-interpret"), mesh)
finally:
    be.range_count_halo, be.denser_nn_halo = orig_rc, orig_nn
both_inf = jnp.isinf(res_h.delta) & jnp.isinf(res_e.delta)
out["pallas_halo"] = {
    "rho_calls": calls["rho"], "nn_calls": calls["nn"],
    "rho_eq": bool(jnp.all(res_h.rho == res_e.rho)),
    "delta_eq": bool(jnp.all((res_h.delta == res_e.delta) | both_inf)),
    "parent_eq": float((np.asarray(res_h.parent)
                        == np.asarray(res_e.parent)).mean()),
}

# --- jnp halo (the gather-form backend primitives) stays exact too ---
res_j = distributed_dpc(pts, DistDPCConfig(d_cut=d_cut, strategy="halo"),
                        mesh)
both_inf = jnp.isinf(res_j.delta) & jnp.isinf(res_e.delta)
out["jnp_halo"] = {
    "rho_calls": 1, "nn_calls": 1,
    "rho_eq": bool(jnp.all(res_j.rho == res_e.rho)),
    "delta_eq": bool(jnp.all((res_j.delta == res_e.delta) | both_inf)),
    "parent_eq": float((np.asarray(res_j.parent)
                        == np.asarray(res_e.parent)).mean()),
}
print("RESULT" + json.dumps(out))
"""


def test_halo_strategy_runs_kernel_backend():
    """ISSUE 3 acceptance: the halo phases route through the pallas(-interpret)
    backend — kernel primitives actually invoked, results exact vs Ex-DPC."""
    out = _run_subprocess(_HALO_SCRIPT)
    for key, r in out.items():
        assert r["rho_calls"] >= 1 and r["nn_calls"] >= 1, (key, r)
        assert r["rho_eq"], (key, r)
        assert r["delta_eq"], (key, r)
        assert r["parent_eq"] == 1.0, (key, r)


# Pallas block-sparse shard phases, every worklist built on the device
# inside shard_map (pallas-interpret on 4 fake devices): exact against the
# single-device algorithms, the layout decision, the shards' count lists
# against the host builder, the fallback's power-of-two pad, and a re-fit
# in a new order compiling nothing.
_DEVICE_WL_SCRIPT = r"""
import warnings, json
warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro import obs
from repro.distributed import distributed_dpc
from repro.distributed import dpc
from repro.core.exdpc import run_exdpc
from repro.core.scan import run_scan
from repro.core.grid import build_grid
from repro.data.points import gaussian_mixture, with_noise
from repro.engine import ExecSpec
from repro.engine.planner import plan
from repro.kernels import ops
from repro.kernels.blocksparse import build_flat_worklist

mesh = jax.make_mesh((4,), ("data",))
spec = ExecSpec(backend="pallas-interpret", layout="block-sparse")
out = {"layout": dpc.shard_blocksparse_layout(plan(None, spec), mesh),
       "gauge": obs.get_metric("dist_bs_layout").value(
           reason="device-worklist"),
       "enabled": obs.get_metric("dist_bs_enabled").value(
           reason="device-worklist")}

cases = {}
for seed, d, k in ((0, 2, 6), (1, 3, 4)):
    pts, lab = gaussian_mixture(1200, k=k, d=d, overlap=0.03, seed=seed)
    cases[f"mixture_{seed}_{d}"] = (with_noise(pts, lab, 0.05, seed=seed)[0],
                                    3000.0)
rng = np.random.default_rng(7)       # integer lattice: exact distance ties
cases["lattice"] = ((rng.integers(0, 13, (900, 3)) * 64.0).astype(np.float32),
                    160.0)
cases["n_1203"] = (gaussian_mixture(1203, k=5, d=2, overlap=0.03, seed=4)[0],
                   3000.0)
obs.configure(level="metrics")
for name, (pts, d_cut) in cases.items():
    obs.reset_spans()
    res = distributed_dpc(pts, mesh=mesh, d_cut=d_cut, exec_spec=spec)
    (fb,) = [s["attrs"] for s in obs.spans() if s["name"] == "dist.fallback"]
    r = {"padded": fb["padded"], "unresolved": fb["unresolved"]}
    for ref_name, ref in (("ex", run_exdpc(pts, d_cut)),
                          ("scan", run_scan(jnp.asarray(pts), d_cut))):
        binf = jnp.isinf(res.delta) & jnp.isinf(ref.delta)
        par, rpar = np.asarray(res.parent), np.asarray(ref.parent)
        # on exact distance ties: a parent as near, and strictly denser
        rk = np.asarray(res.rho_key)
        p = np.asarray(pts, np.float64)
        tie = (par >= 0) & (rpar >= 0) & (rk[np.maximum(par, 0)] > rk)
        tie &= np.isclose(np.linalg.norm(p - p[np.maximum(par, 0)], axis=1),
                          np.linalg.norm(p - p[np.maximum(rpar, 0)], axis=1))
        r[ref_name] = {
            "rho": bool(jnp.all(res.rho == ref.rho)),
            "delta": bool(jnp.all((res.delta == ref.delta) | binf)),
            "parent": float((par == rpar).mean()),
            "parent_or_tie": float(((par == rpar) | tie).mean())}
    out[name] = r

# every shard's device-built count list holds the host builder's pairs
pts, d_cut = cases["mixture_0_2"]
grid = build_grid(jnp.asarray(pts), d_cut)
n = grid.points.shape[0]
pre = dpc._prog_cut_prefix(mesh, "data", n, n // 4, d_cut, 256)(grid.points)
n_chunks = 1 << max(int(np.asarray(pre[4])[:, 0].max()) - 1, 0).bit_length()
meta, lb, live = dpc._prog_cut_list(mesh, "data", d_cut, n_chunks)(*pre)
meta = np.asarray(meta).reshape(4, n_chunks, 4, -1)
same = []
for s in range(4):
    x = np.asarray(grid.points)[s * (n // 4):(s + 1) * (n // 4)]
    host = build_flat_worklist(x, np.asarray(grid.points), d_cut,
                               block_n=256, block_m=ops.DENSITY_BLOCK_M,
                               count=True)
    m, hm = meta[s], np.asarray(host.meta)
    pairs = sorted(map(tuple, m[:, [0, 1, 3]].transpose(0, 2, 1).reshape(
        -1, 3)[m[:, 3].reshape(-1) >= 0].tolist()))
    hpairs = sorted(map(tuple, hm[:, [0, 1, 3]].transpose(0, 2, 1).reshape(
        -1, 3)[hm[:, 3].reshape(-1) >= 0].tolist()))
    same.append(pairs == hpairs and len(pairs) == host.n_kept)
out["count_lists_equal"] = same

# two orders of one draw: the second fit compiles nothing
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, d, **_: compiles.append(ev)
    if ev == "/jax/core/compile/backend_compile_duration" else None)
order = np.random.default_rng(3).permutation(len(pts))
distributed_dpc(pts[order], mesh=mesh, d_cut=d_cut, exec_spec=spec)
compiles.clear()
res = distributed_dpc(pts[order[::-1]], mesh=mesh, d_cut=d_cut,
                      exec_spec=spec)
jax.block_until_ready(res.delta)
out["refit_compiles"] = len(compiles)
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def device_worklists():
    return _run_subprocess(_DEVICE_WL_SCRIPT)


def test_pallas_shards_keep_block_sparse(device_worklists):
    """The pallas shard phases stay block-sparse on a 4-device mesh: the
    layout decision, its reason, the counter and the gauge."""
    out = device_worklists
    assert out["layout"] == "block-sparse"
    assert out["gauge"] == 1.0 and out["enabled"] >= 1


@pytest.mark.parametrize("case", ["mixture_0_2", "mixture_1_3", "lattice",
                                  "n_1203"])
def test_pallas_device_worklists_exact(device_worklists, case):
    """rho, delta and parent equal ``run_exdpc``'s, and ``run_scan``'s up
    to exact distance ties (the lattice), whose parents scan picks by
    input index and the sorted-table algorithms by sorted slot."""
    r = device_worklists[case]
    assert r["ex"] == {"rho": True, "delta": True, "parent": 1.0,
                       "parent_or_tie": 1.0}, r
    assert r["scan"]["rho"] and r["scan"]["delta"], r
    assert r["scan"]["parent_or_tie"] == 1.0, r
    if case != "lattice":
        assert r["scan"]["parent"] == 1.0, r


def test_pallas_fallback_pads_to_a_power_of_two(device_worklists):
    for case in ("mixture_0_2", "mixture_1_3", "lattice", "n_1203"):
        r = device_worklists[case]
        per = r["padded"] // 4
        assert r["padded"] == 4 * per and per & (per - 1) == 0, r
        assert 0 < r["unresolved"] <= r["padded"], r


def test_shard_count_lists_equal_the_host_builder(device_worklists):
    assert device_worklists["count_lists_equal"] == [True] * 4


def test_refit_in_a_new_order_compiles_nothing(device_worklists):
    assert device_worklists["refit_compiles"] == 0
