"""Per-primitive kernel-backend throughput: jnp vs pallas.

The perf baseline for the backend layer (repro.kernels.backend): times every
engine primitive — the two classic sweeps (+ the triangular prefix variant),
the fused ``rho_delta`` against its two-pass formulation, the mixed-precision
fused path, and the halo span-masked primitives — on each backend and writes
a JSON record, so future kernel PRs diff against today's numbers
(``BENCH_core.json`` at the repo root is the committed copy).

On CPU containers the pallas backend runs in *interpret* mode — a
correctness path, orders of magnitude slower than both compiled paths —
so each record carries an ``interpret`` flag and the jnp rows are the
meaningful CPU numbers.  On TPU the ``pallas`` rows are the headline.

    PYTHONPATH=src python -m benchmarks.backend_compare [--n 4096]

``--smoke`` is the CI gate: a quick jnp-gated run plus a small
pallas-interpret exercise pass, failing (exit 1) when

* the fused ``rho_delta`` is less than FUSED_MIN_SPEEDUP x the two-pass
  dense sweep on the jnp CPU baseline (the ISSUE 3 acceptance bar), or
* the block-sparse fused path's speedup over the dense fused path (same
  grid-sorted data, paper-style d_cut) regressed more than SMOKE_TOLERANCE
  relative to the committed ratio (the ISSUE 4 pruning bar), or
* the multi-device distributed row's paired ratio (block-sparse vs dense
  shard phases on a host-device-count mesh, run in a 4-virtual-device
  subprocess) regressed more than SMOKE_TOLERANCE relative to the
  committed ratio, or the shard-layout probe silently degraded (the
  ISSUE 8 bar), or
* any jnp primitive regressed more than SMOKE_TOLERANCE in *relative*
  pairs/s against the committed BENCH_core.json (throughputs are normalized
  by the currently measured jnp range_count rate first, so the gate tracks
  algorithmic regressions rather than CI-machine speed), or
* a stream checkpoint restore breaks tick parity (the ISSUE 9 resilience
  bar: one post-restore ingest must be bit-identical to the uninterrupted
  stream's; the save/restore latencies printed alongside are
  informational, never gated).

``--refresh-baseline`` rewrites BENCH_core.json: the standard-shape record
plus the ISSUE-4 acceptance measurement (block-sparse vs dense fused
``rho_delta`` wall clock at n=64k, d=3, paper-style d_cut, jnp CPU), the
ISSUE-8 distributed rows (dense vs block-sparse shard phases at the
same acceptance shape, plus a smaller smoke shape the CI gate re-measures)
and the ISSUE-9 ``stream_checkpoint`` latency/parity row.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.dpc_types import density_jitter
from repro.core.grid import build_grid
from repro.core.tuning import pick_dcut
from repro.engine import ExecSpec, as_plan
from repro.kernels.backend import get_backend

from .util import CSV, use_compile_cache

PRIMITIVES = ("range_count", "denser_nn", "prefix_nn", "rho_delta_two_pass",
              "rho_delta_fused", "range_count_halo", "denser_nn_halo",
              "rho_delta_fused_dense_gs", "rho_delta_fused_bs")

FUSED_MIN_SPEEDUP = 1.3     # fused vs two-pass, jnp CPU (ISSUE 3 acceptance)
SMOKE_TOLERANCE = 0.30      # relative pairs/s regression tripping the gate
ACCEPT_N = 65536            # ISSUE 4 acceptance shape (n, d, min speedup)
ACCEPT_D = 3
ACCEPT_MIN_SPEEDUP = 3.0
DIST_SMOKE_N = 16384        # distributed smoke shape (gate re-measures it)
DIST_DEVICES = 4            # virtual host devices for the distributed rows


def default_backends() -> list[str]:
    if jax.default_backend() == "tpu":
        return ["jnp", "pallas"]
    return ["jnp", "pallas-interpret"]


def _bench_data(n: int, d: int, seed: int = 0):
    """Clustered-density data: domain 6*d_cut keeps rho ~ tens, so the fused
    path's resolution statistics resemble a real clustering workload."""
    rng = np.random.default_rng(seed)
    d_cut = 900.0
    pts = jnp.asarray(rng.uniform(0, 6 * d_cut, (n, d)), jnp.float32)
    rho_key = jnp.asarray(rng.permutation(n).astype(np.float32))
    # halo layout: each sorted row sees one contiguous window span around it
    width = min(n, 128)
    st = np.clip(np.arange(n) - width // 2, 0, max(n - width, 0))
    starts = jnp.asarray(st[:, None].astype(np.int32))
    ends = jnp.asarray((st + width)[:, None].astype(np.int32))
    return pts, rho_key, d_cut, starts, ends, width


def _bench_data_sparse(n: int, d: int, seed: int = 0):
    """Block-sparse layout rows: same uniform domain, but a *paper-style*
    d_cut (average rho in the tens — the assumption the grid pruning pays
    under) and the points grid-sorted, exactly as the drivers lay them out.
    Returns (pts_sorted, d_cut)."""
    rng = np.random.default_rng(seed)
    pts = rng.uniform(0, 6 * 900.0, (n, d)).astype(np.float32)
    d_cut = float(pick_dcut(pts, target_rho=min(30.0, n / 200)))
    grid = build_grid(jnp.asarray(pts), d_cut)
    return grid.points, d_cut


def bench_backend(name: str, n: int, d: int, repeats: int,
                  precision_rows: bool = True):
    be = get_backend(name)
    pts, rho_key, d_cut, starts, ends, width = _bench_data(n, d)
    jitter = density_jitter(n)

    def two_pass():
        rho = be.range_count(pts, pts, d_cut)
        rk = rho + jitter
        return be.denser_nn(pts, rk, pts, rk)

    runs = {
        "range_count": (lambda: be.range_count(pts, pts, d_cut), n * n),
        "denser_nn": (lambda: be.denser_nn(pts, rho_key, pts, rho_key),
                      n * n),
        "prefix_nn": (lambda: be.prefix_nn(pts), n * n),
        "rho_delta_two_pass": (two_pass, 2 * n * n),
        "rho_delta_fused": (
            lambda: be.rho_delta(pts, pts, d_cut, jitter=jitter), 2 * n * n),
        "range_count_halo": (
            lambda: be.range_count_halo(pts, pts, starts, ends, d_cut,
                                        span_cap=width), n * width),
        "denser_nn_halo": (
            lambda: be.denser_nn_halo(pts, rho_key, pts, rho_key, starts,
                                      ends, d_cut, span_cap=width),
            n * width),
    }
    if precision_rows and be.mxu_dense:
        runs["rho_delta_fused_bf16"] = (
            lambda: be.rho_delta(pts, pts, d_cut, jitter=jitter,
                                 precision="bf16"), 2 * n * n)

    # block-sparse layout rows: dense vs grid-pruned fused rho_delta on the
    # same grid-sorted data at paper-style d_cut.  Both rows use the dense
    # 2*n^2 pair count, so pairs/s is *wall-clock-equivalent* — the sparse
    # row's higher rate IS the pruning win.
    pts_gs, dcut_gs = _bench_data_sparse(n, d)
    runs["rho_delta_fused_dense_gs"] = (
        lambda: be.rho_delta(pts_gs, pts_gs, dcut_gs, jitter=jitter),
        2 * n * n)
    runs["rho_delta_fused_bs"] = (
        lambda: be.rho_delta(pts_gs, pts_gs, dcut_gs, jitter=jitter,
                             layout="block-sparse"), 2 * n * n)

    # Interleaved timing: one pass over the whole primitive set per repeat,
    # so slow machine-load drift hits every primitive equally and the
    # *relative* throughputs (what the smoke gate and the fused-speedup
    # acceptance compare) stay stable on noisy shared CPUs.
    import time as _time

    for fn, _ in runs.values():                    # warmup / compile
        jax.block_until_ready(fn())
    samples = {prim: [] for prim in runs}
    for _ in range(repeats):
        for prim, (fn, _) in runs.items():
            t0 = _time.perf_counter()
            jax.block_until_ready(fn())
            samples[prim].append(_time.perf_counter() - t0)

    out = {}
    for prim, (fn, pairs) in runs.items():
        # best-of-repeats: the minimum is the reproducible statistic on a
        # shared/bursty CPU (load only ever adds time, never subtracts)
        secs = float(np.min(samples[prim]))
        out[prim] = {
            "seconds": secs,
            "pairs_per_s": float(pairs) / secs,
            "interpret": name == "pallas-interpret",
        }
    # fused speedup from *paired* per-repeat ratios: the two formulations
    # run back-to-back inside each repeat, so machine-load drift divides out
    ratios = [t / f for t, f in zip(samples["rho_delta_two_pass"],
                                    samples["rho_delta_fused"])]
    out["_fused_speedup"] = float(np.median(ratios))
    sratios = [t / f for t, f in zip(samples["rho_delta_fused_dense_gs"],
                                     samples["rho_delta_fused_bs"])]
    out["_sparse_speedup"] = float(np.median(sratios))
    return out


def run(n: int, d: int, repeats: int, backends: list[str]):
    csv = CSV("backend_compare")
    csv.header(f"n={n} d={d}")
    rec = {"n": n, "d": d, "d_cut": 900.0,
           "platform": jax.default_backend(),
           "primitives": {}, "fused_speedup": {}, "sparse_speedup": {}}
    for name in backends:
        res = bench_backend(name, n, d, repeats)
        rec["fused_speedup"][name] = res.pop("_fused_speedup")
        rec["sparse_speedup"][name] = res.pop("_sparse_speedup")
        for prim, r in res.items():
            rec["primitives"].setdefault(prim, {})[name] = r
            csv.add(primitive=prim, backend=name, seconds=r["seconds"],
                    pairs_per_s=r["pairs_per_s"])
    return rec


def measure_acceptance(repeats: int = 3) -> dict:
    """The ISSUE 4 acceptance record: block-sparse vs dense fused rho_delta
    wall clock at n=64k, d=3, paper-style d_cut, jnp CPU (grid-sorted)."""
    import time as _time

    be = get_backend("jnp")
    pts, d_cut = _bench_data_sparse(ACCEPT_N, ACCEPT_D)
    jitter = density_jitter(ACCEPT_N)
    forms = {
        "dense": lambda: be.rho_delta(pts, pts, d_cut, jitter=jitter),
        "block_sparse": lambda: be.rho_delta(pts, pts, d_cut, jitter=jitter,
                                             layout="block-sparse"),
    }
    secs = {}
    for name, fn in forms.items():
        jax.block_until_ready(fn())
        ts = []
        for _ in range(repeats):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(_time.perf_counter() - t0)
        secs[name] = float(np.min(ts))
    speedup = secs["dense"] / secs["block_sparse"]
    print(f"[backend_compare] acceptance n={ACCEPT_N}: dense "
          f"{secs['dense']:.2f}s, block-sparse {secs['block_sparse']:.2f}s "
          f"-> {speedup:.2f}x (bar {ACCEPT_MIN_SPEEDUP}x)", flush=True)
    return {"n": ACCEPT_N, "d": ACCEPT_D, "d_cut": float(d_cut),
            "backend": "jnp",
            "dense_seconds": secs["dense"],
            "block_sparse_seconds": secs["block_sparse"],
            "pairs_per_s_equiv_dense": 2 * ACCEPT_N ** 2 / secs["dense"],
            "pairs_per_s_equiv_bs": 2 * ACCEPT_N ** 2 / secs["block_sparse"],
            "speedup": speedup, "min_required": ACCEPT_MIN_SPEEDUP}


# Multi-device shard phases (ISSUE 8): dense vs block-sparse worklists on a
# host-device-count mesh.  XLA's virtual host devices must be configured
# before jax initializes, so the measurement runs in a subprocess; both
# variants run the same _make_rho_dense/_make_delta_dense shard bodies on
# the same grid-sorted padded table, differing only in layout — the paired
# per-repeat ratio is the pruning win and is machine-speed independent.
_DIST_SCRIPT = r"""
import json, sys, time, warnings, os
warnings.filterwarnings("ignore")
os.environ["REPRO_ANALYSIS"] = "suspend"   # bench plans, not production fits
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.grid import build_grid
from repro.core.tuning import pick_dcut
from repro.distributed import dpc as ddpc
from repro.engine import ExecSpec
from repro.engine.planner import plan
from repro.kernels.backend import get_backend

n, d, repeats = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
S = jax.device_count()
mesh = jax.make_mesh((S,), ("data",))
be = get_backend("jnp")

rng = np.random.default_rng(0)
pts = rng.uniform(0, 6 * 900.0, (n, d)).astype(np.float32)
d_cut = float(pick_dcut(pts, target_rho=min(30.0, n / 200)))
grid = build_grid(jnp.asarray(pts), d_cut)
n0 = grid.points.shape[0]
m = -(-n0 // S) * S
pts_s = jnp.pad(grid.points, ((0, m - n0), (0, 0)), constant_values=1e9)
key = rng.permutation(n0).astype(np.float32)   # all-distinct density keys
rk_tab = jnp.asarray(np.concatenate(
    [key, np.full(m - n0, -np.inf, np.float32)]))
rk_q = jnp.asarray(np.concatenate(
    [key, np.full(m - n0, np.inf, np.float32)]))

def phases(layout):
    rho_fn = ddpc._make_rho_dense("data", d_cut, 256, be, layout=layout)
    delta_fn = ddpc._make_delta_dense("data", 256, be, layout=layout)
    sm_rho = jax.jit(shard_map(
        rho_fn, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P("data"), check_vma=False))
    sm_delta = jax.jit(shard_map(
        delta_fn, mesh=mesh, in_specs=(P("data"),) * 4,
        out_specs=(P("data"),) * 3, check_vma=False))
    def run():
        out = (sm_rho(pts_s, pts_s), sm_delta(pts_s, rk_q, pts_s, rk_tab))
        return jax.block_until_ready(out)
    return run

dense_run, bs_run = phases(None), phases("block-sparse")
lay = ddpc.shard_blocksparse_layout(
    plan(None, ExecSpec(backend="jnp", layout="block-sparse")), mesh)
dense_run(); bs_run()                          # warmup / compile
dts, bts = [], []
for _ in range(repeats):
    t0 = time.perf_counter(); dense_run(); dts.append(time.perf_counter() - t0)
    t0 = time.perf_counter(); bs_run(); bts.append(time.perf_counter() - t0)
print("RESULT" + json.dumps({
    "platform": jax.devices()[0].platform,
    "n": n, "d": d, "d_cut": d_cut, "devices": S, "backend": "jnp",
    "layout_probe": lay,
    "dense_seconds": float(np.min(dts)), "bs_seconds": float(np.min(bts)),
    "pairs_per_s_equiv_dense": 2 * n * n / float(np.min(dts)),
    "pairs_per_s_equiv_bs": 2 * n * n / float(np.min(bts)),
    "speedup": float(np.median([a / b for a, b in zip(dts, bts)]))}))
"""


def measure_distributed(n: int, d: int, repeats: int = 3,
                        devices: int = DIST_DEVICES) -> dict:
    """The distributed row: dense vs block-sparse shard phases on a
    ``devices``-device virtual CPU mesh (subprocess pinned to the CPU, so
    it never contends for an accelerator the parent holds; see
    ``_DIST_SCRIPT``).  The record says ``platform: cpu``."""
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run([sys.executable, "-c", _DIST_SCRIPT,
                           str(n), str(d), str(repeats)],
                          env=env, capture_output=True, text=True,
                          timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError("distributed bench subprocess failed:\n"
                           + proc.stderr[-3000:])
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    rec = json.loads(line[len("RESULT"):])
    print(f"[backend_compare] distributed ({rec['platform']}) n={rec['n']} "
          f"S={rec['devices']}: dense shard phases "
          f"{rec['dense_seconds']:.2f}s, block-sparse "
          f"{rec['bs_seconds']:.2f}s -> {rec['speedup']:.2f}x "
          f"(probe: {rec['layout_probe']})", flush=True)
    return rec


def measure_checkpoint(repeats: int = 5, capacity: int = 4096,
                       batch: int = 256, d: int = 3) -> dict:
    """The ISSUE 9 resilience row: crash-safe stream-checkpoint latency
    (save / restore wall clock and file size at the engine's default
    window shape, jnp backend, steady-state ring) plus the restore
    contract itself — one post-restore ingest must be bit-identical to
    the uninterrupted stream's.  Latency is informational (min over
    ``repeats``); only a parity break gates."""
    import tempfile
    import time

    from repro.stream.stream_dpc import StreamDPC, StreamDPCConfig

    rng = np.random.default_rng(7)
    d_cut = 900.0
    pts = rng.uniform(0, 6 * d_cut,
                      (capacity + 3 * batch, d)).astype(np.float32)
    cfg = StreamDPCConfig(d_cut=d_cut, capacity=capacity, batch_cap=batch,
                          rho_min=3.0, exec_spec=ExecSpec(backend="jnp"))
    s = StreamDPC(cfg)
    s.initialize(pts[:capacity])
    s.ingest(pts[capacity:capacity + 2 * batch])   # steady state: ring wraps
    saves, restores = [], []
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "stream.ckpt")
        for _ in range(repeats):
            t0 = time.perf_counter()
            s.save(path)
            saves.append(time.perf_counter() - t0)
        size = os.path.getsize(path)
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = StreamDPC.restore(path)
            restores.append(time.perf_counter() - t0)
        tail = pts[capacity + 2 * batch:]
        a = s.ingest(tail)
        b = r.ingest(tail)
    parity = bool(np.array_equal(a.labels, b.labels)
                  and np.array_equal(a.stable_ids, b.stable_ids))
    rec = {"capacity": capacity, "batch_cap": batch, "d": d,
           "backend": "jnp",
           "save_ms": float(np.min(saves) * 1e3),
           "restore_ms": float(np.min(restores) * 1e3),
           "bytes": int(size),
           "post_restore_parity": parity}
    print(f"[backend_compare] stream checkpoint (capacity={capacity}, "
          f"d={d}): save {rec['save_ms']:.1f} ms, restore "
          f"{rec['restore_ms']:.1f} ms, {size / 1e6:.2f} MB, "
          f"post-restore parity={'OK' if parity else 'BROKEN'}",
          flush=True)
    return rec


def dist_gate(committed, repeats: int,
              tolerance: float = SMOKE_TOLERANCE) -> list[str]:
    """Smoke check of the multi-device row: the probe must keep
    block-sparse enabled, and the paired dense/block-sparse shard-phase
    ratio must hold within ``tolerance`` of the committed record."""
    ref = committed.get("distributed_multidev", {}).get("smoke")
    if ref is None:
        return ["committed baseline lacks the distributed multi-device "
                "smoke row (refresh BENCH_core.json)"]
    now = measure_distributed(ref["n"], ref["d"], repeats=repeats,
                              devices=ref["devices"])
    failures = []
    if now["layout_probe"] != "block-sparse":
        failures.append(f"shard_blocksparse_layout degraded on the "
                        f"{now['devices']}-device mesh: "
                        f"{now['layout_probe']!r}")
    if now["speedup"] < (1.0 - tolerance) * ref["speedup"]:
        failures.append(
            f"distributed block-sparse vs dense shard phases "
            f"{now['speedup']:.2f}x < (1-{tolerance})x committed "
            f"{ref['speedup']:.2f}x")
    return failures


def smoke_gate(rec, committed, tolerance: float = SMOKE_TOLERANCE):
    """Relative-throughput regression check vs the committed baseline."""
    failures = []
    sp = rec["fused_speedup"].get("jnp", 0.0)
    if sp < FUSED_MIN_SPEEDUP:
        failures.append(f"jnp fused rho_delta speedup {sp:.2f}x "
                        f"< required {FUSED_MIN_SPEEDUP}x")
    ssp = rec.get("sparse_speedup", {}).get("jnp", 0.0)
    ssp_ref = committed.get("sparse_speedup", {}).get("jnp")
    if ssp_ref is None:
        failures.append("committed baseline lacks the jnp sparse_speedup "
                        "ratio (refresh BENCH_core.json)")
    elif ssp < (1.0 - tolerance) * ssp_ref:
        failures.append(f"jnp block-sparse speedup {ssp:.2f}x < "
                        f"(1-{tolerance})x committed {ssp_ref:.2f}x")
    try:
        base_now = rec["primitives"]["range_count"]["jnp"]["pairs_per_s"]
        base_ref = committed["primitives"]["range_count"]["jnp"]["pairs_per_s"]
    except KeyError:
        return failures + ["committed baseline lacks jnp range_count row"]
    for prim, rows in committed["primitives"].items():
        for name, ref in rows.items():
            if ref.get("interpret"):
                continue        # interpret timings are not performance
            now = rec["primitives"].get(prim, {}).get(name)
            if now is None:
                failures.append(f"{prim}/{name}: row missing from this run")
                continue
            rel_now = now["pairs_per_s"] / base_now
            rel_ref = ref["pairs_per_s"] / base_ref
            if rel_now < (1.0 - tolerance) * rel_ref:
                failures.append(
                    f"{prim}/{name}: relative pairs/s {rel_now:.3f} < "
                    f"(1-{tolerance})x committed {rel_ref:.3f}")
    return failures


def _export_obs(path: str | None):
    """Write the repro.obs metrics/trace snapshot accumulated by this run
    (worklist builds/cache hits, plan-cache traffic, any spans) so CI can
    archive and diff it alongside the throughput record."""
    if not path:
        return
    from repro.obs import report as obs_report
    obs_report.export_snapshot(path)
    print(f"[backend_compare] wrote obs snapshot to {path}", flush=True)


def main(n: int = 4096, d: int = 3, repeats: int = 3,
         backends: list[str] | None = None,
         out: str = "experiments/backends", smoke: bool = False,
         baseline: str = "BENCH_core.json",
         refresh_baseline: bool = False, obs_snapshot: str | None = None):
    use_compile_cache()
    if smoke:
        # gated jnp pass at the committed shape + a small kernel exercise
        committed = json.load(open(baseline))
        rec = run(n=committed.get("n", 2048), d=committed.get("d", 3),
                  repeats=max(repeats, 5), backends=["jnp"])
        exercise = run(n=512, d=d, repeats=1,
                       backends=["pallas-interpret"]
                       if jax.default_backend() != "tpu" else ["pallas"])
        del exercise  # correctness/coverage only; never gated
        failures = smoke_gate(rec, committed)
        failures += dist_gate(committed, repeats=max(repeats, 3))
        ck = measure_checkpoint(repeats=max(repeats, 3))
        rec["stream_checkpoint"] = ck
        if not ck["post_restore_parity"]:
            failures.append("stream checkpoint restore broke tick parity "
                            "(post-restore ingest != uninterrupted stream)")
        _export_obs(obs_snapshot)
        if failures:
            print("[backend_compare --smoke] FAIL", flush=True)
            for f in failures:
                print("  -", f, flush=True)
            sys.exit(1)
        print(f"[backend_compare --smoke] OK (jnp fused speedup "
              f"{rec['fused_speedup']['jnp']:.2f}x, block-sparse "
              f"{rec['sparse_speedup']['jnp']:.2f}x)", flush=True)
        return rec

    rec = run(n=n, d=d, repeats=repeats,
              backends=backends or default_backends())
    if refresh_baseline:
        rec["acceptance_64k"] = measure_acceptance(repeats=repeats)
        rec["distributed_multidev"] = {
            "acceptance": measure_distributed(ACCEPT_N, ACCEPT_D,
                                              repeats=repeats),
            "smoke": measure_distributed(DIST_SMOKE_N, ACCEPT_D,
                                         repeats=repeats),
        }
        rec["stream_checkpoint"] = measure_checkpoint(repeats=repeats)
        with open(baseline, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"[backend_compare] refreshed {baseline}", flush=True)
    else:
        os.makedirs(out, exist_ok=True)
        path = os.path.join(out, "backend_compare.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"[backend_compare] wrote {path}", flush=True)
    for name, sp in rec["fused_speedup"].items():
        print(f"[backend_compare] {name}: fused rho_delta {sp:.2f}x over "
              f"two-pass; block-sparse {rec['sparse_speedup'][name]:.2f}x "
              f"over dense fused", flush=True)
    _export_obs(obs_snapshot)
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--exec", dest="exec_spec", default=None,
                    help="uniform execution flag backend:layout:precision "
                         "(repro.engine.ExecSpec.parse): bench that one "
                         "backend — layout/precision are validated against "
                         "it (every run still records the dense AND "
                         "block-sparse fused rows; that pairing IS the "
                         "layout comparison)")
    ap.add_argument("--backends", default=None,
                    help="comma-separated (legacy; prefer --exec)")
    ap.add_argument("--out", default="experiments/backends")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate vs the committed BENCH_core.json")
    ap.add_argument("--baseline", default="BENCH_core.json")
    ap.add_argument("--refresh-baseline", action="store_true",
                    help="rewrite the committed baseline, including the "
                         "n=64k block-sparse acceptance record")
    ap.add_argument("--obs-snapshot", default=None,
                    help="write the repro.obs metrics snapshot here "
                         "(CI archives it next to the throughput record)")
    a = ap.parse_args()
    backends = a.backends.split(",") if a.backends else None
    if a.exec_spec:
        if backends:
            ap.error("--exec and --backends are mutually exclusive")
        # plan once: resolves the backend name and fail-fasts on bad
        # names / impossible combos before any timing runs
        backends = [as_plan(ExecSpec.parse(a.exec_spec)).backend_name]
    main(n=a.n, d=a.d, repeats=a.repeats,
         backends=backends, out=a.out,
         smoke=a.smoke, baseline=a.baseline,
         refresh_baseline=a.refresh_baseline,
         obs_snapshot=a.obs_snapshot)
