"""Smoke run of the DPC engine on one TPU chip (or four with ``--chips 4``).

    python chip_smoke.py              # one chip, one process
    python chip_smoke.py --chips 4    # distributed fit + sharded stream only

The default run drives the paper's batch job through ``DPCEngine`` at its
published size: Approx-DPC on the Household proxy (n = 2,049,280, d = 4,
d_cut = 1000, rho_min = 10; ``configs/dpc_paper.py``) in the block-sparse
layout, with the backend left to the planner.  It then checks the result
against references on the same chip, answers ``predict`` batches and runs a
few streaming ticks.  ``--chips 4`` runs the mesh paths only: the
distributed Ex-DPC fit against the one-chip fit, and sharded stream ticks
against replicated ones.

Every line but the last is a human-readable report.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed on a TPU.  With no TPU the script exits non-zero before any
phase.  ``--rehearse`` is for testing on a CPU: tiny sizes, the Pallas
interpreter, no device check, and exit code 3 instead of a result line.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` points, else to
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

REHEARSAL_PASSED = 3        # exit code of a clean --rehearse run

# full-size and rehearsal sizes: fit n, scan-check n, jnp-reference n,
# distributed-fit n, query batch and batches, stream window, batch, ticks
FULL = dict(n=2_049_280, check_n=65_536, ref_n=262_144, dist_n=262_144,
            q=4096, q_batches=3, window=65_536, batch=256, ticks=5)
REHEARSAL = dict(n=6_000, check_n=2_048, ref_n=3_000, dist_n=3_000, q=256,
                 q_batches=2, window=1_024, batch=64, ticks=2)


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Runs named phases, logs each outcome, and remembers failures."""

    def __init__(self):
        self.failed: list[str] = []

    def run(self, name, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:                   # noqa: BLE001 — report and go on
            self.failed.append(name)
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.3f}s")
            log(traceback.format_exc())
            return None
        log(f"[{name}] ok in {time.perf_counter() - t0:.3f}s")
        return out


class CompileClock:
    """Seconds spent in XLA backend compiles (cache reads included) and
    the persistent-cache hits, from jax's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def centers_of(engine):
    import numpy as np

    return np.nonzero(np.asarray(engine.clustering.centers))[0]


# ------------------------------------------------------------- one chip
def fit_phase(pts, sz, spec_kw, want_backend, clock):
    import numpy as np
    from repro.engine import DPCEngine, ExecSpec

    eng = DPCEngine(d_cut=sz["d_cut"], rho_min=sz["rho_min"],
                    algorithm="approxdpc",
                    exec_spec=ExecSpec(layout="block-sparse", **spec_kw))
    c0 = clock.seconds
    t0 = time.perf_counter()
    eng.fit(pts)
    first = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    t0 = time.perf_counter()
    eng.fit(pts)
    warm = time.perf_counter() - t0
    log(f"plan: {eng.plan.describe()}")
    check(eng.plan.backend_name == want_backend,
          f"resolved backend {eng.plan.backend_name!r}, want "
          f"{want_backend!r}")
    wl = eng.plan.telemetry()["worklists"]
    log(f"worklists: {wl['strategy']}")
    n_cl = int(eng.clustering.num_clusters)
    log(f"fit n={pts.shape[0]}: first call {first:.3f}s (backend compile "
        f"{compile_s:.3f}s), warm call {warm:.3f}s, clusters {n_cl}")
    check(n_cl >= 1, "no cluster found")
    rho = np.asarray(eng.result.rho)
    check(bool(np.isfinite(rho).all()) and rho.min() >= 1,
          "rho must count at least the point itself")
    return eng


def scan_phase(pts, sz, spec_kw):
    """Approx-DPC against the Scan oracle on the same chip (same spec).

    Approx-DPC's contract (paper Theorem 4, README): exact rho, exact delta
    for every point whose delta exceeds d_cut, the same centers; rule-1
    points report delta = d_cut by design, so those deltas are not
    compared."""
    import numpy as np
    from repro.core.metrics import rand_index
    from repro.engine import DPCEngine, ExecSpec

    fits = {}
    for algo in ("approxdpc", "scan"):
        fits[algo] = DPCEngine(
            d_cut=sz["d_cut"], rho_min=sz["rho_min"], algorithm=algo,
            exec_spec=ExecSpec(layout="block-sparse", **spec_kw)).fit(pts)
    ap, sc = fits["approxdpc"], fits["scan"]
    rho_eq = bool(np.array_equal(np.asarray(ap.result.rho),
                                 np.asarray(sc.result.rho)))
    d_ap, d_sc = np.asarray(ap.result.delta), np.asarray(sc.result.delta)
    roots = d_ap > sz["d_cut"]
    roots_eq = bool(np.array_equal(d_ap[roots], d_sc[roots]))
    c_ap, c_sc = centers_of(ap), centers_of(sc)
    centers_eq = bool(np.array_equal(c_ap, c_sc))
    l_ap, l_sc = np.asarray(ap.labels_), np.asarray(sc.labels_)
    same = float(np.mean(l_ap == l_sc))
    ri = float(rand_index(l_ap, l_sc))
    log(f"scan check n={pts.shape[0]}: rho equal {rho_eq}, root deltas "
        f"equal {roots_eq} ({int(roots.sum())} roots), centers equal "
        f"{centers_eq} ({len(c_ap)}), labels equal on {same:.6f}, "
        f"rand index {ri:.6f}")
    check(rho_eq and roots_eq and centers_eq, "approxdpc != scan")
    check(ri >= 0.95, f"labels rand index {ri} < 0.95")


def jnp_phase(pts, sz, spec_kw):
    """The same fit on the kernels and on the jnp reference: the centers
    must be the same points.  Both count with the same direct-difference
    arithmetic (``kernels.ref.sq_sum_parts``), so rho agreement is
    reported too."""
    import numpy as np
    from repro.engine import DPCEngine, ExecSpec

    out = {}
    for name, kw in (("kernel", spec_kw), ("jnp", {"backend": "jnp"})):
        eng = DPCEngine(d_cut=sz["d_cut"], rho_min=sz["rho_min"],
                        algorithm="approxdpc",
                        exec_spec=ExecSpec(layout="block-sparse", **kw))
        t0 = time.perf_counter()
        eng.fit(pts)
        out[name] = eng
        log(f"jnp check: {name} fit {time.perf_counter() - t0:.3f}s, "
            f"plan {eng.plan.describe()}")
    a, b = out["kernel"], out["jnp"]
    rho_a, rho_b = np.asarray(a.result.rho), np.asarray(b.result.rho)
    ca, cb = centers_of(a), centers_of(b)
    same = bool(np.array_equal(ca, cb))
    log(f"jnp check n={pts.shape[0]}: centers {len(ca)} vs {len(cb)}, same "
        f"points {same}; rho equal on {np.mean(rho_a == rho_b):.6f}, max "
        f"|drho| {np.abs(rho_a - rho_b).max():.0f}; labels equal on "
        f"{np.mean(a.labels_ == b.labels_):.6f}")
    check(same, "centers differ from the jnp reference")


def predict_phase(eng, pts, sz, seed=1):
    """Label queries scattered around the fitted points (a d_cut-wide
    Gaussian), so both HITs and out-of-coverage fallbacks occur."""
    import numpy as np
    from repro.stream.service import QueryStatus

    rng = np.random.default_rng(seed)
    m = sz["q"] * sz["q_batches"]
    queries = (pts[rng.integers(0, len(pts), m)]
               + rng.normal(0.0, sz["d_cut"], (m, pts.shape[1]))
               ).astype(np.float32)
    ref = np.asarray(pts, np.float64)
    labels = np.asarray(eng.labels_)
    for b in range(sz["q_batches"]):
        q = queries[b * sz["q"]:(b + 1) * sz["q"]]
        t0 = time.perf_counter()
        res = eng.predict(q)
        dt = time.perf_counter() - t0
        counts = {s.name: int((res.status == int(s)).sum())
                  for s in QueryStatus}
        log(f"predict batch {b}: {len(q)} queries in {dt:.3f}s, {counts}")
        check(counts["HIT"] + counts["MISS_FALLBACK"] == len(q),
              "every query must be answered")
        # exact NN on the host for the first 64 queries of the batch
        sub = q[:64].astype(np.float64)
        nn = np.array([np.argmin(((ref - s) ** 2).sum(-1)) for s in sub])
        nn_d = np.sqrt(((ref[nn] - sub) ** 2).sum(-1))
        hit = nn_d < sz["d_cut"] * (1 - 1e-4)
        miss = nn_d > sz["d_cut"] * (1 + 1e-4)
        st = res.status[:64]
        check(bool((st[hit] == int(QueryStatus.HIT)).all())
              and bool((st[miss] == int(QueryStatus.MISS_FALLBACK)).all()),
              "HIT/MISS status disagrees with the exact nearest neighbour")
        check(bool((res.labels[:64][hit] == labels[nn[hit]]).all()),
              "HIT label differs from the exact nearest neighbour's")


def stream_window(sz, seed=0):
    import numpy as np
    from repro.data.points import drifting_batches

    batches = [p for p, _, _ in drifting_batches(
        sz["batch"], sz["window"] // sz["batch"] + sz["ticks"], d=4,
        seed=seed)]
    k = sz["window"] // sz["batch"]
    return np.concatenate(batches[:k]), batches[k:]


def stream_phase(sz, spec_kw, mesh=None):
    """``partial_fit`` ticks into a full window (sharded over ``mesh`` when
    given); the last tick must equal
    Approx-DPC re-run on the window contents (README's parity contract).
    Returns the final tick's (rho, delta, parent, labels)."""
    import numpy as np
    from repro.core.approxdpc import run_approxdpc
    from repro.core.labels import assign_labels
    from repro.engine import DPCEngine, ExecSpec

    spec = ExecSpec(layout="block-sparse", **spec_kw)
    init, ticks = stream_window(sz)
    eng = DPCEngine(d_cut=sz["d_cut"], rho_min=sz["rho_min"],
                    algorithm="approxdpc", exec_spec=spec, mesh=mesh,
                    window_capacity=sz["window"], batch_cap=sz["batch"])
    # the first tick builds the stream; the bulk load then fills the window
    # in one solve (a mesh engine has no batch fit to seed it from)
    eng.partial_fit(init[:sz["batch"]])
    eng.stream.initialize(init)
    for t, b in enumerate(ticks):
        t0 = time.perf_counter()
        tick = eng.partial_fit(b)
        np.asarray(tick.labels)
        log(f"tick {t}: {len(b)} points into {sz['window']} in "
            f"{time.perf_counter() - t0:.3f}s")
    s = eng.stream
    if mesh is not None:
        stages = {k: getattr(s, k) is not None for k in
                  ("_sharded", "_sharded_nn", "_sharded_labels",
                   "_sharded_cdist")}
        # the window stays on one device; each repair stage is a shard_map
        # over the mesh that takes its slice of the window per tick
        log(f"sharded stream stages {stages} over a "
            f"{len(mesh.devices.flat)}-device mesh")
        check(all(stages.values()), "stream repair tail is not sharded")
    w = s.window_points()
    fresh = run_approxdpc(w, sz["d_cut"], exec_spec=spec)
    cl = assign_labels(fresh, sz["rho_min"], 2.0 * sz["d_cut"])
    res = s.result
    parts = {"rho": (fresh.rho, res.rho), "parent": (fresh.parent, res.parent),
             "delta": (fresh.delta, res.delta),
             "labels": (cl.labels, s.clustering.labels)}
    eq = {k: bool(np.array_equal(np.asarray(a), np.asarray(b)))
          for k, (a, b) in parts.items()}
    log(f"stream parity vs approxdpc on the window: {eq}")
    check(all(eq.values()), "stream tick != approxdpc on the window")
    return tuple(np.asarray(parts[k][1])
                 for k in ("rho", "delta", "parent", "labels"))


# ------------------------------------------------------------ four chips
def dist_phase(pts, sz, spec_kw, mesh):
    import numpy as np
    from repro import obs
    from repro.engine import DPCEngine, ExecSpec

    spec = ExecSpec(layout="block-sparse", **spec_kw)
    fits = {}
    for name, m in (("mesh", mesh), ("one-chip", None)):
        eng = DPCEngine(d_cut=sz["d_cut"], rho_min=sz["rho_min"],
                        algorithm="exdpc", exec_spec=spec, mesh=m)
        t0 = time.perf_counter()
        eng.fit(pts)
        fits[name] = eng
        log(f"exdpc {name} fit n={pts.shape[0]}: "
            f"{time.perf_counter() - t0:.3f}s, plan {eng.plan.describe()}")
    a, b = fits["mesh"].result, fits["one-chip"].result
    eq = {k: bool(np.array_equal(np.asarray(getattr(a, k)),
                                 np.asarray(getattr(b, k))))
          for k in ("rho", "delta", "parent")}
    eq["labels"] = bool(np.array_equal(fits["mesh"].labels_,
                                       fits["one-chip"].labels_))
    gauge = obs.metrics.get_metric("dist_bs_layout")
    log(f"dist_bs_layout gauge: {gauge.series() if gauge else None}")
    devs = {str(d) for d in a.rho.sharding.device_set}
    log(f"distributed vs one-chip exdpc: {eq}; rho sharding "
        f"{a.rho.sharding} over {len(devs)} device(s)")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    log(f"peak bytes in use per mesh device: {peaks}")
    check(len(devs) == len(mesh.devices.flat), "results not on the mesh")
    check(all(eq.values()), "distributed exdpc != one-chip exdpc")


def sharded_stream_phase(sz, spec_kw, mesh):
    import numpy as np

    one = stream_phase(sz, spec_kw)
    sharded = stream_phase(sz, spec_kw, mesh=mesh)
    eq = [bool(np.array_equal(a, b)) for a, b in zip(one, sharded)]
    log(f"sharded vs replicated ticks (rho, delta, parent, labels): {eq}")
    check(all(eq), "sharded stream ticks != replicated ticks")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU test run: tiny sizes, Pallas interpreter, no "
                         "result line (exit code 3 when every phase passed)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    log(f"devices: {len(devices)} x {dev.platform} ({dev.device_kind})")
    if not args.rehearse and dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform!r} devices; nothing to smoke")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, JAX found "
            f"{len(devices)}")
        return 2

    from benchmarks.util import use_compile_cache
    from repro.configs.dpc_paper import PAPER_DATASETS, PAPER_RHO_MIN
    from repro.data.points import real_proxy

    log(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    card = PAPER_DATASETS["household"]
    sz = dict(REHEARSAL if args.rehearse else FULL, d_cut=card.d_cut,
              rho_min=PAPER_RHO_MIN)
    spec_kw = {"backend": "pallas-interpret"} if args.rehearse else {}
    want = "pallas-interpret" if args.rehearse else "pallas"
    log(f"household proxy: n={sz['n']} d={card.d} d_cut={sz['d_cut']} "
        f"rho_min={sz['rho_min']}")
    ph = Phases()
    t_start = time.perf_counter()

    if args.chips == 4:
        mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
        log(f"distributed fit at n={sz['dist_n']} instead of {sz['n']}: the "
            f"mesh runs dense per-shard tiles (host-built worklists cannot "
            f"enter shard_map) and the one-chip Ex-DPC it is compared with "
            f"re-queries every unresolved row against the whole table; at "
            f"full size the pair takes minutes of four-chip time")
        pts = ph.run("data", lambda: real_proxy("household", sz["dist_n"],
                                                seed=0)[0])
        if pts is not None:
            ph.run("distributed-fit", dist_phase, pts, sz, spec_kw, mesh)
        ph.run("sharded-stream", sharded_stream_phase, sz, spec_kw, mesh)
    else:
        pts = ph.run("data", lambda: real_proxy("household", sz["n"],
                                                seed=0)[0])
        eng = None
        if pts is not None:
            eng = ph.run("fit", fit_phase, pts, sz, spec_kw, want, clock)
        ph.run("scan-check", scan_phase,
               real_proxy("household", sz["check_n"], seed=0)[0], sz,
               spec_kw)
        log(f"jnp check at n={sz['ref_n']} instead of {sz['n']}: the jnp "
            f"block-sparse walk picks each column tile by a one-hot "
            f"contraction over the whole table, so its cost grows as "
            f"n^3 / tile^2 and the full size does not finish in the run's "
            f"time limit")
        ph.run("jnp-check", jnp_phase,
               real_proxy("household", sz["ref_n"], seed=0)[0], sz, spec_kw)
        if eng is not None:
            ph.run("predict", predict_phase, eng, np.asarray(pts), sz)
        ph.run("stream", stream_phase, sz, spec_kw)

    log(f"backend compile seconds {clock.seconds:.3f}, persistent cache "
        f"hits {clock.cache_hits}, wall {time.perf_counter() - t_start:.3f}s")
    if ph.failed:
        log(f"FAILED phases: {ph.failed}")
        return 1
    if args.rehearse:
        log("rehearsal: every phase passed (no result line off the chip)")
        return REHEARSAL_PASSED
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
