"""CI smoke test for the repro.obs observability layer.

Three checks, exit 1 when any fails:

1. **Span tree** — a traced block-sparse ``DPCEngine.fit`` must emit the
   expected phase tree (``engine.fit`` root with the approxdpc driver and
   labeling children) with fenced device times on the compute phases, and
   the children's host time must account for most of the root's (the
   fence-inside-span design: per-phase times sum to ~wall time).
2. **Disabled overhead** — with obs off, ``span()`` must return the shared
   null singleton at sub-microsecond cost, and an end-to-end ``fit`` must
   not be measurably slower than the same fit at ``level="metrics"``
   (generous noise bound; the off path adds one dict lookup per phase).
3. **Snapshot** — ``--out`` writes the run's metrics/trace snapshot
   (``repro.obs/1`` schema) for CI artifact diffing.

    PYTHONPATH=src python -m benchmarks.obs_smoke [--n 4096] [--out obs-metrics.json]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro import obs
from repro.core.tuning import pick_dcut
from repro.engine import DPCEngine, ExecSpec
from repro.obs import report as obs_report

from .util import timeit_stats

EXPECTED_PATHS = (
    "engine.fit",
    "engine.fit/approxdpc.grid",
    "engine.fit/approxdpc.rho_delta",
    "engine.fit/approxdpc.rules",
    "engine.fit/labels.assign",
)
# children must cover this fraction of the root's host time (the fences run
# inside the phase spans, so orchestration self-time is all that's left out)
MIN_CHILD_COVERAGE = 0.5
# null-span path budget per obs.span() call with obs off (one dict lookup)
MAX_NULL_SPAN_US = 5.0
# off-vs-metrics fit time: off may not exceed metrics by more than this
# factor (both should be ~identical; this is a noise-tolerant upper bound)
MAX_OFF_OVERHEAD = 1.5


def _data(n: int, d: int = 3, seed: int = 0):
    rng = np.random.default_rng(seed)
    pts = rng.uniform(0.0, 5400.0, (n, d)).astype(np.float32)
    d_cut = float(pick_dcut(pts, target_rho=min(30.0, n / 200)))
    return pts, d_cut


def _fresh_engine(d_cut: float) -> DPCEngine:
    return DPCEngine(d_cut=d_cut, algorithm="approxdpc",
                     exec_spec=ExecSpec(backend="jnp", layout="block-sparse"))


def check_span_tree(n: int) -> list[str]:
    failures = []
    pts, d_cut = _data(n)
    obs.reset_spans()
    obs.configure(level="trace")
    try:
        _fresh_engine(d_cut).fit(pts)
    finally:
        obs.configure(level="off")
    recs = obs.spans()
    paths = {r["path"] for r in recs}
    for want in EXPECTED_PATHS:
        if want not in paths:
            failures.append(f"span tree: missing phase {want!r} "
                            f"(got {sorted(paths)})")
    phases = obs_report.aggregate(recs)
    root = phases.get("engine.fit")
    if root is None:
        return failures
    fenced = [p for p, r in phases.items()
              if p != "engine.fit" and r["device_s"] is not None]
    if not fenced:
        failures.append("span tree: no child phase fenced device time at "
                        "level='trace'")
    child_host = sum(r["host_s"] for p, r in phases.items()
                     if p.startswith("engine.fit/") and p.count("/") == 1)
    if root["host_s"] > 0 and child_host < MIN_CHILD_COVERAGE * root["host_s"]:
        failures.append(
            f"span tree: children cover {child_host / root['host_s']:.0%} "
            f"of engine.fit host time < {MIN_CHILD_COVERAGE:.0%} floor")
    print(f"[obs_smoke] span tree OK: {len(recs)} spans, engine.fit "
          f"{root['host_s'] * 1e3:.1f}ms, children "
          f"{child_host * 1e3:.1f}ms", flush=True)
    return failures


def check_disabled_overhead(n: int) -> list[str]:
    failures = []
    obs.configure(level="off")
    # (a) the off-path span() must be the shared null singleton, cheap
    if obs.span("x") is not obs.NULL_SPAN:
        failures.append("off path: span() did not return NULL_SPAN")
    reps = 100_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with obs.span("x") as sp:
            sp.sync(None)
    per_us = (time.perf_counter() - t0) / reps * 1e6
    if per_us > MAX_NULL_SPAN_US:
        failures.append(f"off path: {per_us:.2f}us per span() call "
                        f"> {MAX_NULL_SPAN_US}us budget")
    # (b) end-to-end: off fit must not be slower than metrics fit (bound is
    # generous — the point is catching an accidentally always-on fence)
    pts, d_cut = _data(n)

    def fit_off():
        return _fresh_engine(d_cut).fit(pts).result.rho

    def fit_metrics():
        obs.configure(level="metrics")
        try:
            return _fresh_engine(d_cut).fit(pts).result.rho
        finally:
            obs.configure(level="off")

    off = timeit_stats(fit_off, repeats=3, warmup=1)
    met = timeit_stats(fit_metrics, repeats=3, warmup=1)
    if off["min_s"] > MAX_OFF_OVERHEAD * met["min_s"]:
        failures.append(
            f"off path: fit {off['min_s'] * 1e3:.1f}ms > "
            f"{MAX_OFF_OVERHEAD}x metrics-level fit "
            f"{met['min_s'] * 1e3:.1f}ms")
    print(f"[obs_smoke] disabled overhead OK: {per_us:.2f}us/span, fit "
          f"off {off['min_s'] * 1e3:.1f}ms vs metrics "
          f"{met['min_s'] * 1e3:.1f}ms", flush=True)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--out", default=None,
                    help="write the repro.obs run snapshot here")
    a = ap.parse_args(argv)

    failures = check_span_tree(a.n) + check_disabled_overhead(a.n)
    if a.out:
        obs_report.export_snapshot(a.out)
        print(f"[obs_smoke] wrote snapshot to {a.out}", flush=True)
    if failures:
        print("[obs_smoke] FAIL", flush=True)
        for f in failures:
            print("  -", f, flush=True)
        return 1
    print("[obs_smoke] OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
