"""Shared benchmark utilities: timing, d_cut selection, CSV emission.

Scale note: the paper's machine is a 24-core Xeon running C++ on datasets of
2-6M points; this container is a single-core CPU interpreting JAX, so the
default sizes are scaled down (n ~ 2e4) and every table records its n.  The
paper's *claims* that we validate — accuracy ordering, scaling exponents,
algorithm speed ordering — are size-robust; absolute seconds are not
comparable and are not the deliverable (the roofline/dry-run is).
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left as it is (JAX reads it
    itself).  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
    path, because a cache directory that moves between runs never hits.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def timeit_stats(fn, *args, repeats: int = 3, warmup: int = 1, **kw) -> dict:
    """Device-synced timing of ``fn(*args, **kw)``.

    Every warmup result is fully synced (``jax.block_until_ready`` over the
    whole output tree) *before* t0 of the first measured repeat, so compile
    time can never leak into the measurements.  Each measured repeat is
    likewise synced inside its own window, so ``times_s`` are true
    device-complete wall times, not async-dispatch times.

    Returns ``{"times_s": [per-repeat seconds], "median_s", "min_s",
    "warmup_s" (total seconds spent in the synced warmup runs)}``.
    """
    w0 = time.perf_counter()
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    warmup_s = time.perf_counter() - w0
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return {"times_s": ts, "median_s": float(np.median(ts)),
            "min_s": float(np.min(ts)), "warmup_s": warmup_s}


def timeit(fn, *args, repeats: int = 3, warmup: int = 1, **kw) -> float:
    """Median device-synced wall seconds of fn(*args); see timeit_stats."""
    return timeit_stats(fn, *args, repeats=repeats, warmup=warmup,
                        **kw)["median_s"]


from repro.core.tuning import pick_dcut  # noqa: F401  (re-export)


class CSV:
    """Collects rows and prints a section of `name,key=val,...` lines."""

    def __init__(self, name: str):
        self.name = name
        self.rows = []

    def add(self, **kv):
        self.rows.append(kv)
        print(f"[{self.name}] " + ",".join(f"{k}={_fmt(v)}"
                                           for k, v in kv.items()),
              flush=True)

    def header(self, note: str = ""):
        print(f"\n=== {self.name} {note}", flush=True)


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)
