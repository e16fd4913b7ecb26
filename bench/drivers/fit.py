"""Batch fits: ``DPCEngine.fit`` on the configuration's draw of the
mixture, each fit in a new order drawn from the seed.

A new order is new bytes to the program, so its worklist content cache
misses and every fit pays the host worklist build, as a fit of new data
does.  The multiset of points, and with it every shape the program derives
from the data, stays the same, so nothing compiles inside the window:
``fit_s`` is a re-fit with every program compiled, and leaves out the
compiles that a fit of new data pays today for the shapes it brings.
"""
from __future__ import annotations

import time
import traceback

import numpy as np

from bench import compare, data, reference
from bench.harness import log

GAUGES = ["worklist_len"]
METRICS = ["fit_s"]
COMPARED = 12      # fits compared at most (a window holds 9 today); a
                   # sample beyond, so the check stays shorter than the window


def order(seed: int, i: int, n: int) -> np.ndarray:
    """The order of fit ``i`` (``-1``: the warm-up fit)."""
    return np.random.default_rng([seed, i + 1]).permutation(n)


def engine(ctx, **kw):
    """The engine every driver drives (``kw``: the stream's window)."""
    from repro.engine import DPCEngine

    c = ctx.config
    return DPCEngine(d_cut=c["d_cut"], rho_min=c["rho_min"],
                     algorithm=c["algorithm"], exec_spec=ctx.exec_spec(),
                     **kw)


def outputs(eng) -> dict:
    """The fit's outputs, waited for on the device."""
    import jax

    r, c = eng.result, eng.clustering
    return jax.block_until_ready({"rho": r.rho, "delta": r.delta,
                                  "parent": r.parent, "centers": c.centers,
                                  "labels": c.labels})


def setup(ctx) -> dict:
    c = ctx.config
    pts = data.draw(c, c["n"], c["mixture"]["sample_seed"])
    eng = engine(ctx)
    eng.fit(pts[order(ctx.seed, -1, len(pts))])
    outputs(eng)
    return {"pts": pts, "eng": eng, "outs": []}


def window(ctx, st: dict, seconds: float) -> dict:
    pts, eng, n = st["pts"], st["eng"], len(st["pts"])
    failed = 0
    t0 = time.perf_counter()
    while True:
        i = len(st["outs"])
        with ctx.annotate("bench.datagen"):
            x = pts[order(ctx.seed, i, n)]
        with ctx.call("fit", index=i):
            try:
                eng.fit(x)
                st["outs"].append(outputs(eng))
            except Exception:          # noqa: BLE001 — counted as failed
                traceback.print_exc()
                failed += 1
                st["outs"].append(None)
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    fits = len(st["outs"])
    return {"metrics": {"fit_s": (elapsed / fits, "s")}, "attempted": fits,
            "failed": failed, "note": f"{fits} fits in {elapsed:.3f}s"}


def check(ctx, st: dict) -> dict:
    """Every completed fit in full against the reference (a sample of
    ``COMPARED`` drawn from the seed, where more completed), each number
    the worst over the fits; the program's state is dropped first.  The
    densities and grid cells are found once and permuted to each fit's
    order."""
    c = ctx.config
    outs = [None if o is None else {k: np.asarray(v) for k, v in o.items()}
            for o in st.pop("outs")]
    st.pop("eng")
    pts, n = st["pts"], len(st["pts"])
    done = [i for i, o in enumerate(outs) if o is not None]
    if not done:
        return dict.fromkeys(compare.CLUSTERING, n)
    if len(done) > COMPARED:
        done = sorted(np.random.default_rng([ctx.seed, 0]).choice(
            done, COMPARED, replace=False))
    t0 = time.perf_counter()
    rho = reference.rho(pts, c["d_cut"])
    cell = reference.grid_cells(pts, c["d_cut"])
    t1 = time.perf_counter()
    worst = dict.fromkeys(compare.CLUSTERING, 0)
    for i in done:
        o = order(ctx.seed, i, n)
        ref = reference.approx_dpc(pts[o], c["d_cut"], c["rho_min"],
                                   c["delta_min"], rho_=rho[o],
                                   cell_=cell[o])
        for k, v in compare.clustering(outs[i], ref).items():
            worst[k] = max(worst[k], v)
    log(f"reference: densities and cells {t1 - t0:.3f}s, {len(done)} fits "
        f"compared in {time.perf_counter() - t1:.3f}s")
    return worst
