"""Distributed batch fits: ``DPCEngine.fit`` over a mesh of the
configuration's chips, each fit of the configuration's one draw in a new
order drawn from the seed, as ``fit.py`` drives them (its helpers are used
as they are).

Set-up refuses a program whose shard phases would not run block-sparse on
this mesh (``shard_blocksparse_layout``): dense shard tiles are another
deployment, quadratic in n, and a run of them would be cut by its time
limit instead of failing at once.
"""
from __future__ import annotations

import time

import numpy as np

from bench import compare, data, reference_exdpc
from bench.drivers import fit
from bench.harness import log

GAUGES: list[str] = []
METRICS = fit.METRICS
COMPARED = 3       # fits compared at most, a sample drawn from the seed: the
                   # reference sweeps every row against the whole table


def make_mesh(ctx):
    """One shard per chip of the cell, on the configuration's axis."""
    import jax

    devs = jax.devices()[: ctx.config["chips"]]
    return jax.sharding.Mesh(np.array(devs), (ctx.config["mesh"]["axis"],))


def engine(ctx, mesh):
    return fit.engine(ctx, mesh=mesh,
                      strategy=ctx.config["mesh"]["strategy"])


def setup(ctx) -> dict:
    from repro.distributed.dpc import shard_blocksparse_layout
    from repro.engine.planner import as_plan

    mesh = make_mesh(ctx)
    layout = shard_blocksparse_layout(as_plan(ctx.exec_spec()), mesh)
    if layout != ctx.config["layout"]:
        raise RuntimeError(
            f"the shard phases would run {layout or 'dense'} tiles on this "
            f"mesh, not {ctx.config['layout']}: another deployment")
    c = ctx.config
    pts = data.draw(c, c["n"], c["mixture"]["sample_seed"])
    eng = engine(ctx, mesh)
    eng.fit(pts[fit.order(ctx.seed, -1, len(pts))])
    fit.outputs(eng)
    return {"pts": pts, "eng": eng, "outs": [], "devices": mesh.devices}


window = fit.window


def check(ctx, st: dict) -> dict:
    """At most ``COMPARED`` completed fits, drawn from the seed, each in
    full against the reference, on the cell's chips; each number the worst
    over the fits.  The program's state is dropped first; the densities are
    found once."""
    c = ctx.config
    outs = st.pop("outs")
    st.pop("eng")
    pts, n = st["pts"], len(st["pts"])
    done = [i for i, o in enumerate(outs) if o is not None]
    if not done:
        return dict.fromkeys(compare.CLUSTERING, n)
    if len(done) > COMPARED:
        done = sorted(np.random.default_rng([ctx.seed, 0]).choice(
            done, COMPARED, replace=False))
    outs = {i: {k: np.asarray(v) for k, v in outs[i].items()} for i in done}
    t0 = time.perf_counter()
    ref = reference_exdpc.Reference(pts, c["d_cut"], st["devices"].flat)
    ref.rho()
    t1 = time.perf_counter()
    worst = dict.fromkeys(compare.CLUSTERING, 0)
    for i in done:
        r = ref.fit(fit.order(ctx.seed, i, n), c["rho_min"], c["delta_min"])
        for k, v in compare.clustering(outs[i], r).items():
            worst[k] = max(worst[k], v)
    log(f"reference: densities {t1 - t0:.3f}s, {len(done)} fits compared "
        f"in {time.perf_counter() - t1:.3f}s")
    return worst
