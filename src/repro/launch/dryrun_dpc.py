import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# 512 placeholder devices, set before any jax import (same contract as
# launch/dryrun.py).  This dry-run lowers the DISTRIBUTED DPC phases — the
# paper's parallel algorithm itself — on the production mesh and extracts
# roofline terms, baseline (all-gather) vs optimized (halo ring).
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp                      # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P   # noqa: E402
from jax import shard_map    # noqa: E402

from repro.distributed.dpc import (_make_delta, _make_delta_halo,  # noqa: E402
                                   _make_rho, _make_rho_halo)
from repro.launch.hlo_cost import analyze_compiled   # noqa: E402
from repro.launch.mesh import make_production_mesh   # noqa: E402


def lower_phase(fn, arg_shapes, flat_mesh, axis, n_in, out_specs):
    sm = shard_map(fn, mesh=flat_mesh, in_specs=(P(axis),) * n_in,
                   out_specs=out_specs)
    t0 = time.time()
    compiled = jax.jit(sm).lower(*arg_shapes).compile()
    return compiled, time.time() - t0


def run(n: int, d: int, span_w: int, window_blocks: int, multi_pod: bool,
        out_dir: str):
    mesh = make_production_mesh(multi_pod=multi_pod)
    S = mesh.devices.size
    flat_mesh = Mesh(mesh.devices.reshape(-1), ("data",))
    jax.set_mesh(flat_mesh)
    m = n // S                       # rows per shard
    n_spans = 9                      # 3^(g-1), g=3 leading grid dims
    f32 = jnp.float32
    i32 = jnp.int32

    pts = jax.ShapeDtypeStruct((n, d), f32)
    st = jax.ShapeDtypeStruct((n, n_spans), i32)
    rk = jax.ShapeDtypeStruct((n,), f32)
    lo = jax.ShapeDtypeStruct((S, 1), jnp.int64)

    # halo statics: window = `window_blocks` blocks (space-sorted layout —
    # a uniform-ish distribution needs the two neighbour blocks; skew is
    # absorbed by the host-measured W at runtime)
    W = window_blocks * m
    hf = hb = max(1, (window_blocks - 1) // 2)

    recs = {}
    one = P("data")
    three = (P("data"), P("data"), P("data"))
    phases = {
        "rho_gather": (_make_rho("data", 1.0, 256, span_w),
                       (pts, st, st, pts), 4, one),
        "rho_halo": (_make_rho_halo("data", 1.0, 256, span_w, S, W, hf, hb),
                     (pts, st, st, pts, lo), 5, one),
        "delta_gather": (_make_delta("data", 1.0, 256, span_w),
                         (pts, rk, st, st, pts, rk), 6, three),
        "delta_halo": (_make_delta_halo("data", 1.0, 256, span_w, S, W,
                                        hf, hb),
                       (pts, rk, st, st, pts, rk, lo), 7, three),
    }
    for name, (fn, shapes, n_in, out_specs) in phases.items():
        compiled, dt = lower_phase(fn, shapes, flat_mesh, "data", n_in,
                                   out_specs)
        cost = analyze_compiled(compiled)
        mem = compiled.memory_analysis()
        recs[name] = {
            "compile_s": round(dt, 2),
            "flops": cost["flops"], "dot_flops": cost["dot_flops"],
            "bytes": cost["bytes"],
            "collectives": cost["collectives"],
            "temp_bytes": mem.temp_size_in_bytes,
        }
        print(f"[dpc-dryrun] {name}: flops/dev={cost['flops']:.3g} "
              f"bytes={cost['bytes']:.3g} "
              f"coll={cost['collectives']['total_bytes']:.3g}B "
              f"temp={mem.temp_size_in_bytes:.3g}B", flush=True)

    rec = {"n": n, "d": d, "span_w": span_w, "devices": S,
           "window_blocks": window_blocks, "phases": recs}
    os.makedirs(out_dir, exist_ok=True)
    tag = "pod2x16x16" if multi_pod else "pod16x16"
    with open(os.path.join(out_dir, f"dpc__n{n}__{tag}.json"), "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 24)   # 16.7M points
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--span-w", type=int, default=64)
    ap.add_argument("--window-blocks", type=int, default=3)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    run(args.n, args.d, args.span_w, args.window_blocks, args.multipod,
        args.out)


if __name__ == "__main__":
    main()
