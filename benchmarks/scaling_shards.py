"""Fig. 9: scaling with worker count (threads -> SPMD shards).

The paper varies OpenMP threads on a 24-core Xeon; the TPU-native analogue
is the shard count of the distributed DPC runtime.  Each shard count runs
in a subprocess (XLA fixes the host device count at init).  On this 1-core
container the wall-time is serialized, so the reported metric is the
per-shard WORK (max shard's touched candidate volume) — the load-balance
property the paper's Fig. 9 is actually about — plus wall seconds for
reference.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from .util import CSV

_WORKER = r"""
import warnings, json, time
warnings.filterwarnings("ignore")
import numpy as np, jax
from repro.distributed import distributed_dpc, DistDPCConfig
from repro.data.points import real_proxy
from benchmarks.util import pick_dcut

n, shards, dataset = @N@, @SHARDS@, "@DATASET@"
pts, _ = real_proxy(dataset, n, seed=8)
d_cut = pick_dcut(pts, target_rho=min(30.0, n / 200))
mesh = jax.make_mesh((shards,), ("data",))
t0 = time.time()
res = distributed_dpc(pts, DistDPCConfig(d_cut=d_cut), mesh)
res.rho.block_until_ready()
t1 = time.time()
# load balance: per-shard candidate work = sum of span widths of its rows
from repro.core.grid import build_grid, point_span_bounds
import jax.numpy as jnp
grid = build_grid(jnp.asarray(pts, jnp.float32), d_cut)
st, en = point_span_bounds(grid)
work = np.asarray((en - st).sum(axis=1))
m = -(-len(work) // shards) * shards
work = np.pad(work, (0, m - len(work)))
per = work.reshape(shards, -1).sum(axis=1)
print("RESULT" + json.dumps({
    "platform": jax.devices()[0].platform,
    "wall_s": t1 - t0,
    "work_max": float(per.max()), "work_mean": float(per.mean()),
    "imbalance": float(per.max() / max(per.mean(), 1.0)),
}))
"""


def main(n=16_000, dataset="household", shard_counts=(1, 2, 4, 8)):
    csv = CSV("fig9_shards")
    csv.header(f"distributed DPC vs shard count ({dataset}, n={n})")
    for s in shard_counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={s}"
        # virtual host devices: the children run on the CPU, never on an
        # accelerator the parent process may hold
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("PYTHONPATH", "src")
        code = (_WORKER.replace("@N@", str(n))
                .replace("@SHARDS@", str(s))
                .replace("@DATASET@", dataset))
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=1800)
        if proc.returncode != 0:
            csv.add(shards=s, error=proc.stderr.strip()[-200:])
            continue
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT")][0]
        r = json.loads(line[len("RESULT"):])
        csv.add(shards=s, platform=r["platform"], wall_s=r["wall_s"],
                work_per_shard_max=r["work_max"],
                imbalance=r["imbalance"])
    return csv


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16_000)
    main(ap.parse_args().n)
