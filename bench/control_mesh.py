"""Readings of the controls of a mesh cell: lower precision in the
program's place.

    python3 bench/control_mesh.py --workload household_exdpc_mesh4.fit \
        --seconds 1 --seeds 101

As ``control.py`` does for the ``fit`` cells, in one process: for each
seed the cell runs as ``run.py`` runs it (set-up, a window of
``--seconds``, the check against ``bench/reference_exdpc.py``) once for
each of ``--variants``:

* ``program``: as configured (float32);
* ``program_bf16``: the program's own ``bf16`` ExecSpec;
* ``reference_bf16``: the plain Ex-DPC reference computed in bfloat16, put
  in the program's place.

``--n`` and ``--chips`` run the cell at another size (a cheaper reading);
the line says which.  Each run prints one JSON line: the variant, the
seed, the size, ``correct`` and every number checked.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
from types import SimpleNamespace as NS

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


class ReferenceEngine:
    """``bench.reference_exdpc`` in ``dtype`` answering ``fit``."""

    def __init__(self, cfg: dict, devices, dtype):
        self.cfg, self.devices, self.dtype = cfg, list(devices), dtype

    def fit(self, points):
        from bench import reference_exdpc

        c = self.cfg
        ref = reference_exdpc.Reference(np.asarray(points), c["d_cut"],
                                        self.devices, self.dtype)
        r = ref.fit(np.arange(len(points)), c["rho_min"], c["delta_min"])
        self.result = NS(rho=r["rho"], delta=r["delta"], parent=r["parent"])
        self.clustering = NS(centers=r["centers"], labels=r["labels"])
        return self


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["program", "program_bf16", "reference_bf16"])
    ap.add_argument("--n", type=int, help="points (default: the file's)")
    ap.add_argument("--chips", type=int, help="shards (default: the file's)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU test run at the rehearsal sizes")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from bench import harness
    from bench.drivers import mesh_fit

    program_engine = mesh_fit.engine
    cell = harness.load_cell(args.workload, args.rehearse)
    for key in ("n", "chips"):
        if getattr(args, key) is not None:
            cell["config"][key] = getattr(args, key)
    low = copy.deepcopy(cell)
    low["config"]["precision"] = "bf16"
    variants = {
        "program": (cell, program_engine),
        "program_bf16": (low, program_engine),
        "reference_bf16": (cell, lambda ctx, mesh: ReferenceEngine(
            ctx.config, mesh.devices.flat, jnp.bfloat16)),
    }
    for seed in args.seeds:
        for name in args.variants:
            c, factory = variants[name]
            mesh_fit.engine = factory
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   rehearse=args.rehearse, cell=c)
            print(json.dumps({
                "variant": name, "seed": seed, "n": c["config"]["n"],
                "chips": c["config"]["chips"], "correct": res["correct"],
                "metrics": res["metrics"], "checks": res["checks"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
