"""Readings of the controls: lower precision in the program's place.

    python3 bench/control.py --workload household.fit --seconds 8 \
        --seeds 101 102 103

For each sample of the configuration's points (``--sample-seeds``; by
default the file's own) and each seed, in one process, the cell runs as
``run.py`` runs it (set-up, a window of ``--seconds``, the check against
the reference) once for each of ``--variants``:

* ``program``: as configured (float32);
* ``program_bf16``: the program's own ``bf16`` ExecSpec, the step below
  float32 that would tempt a later change;
* ``reference_bf16``: the plain reference computed in bfloat16, put in the
  program's place (``ReferenceEngine``).

Each run prints one JSON line: the variant, the seed, ``correct`` and every
number checked.  A control has to come out not correct; the benchmark's own
runs never run one.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

LOWER = {"f32": "bf16"}


class ReferenceEngine:
    """The reference in the engine's place, in ``dtype``: ``fit`` answered
    by ``bench.reference``."""

    def __init__(self, cfg: dict, dtype):
        self.cfg, self.dtype = cfg, dtype

    def fit(self, points):
        from types import SimpleNamespace as NS

        from bench import reference

        c = self.cfg
        r = reference.approx_dpc(np.array(points, np.float32), c["d_cut"],
                                 c["rho_min"], c["delta_min"],
                                 dtype=self.dtype)
        self.result = NS(rho=r["rho"], delta=r["delta"], parent=r["parent"])
        self.clustering = NS(centers=r["centers"], labels=r["labels"])
        return self


def variants(cell: dict):
    """(name, cell, engine factory or None for the program's own)."""
    import jax.numpy as jnp

    yield "program", cell, None
    low = copy.deepcopy(cell)
    low["config"]["precision"] = LOWER[cell["config"]["precision"]]
    yield "program_bf16", low, None
    yield "reference_bf16", cell, (
        lambda ctx: ReferenceEngine(ctx.config, jnp.bfloat16))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sample-seeds", type=int, nargs="+", default=[None],
                    help="draw the configuration's points anew from each "
                         "(default: the file's own sample)")
    ap.add_argument("--variants", nargs="+",
                    default=["program", "program_bf16", "reference_bf16"])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU test run at the rehearsal sizes")
    args = ap.parse_args(argv)

    from bench import harness
    from bench.drivers import fit as fit_driver

    program_engine = fit_driver.engine
    for sample in args.sample_seeds:
        cell = harness.load_cell(args.workload, args.rehearse)
        if sample is not None:
            cell["config"]["mixture"]["sample_seed"] = sample
        for seed in args.seeds:
            for name, c, factory in variants(cell):
                if name not in args.variants:
                    continue
                fit_driver.engine = factory or program_engine
                res = harness.run_cell(args.workload, seed, args.seconds,
                                       False, rehearse=args.rehearse, cell=c)
                print(json.dumps({
                    "variant": name, "seed": seed,
                    "sample_seed": cell["config"]["mixture"]["sample_seed"],
                    "correct": res["correct"], "metrics": res["metrics"],
                    "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
