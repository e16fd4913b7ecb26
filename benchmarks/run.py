"""Benchmark driver: one section per paper table/figure + the roofline and
beyond-paper benches.  ``--quick`` (default) uses CPU-container sizes; pass
--full for larger n.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table2,...]
"""
from __future__ import annotations

import argparse
import time
import traceback


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    args = ap.parse_args()
    from .util import use_compile_cache
    use_compile_cache()
    big = args.full

    from . import (accuracy, backend_compare, decomposed, dpc_kv_bench,
                   eps_sweep, memory, scaling_dcut, scaling_n, scaling_shards)

    sections = {
        "table2_3_4_accuracy": lambda: accuracy.main(
            n=40_000 if big else 12_000),
        "table5_eps": lambda: eps_sweep.main(n=40_000 if big else 12_000),
        "table6_decomposed": lambda: decomposed.main(
            n=20_000 if big else 8_000),
        "table7_memory": lambda: memory.main(n=40_000 if big else 16_000),
        "fig7_scaling_n": lambda: scaling_n.main(
            n_max=64_000 if big else 16_000),
        "fig8_dcut": lambda: scaling_dcut.main(n=20_000 if big else 8_000),
        "fig9_shards": lambda: scaling_shards.main(
            n=32_000 if big else 10_000),
        "dpc_kv": lambda: dpc_kv_bench.main(S=2048 if big else 768),
        "backend_compare": lambda: backend_compare.main(
            n=8192 if big else 2048),
        "roofline": _roofline,
    }
    only = set(args.only.split(",")) if args.only else None
    failures = 0
    for name, fn in sections.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"[run] {name} done in {time.time() - t0:.1f}s",
                  flush=True)
        except Exception:
            failures += 1
            print(f"[run] {name} FAILED:\n{traceback.format_exc()}",
                  flush=True)
    print(f"[run] complete, {failures} failed sections", flush=True)
    raise SystemExit(1 if failures else 0)


def _roofline():
    import os
    import sys
    from .roofline import main as roofline_main
    if not os.path.isdir("experiments/dryrun"):
        print("[roofline] no dry-run records; run "
              "PYTHONPATH=src python -m repro.launch.dryrun first")
        return
    argv = sys.argv
    sys.argv = [argv[0]]
    try:
        roofline_main()
    finally:
        sys.argv = argv


if __name__ == "__main__":
    main()
