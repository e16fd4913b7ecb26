"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload household.fit --seed 7 --seconds 40 \
        --trace 0

Set-up (data from the seed, the engine, every shape the window uses) is
timed as ``setup_s``; the window then drives the cell's entry for
``--seconds``; the outputs are checked against the plain reference
(``bench/reference.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics) and
``device``; each number checked is printed beside its limit as the last
lines of standard error and under ``checks``, the line's last key.  With
no TPU, or fewer chips than the cell asks for, the run exits with code 2
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        res = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return harness.NOT_A_TPU
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
