"""Load imbalance of the shards' sweeps: the slowest chip's Pallas device
time in the window over the mean chip's, less 100, in percent.  The shards
hold equal counts of the cell-sorted table (the stand-in for the paper's
``schedule(dynamic)``), not equal work."""
import sys

from bench import harness, tracing

UNIT = "%"


def read(run):
    pats = harness.load_json("kernels.json")["pallas"]["patterns"]
    per = [tracing.kernel_s(
        {"device": [e for e in run.trace["device"] if e[3] == dev]},
        pats, *run.window) for dev in range(run.trace["devices"])]
    if len(per) < 2 or min(per) <= 0:
        return None
    mean = sum(per) / len(per)
    print("shard_imbalance.mesh4: Pallas seconds per chip "
          + ", ".join(f"{s:.6f}" for s in per), file=sys.stderr)
    return 100.0 * (max(per) / mean - 1.0)
