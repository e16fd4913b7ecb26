"""The block-sparse sweep's share of its memory roofline: the least time in
which the chip moves the sweep's bytes (``bench/roofline.py``, counted from
the kept pairs of each fit's worklist build) over the device time of its
chunk kernels.  The compute side has no sound peak (``roofline.py``)."""
import sys

from bench import harness, roofline, tracing

UNIT = "%"


def read(run):
    k = harness.load_json("kernels.json")["worklist_sweep"]
    fits = [c for c in run.calls if c["kind"] == "fit"]
    secs = tracing.kernel_s(run.trace, k["patterns"], *run.window)
    kept = [v for c in fits for v in c.get("worklist_len", [])]
    if not kept or secs <= 0:
        return None
    cfg = run.cell["config"]
    row_tiles = -(-cfg["n"] // k["block_n"])
    nbytes = roofline.sweep_bytes(sum(kept), row_tiles * len(kept),
                                  d=cfg["d"], block_n=k["block_n"],
                                  block_m=k["block_m"], topk=k["topk"])
    t = roofline.least_time(nbytes, run.peaks)
    print(f"sweep_roofline.fit: {nbytes:.6g} bytes, least time {t:.6f}s, "
          f"over {secs:.6f}s of worklist sweep kernels", file=sys.stderr)
    return 100.0 * t / secs
