"""The shards' sweeps' share of their memory roofline: the least time in
which a chip moves its share of the bytes the listed tile pairs need, over
the mean chip's Pallas device time in the window.

Bytes, from the ``dist.worklist`` spans' ``listed`` pairs and
``row_tiles``: a d_cut count list (``phase`` ``count``) is the fused
count + kept-k sweep of ``household.fit`` at its tiles, 256 x 512
(``bench/roofline.sweep_bytes``); a fallback ring (``phase`` ``ring``) is a
best-1 masked-NN sweep at 256 x 256 tiles, whose every listed pair streams
its column tile (coordinates and key, ``RING_COL_BYTES``) whether or not the
kernel skips the pair, and whose row tiles are read once (coordinates and
key) and written once (best distance and index, ``RING_ROW_BYTES``).  The
compute side has no sound peak (``bench/roofline.py``)."""
import sys

from bench import harness, roofline, tracing

UNIT = "%"
SPAN = "dist.worklist"
COUNT = {"block_n": 256, "block_m": 512, "topk": 8}
RING_BLOCK = 256


def ring_bytes(pairs: float, row_tiles: float, d: int) -> float:
    col = RING_BLOCK * (d + 1) * 4
    row = RING_BLOCK * (d + 1 + 2) * 4
    return pairs * col + row_tiles * row


def read(run):
    pats = harness.load_json("kernels.json")["pallas"]["patterns"]
    secs = tracing.kernel_s(run.trace, pats, *run.window)
    wl = [s["attrs"] for s in run.spans
          if s["name"] == SPAN and "listed" in s.get("attrs", {})]
    if not wl or secs <= 0:
        return None
    d = run.cell["config"]["d"]
    nbytes = 0.0
    for a in wl:
        if a.get("phase") == "ring":
            nbytes += ring_bytes(a["listed"], a["row_tiles"], d)
        else:
            nbytes += roofline.sweep_bytes(a["listed"], a["row_tiles"], d=d,
                                           **COUNT)
    chips = max(run.trace["devices"], 1)
    t = roofline.least_time(nbytes / chips, run.peaks)
    print(f"shard_roofline.mesh4: {nbytes:.6g} bytes over {chips} chips, "
          f"least time {t:.6f}s a chip, over {secs / chips:.6f}s of Pallas "
          f"kernels a chip", file=sys.stderr)
    return 100.0 * t * chips / secs
