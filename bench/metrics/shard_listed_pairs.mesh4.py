"""Tile pairs the shards' worklists list per fit, summed over the shards:
the ``listed`` attribute of the program's ``dist.worklist`` spans recorded
in the window (each fit's d_cut count lists and its fallback rings), over
the window's fits.  A program whose shards build no lists reads nothing."""
import sys

UNIT = "pairs"
SPAN = "dist.worklist"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    wl = [s["attrs"] for s in run.spans
          if s["name"] == SPAN and "listed" in s.get("attrs", {})]
    if not fits or not wl:
        return None
    by = {}
    for a in wl:
        by[a.get("phase")] = by.get(a.get("phase"), 0) + a["listed"]
    print("shard_listed_pairs.mesh4: " + ", ".join(
        f"{k} {v / len(fits):.1f}" for k, v in sorted(by.items(), key=str))
        + " pairs per fit", file=sys.stderr)
    return sum(by.values()) / len(fits)
