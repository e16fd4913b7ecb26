"""Rows the shards' kept-k d_cut pass leaves unresolved per fit, summed
over the shards: the ``unresolved`` attribute of the program's
``dist.fallback`` spans recorded in the window, over the window's fits
(the rows each fallback ring answers; ``padded`` rows are swept)."""
import sys

UNIT = "rows"
SPAN = "dist.fallback"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    fb = [s.get("attrs", {}) for s in run.spans if s["name"] == SPAN]
    if not fits or not fb:
        return None
    padded = sum(a.get("padded", 0) for a in fb)
    print(f"dist_fallback_rows.mesh4: {padded / len(fits):.1f} rows swept "
          f"per fit, in {len(fb)} fallbacks", file=sys.stderr)
    return sum(a.get("unresolved", 0) for a in fb) / len(fits)
