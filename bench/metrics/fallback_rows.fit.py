"""Rows the masked-NN fallback sweeps per fit: the ``padded`` attribute of
the program's ``rho_delta.fallback`` spans recorded in the window (the
unresolved rows it answers, padded to a power of two), summed, over the
window's fits."""
import sys

UNIT = "rows"
SPAN = "rho_delta.fallback"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    fb = [s.get("attrs", {}) for s in run.spans if s["name"] == SPAN]
    if not fits or not fb:
        return None
    rows = sum(a.get("rows", 0) for a in fb)
    print(f"fallback_rows.fit: {rows / len(fits):.1f} unresolved rows per "
          f"fit, in {len(fb)} fallbacks", file=sys.stderr)
    return sum(a.get("padded", 0) for a in fb) / len(fits)
