"""Tile pairs the masked-NN fallback computes per fit: the ``live``
attribute of the program's ``rho_delta.fallback`` spans recorded in the
window (ring worklist entries whose lower bound is within their row tile's
final radius, the pairs a ring-ordered walk does not skip), summed, over
the window's fits.  A program whose fallback walks no ring reads nothing."""
import sys

UNIT = "pairs"
SPAN = "rho_delta.fallback"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    fb = [s["attrs"] for s in run.spans
          if s["name"] == SPAN and "live" in s.get("attrs", {})]
    if not fits or not fb:
        return None
    pairs = sum(a["pairs"] for a in fb)
    print(f"fallback_live_pairs.fit: {pairs / len(fits):.1f} ring pairs "
          f"listed per fit, in {len(fb)} fallbacks", file=sys.stderr)
    return sum(a["live"] for a in fb) / len(fits)
