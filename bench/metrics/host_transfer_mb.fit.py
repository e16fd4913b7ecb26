"""Bytes copied between host and device per fit, in MB (1e6 bytes): the
``d2h_bytes`` and ``h2d_bytes`` attributes of the program's spans recorded
in the window, summed, over the window's fits."""
import sys

UNIT = "MB"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    d2h = sum(s.get("attrs", {}).get("d2h_bytes", 0) for s in run.spans)
    h2d = sum(s.get("attrs", {}).get("h2d_bytes", 0) for s in run.spans)
    if not fits or d2h + h2d <= 0:
        return None
    print(f"host_transfer_mb.fit: {d2h / len(fits) / 1e6:.6f} MB device to "
          f"host, {h2d / len(fits) / 1e6:.6f} MB host to device per fit",
          file=sys.stderr)
    return (d2h + h2d) / len(fits) / 1e6
