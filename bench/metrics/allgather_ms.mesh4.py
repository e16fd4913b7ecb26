"""Device time of the all-gathers per fit, mean over the chips: the trace's
ops whose instruction is an ``all-gather`` (or its async start and done),
named before the `` = `` of the op's text, summed, over the chips and the
window's fits.  Each shard phase gathers the cell-sorted table, and the
key phases the density keys."""
import re

UNIT = "ms"
OP = re.compile(r"^%?all-gather(-start|-done)?(\.\d+)?$")


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    w0, w1 = run.window
    ns = sum(min(a + dur, w1) - max(a, w0)
             for name, a, dur, _ in run.trace["device"]
             if a < w1 and a + dur > w0
             and OP.match(name.split(" = ")[0].strip()))
    chips = max(run.trace["devices"], 1)
    return ns / 1e6 / chips / len(fits) if fits and ns > 0 else None
