"""Device time of the Pallas sweep kernels per fit, mean over the chips:
the ops matching ``kernels.json``'s ``pallas`` patterns in the trace (the
shards' count sweeps and fallback rings), summed, over the chips and the
window's fits."""
from bench import harness, tracing

UNIT = "ms"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    pats = harness.load_json("kernels.json")["pallas"]["patterns"]
    s = tracing.kernel_s(run.trace, pats, *run.window)
    chips = max(run.trace["devices"], 1)
    return s / chips / len(fits) * 1e3 if fits and s > 0 else None
