"""Device time of the Pallas sweep kernels per fit (the fused rho/delta
sweep and the masked-NN fallback), from the profiler trace."""
from bench import harness, tracing

UNIT = "ms"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    pats = harness.load_json("kernels.json")["pallas"]["patterns"]
    s = tracing.kernel_s(run.trace, pats, *run.window)
    return s / len(fits) * 1e3 if fits and s > 0 else None
