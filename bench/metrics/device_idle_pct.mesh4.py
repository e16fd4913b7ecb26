"""Share of the traced window in which no op ran on a chip, averaged over
the chips."""
from bench import tracing

UNIT = "%"


def read(run):
    w0, w1 = run.window
    return 100.0 * (1.0 - tracing.busy_s(run.trace, w0, w1) / ((w1 - w0) / 1e9))
