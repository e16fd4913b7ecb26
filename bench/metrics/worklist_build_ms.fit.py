"""Host time of the program's worklist builds per fit: the events of the
``worklist.build`` span (an obs span, which opens a profiler annotation of
its name) in the trace's host events, clipped to the window, summed, over
the window's fits."""
UNIT = "ms"
SPAN = "worklist.build"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    w0, w1 = run.window
    ns = sum(min(a + dur, w1) - max(a, w0)
             for name, a, dur, _ in run.trace["host"]
             if name == SPAN and a < w1 and a + dur > w0)
    return ns / 1e6 / len(fits) if fits and ns > 0 else None
