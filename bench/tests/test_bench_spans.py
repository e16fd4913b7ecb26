"""The metrics that read the program's own spans: the worklist build's host
time, the bytes copied between host and device, the fallback's rows; by hand,
in a CPU rehearsal, and on a recorded chip trace."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, tracing  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# Two fits in the window [0, 500) ns: the program's worklist builds on the
# host's clock (one cut by the window's end), and the span records that
# carry its byte and row counts
BARE_TRACE = {
    "devices": 1,
    "device": [["fusion.1", 100, 50, 0], ["fusion.2", 300, 100, 0]],
    "modules": [["jit_f", 100, 50, 0], ["jit_g", 300, 100, 0]],
    "host": [["bench.window", 0, 500, "main"],
             ["bench.fit", 50, 200, "main"],
             ["bench.fit", 260, 240, "main"]],
}
SPANS_TRACE = {**BARE_TRACE, "host": BARE_TRACE["host"] + [
    ["worklist.build", 60, 40, "main"], ["worklist.build", 470, 60, "main"],
    ["worklist.build_other", 100, 50, "main"]]}
SPANS = [
    {"name": "worklist.fetch", "attrs": {"d2h_bytes": 3_000_000}},
    {"name": "worklist.upload", "attrs": {"h2d_bytes": 1_000_000}},
    {"name": "rho_delta.fallback",
     "attrs": {"rows": 100, "padded": 128, "d2h_bytes": 500_000,
               "h2d_bytes": 500_000}},
    {"name": "rho_delta.fallback",
     "attrs": {"rows": 60, "padded": 64, "d2h_bytes": 0, "h2d_bytes": 0}},
    {"name": "engine.fit", "attrs": {"n": 8}},
    {"name": "labels.assign"},
]


@pytest.mark.parametrize("metric, value", [
    ("worklist_build_ms.fit", (40 + 30) / 1e6 / 2),
    ("host_transfer_mb.fit", 5.0 / 2),
    ("fallback_rows.fit", (128 + 64) / 2),
])
def test_program_span_metrics_by_hand(metric, value):
    mod = harness.load_module("metrics", metric)
    calls = [{"kind": "fit"}, {"kind": "fit"}]
    run = harness.Run(cell={}, calls=calls, spans=SPANS, trace=SPANS_TRACE,
                      window=(0, 500), peaks=None)
    assert mod.read(run) == pytest.approx(value)
    # a program without these spans (the parent of their PR) reads nothing
    bare = harness.Run(cell={}, calls=calls, spans=SPANS[4:],
                       trace=BARE_TRACE, window=(0, 500), peaks=None)
    assert mod.read(bare) is None


def test_traced_rehearsal_reads_the_program_spans(monkeypatch):
    """The worklist, transfer and fallback metrics read the program's own
    spans; the build time read from the trace is the spans' host time."""
    runs = []
    load = harness.load_module

    def keep_run(kind, name):
        mod = load(kind, name)
        read = mod.read
        mod.read = lambda run: runs.append(run) or read(run)
        return mod
    monkeypatch.setattr(harness, "load_module", keep_run)
    res = harness.run_cell("household.fit", 2**40 + 3, 0.5, True,
                           rehearse=True)
    m = res["metrics"]
    for name in ("worklist_build_ms.fit", "host_transfer_mb.fit",
                 "fallback_rows.fit"):
        assert m[name]["value"] > 0, name
    run = runs[0]
    fits = [c for c in run.calls if c["kind"] == "fit"]
    host_s = sum(s["host_s"] for s in run.spans
                 if s["name"] == "worklist.build")
    assert m["worklist_build_ms.fit"]["value"] == pytest.approx(
        host_s * 1e3 / len(fits), rel=0.01)


# The fit cell on one TPU v5e, traced with the program's spans as profiler
# annotations and its kernels named (45 s window, cut to its first fit)
RECORDED = os.path.join(DATA, "household.fit.spans.trace.json.gz")
PROGRAM_SPANS = ("engine.", "planner.", "approxdpc.", "worklist.",
                 "rho_delta.", "labels.")


def test_recorded_chip_trace_with_spans():
    """The reduction reads the recording as it read the trace without
    spans: the same busy time, kernel times and top kernel, now by name."""
    tr = tracing.load(RECORDED)
    w0, w1 = tracing.window(tr)
    k = harness.load_json("kernels.json")
    busy = 4.416456313
    assert tracing.busy_s(tr, w0, w1) == pytest.approx(busy, abs=1e-9)
    assert tracing.kernel_s(tr, k["pallas"]["patterns"], w0, w1) \
        == pytest.approx(3.604616046, abs=1e-9)
    assert tracing.kernel_s(tr, k["worklist_sweep"]["patterns"], w0, w1) \
        == pytest.approx(1.883924613, abs=1e-9)
    assert tracing.top_ops(tr, w0, w1, 1)[0][0] \
        == "jit__tile_sweep_jit:%tile_sweep_worklist.4"
    gaps = tracing.idle_gaps(tr, w0, w1)
    assert gaps[0][0] == "bench.fit / worklist.bounds" and len(gaps) <= 10
    idle = (w1 - w0) / 1e9 - busy
    assert sum(s for _, s in tracing.idle_gaps(tr, w0, w1, k=10**6)) \
        == pytest.approx(idle, abs=1e-6)


def test_recorded_trace_names_the_program_spans():
    """Inside a fit, the device's idle time is labelled by the program's
    own spans, not by the benchmark's call alone."""
    tr = tracing.load(RECORDED)
    w0, w1 = tracing.window(tr)
    gaps = tracing.idle_gaps(tr, w0, w1, k=10**6)
    in_fit = [(label.split(" / ")[-1], s) for label, s in gaps
              if label.startswith("bench.fit")]
    total = sum(s for _, s in in_fit)
    named = sum(s for leaf, s in in_fit if leaf.startswith(PROGRAM_SPANS))
    assert named >= 0.9 * total
    assert dict(in_fit)["worklist.bounds"] > 1.0
    assert dict(gaps)["bench.fit"] < 0.01 * total     # the call's bare label
