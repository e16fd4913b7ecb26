"""The metric that reads the ring pairs of the program's masked-NN fallback,
``fallback_live_pairs.fit``: by hand, on a program whose fallback sweeps
densely, and in a CPU rehearsal."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

METRIC = "fallback_live_pairs.fit"

# Two fits in the window [0, 500) ns, each with one fallback
TRACE = {
    "devices": 1,
    "device": [["fusion.1", 100, 50, 0], ["fusion.2", 300, 100, 0]],
    "modules": [["jit_f", 100, 50, 0], ["jit_g", 300, 100, 0]],
    "host": [["bench.window", 0, 500, "main"],
             ["bench.fit", 50, 200, "main"],
             ["bench.fit", 260, 240, "main"]],
}
RING = [
    {"name": "rho_delta.fallback",
     "attrs": {"rows": 100, "padded": 128, "d2h_bytes": 500_000,
               "h2d_bytes": 500_000, "pairs": 2048, "live": 500}},
    {"name": "rho_delta.fallback",
     "attrs": {"rows": 60, "padded": 64, "d2h_bytes": 0, "h2d_bytes": 0,
               "pairs": 1024, "live": 300}},
    {"name": "engine.fit", "attrs": {"n": 8}},
]
# the same fallbacks on the dense layout, or on the parent of the ring
DENSE = [{**s, "attrs": {k: v for k, v in s["attrs"].items()
                         if k not in ("pairs", "live")}} for s in RING]


def _run(spans, fits=2):
    return harness.Run(cell={}, calls=[{"kind": "fit"}] * fits, spans=spans,
                       trace=TRACE, window=(0, 500), peaks=None)


@pytest.mark.parametrize("spans, fits, value", [
    (RING, 2, (500 + 300) / 2),
    (RING[1:], 1, 300),
    (RING[:1] + DENSE[1:], 2, 500 / 2),
])
def test_live_pairs_by_hand(spans, fits, value):
    mod = harness.load_module("metrics", METRIC)
    assert mod.read(_run(spans, fits)) == pytest.approx(value)


@pytest.mark.parametrize("spans", [DENSE, DENSE[2:], []])
def test_without_a_ring_reads_nothing(spans):
    """A fallback that sweeps densely records rows but no ring pairs; the
    metric then leaves itself out of the line."""
    mod = harness.load_module("metrics", METRIC)
    assert mod.read(_run(spans)) is None
    assert mod.read(_run(RING, fits=0)) is None


def test_traced_rehearsal_reads_the_ring_pairs(monkeypatch):
    """The block-sparse fit's fallback walks a ring: the rehearsal's spans
    carry ``pairs`` and ``live``, and the metric is their live count per
    fit, within the listed pairs."""
    runs = []
    load = harness.load_module

    def keep_run(kind, name):
        mod = load(kind, name)
        read = mod.read
        mod.read = lambda run: runs.append(run) or read(run)
        return mod
    monkeypatch.setattr(harness, "load_module", keep_run)
    res = harness.run_cell("household.fit", 2**40 + 5, 0.5, True,
                           rehearse=True)
    value = res["metrics"][METRIC]["value"]
    assert value > 0
    run = runs[0]
    fits = [c for c in run.calls if c["kind"] == "fit"]
    fb = [s["attrs"] for s in run.spans if s["name"] == "rho_delta.fallback"]
    assert fb and all(0 < a["live"] <= a["pairs"] for a in fb)
    assert value == pytest.approx(sum(a["live"] for a in fb) / len(fits))
