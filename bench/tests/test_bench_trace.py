"""The trace reduction and the sweep's work count, on the CPU."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, roofline, tracing  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# window [0, 500) ns; device busy [100, 220) and [300, 400)
HAND = {
    "devices": 1,
    "device": [["while.1", 100, 120, 0], ["fusion.1", 100, 50, 0],
               ["sweep_kernel(x)", 160, 60, 0], ["fusion.2", 300, 100, 0],
               ["late", 600, 10, 0]],
    "modules": [["jit_f", 100, 120, 0], ["jit_g", 300, 100, 0]],
    "host": [["bench.window", 0, 500, "main"],
             ["bench.fit", 50, 300, "main"],
             ["PjitFunction(f)", 230, 60, "main"],
             ["bench.predict", 380, 120, "main"]],
}


def test_busy_idle_kernels_and_gaps_by_hand():
    w0, w1 = tracing.window(HAND)
    assert (w0, w1) == (0, 500)
    assert tracing.busy_intervals(HAND, w0, w1) == [(100, 220), (300, 400)]
    assert tracing.busy_s(HAND, w0, w1) == pytest.approx(220e-9)
    assert tracing.kernel_s(HAND, ["sweep"], w0, w1) == pytest.approx(6e-8)
    assert tracing.kernel_s(HAND, ["while"], w0, w1) == 0   # holds others
    assert tracing.top_ops(HAND, w0, w1) == [
        ["jit_g:fusion.2", 1e-7], ["jit_f:sweep_kernel(x)", 6e-8],
        ["jit_f:fusion.1", 5e-8]]
    gaps = dict(tracing.idle_gaps(HAND, w0, w1))
    assert gaps == pytest.approx({"bench.fit": 100e-9,
                                  "bench.fit / PjitFunction(f)": 80e-9,
                                  "bench.predict": 100e-9})


def test_sweep_work_by_hand():
    # 3 kept pairs of 2 x 4 points in 4 dims over 2 row tiles, top-1: each
    # pair reads its column tile twice (rows and columns: 2 * 4 * 4 floats);
    # each row tile reads its 2 x 4 coordinates and writes 2 counts and
    # 2 x 1 candidates as value and index
    nbytes = roofline.sweep_bytes(3, 2, d=4, block_n=2, block_m=4, topk=1)
    assert nbytes == 3 * (2 * 4 * 4 * 4) + 2 * (2 * 4 + 2 + 2 * 2) * 4
    assert nbytes == 496
    t = roofline.least_time(nbytes, harness.peaks("TPU v5 lite"))
    assert t == pytest.approx(496 / 8.19e11)
    k = harness.load_json("kernels.json")["worklist_sweep"]
    assert (k["block_n"], k["block_m"], k["topk"]) == (256, 512, 8)


# Traces of one TPU v5e (20 s windows), cut to the first calls of each
# window, and what the reduction read from them then: the fit cell, and two
# loops whose device is idle most of the time (label queries against a
# fitted table, 256-point stream ticks; PERF.md, Open questions).
RECORDED = {
    "household.fit": (4.394124549, 3.610764619, 1.890068644, "bench.fit",
                      "jit__tile_sweep_jit:%closed_call.11"),
    "household.predict": (0.58654584, 0.580814097, 0.0, "outside any call",
                          "jit__tile_sweep_jit:%_tile_sweep_jit.1"),
    "household_stream.ingest": (0.581490264, 0.55831984, 0.0, "bench.tick",
                                "jit_gather_nn:%gather_nn.1"),
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_chip_trace(cell):
    busy, pallas, sweep, gap, op = RECORDED[cell]
    tr = tracing.load(os.path.join(DATA, f"{cell}.trace.json.gz"))
    w0, w1 = tracing.window(tr)
    k = harness.load_json("kernels.json")
    assert tracing.busy_s(tr, w0, w1) == pytest.approx(busy, abs=1e-9)
    assert tracing.kernel_s(tr, k["pallas"]["patterns"], w0, w1) \
        == pytest.approx(pallas, abs=1e-9)
    assert tracing.kernel_s(tr, k["worklist_sweep"]["patterns"], w0, w1) \
        == pytest.approx(sweep, abs=1e-9)
    assert tracing.top_ops(tr, w0, w1, 1)[0][0] == op
    gaps = tracing.idle_gaps(tr, w0, w1)
    assert gaps[0][0] == gap and len(gaps) <= 10
    idle = (w1 - w0) / 1e9 - busy
    assert sum(s for _, s in tracing.idle_gaps(tr, w0, w1, k=10**6)) \
        == pytest.approx(idle, abs=1e-6)
