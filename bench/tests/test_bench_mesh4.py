"""The distributed Ex-DPC cell, ``household_exdpc_mesh4.fit``: its seven
per-layer metrics by hand (and silent where the program records nothing),
its rehearsal over four fake devices, and its driver's refusal of a mesh
whose shard phases would sweep dense tiles."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, roofline  # noqa: E402

CELL = "household_exdpc_mesh4.fit"
PALLAS = 'custom-call(f32[8]) custom_call_target="tpu_custom_call"'

# Two fits in the window [0, 1000) ns on two chips: Pallas kernels, an
# all-gather (sync on chip 0, async start/done on chip 1), a fusion that
# reads the gathered table (not a collective), and one kernel outside the
# window
TRACE = {
    "devices": 2,
    "device": [
        [f"tile_sweep.1 = {PALLAS}", 100, 300, 0],
        [f"tile_sweep.1 = {PALLAS}", 100, 100, 1],
        ["all-gather.3 = f32[16,4] all-gather(f32[8,4] %p)", 450, 40, 0],
        ["all-gather-start.2 = (f32[8,4]) all-gather-start(%p)", 450, 10, 1],
        ["all-gather-done.2 = f32[16,4] all-gather-done(%s)", 480, 30, 1],
        ["fusion.9 = f32[16] fusion(f32[16,4] %all-gather.3)", 500, 100, 0],
        [f"tile_sweep.2 = {PALLAS}", 600, 200, 1],
        [f"tile_sweep.2 = {PALLAS}", 1200, 100, 0],
    ],
    "modules": [],
    "host": [["bench.window", 0, 1000, "main"],
             ["bench.fit", 0, 500, "main"],
             ["bench.fit", 500, 500, "main"]],
}
SPANS = [
    {"name": "dist.worklist",
     "attrs": {"phase": "count", "listed": 300, "chunks": 4,
               "row_tiles": 8, "d2h_bytes": 32}},
    {"name": "dist.worklist",
     "attrs": {"phase": "ring", "listed": 100, "chunks": 2,
               "row_tiles": 2, "d2h_bytes": 0}},
    {"name": "dist.fallback",
     "attrs": {"unresolved": 70, "padded": 1024, "listed": 100, "live": 40}},
    {"name": "dist.worklist",
     "attrs": {"phase": "count", "listed": 200, "chunks": 4,
               "row_tiles": 8, "d2h_bytes": 32}},
    {"name": "dist.fallback",
     "attrs": {"unresolved": 0, "padded": 0, "listed": 0, "live": 0}},
    {"name": "engine.fit", "attrs": {"n": 8}},
]
PEAKS = {"hbm_bytes_per_s": 8.19e11}


def _run(spans=SPANS, trace=TRACE, fits=2):
    cell = harness.load_cell(CELL)
    return harness.Run(cell=cell, calls=[{"kind": "fit"}] * fits,
                       spans=spans, trace=trace, window=(0, 1000),
                       peaks=PEAKS)


def _bytes():
    count = roofline.sweep_bytes(500, 16, d=4, block_n=256, block_m=512,
                                 topk=8)
    ring = 100 * 256 * 5 * 4 + 2 * 256 * 7 * 4
    return count + ring


BY_HAND = {
    "shard_listed_pairs.mesh4": (300 + 100 + 200) / 2,
    # Pallas ns in the window: chip 0 300, chip 1 100 + 200
    "shard_sweep_ms.mesh4": 600 / 2 / 2 / 1e6,
    "shard_roofline.mesh4": 100.0 * _bytes() / 8.19e11 / (600 / 1e9),
    "shard_imbalance.mesh4": 0.0,
    "allgather_ms.mesh4": (40 + 10 + 30) / 2 / 2 / 1e6,
    "dist_fallback_rows.mesh4": 70 / 2,
    "device_idle_pct.mesh4": 100.0 * (1 - (
        (300 + 40 + 100) + (100 + 10 + 30 + 200)) / 2 / 1000),
}


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_metric_by_hand(metric):
    mod = harness.load_module("metrics", metric)
    assert mod.read(_run()) == pytest.approx(BY_HAND[metric])


@pytest.mark.parametrize("metric", [
    "shard_listed_pairs.mesh4", "shard_roofline.mesh4",
    "dist_fallback_rows.mesh4"])
def test_metric_without_the_spans_reads_nothing(metric):
    """The parent's program records no dist.worklist spans and no
    unresolved rows: these metrics leave the line."""
    mod = harness.load_module("metrics", metric)
    assert mod.read(_run(spans=SPANS[5:])) is None
    if metric != "shard_roofline.mesh4":       # a share, not per fit
        assert mod.read(_run(fits=0)) is None


@pytest.mark.parametrize("metric", [
    "shard_sweep_ms.mesh4", "shard_roofline.mesh4", "shard_imbalance.mesh4",
    "allgather_ms.mesh4"])
def test_metric_without_the_ops_reads_nothing(metric):
    """A trace with no Pallas kernel or no collective (a CPU rehearsal, or
    one chip) leaves the device-time metrics out."""
    bare = {**TRACE, "devices": 1, "device": [
        e for e in TRACE["device"] if "fusion" in e[0]]}
    mod = harness.load_module("metrics", metric)
    assert mod.read(_run(trace=bare)) is None


def test_imbalance_reads_the_slowest_chip():
    trace = {**TRACE, "device": TRACE["device"] + [
        [f"tile_sweep.3 = {PALLAS}", 850, 100, 0]]}
    mod = harness.load_module("metrics", "shard_imbalance.mesh4")
    assert mod.read(_run(trace=trace)) == pytest.approx(
        100.0 * (400 / 350 - 1))


def test_benchmark_json_lists_the_cell_and_its_metrics():
    idx = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (w,) = [w for w in idx["workloads"] if w["name"] == CELL]
    assert w["chips"] == 4
    names = harness.per_layer_metrics(CELL, ["fit_s"])
    assert sorted(names) == sorted(BY_HAND)
    (fit_s,) = [m for m in idx["end_to_end"] if m["name"] == "fit_s"]
    assert CELL in fit_s["workloads"]
    cfg = harness.load_json("configs", "household_exdpc_mesh4.json")
    assert cfg["n"] == cfg["n_published"] == 2049280
    assert cfg["reduced"] == [] and cfg["chips"] == 4


def test_rehearsal_over_four_devices():
    """The cell at its rehearsal size with its four chips: four fake CPU
    devices, so every shard phase runs across a mesh."""
    script = (
        "import json, sys\n"
        f"sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, 'src')!r}]\n"
        "from bench import harness\n"
        f"cell = harness.load_cell({CELL!r}, rehearse=True)\n"
        "cell['config']['chips'] = 4\n"
        f"res = harness.run_cell({CELL!r}, 2**33 + 7, 1.0, False,\n"
        "                       rehearse=True, cell=cell)\n"
        "print('RESULT' + json.dumps(res))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.startswith("RESULT")][0][len("RESULT"):])
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["window_compiles"] == 0
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_setup_refuses_dense_shard_phases(monkeypatch):
    """A program whose shard phases would not run block-sparse (the
    parent's pallas shards) is refused at set-up, before any data or
    fit."""
    from bench import data
    from repro.distributed import dpc

    monkeypatch.setattr(dpc, "shard_blocksparse_layout",
                        lambda pl, mesh: None)

    def no_data(*a, **k):
        raise AssertionError("set-up drew data before refusing")
    monkeypatch.setattr(data, "draw", no_data)
    with pytest.raises(RuntimeError, match="dense"):
        harness.run_cell(CELL, 5, 0.5, False, rehearse=True)
