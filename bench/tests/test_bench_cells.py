"""Each cell rehearsed on the CPU through its own data files, and the
harness's refusals: a CPU without the rehearsal, an unknown device."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

# every cell file, those BENCHMARK.json has not admitted yet too
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench",
                                                       "workloads")))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    res = harness.run_cell(cell, 2**31 + 11, 1.0, False, rehearse=True)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name
    assert "setup_s" in res["metrics"] and len(res["metrics"]) == 2
    assert res["window_compiles"] == 0


def test_traced_rehearsal_reads_the_program_counters():
    res = harness.run_cell("household.fit", 7, 0.5, True, rehearse=True)
    assert res["metrics"]["worklist_kept_pairs.fit"]["value"] > 0
    assert {"device_ops", "idle_gaps"} <= set(res["breakdown"])
    assert "setup_s" not in res["metrics"]


def test_benchmark_json_names_every_file():
    idx = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in idx["workloads"]:
        cell = harness.load_json("workloads", f"{w['name']}.json")
        assert (cell["config"], cell["traffic"]) == (w["config"],
                                                     w["traffic"])
        harness.load_json("traffic", f"{w['traffic']}.json")
    for c in idx["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for m in idx["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert mod.UNIT == m["unit"]


def test_any_whole_seed_runs():
    res = harness.run_cell("household.fit", -(2**31) - 5, 0.3, False,
                           rehearse=True)
    assert res["correct"] is True


def test_copied_cell_runs_with_no_code_edit(tmp_path, monkeypatch):
    """A new cell is data: a copy of a cell file under a new name runs."""
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copy(bench / "workloads" / "household.fit.json",
                bench / "workloads" / "household.fit_copy.json")
    monkeypatch.setattr(harness, "BENCH", str(bench))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    res = harness.run_cell("household.fit_copy", 5, 0.5, False,
                           rehearse=True)
    assert res["correct"] is True
    assert "fit_s" in res["metrics"]


def test_cpu_without_rehearsal_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "household.fit", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == harness.NOT_A_TPU, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_refused():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        harness.peaks("cpu")
