"""The check fails when the timed path is broken underneath, once for each
fault a cell can have, and fails on the control (the program's bf16 path).

Each test rehearses a cell on the CPU with the chip check skipped and one
fault planted in the program for that run only."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import control, harness  # noqa: E402
from bench.drivers import fit as fit_driver  # noqa: E402
from repro.engine import DPCEngine  # noqa: E402

SEED = 2**31 + 3


def run(name, seconds=0.5, **kw):
    return harness.run_cell(name, SEED, seconds, False, rehearse=True, **kw)


def test_sound_runs_are_correct():
    assert run("household.fit")["correct"] is True


def test_fit_answer_altered(monkeypatch):
    fit = DPCEngine.fit

    def altered(self, points):
        fit(self, points)
        self._result = self._result._replace(rho=self._result.rho + 1)
        return self
    monkeypatch.setattr(DPCEngine, "fit", altered)
    res = run("household.fit")
    assert res["correct"] is False
    assert res["checks"]["rho_rows_off"]["value"] > \
        res["checks"]["rho_rows_off"]["limit"]


def test_later_fit_answer_altered(monkeypatch):
    """A wrong answer in any fit of the window, not only the first."""
    fit = DPCEngine.fit
    calls = {"n": 0}

    def altered(self, points):
        fit(self, points)
        calls["n"] += 1
        if calls["n"] == 4:          # set-up's fit, then the window's third
            self._result = self._result._replace(
                parent=self._result.parent[::-1])
        return self
    monkeypatch.setattr(DPCEngine, "fit", altered)
    res = run("household.fit", seconds=3.0)
    assert res["attempted"] >= 3
    assert res["correct"] is False
    assert res["checks"]["parent_rows_off"]["value"] > \
        res["checks"]["parent_rows_off"]["limit"]


def test_fit_returns_state_unchanged(monkeypatch):
    fit = DPCEngine.fit
    calls = {"n": 0}

    def stale(self, points):
        calls["n"] += 1
        # set-up's fit runs; the window's keep its answer
        return fit(self, points) if calls["n"] <= 1 else self
    monkeypatch.setattr(DPCEngine, "fit", stale)
    assert run("household.fit")["correct"] is False


def test_fit_drops_half_the_points(monkeypatch):
    fit = DPCEngine.fit
    monkeypatch.setattr(DPCEngine, "fit",
                        lambda self, x: fit(self, np.asarray(x)[::2]))
    assert run("household.fit")["correct"] is False


@pytest.mark.parametrize("cell,variant", [
    ("household.fit", "program_bf16"),
    ("household.fit", "reference_bf16")])
def test_control_is_not_correct(cell, variant, monkeypatch):
    ctl = {v: (c, f) for v, c, f in control.variants(
        harness.load_cell(cell, True))}
    c, factory = ctl[variant]
    if factory is not None:
        monkeypatch.setattr(fit_driver, "engine", factory)
    assert run(cell, cell=c)["correct"] is False
