"""repro.resilience: checkpoints, transactional ticks, quarantine, degrade.

Acceptance contract (ISSUE 9): for every fault-injection site, a mid-tick
kill + restore yields bit-identical rho/delta/labels/center-ids versus the
uninterrupted run; and a poisoned (NaN/Inf) batch under each quarantine
policy never changes the labels of already-windowed points.
"""
import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.data.points import gaussian_mixture
from repro.engine import ExecSpec
from repro.engine.dpc_engine import DPCEngine
from repro.engine.planner import plan, plan_cache_clear
from repro.resilience import checkpoint, degrade, faultinject
from repro.resilience.sanitize import (AdmissionConfig, PoisonedInputError,
                                       admit, finite_or)
from repro.stream import (QueryStatus, StreamDPC, StreamDPCConfig,
                          StreamServeConfig, StreamService)

CAP, B, D_CUT, RHO_MIN = 512, 64, 8000.0, 3.0


def _cfg(backend="jnp", **kw):
    base = dict(d_cut=D_CUT, capacity=CAP, batch_cap=B, rho_min=RHO_MIN,
                exec_spec=ExecSpec(backend=backend))
    base.update(kw)
    return StreamDPCConfig(**base)


def _data(ticks=3, seed=2):
    pts, _ = gaussian_mixture(CAP + ticks * B, k=4, d=2, overlap=0.05,
                              seed=seed)
    return pts


def _stream(backend="jnp", ticks=2, seed=2, **kw):
    pts = _data(ticks=ticks, seed=seed)
    s = StreamDPC(_cfg(backend, **kw))
    s.initialize(pts[:CAP])
    for t in range(ticks):
        s.ingest(pts[CAP + t * B: CAP + (t + 1) * B])
    return s, pts


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    """Every test starts and ends with no armed fault plan."""
    faultinject.deactivate()
    yield
    faultinject.deactivate()


# --------------------------------------------------------------- sanitize
class TestSanitize:
    def test_clean_points_pass_untouched(self):
        pts = np.array([[1.0, 2.0], [9e8, -9e8]], np.float32)
        for policy in ("reject", "drop", "clamp"):
            out = admit(pts, AdmissionConfig(policy=policy))
            assert np.array_equal(out.points, pts)
            assert out.keep.all() and out.quarantined == 0

    def test_reject_raises_and_names_the_row(self):
        pts = np.array([[1.0, 2.0], [np.nan, 0.0]], np.float32)
        with pytest.raises(PoisonedInputError, match="row 1"):
            admit(pts, AdmissionConfig())

    def test_drop_keeps_alignment_mask(self):
        pts = np.array([[1.0, 1.0], [np.inf, 0.0], [2.0, 2.0], [2e9, 0.0]],
                       np.float32)
        out = admit(pts, AdmissionConfig(policy="drop"))
        assert out.keep.tolist() == [True, False, True, False]
        assert np.array_equal(out.points, pts[[0, 2]])
        assert out.quarantined == 2

    def test_clamp_repairs_in_place(self):
        pts = np.array([[np.nan, np.inf], [-np.inf, 3.0], [2e9, -2e9]],
                       np.float32)
        out = admit(pts, AdmissionConfig(policy="clamp"))
        assert out.keep.all() and out.quarantined == 3
        assert np.isfinite(out.points).all()
        assert (np.abs(out.points) < 1e9).all()
        assert out.points[0, 0] == 0.0          # NaN -> 0
        assert out.points[1, 1] == 3.0          # finite coords untouched

    def test_bad_dtype_rejected_under_every_policy(self):
        for policy in ("reject", "drop", "clamp"):
            with pytest.raises(PoisonedInputError, match="dtype"):
                admit(np.array([["a", "b"]]), AdmissionConfig(policy=policy))

    def test_out_of_range_bound_is_open_at_pad_coord(self):
        # 1e9 == PAD_COORD must quarantine; just below it must pass (the
        # serve miss-fallback tests probe with 9e8 coordinates)
        with pytest.raises(PoisonedInputError):
            admit(np.array([[1e9, 0.0]]), AdmissionConfig())
        out = admit(np.array([[9e8, 0.0]]), AdmissionConfig())
        assert out.quarantined == 0

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="policy"):
            AdmissionConfig(policy="ignore")
        with pytest.raises(ValueError, match="max_abs"):
            AdmissionConfig(max_abs=0.0)

    def test_quarantine_counter_increments(self):
        m = obs.counter("resilience_quarantined_points", "")
        before = m.value(reason="non_finite", policy="drop",
                         where="unit") or 0
        admit(np.array([[np.nan, 0.0]]), AdmissionConfig(policy="drop"),
              where="unit")
        after = m.value(reason="non_finite", policy="drop", where="unit")
        assert after == before + 1

    def test_finite_or_under_jit(self):
        import jax
        f = jax.jit(lambda x: finite_or(x, 7.0))
        x = jnp.array([1.0, jnp.inf, -jnp.inf, jnp.nan])
        assert np.array_equal(np.asarray(f(x)), [1.0, 7.0, 7.0, 7.0])


# ------------------------------------------------------------ faultinject
class TestFaultInject:
    def test_fires_on_nth_hit(self):
        faultinject.activate("tick.finish", trigger=3)
        faultinject.fire("tick.finish")
        faultinject.fire("tick.finish")
        with pytest.raises(faultinject.FaultError):
            faultinject.fire("tick.finish")
        # one-shot: hit 4 does not re-fire
        faultinject.fire("tick.finish")

    def test_trigger_zero_fires_every_hit(self):
        faultinject.activate("kernel.dispatch", trigger=0)
        for _ in range(3):
            with pytest.raises(faultinject.FaultError):
                faultinject.fire("kernel.dispatch")

    def test_other_sites_unaffected(self):
        faultinject.activate("tick.rho_repair", trigger=1)
        faultinject.fire("tick.finish")
        faultinject.fire("checkpoint.write")

    def test_seed_trigger_is_deterministic(self):
        t1 = faultinject.activate("tick.finish", seed=7).trigger
        t2 = faultinject.activate("tick.finish", seed=7).trigger
        t3 = faultinject.activate("tick.finish", seed=8).trigger
        assert t1 == t2 and t1 >= 1 and t3 >= 1

    def test_unknown_site_or_mode_fails_loudly(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            faultinject.activate("tick.typo")
        with pytest.raises(ValueError, match="unknown fault mode"):
            faultinject.activate("tick.finish", mode="explode")

    def test_corrupt_mode_never_raises_at_fire(self):
        faultinject.activate("checkpoint.write", mode="corrupt", trigger=1)
        faultinject.fire("checkpoint.write")
        assert faultinject.should_corrupt("checkpoint.write")
        assert not faultinject.should_corrupt("checkpoint.serialize")


# ---------------------------------------------------- transactional ingest
class TestTransactionalIngest:
    @pytest.mark.parametrize("site", ["tick.grid_apply", "tick.rho_repair",
                                      "tick.nn_update", "tick.finish"])
    def test_failed_tick_rolls_back_and_replays_bit_identical(self, site):
        pts = _data(ticks=2)
        control = StreamDPC(_cfg())
        control.initialize(pts[:CAP])
        control.ingest(pts[CAP: CAP + B])
        t_ref = control.ingest(pts[CAP + B: CAP + 2 * B])

        s = StreamDPC(_cfg())
        s.initialize(pts[:CAP])
        s.ingest(pts[CAP: CAP + B])
        pre_host = s.window.host.copy()
        pre_rho = np.asarray(s._rho).copy()
        pre_stats = s.stats()
        faultinject.activate(site, trigger=1)
        with pytest.raises(faultinject.FaultError):
            s.ingest(pts[CAP + B: CAP + 2 * B])
        faultinject.deactivate()
        # rollback: window/grid/rho/counters exactly pre-tick
        assert np.array_equal(s.window.host, pre_host)
        assert np.array_equal(np.asarray(s._rho), pre_rho)
        assert s.stats() == pre_stats
        # replaying the failed batch matches the never-faulted control
        t = s.ingest(pts[CAP + B: CAP + 2 * B])
        assert np.array_equal(t.labels, t_ref.labels)
        assert np.array_equal(t.stable_ids, t_ref.stable_ids)
        assert np.array_equal(np.asarray(s._rho), np.asarray(control._rho))
        assert np.array_equal(np.asarray(s.result.delta),
                              np.asarray(control.result.delta))

    def test_transactional_off_skips_snapshots(self):
        s, _ = _stream(ticks=1, transactional=False)
        pts = _data(ticks=2)
        faultinject.activate("tick.finish", trigger=1)
        with pytest.raises(faultinject.FaultError):
            s.ingest(pts[CAP + B: CAP + 2 * B])


# ------------------------------------------------------------ edge inputs
class TestEdgeInputs:
    def test_empty_ingest_is_a_noop(self):
        s, _ = _stream(ticks=1)
        last = s._last
        ticks = s._ticks
        assert s.ingest(np.zeros((0, 2), np.float32)) is last
        assert s._ticks == ticks

    def test_initialize_overfill_raises(self):
        s = StreamDPC(_cfg())
        with pytest.raises(ValueError, match="capacity"):
            s.initialize(np.zeros((CAP + 1, 2), np.float32))

    def test_dim_mismatch_raises(self):
        s, _ = _stream(ticks=1)
        with pytest.raises(ValueError, match="dimensionality"):
            s.ingest(np.zeros((4, 3), np.float32))

    def test_empty_submit_and_flush(self):
        svc = StreamService(StreamServeConfig(stream=_cfg()))
        assert svc.submit(np.zeros((0, 2), np.float32)) == []
        assert svc.flush() is None
        assert svc.stats()["buffered"] == 0


# ------------------------------------------------------- admission control
class TestAdmission:
    def _service(self, policy):
        pts = _data(ticks=1)
        svc = StreamService(StreamServeConfig(
            stream=_cfg(), admission=AdmissionConfig(policy=policy)))
        svc.engine.initialize(pts[:CAP])
        return svc, pts

    def test_reject_poisoned_submit_leaves_state_untouched(self):
        svc, pts = self._service("reject")
        before = svc.engine._last
        bad = pts[CAP: CAP + B].copy()
        bad[3, 0] = np.nan
        with pytest.raises(PoisonedInputError):
            svc.submit(bad)
        assert svc.engine._last is before
        assert svc.stats()["buffered"] == 0

    def test_drop_all_poisoned_batch_is_a_noop(self):
        svc, _ = self._service("drop")
        before = svc.engine._last
        bad = np.full((B, 2), np.nan, np.float32)
        assert svc.submit(bad) == []
        assert svc.engine._last is before
        assert svc.stats()["buffered"] == 0

    def test_drop_mixed_batch_equals_clean_only_ingest(self):
        svc, pts = self._service("drop")
        batch = pts[CAP: CAP + B].copy()
        batch[5, 1] = np.inf
        batch[17, 0] = np.nan
        svc.submit(batch)
        clean = np.delete(pts[CAP: CAP + B], [5, 17], axis=0)
        ref = StreamDPC(_cfg())
        ref.initialize(pts[:CAP])
        ref.ingest(clean)        # partial tick buffered in svc: flush first
        tick = svc.flush()
        assert np.array_equal(tick.labels, ref._last.labels)
        assert np.array_equal(tick.stable_ids, ref._last.stable_ids)

    def test_clamp_equals_presanitized_ingest(self):
        svc, pts = self._service("clamp")
        batch = pts[CAP: CAP + B].copy()
        batch[0, 0] = np.nan
        batch[1, 1] = np.inf
        ticks = svc.submit(batch)
        assert len(ticks) == 1
        fixed = admit(batch, AdmissionConfig(policy="clamp")).points
        ref = StreamDPC(_cfg())
        ref.initialize(pts[:CAP])
        t_ref = ref.ingest(fixed)
        assert np.array_equal(ticks[0].labels, t_ref.labels)
        assert np.array_equal(ticks[0].stable_ids, t_ref.stable_ids)

    def test_admission_disabled_passes_through(self):
        svc = StreamService(StreamServeConfig(stream=_cfg(), admission=None))
        svc.submit(np.full((4, 2), 42.0, np.float32))
        assert svc.stats()["buffered"] == 4

    @pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
    def test_query_quarantines_non_finite_rows(self, backend):
        pts = _data(ticks=1)
        svc = StreamService(StreamServeConfig(stream=_cfg(backend)))
        svc.engine.initialize(pts[:CAP])
        q = np.array([pts[0], [np.nan, 1.0], [np.inf, -np.inf]], np.float32)
        out = svc.query(q)
        assert out.status[0] == int(QueryStatus.HIT)
        assert (out.status[1:] == int(QueryStatus.QUARANTINED)).all()
        assert (out.labels[1:] == -1).all()

    def test_engine_fit_rejects_poison(self):
        pts = _data(ticks=0)[:256].copy()
        pts[7, 0] = np.nan
        eng = DPCEngine(d_cut=D_CUT, rho_min=RHO_MIN,
                        exec_spec=ExecSpec(backend="jnp"))
        with pytest.raises(PoisonedInputError):
            eng.fit(pts)

    def test_engine_predict_drop_expands_quarantined_rows(self):
        pts = _data(ticks=0)
        eng = DPCEngine(d_cut=D_CUT, rho_min=RHO_MIN,
                        exec_spec=ExecSpec(backend="jnp"),
                        admission=AdmissionConfig(policy="drop"))
        eng.fit(pts[:256])
        q = np.array([pts[0], [np.nan, 0.0], pts[1]], np.float32)
        out = eng.predict(q)
        assert len(out.labels) == 3
        assert out.status[1] == int(QueryStatus.QUARANTINED)
        assert out.labels[1] == -1
        clean = eng.predict(np.array([pts[0], pts[1]], np.float32))
        assert np.array_equal(out.labels[[0, 2]], clean.labels)

    def test_engine_partial_fit_quarantined_batch_is_noop(self):
        pts = _data(ticks=1)
        eng = DPCEngine(d_cut=D_CUT, rho_min=RHO_MIN, window_capacity=CAP,
                        batch_cap=B, exec_spec=ExecSpec(backend="jnp"),
                        admission=AdmissionConfig(policy="drop"))
        eng.partial_fit(pts[:CAP])
        last = eng.stream._last
        out = eng.partial_fit(np.full((8, 2), np.inf, np.float32))
        assert out is last


# ------------------------------------------------------------- checkpoints
class TestCheckpoint:
    @pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
    def test_restore_ticks_bit_identical(self, backend, tmp_path):
        ticks = 3
        pts = _data(ticks=ticks)
        ref = StreamDPC(_cfg(backend))
        ref.initialize(pts[:CAP])
        for t in range(ticks):
            t_ref = ref.ingest(pts[CAP + t * B: CAP + (t + 1) * B])

        s = StreamDPC(_cfg(backend))
        s.initialize(pts[:CAP])
        s.ingest(pts[CAP: CAP + B])
        p = str(tmp_path / "ckpt.npz")
        s.save(p)
        r = StreamDPC.restore(p)
        assert r.stats() == s.stats()
        for t in range(1, ticks):
            tick = r.ingest(pts[CAP + t * B: CAP + (t + 1) * B])
        assert np.array_equal(tick.labels, t_ref.labels)
        assert np.array_equal(tick.stable_ids, t_ref.stable_ids)
        assert np.array_equal(np.asarray(r._rho), np.asarray(ref._rho))
        assert np.array_equal(np.asarray(r.result.delta),
                              np.asarray(ref.result.delta))
        assert np.array_equal(np.asarray(r.result.parent),
                              np.asarray(ref.result.parent))

    def test_warmup_state_round_trips(self, tmp_path):
        pts = _data(ticks=0)
        s = StreamDPC(_cfg())
        s.initialize(pts[: CAP // 2])       # below capacity: grid unbuilt
        p = str(tmp_path / "warm.npz")
        s.save(p)
        r = StreamDPC.restore(p)
        t1 = r.ingest(pts[CAP // 2: CAP // 2 + B])
        t2 = s.ingest(pts[CAP // 2: CAP // 2 + B])
        assert np.array_equal(t1.labels, t2.labels)

    def test_save_before_data_raises(self, tmp_path):
        with pytest.raises(ValueError, match="window state"):
            StreamDPC(_cfg()).save(str(tmp_path / "x.npz"))

    def test_atomic_write_keeps_previous_checkpoint(self, tmp_path):
        s, pts = _stream(ticks=2)
        p = str(tmp_path / "ckpt.npz")
        s.save(p)
        ticks_saved = s._ticks
        s.ingest(pts[CAP + B: CAP + 2 * B])
        faultinject.activate("checkpoint.write", trigger=1)
        with pytest.raises(faultinject.FaultError):
            s.save(p)
        faultinject.deactivate()
        r = StreamDPC.restore(p)        # previous file intact + readable
        assert r._ticks == ticks_saved

    def test_corrupted_file_raises_checkpoint_error(self, tmp_path):
        s, _ = _stream(ticks=1)
        p = str(tmp_path / "ckpt.npz")
        faultinject.activate("checkpoint.write", mode="corrupt", trigger=1)
        s.save(p)
        faultinject.deactivate()
        with pytest.raises(checkpoint.CheckpointError):
            StreamDPC.restore(p)

    def test_garbage_file_raises_checkpoint_error(self, tmp_path):
        p = tmp_path / "junk.npz"
        p.write_bytes(b"not a checkpoint")
        with pytest.raises(checkpoint.CheckpointError):
            StreamDPC.restore(str(p))

    def test_future_version_raises_checkpoint_error(self, tmp_path):
        import json
        meta = {"format": checkpoint.FORMAT, "version": checkpoint.VERSION + 1}
        p = str(tmp_path / "future.npz")
        np.savez(p, meta=np.frombuffer(json.dumps(meta).encode(), np.uint8))
        with pytest.raises(checkpoint.CheckpointError, match="version"):
            StreamDPC.restore(p)


# -------------------------------------------------------------- degradation
class TestDegrade:
    def test_pallas_degrades_to_interpret_on_cpu(self, monkeypatch):
        # natural degradation: no TPU, Mosaic cannot compile
        monkeypatch.setenv("REPRO_ANALYSIS", "0")
        assert degrade.resolve_backend("pallas") == "pallas-interpret"
        pl = plan(None, ExecSpec(backend="pallas"))
        assert pl.backend_name == "pallas-interpret"
        m = obs.counter("resilience_degrade_total", "")
        assert any("src=pallas" in k for k in m._vals), \
            "degrade counter never incremented"

    def test_forced_full_chain_lands_on_jnp(self):
        faultinject.activate("degrade.probe", trigger=0)
        degrade.reset()
        try:
            assert degrade.resolve_backend("pallas") == "jnp"
        finally:
            faultinject.deactivate()
            degrade.reset()

    def test_bf16_never_degrades_to_jnp(self):
        faultinject.activate("degrade.probe", trigger=0)
        degrade.reset()
        try:
            with pytest.raises(RuntimeError, match="bf16"):
                degrade.resolve_backend("pallas", precision="bf16")
        finally:
            faultinject.deactivate()
            degrade.reset()

    def test_failed_probe_raises_on_tpu(self, monkeypatch):
        # on a chip the chain is never walked: the probe's error surfaces
        monkeypatch.setattr(degrade, "_platform", lambda: "tpu")
        faultinject.activate("degrade.probe", trigger=0)
        degrade.reset()
        try:
            with pytest.raises(RuntimeError, match="on the TPU.*injected"):
                degrade.resolve_backend("pallas")
        finally:
            faultinject.deactivate()
            degrade.reset()

    def test_degrade_disabled_returns_request_unprobed(self, monkeypatch):
        monkeypatch.setenv("REPRO_DEGRADE", "0")
        assert degrade.resolve_backend("pallas") == "pallas"

    def test_jnp_and_auto_never_probe(self):
        assert degrade.resolve_backend("jnp") == "jnp"
        # auto on CPU resolves to jnp before the chain is consulted
        assert degrade.resolve_backend(None) == "jnp"


# ------------------------------------------------------------- chaos suite
# A subprocess runs the stream with checkpoints after every tick and an
# env-armed kill fault; the parent restores from the last checkpoint and
# proves the resumed run is bit-identical to an uninterrupted one.
_CHAOS_SCRIPT = r"""
import sys, warnings
warnings.filterwarnings("ignore")
import numpy as np
from repro.data.points import gaussian_mixture
from repro.engine import ExecSpec
from repro.stream import StreamDPC, StreamDPCConfig

ckpt, backend = sys.argv[1], sys.argv[2]
CAP, B, TICKS = 512, 64, 4
pts, _ = gaussian_mixture(CAP + TICKS * B, k=4, d=2, overlap=0.05, seed=2)
s = StreamDPC(StreamDPCConfig(d_cut=8000.0, capacity=CAP, batch_cap=B,
                              rho_min=3.0,
                              exec_spec=ExecSpec(backend=backend)))
s.initialize(pts[:CAP])
s.save(ckpt)
for t in range(TICKS):
    s.ingest(pts[CAP + t * B: CAP + (t + 1) * B])   # env fault kills here
    s.save(ckpt)
print("SURVIVED")   # only reached when no fault is armed
"""

_SHARDED_CKPT_SCRIPT = r"""
import json, warnings
warnings.filterwarnings("ignore")
import numpy as np, jax
from repro.data.points import gaussian_mixture
from repro.engine import ExecSpec
from repro.stream import StreamDPC, StreamDPCConfig
import sys

ckpt = sys.argv[1]
assert jax.device_count() == 4
CAP, B = 512, 64
mesh = jax.make_mesh((2, 2), ("data", "model"))
pts, _ = gaussian_mixture(CAP + 3 * B, k=4, d=2, overlap=0.05, seed=2)
s = StreamDPC(StreamDPCConfig(d_cut=8000.0, capacity=CAP, batch_cap=B,
                              rho_min=3.0,
                              exec_spec=ExecSpec(backend="jnp")), mesh=mesh)
s.initialize(pts[:CAP])
for t in range(2):
    s.ingest(pts[CAP + t * B: CAP + (t + 1) * B])
s.save(ckpt)                    # checkpoint of a 4-device sharded stream
tick = s.ingest(pts[CAP + 2 * B: CAP + 3 * B])
out = {"labels": tick.labels.tolist(),
       "stable": tick.stable_ids.tolist(),
       "rho": np.asarray(s._rho).tolist(),
       "delta": np.asarray(s.result.delta).tolist()}
print("RESULT" + json.dumps(out))
"""


def _run_chaos(tmp_path, site, trigger, backend="jnp"):
    import subprocess
    import sys

    ckpt = str(tmp_path / f"chaos-{site.replace('.', '-')}.npz")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_FAULT_SITE"] = site
    env["REPRO_FAULT_MODE"] = "kill"
    env["REPRO_FAULT_TRIGGER"] = str(trigger)
    proc = subprocess.run([sys.executable, "-c", _CHAOS_SCRIPT, ckpt,
                           backend], env=env, capture_output=True,
                          text=True, timeout=900)
    return proc, ckpt


class TestChaosCrashRestore:
    @pytest.mark.slow
    @pytest.mark.parametrize("site,trigger", [
        ("tick.grid_apply", 2), ("tick.rho_repair", 2),
        ("tick.nn_update", 2),
        # initialize's full tick hits tick.finish once already
        ("tick.finish", 3),
        # between the temp write and the rename: the old file must survive
        ("checkpoint.write", 3),
    ])
    def test_kill_restore_parity(self, site, trigger, tmp_path):
        """Kill the stream mid-tick at every injection site, restore from
        the last checkpoint, replay — bit-identical to uninterrupted."""
        CAP_, B_, TICKS = 512, 64, 4
        pts = _data(ticks=TICKS)
        ref = StreamDPC(_cfg())
        ref.initialize(pts[:CAP_])
        for t in range(TICKS):
            t_ref = ref.ingest(pts[CAP_ + t * B_: CAP_ + (t + 1) * B_])

        proc, ckpt = _run_chaos(tmp_path, site, trigger)
        assert proc.returncode == faultinject.KILL_EXIT_CODE, \
            (proc.returncode, proc.stderr[-2000:])
        assert "SURVIVED" not in proc.stdout
        r = StreamDPC.restore(ckpt)
        done = r.stats()["ticks"] - 1      # initialize counts one tick
        assert 0 <= done < TICKS
        for t in range(done, TICKS):
            tick = r.ingest(pts[CAP_ + t * B_: CAP_ + (t + 1) * B_])
        assert np.array_equal(tick.labels, t_ref.labels)
        assert np.array_equal(tick.stable_ids, t_ref.stable_ids)
        assert np.array_equal(np.asarray(r._rho), np.asarray(ref._rho))
        assert np.array_equal(np.asarray(r.result.delta),
                              np.asarray(ref.result.delta))
        assert np.array_equal(np.asarray(r.result.parent),
                              np.asarray(ref.result.parent))

    @pytest.mark.slow
    def test_sharded_checkpoint_restores_onto_one_device(self, tmp_path):
        """A 4-device sharded stream's checkpoint restores onto a single
        device and the next tick is bit-identical — the restore-across-
        device-count contract riding the sharded-parity guarantee."""
        import json
        import subprocess
        import sys

        ckpt = str(tmp_path / "sharded.npz")
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([sys.executable, "-c", _SHARDED_CKPT_SCRIPT,
                               ckpt], env=env, capture_output=True,
                              text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT")][0]
        out = json.loads(line[len("RESULT"):])

        pts = _data(ticks=3)
        r = StreamDPC.restore(ckpt)             # mesh=None: one device
        tick = r.ingest(pts[CAP + 2 * B: CAP + 3 * B])
        assert np.array_equal(tick.labels, np.array(out["labels"]))
        assert np.array_equal(tick.stable_ids, np.array(out["stable"]))
        assert np.array_equal(np.asarray(r._rho),
                              np.array(out["rho"], np.float32))
        assert np.array_equal(np.asarray(r.result.delta),
                              np.array(out["delta"], np.float32))
