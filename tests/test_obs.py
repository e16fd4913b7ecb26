"""repro.obs acceptance: tracing, metrics and kernel telemetry.

The ISSUE 7 contract:

* **Off is free** — at the default level, ``obs.span()`` returns one shared
  null singleton (no allocation, no recording) and ``sync`` is the
  identity.
* **Spans nest** — paths/parents/depths follow the runtime call tree; a
  traced ``DPCEngine.fit`` emits the engine/driver/labeling phase tree
  with fenced device times; traces round-trip through the JSONL file and
  the ``python -m repro.obs report`` CLI.
* **Metrics migrate** — the planner plan-cache, blocksparse worklist,
  stream tick and serve query-status counters live on the registry while
  the legacy read surfaces (``plan_cache_info``, ``worklist_build_count``,
  ``StreamDPC.stats``) keep their exact semantics.
* **Plan telemetry** — ``DPCPlan.telemetry()`` reports the resolved axes,
  pad waste and worklist cache; ``include_cost=True`` adds the hlo_cost
  flop/byte estimate.
"""
import json

import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.engine import DPCEngine, ExecSpec, as_plan
from repro.kernels import blocksparse
from repro.obs import report as obs_report
from repro.obs.__main__ import main as obs_main
from repro.stream import QueryStatus


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.configure(level="off", trace_path=None)
    obs.reset_spans()
    yield
    obs.configure(level="off", trace_path=None)
    obs.reset_spans()


def _blobs(n=256, d=2, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, 6000.0, (4, d))
    pts = (centers[rng.integers(0, 4, n)]
           + rng.normal(0, 150.0, (n, d))).astype(np.float32)
    return pts


# --------------------------------------------------------------- tracer
class TestTracer:
    def test_off_returns_null_singleton(self):
        s1 = obs.span("a", n=3)
        s2 = obs.span("b")
        assert s1 is obs.NULL_SPAN and s2 is obs.NULL_SPAN
        x = object()
        with s1 as sp:
            assert sp.sync(x) is x
            sp.set(ignored=1)
        assert obs.spans() == []

    def test_metrics_level_host_time_only(self):
        obs.configure(level="metrics")
        with obs.span("phase", n=7):
            pass
        (rec,) = obs.spans()
        assert rec["name"] == "phase" and rec["path"] == "phase"
        assert rec["host_s"] >= 0.0
        assert rec["device_s"] is None
        assert rec["attrs"] == {"n": 7}

    def test_trace_level_fences_device_time(self):
        obs.configure(level="trace")
        with obs.span("compute") as sp:
            out = sp.sync(jnp.arange(1024.0).sum())
        assert float(out) == 1024.0 * 1023.0 / 2.0
        (rec,) = obs.spans()
        assert rec["device_s"] is not None and rec["device_s"] >= 0.0
        assert rec["host_s"] >= rec["device_s"]

    def test_nesting_paths_and_parents(self):
        obs.configure(level="metrics")
        with obs.span("outer"):
            with obs.span("mid"):
                with obs.span("inner"):
                    pass
        recs = {r["name"]: r for r in obs.spans()}
        assert recs["outer"]["path"] == "outer"
        assert recs["mid"]["path"] == "outer/mid"
        assert recs["inner"]["path"] == "outer/mid/inner"
        assert recs["inner"]["depth"] == 2
        assert recs["mid"]["parent"] == recs["outer"]["id"]

    def test_exception_closes_span(self):
        obs.configure(level="metrics")
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("x")
        (rec,) = obs.spans()
        assert rec["error"] == "RuntimeError"
        # the stack unwound: a fresh span is a root again
        with obs.span("after"):
            pass
        assert obs.spans()[-1]["path"] == "after"

    def test_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        obs.configure(level="trace", trace_path=path)
        with obs.span("a", n=1):
            with obs.span("b"):
                pass
        obs.flush()
        obs.configure(trace_path=None)
        recs = obs_report.load_trace(path)
        assert [r["path"] for r in recs] == ["a/b", "a"]
        assert all({"id", "host_s", "t0", "depth"} <= set(r) for r in recs)

    def test_configure_rejects_bad_level(self):
        with pytest.raises(ValueError, match="level"):
            obs.configure(level="verbose")

    def test_span_opens_a_profiler_annotation(self, monkeypatch):
        events = _annotations(monkeypatch)
        obs.configure(level="metrics")
        with obs.span("outer", n=1):
            with obs.span("inner"):
                pass
        assert events == [("enter", "outer"), ("enter", "inner"),
                          ("exit", "inner"), ("exit", "outer")]

    def test_off_opens_no_annotation(self, monkeypatch):
        events = _annotations(monkeypatch)
        with obs.span("a", n=2) as sp:
            assert sp is obs.NULL_SPAN
        assert events == [] and obs.spans() == []


def _annotations(monkeypatch) -> list:
    """Replace the profiler's annotation with one that logs its use."""
    import jax

    events = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return events


# -------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_labels_and_total(self):
        c = obs.counter("t_obs_counter")
        c._reset()
        c.inc()
        c.inc(3, kind="x")
        c.inc(2, kind="x")
        assert c.value() == 1
        assert c.value(kind="x") == 5
        assert c.total() == 6
        assert c.series() == {"": 1, "kind=x": 5}

    def test_gauge_and_histogram(self):
        g = obs.gauge("t_obs_gauge")
        g.set(0.25)
        g.set(0.5)
        assert g.value() == 0.5
        h = obs.histogram("t_obs_hist")
        h._reset()
        for v in (1.0, 3.0, 2.0):
            h.observe(v)
        assert h.stats() == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0}
        assert h.stats(missing="yes") is None

    def test_registry_get_or_register(self):
        a = obs.counter("t_obs_same")
        b = obs.counter("t_obs_same")
        assert a is b
        with pytest.raises(TypeError, match="already registered"):
            obs.gauge("t_obs_same")
        assert obs.get_metric("t_obs_same") is a

    def test_snapshot_and_reset(self):
        c = obs.counter("t_obs_snap")
        c._reset()
        c.inc(4)
        snap = obs.metrics_snapshot()
        assert snap["t_obs_snap"]["kind"] == "counter"
        assert snap["t_obs_snap"]["values"] == {"": 4}
        c._reset()
        assert obs.metrics_snapshot()["t_obs_snap"]["values"] == {}

    def test_suspend_counters_restores_worklist_metrics(self):
        builds = obs.get_metric("worklist_builds")
        before = builds.value()
        with blocksparse.suspend_counters():
            builds.inc(17)
            assert builds.value() == before + 17
        assert builds.value() == before
        assert blocksparse.worklist_build_count() == int(before)


# ------------------------------------------------------ engine tracing
class TestEngineTracing:
    def test_fit_emits_phase_tree_with_device_times(self):
        pts = _blobs(256)
        eng = DPCEngine(d_cut=300.0, algorithm="approxdpc",
                        exec_spec=ExecSpec(backend="jnp",
                                           layout="block-sparse"))
        obs.configure(level="trace")
        eng.fit(pts)
        paths = {r["path"] for r in obs.spans()}
        assert {"engine.fit", "engine.fit/approxdpc.grid",
                "engine.fit/approxdpc.rho_delta",
                "engine.fit/approxdpc.rules",
                "engine.fit/labels.assign"} <= paths
        phases = obs_report.aggregate(obs.spans())
        assert phases["engine.fit/approxdpc.rho_delta"]["device_s"] is not None
        root = phases["engine.fit"]
        # direct children only: spans nest further below them
        child = sum(r["host_s"] for p, r in phases.items()
                    if p.startswith("engine.fit/") and p.count("/") == 1)
        assert child <= root["host_s"] + 1e-6

    def test_spans_of_one_fit_share_its_root(self):
        pts = _blobs(256)
        eng = DPCEngine(d_cut=300.0, algorithm="approxdpc",
                        exec_spec=ExecSpec(backend="jnp",
                                           layout="block-sparse"))
        obs.configure(level="metrics")
        eng.fit(pts)
        first = obs.spans()
        obs.reset_spans()
        eng.fit(pts[::-1].copy())
        second = obs.spans()
        for recs in (first, second):
            (fit,) = [r for r in recs if r["name"] == "engine.fit"]
            assert len(recs) > 4
            assert {r["root"] for r in recs} == {fit["id"]}
        assert first[0]["root"] != second[0]["root"]

    def test_block_sparse_fit_traces_worklist_and_fallback(self):
        pts = _blobs(1024)
        eng = DPCEngine(d_cut=300.0, algorithm="approxdpc",
                        exec_spec=ExecSpec(backend="pallas-interpret",
                                           layout="block-sparse"))
        obs.configure(level="metrics")
        eng.fit(pts)
        recs = obs.spans()
        by = {r["name"]: r for r in recs}
        build = by["worklist.build"]
        children = {r["name"]: r for r in recs
                    if r["parent"] == build["id"]}
        assert set(children) == {"worklist.fetch", "worklist.fingerprint",
                                 "worklist.bounds", "worklist.upload"}
        assert build["path"] == ("engine.fit/approxdpc.rho_delta/"
                                 "worklist.build")
        a = build["attrs"]
        assert a["hit"] is False and a["chunks"] >= 1
        assert 0 < a["kept"] <= a["total"]
        # x and y are one device array: fetched once
        assert children["worklist.fetch"]["attrs"] == {
            "d2h_bytes": pts.nbytes}
        # (chunks, 4, C) int32 pairs and (chunks, C) f32 bounds
        up = children["worklist.upload"]["attrs"]["h2d_bytes"]
        assert up % (20 * a["chunks"]) == 0 and up >= 20 * a["kept"]
        fb = by["rho_delta.fallback"]
        assert fb["path"] == "engine.fit/approxdpc.rho_delta/" \
                             "rho_delta.fallback"
        f = fb["attrs"]
        n = len(pts)
        assert 0 < f["rows"] <= f["padded"] < 2 * f["rows"]
        # resolved and the interest mask (bool), delta and parent, the
        # fallback's answers and its live-pair count (int32); rows (int64)
        # up twice, delta and parent up
        assert f["d2h_bytes"] == 2 * n + 8 * n + 8 * f["padded"] + 4
        assert 0 < f["live"] <= f["pairs"]
        assert f["h2d_bytes"] == 2 * 8 * f["padded"] + 8 * n
        assert by["engine.fit"]["attrs"]["h2d_bytes"] == pts.nbytes
        assert by["approxdpc.rules"]["attrs"]["d2h_bytes"] == n
        assert by["approxdpc.rules"]["attrs"]["h2d_bytes"] > 0

        # the same points again: the worklist cache answers
        obs.reset_spans()
        eng.fit(pts)
        recs = obs.spans()
        (build,) = [r for r in recs if r["name"] == "worklist.build"]
        assert build["attrs"]["hit"] is True
        assert {r["name"] for r in recs if r["parent"] == build["id"]} \
            == {"worklist.fetch", "worklist.fingerprint"}

    def test_mesh_fit_traces_device_worklists_and_fallback(self):
        """A 4-device block-sparse pallas fit (a subprocess: the fake
        devices need XLA_FLAGS before jax starts): the shards' lists under
        ``dist.worklist``, the ring pairs computed within those listed, and
        the host copies of a fit, one int32 per shard and read."""
        import os
        import subprocess
        import sys

        script = (
            "import json, numpy as np, jax\n"
            "from repro import obs\n"
            "from repro.engine import DPCEngine, ExecSpec\n"
            "rng = np.random.default_rng(0)\n"
            "c = rng.uniform(0, 6000.0, (4, 2))\n"
            "pts = (c[rng.integers(0, 4, 1024)]\n"
            "       + rng.normal(0, 150.0, (1024, 2))).astype(np.float32)\n"
            "eng = DPCEngine(d_cut=300.0, algorithm='exdpc',\n"
            "    exec_spec=ExecSpec(backend='pallas-interpret',\n"
            "                       layout='block-sparse'),\n"
            "    mesh=jax.make_mesh((4,), ('data',)))\n"
            "obs.configure(level='metrics')\n"
            "eng.fit(pts)\n"
            "print('RESULT' + json.dumps([(r['name'], r['path'],\n"
            "    r['attrs']) for r in obs.spans()\n"
            "    if r['name'].startswith('dist.')]))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
        p = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=900)
        assert p.returncode == 0, p.stderr[-3000:]
        recs = json.loads([ln for ln in p.stdout.splitlines()
                           if ln.startswith("RESULT")][0][len("RESULT"):])
        by = {}
        for name, path, attrs in recs:
            by.setdefault(name, []).append((path, attrs))
        count, ring = [a for _, a in by["dist.worklist"]]
        assert count["phase"] == "count" and ring["phase"] == "ring"
        assert 0 < count["listed"] and count["chunks"] >= 4
        assert count["d2h_bytes"] == 4 * 2 * 4      # (chunks, pairs) a shard
        [(path, rho)] = by["dist.rho"]
        assert rho["layout"] == "block-sparse"
        assert rho["listed"] == count["listed"] <= rho["padded"]
        [(_, delta)] = by["dist.delta"]
        assert delta["d2h_bytes"] == 4 * 4           # unresolved, a shard
        [(path, fb)] = by["dist.fallback"]
        assert by["dist.worklist"][1][0] == "engine.fit/dist.fallback/" \
                                            "dist.worklist"
        assert 0 < fb["live"] <= fb["listed"] == ring["listed"]
        assert 0 < fb["unresolved"] <= fb["padded"]
        assert fb["d2h_bytes"] == 4 * 4 and fb["h2d_bytes"] == 0

    def test_kept_pairs_equal_the_worklist_len_gauge(self):
        obs.configure(level="metrics")
        pts = _blobs(1024, seed=3)
        DPCEngine(d_cut=300.0, algorithm="approxdpc",
                  exec_spec=ExecSpec(backend="pallas-interpret",
                                     layout="block-sparse")).fit(pts)
        (build,) = [r for r in obs.spans() if r["name"] == "worklist.build"]
        assert build["attrs"]["kept"] == \
            obs.get_metric("worklist_len").value()

    def test_fit_off_emits_nothing(self):
        pts = _blobs(128)
        DPCEngine(d_cut=300.0).fit(pts)
        assert obs.spans() == []

    def test_predict_spans_and_serve_status_counters(self):
        pts = _blobs(256)
        eng = DPCEngine(d_cut=300.0).fit(pts)
        calls = obs.get_metric("serve_query_calls")
        points = obs.get_metric("serve_query_points")
        c0, p0 = calls.value(), points.total()
        obs.configure(level="metrics")
        out = eng.predict(pts[:17])
        assert {"engine.predict", "engine.predict/serve.query"} <= {
            r["path"] for r in obs.spans()}
        assert calls.value() == c0 + 1
        assert points.total() == p0 + 17
        # fitted points queried back are coverage hits
        assert points.value(status=QueryStatus.HIT.name) > 0
        assert (out.status == int(QueryStatus.HIT)).all()

    def test_stream_metrics_dual_write(self):
        from repro.stream import StreamDPC, StreamDPCConfig

        ticks = obs.get_metric("stream_ticks")
        t0 = ticks.value()
        s = StreamDPC(StreamDPCConfig(d_cut=300.0, capacity=64,
                                      batch_cap=32))
        s.ingest(_blobs(64, seed=1))     # fills the window
        s.ingest(_blobs(32, seed=2))     # steady-state tick
        assert ticks.value() >= t0 + 3
        st = s.stats()
        assert st["ticks"] == 3          # legacy dict unchanged
        assert st["nn_queries"] <= st["nn_maxima_total"]


# -------------------------------------------------------- plan telemetry
class TestPlanTelemetry:
    def test_static_axes_and_pad(self):
        pts = _blobs(200)
        pl = as_plan(ExecSpec(backend="jnp", layout="block-sparse"),
                     jnp.asarray(pts))
        t = pl.telemetry()
        assert t["backend"] == "jnp"
        assert t["layout"] == "block-sparse"
        assert t["worklist_strategy"] == "traced"
        assert t["shape"] == {"n": 200, "d": 2}
        pad = t["pad"]
        assert pad["row_block"] == blocksparse.BS_BLOCK_N
        assert pad["padded_n"] % pad["row_block"] == 0
        assert 0.0 <= pad["pad_waste_frac"] < 1.0
        assert t["worklists"]["strategy"] == "traced"
        assert "hlo_cost" not in t

    def test_cost_estimate_cached(self):
        pts = _blobs(128)
        pl = as_plan(ExecSpec(backend="jnp"), jnp.asarray(pts))
        builds0 = blocksparse.worklist_build_count()
        cost = pl.telemetry(include_cost=True)["hlo_cost"]
        assert cost["formulation"] == "dense"
        assert cost.get("flops", 0) > 0
        # compiled once, cached after
        assert pl.telemetry(include_cost=True)["hlo_cost"] is cost or \
            pl.telemetry(include_cost=True)["hlo_cost"] == cost
        # probe compilation left the worklist counters untouched
        assert blocksparse.worklist_build_count() == builds0


# --------------------------------------------------------------- report
class TestReport:
    def _recs(self):
        return [
            {"name": "fit", "path": "fit", "id": 1, "parent": None,
             "depth": 0, "t0": 0.0, "host_s": 1.0, "device_s": 0.6},
            {"name": "rho", "path": "fit/rho", "id": 2, "parent": 1,
             "depth": 1, "t0": 0.1, "host_s": 0.7, "device_s": 0.5},
        ]

    def test_aggregate_self_time(self):
        phases = obs_report.aggregate(self._recs())
        assert phases["fit"]["self_s"] == pytest.approx(0.3)
        assert phases["fit/rho"]["host_s"] == pytest.approx(0.7)
        assert phases["fit/rho"]["device_s"] == pytest.approx(0.5)

    def test_render_table_and_metrics(self):
        table = obs_report.render_table(obs_report.aggregate(self._recs()))
        assert "fit" in table and "rho" in table and "%run" in table
        assert obs_report.render_table({}) == "(no spans recorded)"
        text = obs_report.render_metrics(
            {"c": {"kind": "counter", "help": "", "values": {"": 3}}})
        assert "c = 3" in text

    def test_snapshot_schema(self):
        snap = obs_report.build_snapshot(self._recs(), {})
        assert snap["schema"] == "repro.obs/1"
        assert "fit/rho" in snap["phases"]

    def test_cli_report(self, tmp_path, capsys):
        trace = tmp_path / "t.jsonl"
        trace.write_text("".join(json.dumps(r) + "\n" for r in self._recs()))
        mpath = tmp_path / "m.json"
        mpath.write_text(json.dumps(
            {"plan_cache_hits": {"kind": "counter", "help": "",
                                 "values": {"": 2}}}))
        out = tmp_path / "snap.json"
        rc = obs_main(["report", "--trace", str(trace), "--metrics",
                       str(mpath), "--json", str(out)])
        assert rc == 0
        printed = capsys.readouterr().out
        assert "rho" in printed and "plan_cache_hits = 2" in printed
        snap = json.loads(out.read_text())
        assert snap["schema"] == "repro.obs/1"
        assert snap["metrics"]["plan_cache_hits"]["values"][""] == 2
