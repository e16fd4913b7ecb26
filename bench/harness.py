"""The benchmark's one general harness: cell -> set-up -> window -> check.

Everything particular to a cell is data, found by name:

* ``workloads/<cell>.json``  — its configuration, traffic mix and limits;
* ``configs/<config>.json``  — the deployment (sizes, precision, source);
* ``traffic/<traffic>.json`` — the mix, read by the driver it names;
* ``metrics/<metric>.py``    — one per-layer metric, ``read(run)``;
* ``peaks.json``             — device peaks keyed by ``device_kind``;
* ``kernels.json``           — the trace names of the program's kernels.

``BENCHMARK.json`` at the checkout's root says which per-layer metrics a
cell reports.  A driver (``drivers/<name>.py``) runs one entry of the
engine: ``setup(ctx)``, ``window(ctx, state, seconds)``, ``check(ctx,
state)``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")
CACHE = os.path.join(ROOT, ".jax_cache")
NOT_A_TPU = 2          # exit code: no accelerator, or too few chips


class NoChip(RuntimeError):
    pass


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool = False) -> dict:
    """The cell's files, with the rehearsal sizes laid over them if asked."""
    cell = load_json("workloads", f"{name}.json")
    cfg = load_json("configs", f"{cell['config']}.json")
    tr = load_json("traffic", f"{cell['traffic']}.json")
    if rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
        tr = {**tr, **tr.get("rehearsal", {})}
    return {"name": name, "config": cfg, "traffic": tr,
            "limits": cell["limits"]}


def per_layer_metrics(cell: str, e2e: list[str]) -> list[str]:
    """Per-layer metrics that ``BENCHMARK.json`` gives this cell: those that
    list it, and those with no list that move one of its metrics."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        idx = json.load(f)
    return [m["name"] for m in idx.get("per_layer", [])
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


class CompileClock:
    """XLA backend compiles (count and seconds, persistent-cache reads
    included) and persistent-cache hits, from jax's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GaugeTap:
    """Records every value set on the program's named obs gauges.  A gauge
    keeps only its last value; a call may set it several times (one
    worklist build per sweep), and a per-layer metric needs each."""

    def __init__(self, names: list[str]):
        from repro import obs

        self.values: dict[str, list[float]] = {n: [] for n in names}
        for n in names:
            g = obs.get_metric(n)
            if g is None:
                continue
            # one wrapper per gauge and process, feeding the newest tap
            if not hasattr(g, "_bench_tap"):
                orig = g.set

                def tapped(v, _g=g, _orig=orig, _n=n, **labels):
                    _g._bench_tap.values[_n].append(float(v))
                    return _orig(v, **labels)
                g.set = tapped
            g._bench_tap = self

    def take(self) -> dict[str, list[float]]:
        out = {n: list(v) for n, v in self.values.items()}
        for v in self.values.values():
            v.clear()
        return out


@dataclass
class Ctx:
    """What a driver sees: the cell's data, the seed and the hooks."""

    cell: dict
    seed: int
    rehearse: bool
    tap: GaugeTap
    calls: list = field(default_factory=list)

    @property
    def config(self) -> dict:
        return self.cell["config"]

    @property
    def traffic(self) -> dict:
        return self.cell["traffic"]

    def exec_spec(self):
        from repro.engine import ExecSpec

        c = self.config
        backend = "pallas-interpret" if self.rehearse else c["backend"]
        return ExecSpec(backend=backend, layout=c["layout"],
                        precision=c["precision"])

    @staticmethod
    def annotate(name: str):
        """Host annotation on the profiler's clock (free when no trace)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def call(self, kind: str, **attrs):
        """One timed call into the engine: annotated, recorded with its
        host start and end and the gauge values it set."""
        rec = {"kind": kind, **attrs}
        with self.annotate(f"bench.{kind}"):
            rec["t0"] = time.perf_counter()
            try:
                yield rec
            finally:
                rec["t1"] = time.perf_counter()
                rec.update(self.tap.take())
                self.calls.append(rec)


@dataclass
class Run:
    """What a per-layer metric's ``read(run)`` sees."""

    cell: dict
    calls: list
    spans: list
    trace: dict | None
    window: tuple[int, int] | None
    peaks: dict | None


def check_device(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one.  Every program is cached, so
    the second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, t_start: float | None = None,
             cell: dict | None = None) -> dict:
    """One run of a cell; returns the result object (see ``run.py``).
    ``cell`` replaces the cell's files (the control's variants)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell or load_cell(workload, rehearse)
    devs = check_device(cell["config"].get("chips", 1), rehearse)
    kind = devs[0].device_kind
    pk = None if rehearse else peaks(kind)
    if not rehearse:
        log(f"compile cache: {use_compile_cache()}")

    import jax
    import repro.engine  # noqa: F401  (registers the program's obs metrics)
    from repro import obs

    clock = CompileClock()
    driver = importlib.import_module(
        f"bench.drivers.{cell['traffic']['driver']}")
    # numpy seeds its generators from non-negative integers only
    ctx = Ctx(cell=cell, seed=seed % (1 << 64), rehearse=rehearse,
              tap=GaugeTap(driver.GAUGES))
    state = driver.setup(ctx)
    ctx.tap.take()
    ctx.calls.clear()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: {clock.count} compiles "
        f"({clock.seconds:.3f}s), {clock.cache_hits} persistent-cache hits")

    names = per_layer_metrics(workload, driver.METRICS)
    prof = os.path.join(OUT, f"profile-{workload}")
    if trace:
        shutil.rmtree(prof, ignore_errors=True)
        obs.reset_spans()
        obs.configure(level="metrics")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host TraceMe events only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(prof, profiler_options=opts)
    c0 = clock.count
    try:
        with ctx.annotate("bench.window"):
            win = driver.window(ctx, state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
            obs.configure(level="off")
    in_window = clock.count - c0
    log(f"window: {win['attempted']} calls, {win['failed']} failed, "
        f"{in_window} compiles inside the window; {win.get('note', '')}")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[: cell["config"].get("chips", 1)])

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result = {"correct": None, "attempted": win["attempted"],
              "failed": win["failed"]}
    if trace:
        from bench import tracing

        spans = obs.spans()
        obs.reset_spans()
        tr = tracing.compact(prof)
        os.makedirs(OUT, exist_ok=True)
        tracing.save(tr, os.path.join(OUT, f"{workload}.trace.json.gz"))
        shutil.rmtree(prof, ignore_errors=True)
        w0, w1 = tracing.window(tr)
        run = Run(cell=cell, calls=ctx.calls, spans=spans, trace=tr,
                  window=(w0, w1), peaks=pk)
        metrics = {}
        for name in names:
            mod = load_module("metrics", name)
            v = mod.read(run)
            if v is not None:
                metrics[name] = {"value": v, "unit": mod.UNIT}
        device["busy_s"] = tracing.busy_s(tr, w0, w1)
        device["window_s"] = (w1 - w0) / 1e9
        result["breakdown"] = {"device_ops": tracing.top_ops(tr, w0, w1),
                               "idle_gaps": tracing.idle_gaps(tr, w0, w1)}
    else:
        metrics = {k: {"value": v, "unit": u}
                   for k, (v, u) in win["metrics"].items()}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result["metrics"] = metrics
    result["device"] = device

    numbers = driver.check(ctx, state)
    del state
    # the cell's file names the numbers compared; any other is a reading
    for k, v in numbers.items():
        if k not in cell["limits"]:
            log(f"reading {k}: {v} (not compared: no control moves it)")
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in cell["limits"].items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    result["correct"] = bool(ok and win["failed"] == 0)
    result["window_compiles"] = in_window
    result["checks"] = checks
    return result
