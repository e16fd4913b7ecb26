"""Traced targets: the canonical computations the analyzer checks.

Two consumers share these traces:

* **plan time** — ``repro.engine.planner.plan`` calls :func:`analyze_plan`
  on every fresh plan (memoized per spec): the plan's two driver-facing
  primitives are traced on a small canonical input and every jaxpr rule
  runs over them, so an ExecSpec that would dispatch into a flagged
  kernel path fails at ``plan()`` — before any data is touched.
* **the CLI sweep** (``python -m repro.analysis``) — every valid ExecSpec
  combo x every subsystem entry point: the batch primitives (as at plan
  time), the distributed phase shard_maps exactly as ``distributed_dpc``
  would assemble them for that plan (halo / dense / stencil dispatch
  mirrored, including the block-sparse shard-layout guard), the sharded
  stream repair, and the DPC-KV per-head compression.

Every target is a *trace* (``jax.make_jaxpr``) — nothing executes, so the
pallas targets work on hosts with no TPU and the distributed targets only
need ``--xla_force_host_platform_device_count`` (the CLI sets it).

Targets that a plan cannot express return alongside a skip *reason*
(e.g. DPC-KV rejects host-worklist layouts at construction) rather than a
finding: an impossible combination is the validation table working, not a
defect — R5 checks that table separately.
"""
from __future__ import annotations

import numpy as np
from typing import Any

from .rules import Finding, analyze_jaxpr

# Canonical trace input: small (tracing cost rides every plan() miss),
# 2-D (the paper's regime), sized to cover multiple jnp row blocks and a
# non-trivial block-sparse grid.  Values are a fixed low-discrepancy-ish
# lattice + deterministic jitter — no RNG, identical across processes.
N_POINTS = 96
DIM = 2
D_CUT = 0.35


def canonical_points() -> np.ndarray:
    i = np.arange(N_POINTS, dtype=np.float32)
    pts = np.stack([(i * 0.6180339887) % 1.0, (i * 0.7548776662) % 1.0], 1)
    return np.ascontiguousarray(pts[:, :DIM], dtype=np.float32)


def _trace_failure(target: str, exc: Exception) -> Finding:
    return Finding(rule="trace", severity="warn", target=target,
                   message=f"could not trace: {type(exc).__name__}: {exc}",
                   where="<trace>")


# --------------------------------------------------------- batch (plan time)
def plan_targets(pl: Any) -> list:
    """``(name, thunk)`` pairs tracing the plan's driver-facing primitives.

    ``fused_traceable`` backends trace ``plan.rho_delta`` / ``plan.denser_nn``
    directly.  The pallas backends' fused path is host-orchestrated (the
    unresolved-tail fallback), so their targets are the traced *segments*
    the host stitches: the fused tile sweep + the f32 direct-diff resolve
    epilogue (the R3 subject), and the masked-NN kernel — with the
    backend's worklists when the plan is block-sparse (built here, outside
    the trace, on the device as the backend builds them).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.dpc_types import density_jitter

    x = jnp.asarray(canonical_points())
    jitter = density_jitter(N_POINTS)
    rk = jnp.arange(N_POINTS, dtype=jnp.float32)   # all-distinct NN keys
    be = pl.backend
    targets: list[tuple[str, Any]] = []

    if be.fused_traceable:
        targets.append((
            "rho_delta",
            lambda: jax.make_jaxpr(
                lambda a, b: pl.rho_delta(a, b, D_CUT))(x, x)))
        targets.append((
            "denser_nn",
            lambda: jax.make_jaxpr(
                lambda a, ak, b, bk: pl.denser_nn(a, ak, b, bk))(
                    x, rk, x, rk)))
        return targets

    from repro.kernels import blocksparse, ops
    from repro.kernels.backend import _fused_resolve

    interpret = bool(getattr(be, "interpret", False))
    bn = pl.block or ops.DENSITY_BLOCK_N
    nn_bn = min(pl.block or 128, 1024)
    wl = nn_wl = None
    if pl.sparse:
        wl = blocksparse.fused_worklist(
            x, x, D_CUT, block_n=bn, block_m=ops.DENSITY_BLOCK_M,
            k=ops.FUSED_TOPK)
        nn_wl = blocksparse.ring_worklist(x, x, block_n=nn_bn,
                                          block_m=blocksparse.BS_BLOCK_M)

    def fused(a: Any, b: Any, jit_: Any) -> Any:
        cnt, topv, topi = ops.fused_sweep(
            a, b, D_CUT, precision=pl.precision, block_n=bn,
            interpret=interpret, worklist=wl)
        rho_key = cnt + jit_
        return _fused_resolve(a, b, rho_key, rho_key, topv, topi)

    def masked_nn(a: Any, ak: Any, b: Any, bk: Any) -> Any:
        return ops.dependent_masked(a, ak, b, bk, block_n=nn_bn,
                                    interpret=interpret, worklist=nn_wl)

    targets.append(("fused_sweep+resolve",
                    lambda: jax.make_jaxpr(fused)(x, x, jitter)))
    targets.append(("dependent_masked",
                    lambda: jax.make_jaxpr(masked_nn)(x, rk, x, rk)))
    return targets


def analyze_plan(pl: Any) -> list:
    """Run every jaxpr rule over the plan's canonical traces, then every
    plan rule over the plan itself.  Tracing is side-effect-neutral: an
    armed chaos fault neither fires in here nor has its hit budget spent
    by probe traffic (``faultinject.suspended``)."""
    from repro.resilience import faultinject

    from .rules import plan_rules

    label = f"plan[{pl.backend_name}:{pl.layout}:{pl.precision}]"
    findings: list[Finding] = []
    with faultinject.suspended():
        for name, thunk in plan_targets(pl):
            target = f"{label}:{name}"
            try:
                closed = thunk()
            except Exception as exc:      # noqa: BLE001 — report, don't die
                findings.append(_trace_failure(target, exc))
                continue
            findings.extend(analyze_jaxpr(target, closed))
        for rule in plan_rules():
            try:
                findings.extend(rule.check_plan(pl))
            except Exception as exc:      # noqa: BLE001 — report, don't die
                findings.append(_trace_failure(f"{label}:{rule.name}", exc))
    return findings


# ------------------------------------------------------- sweep-only targets
def distributed_targets(pl: Any) -> tuple[list, list]:
    """The distributed phase shard_maps this plan dispatches, traced on a
    flat mesh over every visible device.  Returns (targets, skip_reasons).

    Mirrors ``distributed_dpc``'s halo / dense / stencil branch selection —
    including the block-sparse shard-layout guard, so these traces show the
    phases that would actually run (and stay clean exactly when the guard
    lets a layout through).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    S = len(devs)
    if S < 2:
        return [], ["distributed: single-device runtime — shard phases "
                    "degenerate (the CLI sweep forces a 4-device host "
                    "platform; in-process callers see plan-time checks only)"]

    from repro.distributed import dpc as ddpc

    be = pl.backend
    axis = pl.data_axis
    mesh = Mesh(np.array(devs), (axis,))
    block = pl.block if pl.block is not None else 256
    rows = 8
    m = S * rows
    span_w = 4
    pts = jnp.zeros((m, DIM), jnp.float32)
    rk = jnp.zeros((m,), jnp.float32)
    starts = jnp.zeros((m, span_w), jnp.int32)
    ends = jnp.zeros((m, span_w), jnp.int32)
    lo_arr = jnp.zeros((S, 1), jnp.int64)

    shard_layout = ddpc.shard_blocksparse_layout(pl, mesh)
    dense = be.mxu_dense or shard_layout == "block-sparse"
    targets: list[tuple[str, Any]] = []

    def add(name: str, fn: Any, in_specs: Any, out_specs: Any,
            args: Any, check_vma: bool = True) -> None:
        sm = shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=check_vma)
        targets.append((name, lambda sm=sm, args=args:
                        jax.make_jaxpr(sm)(*args)))

    # halo strategy: reachable for every backend via strategy="halo"
    rho_halo = ddpc._make_rho_halo(axis, D_CUT, block, span_w, S,
                                   2 * rows, 1, 1, be)
    add("halo:rho", rho_halo, (P(axis),) * 5, P(axis),
        (pts, starts, ends, pts, lo_arr), check_vma=not be.mxu_dense)
    delta_halo = ddpc._make_delta_halo(axis, D_CUT, block, span_w, S,
                                       2 * rows, 1, 1, be)
    add("halo:delta", delta_halo, (P(axis),) * 7,
        (P(axis), P(axis), P(axis)),
        (pts, rk, starts, ends, pts, rk, lo_arr),
        check_vma=not be.mxu_dense)

    # gather strategy: dense engine tiles or the grid stencil, per dispatch
    if dense:
        rho_fn = ddpc._make_rho_dense(axis, D_CUT, block, be,
                                      layout=shard_layout)
        add("dense:rho", rho_fn, (P(axis), P(axis)), P(axis),
            (pts, pts), check_vma=False)
        delta_fn = ddpc._make_delta_dense(axis, block, be,
                                          layout=shard_layout)
        add("dense:delta", delta_fn, (P(axis),) * 4,
            (P(axis), P(axis), P(axis)), (pts, rk, pts, rk),
            check_vma=False)
    else:
        rho_fn = ddpc._make_rho(axis, D_CUT, block, span_w)
        add("stencil:rho", rho_fn, (P(axis),) * 4, P(axis),
            (pts, starts, ends, pts))
        delta_fn = ddpc._make_delta(axis, D_CUT, block, span_w)
        add("stencil:delta", delta_fn, (P(axis),) * 6,
            (P(axis), P(axis), P(axis)), (pts, rk, starts, ends, pts, rk))
        fb_fn = ddpc._make_fallback(axis, max(block, 1024), be,
                                    layout=shard_layout)
        add("stencil:fallback", fb_fn, (P(axis),) * 4, (P(axis), P(axis)),
            (pts, rk, pts, rk), check_vma=not be.mxu_dense)
    return targets, []


def stream_targets(pl: Any) -> tuple[list, list]:
    """Every sharded stage of the stream repair tail, traced over every
    visible device: rho repair, dirty-maxima NN re-query (at the plan's
    probe-resolved layout), label propagation and the center-continuity
    distances."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 2:
        return [], ["stream: single-device runtime — sharded repair "
                    "degenerates (the CLI sweep forces 4 devices)"]
    from repro.distributed.dpc import shard_blocksparse_layout
    from repro.stream.incremental import make_sharded_repair
    from repro.stream.sharded import make_sharded_center_dists, \
        make_sharded_labels, make_sharded_nn_update

    axis = pl.data_axis
    mesh = Mesh(np.array(devs), (axis,))
    S = len(devs)
    repair = make_sharded_repair(mesh, axis, pl.backend, D_CUT)
    m = S * 8
    window = jnp.zeros((m, DIM), jnp.float32)
    rho = jnp.zeros((m,), jnp.float32)
    batch = jnp.zeros((4, DIM), jnp.float32)
    signs = jnp.zeros((4,), jnp.float32)
    ins = jnp.zeros((4, DIM), jnp.float32)
    slots = jnp.zeros((4,), jnp.int32)
    targets = [("stream:sharded_repair",
                lambda: jax.make_jaxpr(repair)(window, rho, batch, signs,
                                               ins, slots))]

    # the post-repair tail: each factory exposes its shard_map body on
    # ``.inner`` (the host wrappers around them do numpy/obs work and are
    # not traceable); the NN stage traces at the plan's probe-resolved
    # layout, so a future R1 regression in the one-hot ring walk surfaces
    # here as well as in the probe
    lay = shard_blocksparse_layout(pl, mesh)
    nn = make_sharded_nn_update(mesh, axis, pl.backend, layout=lay)
    q = jnp.zeros((4, DIM), jnp.float32)
    qk = jnp.zeros((4,), jnp.float32)
    targets.append((f"stream:sharded_nn[{lay or 'dense'}]",
                    lambda: jax.make_jaxpr(nn.inner)(window, rho, q, qk)))

    labels = make_sharded_labels(mesh, axis, m)
    parent = jnp.zeros((m,), jnp.int32)
    targets.append(("stream:sharded_labels",
                    lambda: jax.make_jaxpr(labels.inner)(parent)))

    cdist = make_sharded_center_dists(mesh, axis)
    new_pos = jnp.zeros((S * 2, DIM), jnp.float32)
    prev = jnp.zeros((3, DIM), jnp.float32)
    targets.append(("stream:sharded_center_dists",
                    lambda: jax.make_jaxpr(cdist.inner)(new_pos, prev)))
    return targets, []


def serve_targets(spec: Any) -> tuple[list, list]:
    """DPC-KV per-head compression (fully traced serve path) for a spec."""
    import jax
    import jax.numpy as jnp

    from repro.serve.dpc_kv import DPCKVConfig, _compress_head

    try:
        cfg = DPCKVConfig(budget=8, exec_spec=spec)
    except ValueError as exc:
        return [], [f"serve: spec {spec.describe()} rejected at config "
                    f"time ({exc})"]
    k = jnp.zeros((32, 8), jnp.float32)
    v = jnp.zeros((32, 8), jnp.float32)
    valid = jnp.ones((32,), bool)
    return [("serve:compress_head",
             lambda: jax.make_jaxpr(
                 lambda kk, vv, va: _compress_head(kk, vv, va, cfg))(
                     k, v, valid))], []


# -------------------------------------------------------------- sweep specs
def sweep_specs() -> list:
    """Every ExecSpec combo the sweep analyzes: the default spec plus the
    explicit backend x layout x precision product, minus combos the spec /
    plan validation rejects (R5 checks that rejection table separately)."""
    from repro.engine.spec import ExecSpec, LAYOUTS, PRECISIONS
    from repro.kernels.backend import available_backends, get_backend

    specs = [ExecSpec()]
    for backend in available_backends():
        for layout in (None, *LAYOUTS):
            for precision in (None, *PRECISIONS):
                try:
                    spec = ExecSpec(backend=backend, layout=layout,
                                    precision=precision)
                except ValueError:
                    continue
                if spec.resolved_precision == "bf16" \
                        and not get_backend(backend).mxu_dense:
                    continue
                specs.append(spec)
    return specs
