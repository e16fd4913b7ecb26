"""DPC-KV: density-peaks compression of attention KV caches.

The paper's clustering is the serving-layer feature here: cached keys of each
(sequence, kv-head) are clustered with DPC and the cache is replaced by one
(k, v) pair per cluster — cluster centers are *density peaks* of the key
distribution, so the kept keys are exactly the attention modes; members are
merged into their center (softmax of attention is locally flat around a
dense mode, so merging members of one peak perturbs outputs least).

Head_dim (64-256) is far above DPC's low-dim regime, so keys are first
projected with a fixed random orthonormal matrix to proj_dim dims — the
dimensionality-reduction recipe the paper itself points to (§2.1).  rho and
the dependent structure are computed in the projected space with the exact
O(n^2/blocked) scan (cache slices are <= a few k tokens per head, where the
quadratic scan is faster than grid construction); centers are the top-M
gamma = rho * delta peaks (the decision-graph rule, Def. 5, with the
threshold replaced by a budget — serving wants a fixed compressed size).

Returns fixed-shape compressed caches: (B, M, n_kv, head_dim) + counts, so
the decode step keeps a static schedule (straggler discipline).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.audit import audit_determinism
from repro.core.dpc_types import density_jitter, with_jitter
from repro.engine.planner import as_plan
from repro.engine.spec import ExecSpec, merge_legacy
from repro.kernels.backend import get_backend
from repro.resilience.sanitize import finite_or
from repro.kernels.ref import sq_sum


@dataclass(frozen=True)
class DPCKVConfig:
    """DPC-KV compression parameters.

    Execution is one :class:`repro.engine.ExecSpec` on ``exec_spec`` —
    the kernel backend for the rho / denser-NN primitives (None = platform
    default; the per-head d_cut is a traced scalar, which the kernels
    accept as an SMEM threshold), the sweep block, and the layout
    (``"block-sparse"`` is legal on ``worklist_traceable`` backends whose
    jit-built worklists survive the jit+vmap this module runs under; the
    pallas count lists, sized on the host, are rejected *here*, at
    construction).
    The ``backend`` / ``block`` fields are the legacy spellings and fold
    into the spec with a ``DeprecationWarning`` (see ``repro.engine``).
    """

    budget: int = 256          # M: kept (k, v) pairs per head
    d_cut_quantile: float = 0.05   # d_cut = this quantile of pair distances
    proj_dim: int = 4
    exec_spec: ExecSpec | None = None
    block: int | None = None       # deprecated -> ExecSpec.block
    backend: str | None = None     # deprecated -> ExecSpec.backend

    def __post_init__(self):
        if self.budget < 1:
            raise ValueError(f"budget must be >= 1, got {self.budget!r}")
        ex = merge_legacy(self.exec_spec, owner="DPCKVConfig",
                          backend=self.backend, block=self.block)
        object.__setattr__(self, "exec_spec", ex)
        # THE plan-resolved sweep block (not a field: derived, so equal
        # configs still hash/compare equal as jit static args).  The
        # compression itself is traced code, but its block default is the
        # planner's.
        pl = as_plan(ex)
        object.__setattr__(self, "resolved_block", pl.resolved_block)
        # fail fast on combos that cannot run under this module's jit+vmap:
        # the whole compression is one traced function per head.
        be = pl.backend
        if ex.sparse and not be.worklist_traceable:
            raise ValueError(
                f"DPC-KV runs under jit; layout='block-sparse' on the "
                f"{be.name!r} backend sizes its count lists on the host, "
                f"which traced code cannot do — use the 'jnp' backend "
                f"(jit-built worklists) or the dense layout")
        if ex.resolved_precision == "bf16" and not (be.fused_traceable
                                                    and be.mxu_dense):
            raise ValueError(
                f"DPC-KV precision='bf16' needs a backend whose fused "
                f"rho_delta is both jit-safe and MXU-dense; {be.name!r} "
                f"is not (jnp is the f32 reference, the pallas fused "
                f"epilogue is host-orchestrated)")

    def resolved_exec(self) -> ExecSpec:
        return self.exec_spec


def _project(keys, proj_dim: int, seed: int = 0):
    """Fixed random orthonormal projection (S, hd) -> (S, proj_dim)."""
    hd = keys.shape[-1]
    q, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(seed),
                                           (hd, hd), jnp.float32))
    return keys.astype(jnp.float32) @ q[:, :proj_dim]


def _dcut_estimate(pts, quantile: float):
    """d_cut from a sampled pairwise-distance quantile (paper's 1-2% rule)."""
    S = pts.shape[0]
    m = min(S, 256)
    sub = pts[:: max(S // m, 1)][:m]
    d2 = sq_sum(sub[:, None, :] - sub[None, :, :])
    d = jnp.sqrt(jnp.maximum(d2, 0.0)).reshape(-1)
    return jnp.quantile(d, quantile) + 1e-6


@partial(jax.jit, static_argnames=("cfg",))
@audit_determinism(
    "the member-slot scatter-adds collide by design (every member of a "
    "cluster lands on its center's slot); on the single-device serving "
    "path XLA lowers them to one fixed in-order loop, and the centroids "
    "they produce are approximate summaries by construction — last-bit "
    "accumulation wobble is within the compressor's accepted error",
    ops=("scatter-add",))
def _compress_head(k_head, v_head, valid, cfg: DPCKVConfig):
    """One (S, hd) head -> (M, hd) k/v + member counts.

    valid: (S,) bool — positions actually written.  Padded/invalid rows get
    rho = -inf so they are never centers and never merged.
    """
    S, hd = k_head.shape
    M = cfg.budget
    pts = _project(k_head, cfg.proj_dim)
    # push invalid rows far away so they do not contribute to any density
    pts = jnp.where(valid[:, None], pts, 1e9 + jnp.arange(S)[:, None] * 1e3)
    d_cut = _dcut_estimate(jnp.where(valid[:, None], pts, 0.0),
                           cfg.d_cut_quantile)
    ex = cfg.resolved_exec()
    be = get_backend(ex.backend)
    block = min(cfg.resolved_block, S)
    layout = "block-sparse" if ex.sparse else None
    if be.fused_traceable:
        # fused rho+delta in one backend call (this whole function runs
        # under jit+vmap, so only jit-safe fused paths qualify; the
        # construction-time validation guarantees the layout/precision
        # axes are jit-legal here).  A -inf jitter on invalid rows makes
        # their keys -inf exactly as the two-pass formulation's masking
        # does.
        jit_mask = jnp.where(valid, density_jitter(S), -jnp.inf)
        rho, rho_key, delta, parent = be.rho_delta(
            pts, pts, d_cut, jitter=jit_mask, block=block,
            precision=ex.precision, layout=layout)
        rho = jnp.where(valid, rho, 0.0)
    else:
        rho = be.range_count(pts, pts, d_cut, block=block, layout=layout)
        rho = jnp.where(valid, rho, 0.0)
        rho_key = with_jitter(rho)
        rho_key = jnp.where(valid, rho_key, -jnp.inf)
        delta, parent = be.denser_nn(pts, rho_key, pts, rho_key,
                                     block=block, layout=layout)
    # global peak: delta = inf -> cap at the domain diameter for gamma
    delta = finite_or(delta, 2.0 * d_cut * 10.0)
    gamma = jnp.where(valid, rho * delta, -jnp.inf)

    # top-M gamma peaks are the kept centers
    _, centers = jax.lax.top_k(gamma, M)                     # (M,) indices
    is_center = jnp.zeros((S,), bool).at[centers].set(True) & valid

    # members follow dependent chains to the nearest center (pointer jump)
    import math
    p = jnp.where(is_center | (parent < 0), jnp.arange(S), parent)
    for _ in range(max(int(math.ceil(math.log2(max(S, 2)))), 1)):
        p = jnp.where(is_center[p], p, p[p])
    root = p                                                  # (S,)
    # map each root to its slot in the centers list (or drop)
    slot_of = jnp.full((S,), M, jnp.int32).at[centers].set(
        jnp.arange(M, dtype=jnp.int32))
    member_slot = jnp.where(valid & is_center[root], slot_of[root], M)

    ones = (member_slot < M).astype(jnp.float32)
    counts = jnp.zeros((M + 1,), jnp.float32).at[member_slot].add(ones)[:M]
    ksum = jnp.zeros((M + 1, hd), jnp.float32).at[member_slot].add(
        k_head.astype(jnp.float32) * ones[:, None])[:M]
    vsum = jnp.zeros((M + 1, hd), jnp.float32).at[member_slot].add(
        v_head.astype(jnp.float32) * ones[:, None])[:M]
    denom = jnp.maximum(counts, 1.0)[:, None]
    k_out = (ksum / denom).astype(k_head.dtype)
    v_out = (vsum / denom).astype(v_head.dtype)
    return k_out, v_out, counts


def compress_kv(k, v, length, cfg: DPCKVConfig):
    """k/v: (B, S, n_kv, hd); length: () or (B,) valid prefix length.

    Returns (k_c, v_c, counts): (B, M, n_kv, hd) x2 and (B, M, n_kv).
    ``counts`` feed the attention correction  log(count) added to logits —
    a merged center stands for `count` keys (mass-preserving softmax).
    """
    B, S, K, hd = k.shape
    length = jnp.broadcast_to(jnp.asarray(length), (B,))
    valid = jnp.arange(S)[None, :] < length[:, None]          # (B, S)

    def per_bk(kk, vv, val):
        return _compress_head(kk, vv, val, cfg)

    # outer vmap eats the batch axis, so heads sit at axis 1 of (S, K, hd)
    f = jax.vmap(jax.vmap(per_bk, in_axes=(1, 1, None), out_axes=(1, 1, 1)),
                 in_axes=(0, 0, 0))
    k_c, v_c, counts = f(k, v, valid)
    return k_c, v_c, counts


def attend_compressed(q, k_c, v_c, counts, scale=None):
    """Reference attention over a compressed cache with mass correction.

    q: (B, H, hd); k_c/v_c: (B, M, K, hd); counts: (B, M, K).
    Returns (B, H, hd).  Used by tests/benchmarks to measure the
    output error of DPC-KV against full-cache attention.
    """
    B, H, hd = q.shape
    Kh = k_c.shape[2]
    G = H // Kh
    qg = q.reshape(B, Kh, G, hd).astype(jnp.float32)
    scale = scale if scale is not None else hd ** -0.5
    logits = jnp.einsum("bkgh,bmkh->bkgm", qg, k_c.astype(jnp.float32))
    logits = logits * scale + jnp.log(jnp.maximum(
        counts, 1e-9)).transpose(0, 2, 1)[:, :, None, :]
    logits = jnp.where(counts.transpose(0, 2, 1)[:, :, None, :] > 0,
                       logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgm,bmkh->bkgh", probs, v_c.astype(jnp.float32))
    return out.reshape(B, H, hd)
