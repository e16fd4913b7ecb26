"""Sharded streaming repair tail: maxima NN, label propagation, centers.

``incremental.make_sharded_repair`` shards the rho repair, but until PR 8
every stage *after* it ran replicated: the dirty-maxima NN re-query, label
propagation and the center-continuity distance matrix all touched the whole
window on every member.  At the north-star scale (64M-point windows) those
replicated stages dominate the tick, so this module gives each one the same
shard_map treatment, over the same flattened data axis:

* **maxima NN re-query** (:func:`make_sharded_nn_update`) — drop-in for
  ``backend.denser_nn_update``: the window rows and their density keys
  shard ``P(axis)``, the (replicated) query rows run the backend's own
  masked-NN primitive against each member's local slice, and the global
  winner is recovered with two explicit lexicographic ``pmin`` reductions
  (value, then lowest global column among the value's holders) — exactly
  the replicated kernel's lowest-index tie-break, bit for bit.  The
  per-shard primitive honors the plan's layout through the same
  ``shard_blocksparse_layout`` probe the batch path uses (no new guards):
  with the PR 8 one-hot ring walk the jnp block-sparse sweep is R1-clean
  inside the multi-partition body.
* **label propagation** (:func:`make_sharded_labels`) — pointer jumping in
  the one-hot-matmul formulation (Xu et al., Faithful-DPC-on-MPI): each
  round, every member jumps its own ``P(axis)`` chunk of the pointer table
  by contracting a ``(chunk, n)`` one-hot of its parents against the
  replicated table (exact 0/1 weights; parent ids < 2^24 are exact in
  f32), then re-replicates with an ``all_gather``.  ceil(log2 n) rounds,
  identical integer trajectories to ``core.labels._propagate``.
* **center matching** (:func:`make_sharded_center_dists`) — the f64
  center-continuity distance matrix, new centers sharded over the data
  axis, previous centers replicated; the greedy host matching consumes the
  gathered matrix unchanged.  No collectives and only ``P(axis)``-local
  outputs, so this body keeps ``check_vma=True``.

Every stage is bit-identical to its replicated predecessor (parity-tested
in ``tests/test_stream.py``) and traced by the R1/R2 analysis rules via
``analysis.targets.stream_targets``.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.analysis.audit import audit_check_rep
from repro.launch.mesh import flatten_mesh

_INT32_MAX = np.iinfo(np.int32).max


def make_sharded_nn_update(mesh, axis: str, backend, layout: str | None = None):
    """Sharded Def.-2 re-query: ``backend.denser_nn_update``'s signature,
    window rows sharded ``P(axis)``.

    Build once per (mesh, backend, layout) and reuse across ticks; callers
    resolve ``layout`` through ``distributed.dpc.shard_blocksparse_layout``
    so the shard-phase layout decision (and its R1 probe) is shared with
    the batch path.
    """
    flat = flatten_mesh(mesh, axis)
    S = int(flat.devices.size)

    @audit_check_rep(
        "window rows and keys are P(axis)-local; both outputs are made "
        "identical on every member by explicit lexicographic pmin "
        "reductions (best value, then lowest global winner column among "
        "the holders of that value)",
        collectives=("pmin", "axis_index"))
    def f(w_my, k_my, q, qk):
        rows_per = w_my.shape[0]
        off = (jax.lax.axis_index(axis) * rows_per).astype(jnp.int32)
        # the backend's own masked-NN primitive on my slice: per-pair d2 is
        # the same direct-difference expression as the replicated pass, so
        # min over shards == the replicated min, bitwise
        dd, pp = backend.denser_nn(q, qk, w_my, k_my, layout=layout)
        best = jax.lax.pmin(dd, axis)
        hit = (dd == best) & jnp.isfinite(dd)
        argc = jnp.where(hit, off + pp, _INT32_MAX)
        arg = jax.lax.pmin(argc, axis)
        parent = jnp.where(jnp.isfinite(best), arg, -1).astype(jnp.int32)
        return best, parent

    sm = shard_map(f, mesh=flat,
                   in_specs=(P(axis), P(axis), P(None), P(None)),
                   out_specs=(P(None), P(None)),
                   check_vma=False)   # pallas_call lacks a rep rule
    sm_jit = jax.jit(sm)

    def nn_update(window_dev, rho_key, q_slots):
        n = window_dev.shape[0]
        assert n % S == 0, "device count must divide the window capacity"
        # the replicated prelude of KernelBackend.denser_nn_update: gather
        # the query rows by (clean, slot-derived) index; pad slots >= n are
        # inert +inf-key rows and come back (inf, -1)
        slot_c = jnp.clip(q_slots, 0, n - 1)
        valid = q_slots < n
        q = window_dev[slot_c]
        qk = jnp.where(valid, rho_key[slot_c], jnp.inf)
        return sm_jit(window_dev, rho_key, q, qk)

    nn_update.inner = sm        # the shard_map body, for the R1/R2 sweep
    return nn_update


def make_sharded_labels(mesh, axis: str, capacity: int):
    """Sharded ``assign_labels``: pointer jumping as one-hot matmuls.

    Returns ``assign(res, rho_min, delta_min) -> Clustering``, bit-identical
    to ``core.labels.assign_labels`` (same integer pointer trajectories,
    same center selection and densification).
    """
    from repro.core.labels import Clustering, select_centers

    flat = flatten_mesh(mesh, axis)
    S = int(flat.devices.size)
    n = int(capacity)
    assert n % S == 0, "device count must divide the window capacity"
    chunk = n // S
    steps = max(int(math.ceil(math.log2(max(n, 2)))), 1)

    @audit_check_rep(
        "each pointer-jump round contracts my P(axis) chunk's one-hot "
        "against the replicated table and re-replicates with an explicit "
        "all_gather(tiled), identical on every member by construction",
        collectives=("all_gather", "axis_index"))
    def propagate(p0):
        off = jax.lax.axis_index(axis) * chunk
        iota = jnp.arange(n, dtype=jnp.int32)

        def jump(p, _):
            p_my = jax.lax.dynamic_slice_in_dim(p, off, chunk, 0)
            # Xu et al.'s matrix formulation: parent ids select rows of the
            # replicated table by exact 0/1 contraction weights (ids < 2^24
            # are exact in f32), never by a gather index
            onehot = (p_my[:, None] == iota[None, :]).astype(jnp.float32)
            jumped = jax.lax.dot_general(
                onehot, p.astype(jnp.float32)[:, None],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)[:, 0].astype(jnp.int32)
            return jax.lax.all_gather(jumped, axis, axis=0, tiled=True), None

        p, _ = jax.lax.scan(jump, p0, None, length=steps)
        return p

    sm = shard_map(propagate, mesh=flat, in_specs=(P(None),),
                   out_specs=P(None), check_vma=False)
    sm_jit = jax.jit(sm)

    def assign(res, rho_min: float, delta_min: float) -> Clustering:
        from repro import obs

        with obs.span("labels.assign", shards=S) as sp:
            centers, noise = select_centers(res, rho_min, delta_min)
            iota = jnp.arange(n, dtype=res.parent.dtype)
            p0 = jnp.where(centers, iota, res.parent)
            p0 = jnp.where(p0 < 0, iota, p0)          # global peak self-loop
            root = sm_jit(p0.astype(jnp.int32))
            cid = jnp.cumsum(centers.astype(jnp.int32)) - 1
            labels = cid[root]
            reached = centers[root]
            labels = jnp.where(noise | ~reached, -1, labels).astype(jnp.int32)
            sp.sync(labels)
        return Clustering(labels=labels, centers=centers,
                          num_clusters=jnp.sum(centers.astype(jnp.int32)))

    assign.inner = sm           # the shard_map body, for the R1/R2 sweep
    return assign


def make_sharded_center_dists(mesh, axis: str):
    """Sharded center-continuity distances: (m_new, m_old) f64 matrix with
    the new centers sharded over the data axis.  The host greedy matching
    (``StreamDPC._match_centers``) consumes the gathered matrix unchanged;
    per-entry math mirrors the numpy expression exactly."""
    flat = flatten_mesh(mesh, axis)
    S = int(flat.devices.size)

    def dists(new_my, prev):
        diff = (new_my[:, None, :].astype(jnp.float64)
                - prev[None, :, :].astype(jnp.float64))
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))

    # no collectives, P(axis)-local outputs only: rep checking stays on
    sm = shard_map(dists, mesh=flat, in_specs=(P(axis), P(None)),
                   out_specs=P(axis))
    sm_jit = jax.jit(sm)

    def center_dists(new_pos: np.ndarray, prev_pos: np.ndarray) -> np.ndarray:
        m = int(new_pos.shape[0])
        mp = -(-m // S) * S
        pad = np.zeros((mp, new_pos.shape[1]), np.float32)
        pad[:m] = new_pos
        out = sm_jit(jnp.asarray(pad), jnp.asarray(prev_pos, jnp.float32))
        return np.asarray(out)[:m]

    center_dists.inner = sm     # the shard_map body, for the R1/R2 sweep
    return center_dists
