"""Incremental maintenance of the grouping cell list + sliding-window rho.

The static path rebuilds ``core.grid.build_grid`` (a global sort plus host
capacity measurement) for every point set.  Streaming keeps the only grid
state the Approx-DPC rules actually consume — the *grouping-cell partition*
(rule 1's segments) — incrementally:

* Cell coordinates are **canonical** (``core.grid.canonical_group_coords``:
  absolute-origin ``floor(p / side)``), so the maintained partition is
  bit-identical to what a from-scratch ``build_grid`` of the current window
  would produce — the parity contract of ``repro.stream``.
* A batched insert/evict (``apply``) updates cell membership with O(batch)
  host bookkeeping: a key->cell-id dict, per-cell member counts, and a
  free-list that recycles the ids of emptied cells, keeping every id below
  the window capacity.  The per-slot segment-id table mirrors to device with
  one fixed-shape scatter.
* Capacities are *measured at rebuild time* (the standard cell-list
  pattern): the live-cell budget ``maxima_cap`` (bounds the rule-2/3 query
  pad) and the coordinate box (bounds key packing).  When a batch overflows
  either — density collapse spawning cells, or drift walking out of the
  indexed box — ``apply`` raises :class:`CellOverflow` and the caller falls
  back to a full ``rebuild``.  A rebuild re-derives bookkeeping only; rho is
  partition-independent and survives untouched.
* **Per-cell dirty tracking**: ``apply`` records the grouping-cell coords the
  batch touched (inserted + evicted points), and ``dirty_near`` answers
  which query points sit within a Chebyshev cell radius of any of them.  A
  cell maximum whose answer could have changed must be within 2*d_cut of a
  touched point (its own key, a candidate's key, or a candidate's existence
  can only change there — see ``stream_dpc``), so ``StreamDPC`` skips the
  maxima NN re-query for everything farther away.  A rebuild clears the
  record (``None`` = treat everything dirty — apply may have part-mutated).
* ``repair_rho`` is the density repair: one signed range count over the
  insert/evict delta batch (each surviving neighbor's rho changes by +-1 per
  batch point) plus fresh counts for the inserted rows — O(n * batch)
  instead of the O(n * stencil) full pass.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.analysis.audit import audit_check_rep, audit_determinism
from repro.core.grid import canonical_group_coords
from repro.launch.mesh import flatten_mesh


class CellOverflow(Exception):
    """A batch exceeded a measured capacity; the grid must be rebuilt."""


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


class IncrementalGrid:
    """Slot-indexed grouping-cell bookkeeping over a sliding window."""

    def __init__(self, d_cut: float, capacity: int, dim: int,
                 cell_slack: float = 2.0, extent_margin: int = 4):
        assert cell_slack >= 1.0, "cell_slack must be >= 1"
        self.d_cut = float(d_cut)
        self.capacity = int(capacity)
        self.dim = int(dim)
        self.cell_slack = float(cell_slack)
        self.extent_margin = int(extent_margin)
        self.rebuilds = 0
        self._built = False
        # grouping-cell coords touched by the last successful apply();
        # None = unknown (fresh build / rebuild) -> treat everything dirty
        self.last_touched: np.ndarray | None = None

    # ------------------------------------------------------------- helpers
    def _coords(self, pts: np.ndarray) -> np.ndarray:
        """Canonical grouping coords via the shared device helper (the same
        float math as build_grid -> bit-identical partitions)."""
        return np.asarray(canonical_group_coords(jnp.asarray(pts, jnp.float32),
                                                 self.d_cut))

    def _pack(self, coords: np.ndarray) -> np.ndarray:
        """Pack coords into int64 keys against the measured box.

        Raises CellOverflow when any coordinate falls outside the box the
        strides were measured for (drift out of the indexed region)."""
        rel = coords - self.box_lo
        if (rel < 0).any() or (rel >= self.box_extent).any():
            raise CellOverflow("coordinate outside the indexed box")
        return rel @ self.strides

    # ------------------------------------------------------------- rebuild
    def rebuild(self, pts: np.ndarray, count: int) -> None:
        """Re-derive all bookkeeping from the current window (host, O(n))."""
        pts = np.asarray(pts[:count], np.float32)
        coords = self._coords(pts)
        margin = self.extent_margin
        self.box_lo = coords.min(axis=0) - margin
        self.box_extent = (coords.max(axis=0) + margin + 1) - self.box_lo
        ext = self.box_extent.astype(np.int64)
        self.strides = np.concatenate(
            [np.cumprod(ext[::-1])[::-1][1:], np.ones(1, np.int64)])
        keys = self._pack(coords)
        uniq, inv = np.unique(keys, return_inverse=True)
        live = len(uniq)
        self.key_to_id = {int(k): i for i, k in enumerate(uniq)}
        self.cell_count = np.zeros(self.capacity, np.int32)
        self.cell_count[:live] = np.bincount(inv, minlength=live)
        self.live_cells = live
        self.free_ids: list[int] = []
        self.next_id = live
        self.maxima_cap = min(
            self.capacity,
            _round_up(max(64, int(live * self.cell_slack)), 64))
        self.seg_np = np.zeros(self.capacity, np.int32)
        self.seg_np[:count] = inv
        self.seg_dev = jnp.asarray(self.seg_np)
        self.rebuilds += 1 if self._built else 0
        self._built = True
        self.last_touched = None        # apply may have part-mutated

    # --------------------------------------------------------------- apply
    def apply(self, slots: np.ndarray, new_pts: np.ndarray,
              old_pts: np.ndarray, r: int) -> None:
        """Batched insert/evict: slot ``slots[i]``'s point changes from
        ``old_pts[i]`` to ``new_pts[i]`` for i < r.

        Raises CellOverflow when the live-cell count would exceed the
        measured ``maxima_cap`` or a new point leaves the indexed box; the
        caller must ``rebuild`` (bookkeeping may be part-updated — rebuild
        resets everything from the window)."""
        assert self._built
        if r == 0:
            self.last_touched = np.zeros((0, self.dim), np.int64)
            return
        old_coords = self._coords(old_pts[:r])
        new_coords = self._coords(new_pts[:r])
        old_keys = self._pack(old_coords)
        new_keys = self._pack(new_coords)                    # may raise
        # evictions first: emptied ids return to the free list before the
        # insert loop allocates, so ids never exceed the live-cell bound
        for k in old_keys:
            cid = self.key_to_id[int(k)]
            self.cell_count[cid] -= 1
            if self.cell_count[cid] == 0:
                del self.key_to_id[int(k)]
                self.free_ids.append(cid)
                self.live_cells -= 1
        ids = np.empty(r, np.int32)
        for i, k in enumerate(new_keys):
            cid = self.key_to_id.get(int(k))
            if cid is None:
                if self.live_cells + 1 > self.maxima_cap:
                    raise CellOverflow("live cells exceed measured capacity")
                cid = self.free_ids.pop() if self.free_ids else self.next_id
                if cid == self.next_id:
                    self.next_id += 1
                self.key_to_id[int(k)] = cid
                self.live_cells += 1
            self.cell_count[cid] += 1
            ids[i] = cid
        self.seg_np[slots[:r]] = ids
        # one fixed-shape scatter keeps the device mirror in sync
        B = slots.shape[0]
        ids_p = np.zeros(B, np.int32)
        ids_p[:r] = ids
        self.seg_dev = self.seg_dev.at[jnp.asarray(slots)].set(
            jnp.asarray(ids_p), mode="drop")
        self.last_touched = np.concatenate([old_coords, new_coords])

    # ----------------------------------------------------------- snapshot
    def snapshot(self) -> dict:
        """Cheap pre-tick state capture for transactional rollback.

        Host arrays that ``apply`` mutates in place (``cell_count``,
        ``seg_np``) are copied; everything ``apply``/``rebuild`` only
        *reassigns* (``seg_dev``, the box arrays, ``last_touched``) is
        captured by reference — the old object stays valid."""
        if not self._built:
            return {"built": False}
        return {
            "built": True,
            "box_lo": self.box_lo, "box_extent": self.box_extent,
            "strides": self.strides,
            "key_to_id": dict(self.key_to_id),
            "cell_count": self.cell_count.copy(),
            "live_cells": self.live_cells,
            "free_ids": list(self.free_ids),
            "next_id": self.next_id,
            "maxima_cap": self.maxima_cap,
            "seg_np": self.seg_np.copy(),
            "seg_dev": self.seg_dev,
            "rebuilds": self.rebuilds,
            "last_touched": self.last_touched,
        }

    def restore(self, snap: dict) -> None:
        """Roll back to a :meth:`snapshot` (a failed tick's grid state may
        be part-mutated — see ``apply``)."""
        self._built = snap["built"]
        if not self._built:
            self.last_touched = None
            return
        self.box_lo = snap["box_lo"]
        self.box_extent = snap["box_extent"]
        self.strides = snap["strides"]
        self.key_to_id = dict(snap["key_to_id"])
        self.cell_count = snap["cell_count"].copy()
        self.live_cells = snap["live_cells"]
        self.free_ids = list(snap["free_ids"])
        self.next_id = snap["next_id"]
        self.maxima_cap = snap["maxima_cap"]
        self.seg_np = snap["seg_np"].copy()
        self.seg_dev = snap["seg_dev"]
        self.rebuilds = snap["rebuilds"]
        self.last_touched = snap["last_touched"]

    # --------------------------------------------------------------- dirty
    def dirty_near(self, coords: np.ndarray, radius_cells: int) -> np.ndarray:
        """(len(coords),) bool: within ``radius_cells`` (Chebyshev, grouping
        cells) of any cell the last batch touched.  ``None`` record (fresh
        build / rebuild / overflow) conservatively reports all-dirty."""
        if self.last_touched is None:
            return np.ones(len(coords), bool)
        if len(self.last_touched) == 0:
            return np.zeros(len(coords), bool)
        cheb = np.max(np.abs(coords[:, None, :].astype(np.int64)
                             - self.last_touched[None, :, :]), axis=-1)
        return (cheb <= radius_cells).any(axis=1)


# ------------------------------------------------------------- rho repair
def repair_rho(backend, d_cut: float, window_dev, rho, delta_batch, signs,
               ins_batch, slots):
    """Exact sliding-window density repair (slot-indexed, fixed shapes).

    * survivors:  rho += signed range count over the (insert +1 / evict -1)
      delta batch — ``range_count_delta``, the streaming kernel primitive;
    * inserted rows: fresh ``range_count`` against the post-insert window,
      scattered into their slots (padding rows scatter-drop).

    Counts are exact integers in f32, so repairs never drift from a
    from-scratch recount (parity-tested per backend).
    """
    delta = backend.range_count_delta(window_dev, delta_batch, signs, d_cut)
    fresh = backend.range_count(ins_batch, window_dev, d_cut)
    return (rho + delta).at[slots].set(fresh, mode="drop")


def make_sharded_repair(mesh, axis: str, backend, d_cut: float):
    """Sharded ingest: the rho repair as one SPMD pass over the window.

    The window rows shard over every device (``launch.mesh.flatten_mesh`` —
    the same flattening ``DistDPCConfig`` uses for the batch path); the
    delta batch replicates.  Each shard repairs its rows locally and the
    inserted rows' fresh counts reduce with a psum (integer-exact in f32,
    so the sharded repair is bit-identical to the replicated one).
    Returns a jitted callable with ``repair_rho``'s signature (minus
    backend/d_cut); build once per (mesh, backend) and reuse across ticks.
    """
    flat = flatten_mesh(mesh, axis)

    @audit_check_rep(
        "per-row repairs are P(axis)-local; the one replicated output "
        "(inserted rows' fresh counts) is produced by an explicit psum, "
        "identical on every member by construction",
        collectives=("psum",))
    @audit_determinism(
        "the psum reduces per-shard neighbor *counts* — exact integers in "
        "f32 far below 2^24, so addition is associative over them and "
        "every reduction order (ring, tree, any device count) yields "
        "identical bits; parity-tested against the replicated recount",
        ops=("psum",))
    def f(w_my, rho_my, batch, sgn, ins):
        d = backend.range_count_delta(w_my, batch, sgn, d_cut)
        part = backend.range_count(ins, w_my, d_cut)
        return rho_my + d, jax.lax.psum(part, axis)

    sm = shard_map(f, mesh=flat,
                   in_specs=(P(axis), P(axis), P(None), P(None), P(None)),
                   out_specs=(P(axis), P(None)),
                   check_vma=False)   # pallas_call lacks a rep rule
    sm_jit = jax.jit(sm)

    def repair(window_dev, rho, delta_batch, signs, ins_batch, slots):
        n_dev = flat.devices.size
        assert window_dev.shape[0] % n_dev == 0, \
            "device count must divide the window capacity"
        rho2, fresh = sm_jit(window_dev, rho, delta_batch, signs, ins_batch)
        return rho2.at[slots].set(fresh, mode="drop")

    return repair
