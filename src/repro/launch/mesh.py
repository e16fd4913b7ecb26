"""Production mesh construction + logical sharding rule resolution.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run process sets
XLA_FLAGS for 512 host devices before calling it, every other process sees
the real (single-CPU) topology.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.models.common import MeshRules


def flatten_mesh(mesh, axis_name: str = "data"):
    """Collapse every mesh axis into one ``axis_name`` axis.

    DPC is data-parallel only (the paper's algorithm has no model axis), so
    both the batch path (``distributed.dpc``) and the streaming window
    (``repro.stream``) shard over the flattened device list: the model axis
    is reused as more data workers."""
    from jax.sharding import Mesh

    return Mesh(mesh.devices.reshape(-1), (axis_name,))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def rules_for(mesh, *, data_only: bool = False) -> MeshRules:
    """Logical rules for a mesh.  ``data_only`` folds the model axis into
    the data axes (pure DP) — the right layout for small archs whose dims
    cannot use 16-way tensor parallelism (mamba2-130m; §Perf)."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    if data_only:
        return MeshRules(data_axes=data_axes + ("model",), model_axis=None,
                         axis_sizes=axis_sizes)
    return MeshRules(data_axes=data_axes, model_axis="model",
                     axis_sizes=axis_sizes)


def batch_spec(rules: MeshRules, global_batch: int) -> P:
    """Batch-dim sharding over the data axes, falling back to replication
    when the batch does not divide (long_500k has global_batch=1)."""
    total = 1
    for a in rules.data_axes:
        total *= rules.axis_sizes.get(a, 1)
    if global_batch % total == 0:
        return P(rules.data)
    # try the trailing data axis alone before giving up
    last = rules.data_axes[-1]
    if global_batch % rules.axis_sizes.get(last, 1) == 0:
        return P(last)
    return P(None)


def batch_sharding(rules: MeshRules, batch_tree):
    """Per-leaf input sharding: batch dim over data, rest replicated."""
    import jax.tree_util as jtu

    def leaf(spec: jax.ShapeDtypeStruct):
        bs = batch_spec(rules, spec.shape[0])
        return P(*(tuple(bs) + (None,) * (len(spec.shape) - 1)))

    return jtu.tree_map(leaf, batch_tree)
