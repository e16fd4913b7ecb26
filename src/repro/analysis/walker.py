"""Jaxpr walking + sort-taint propagation (the analyzer's engine).

Everything here is grounded in how jax 0.9 actually lowers the
repo's code (probed, not guessed):

* ``jnp.argsort`` lowers to a nested ``jit`` eqn whose body holds ``iota``
  + ``sort`` — so taint sources hide one call level down and the engine
  must recurse through ``jit`` bodies.
* scalar indexing ``order[p]`` inside a ``while_loop`` lowers to
  ``dynamic_slice`` (NOT ``gather``) with a traced start index; array
  indexing (``take_along_axis``, ``tbl[idx_array]``) lowers to ``gather``.
  The PR 4 miscompile class therefore covers *both* read primitives.
* ``while`` eqn invars are ``cond_consts + body_consts + carry`` and the
  body jaxpr's invars are ``body_consts + carry``; carry taint needs a
  fixpoint (monotone, so it terminates in <= len(carry) rounds).
* ``shard_map`` eqn params carry the raw body ``Jaxpr`` under ``jaxpr``,
  the ``mesh``, per-operand ``in_names``/``out_names`` dicts and
  ``check_vma``; body invars map 1:1 onto eqn invars.

The taint engine answers R1's question: *does any ``gather`` /
``dynamic_slice`` read use an index derived from a ``sort`` computed in
traced code, inside a shard_map body over a multi-partition axis?*  That
is exactly the shape of the jax-0.4.37 XLA CPU SPMD miscompile that broke
PR 4's distributed block-sparse path (the ring walk's order-gather), and
narrowing the taint source to ``sort`` outputs is what keeps the clean
stencil paths — which gather with *span-table*-derived indices inside the
very same shard_maps, correctly — out of the findings.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterator

from jax._src import core as jcore

Jaxpr = jcore.Jaxpr
ClosedJaxpr = jcore.ClosedJaxpr


def unwrap(j: Any) -> Any:
    """ClosedJaxpr | Jaxpr -> Jaxpr."""
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def sub_jaxprs(eqn: Any) -> Iterator[tuple[str, Jaxpr]]:
    """Every sub-jaxpr a primitive's params carry (pjit/while/scan/cond
    bodies, shard_map bodies, pallas kernels), with its param name."""
    for key, val in eqn.params.items():
        if isinstance(val, (Jaxpr, ClosedJaxpr)):
            yield key, unwrap(val)
        elif isinstance(val, (tuple, list)):
            for i, item in enumerate(val):
                if isinstance(item, (Jaxpr, ClosedJaxpr)):
                    yield f"{key}[{i}]", unwrap(item)


@dataclass(frozen=True)
class ShardCtx:
    """The shard_map context an eqn sits inside."""

    axis_sizes: tuple[tuple[str, int], ...]   # mapped mesh axes and sizes
    check_vma: bool

    @property
    def multi_partition(self) -> bool:
        return any(s > 1 for _, s in self.axis_sizes)


def shard_ctx_of(eqn: Any) -> ShardCtx:
    """Build the ShardCtx for a shard_map eqn (defensive over param shape)."""
    mesh = eqn.params.get("mesh")
    names: set = set()
    for spec in tuple(eqn.params.get("in_names") or ()) + \
            tuple(eqn.params.get("out_names") or ()):
        if isinstance(spec, dict):
            for axes in spec.values():
                names.update(axes if isinstance(axes, (tuple, list))
                             else (axes,))
    sizes: list[tuple[str, int]] = []
    shape = getattr(mesh, "shape", None)
    if shape:
        for ax, sz in dict(shape).items():
            if not names or ax in names:
                sizes.append((str(ax), int(sz)))
    return ShardCtx(axis_sizes=tuple(sizes),
                    check_vma=bool(eqn.params.get("check_vma", True)))


@dataclass(frozen=True)
class Site:
    """One eqn with its nesting path and innermost shard_map context."""

    eqn: Any
    path: tuple[str, ...]
    shard: ShardCtx | None

    @property
    def where(self) -> str:
        return "/".join(self.path) or "<top>"


def iter_sites(jaxpr: Any, path: tuple[str, ...] = (),
               shard: ShardCtx | None = None) -> Iterator[Site]:
    """Recursively yield every eqn in the program as a :class:`Site`.

    Structural iteration only — no dataflow.  Used by the shape/dtype
    rules (R3, R4); R1 uses the taint engine below, which needs value
    tracking the Site stream cannot carry.
    """
    jaxpr = unwrap(jaxpr)
    for eqn in jaxpr.eqns:
        yield Site(eqn=eqn, path=path, shard=shard)
        name = eqn.primitive.name
        sub_shard = shard_ctx_of(eqn) if name == "shard_map" else shard
        for key, sub in sub_jaxprs(eqn):
            yield from iter_sites(sub, path + (f"{name}.{key}",), sub_shard)


# --------------------------------------------------------------- taint (R1)
# read primitives and their index/start operands: gather's indices are
# invars[1]; dynamic_slice's start indices are invars[1:]
_INDEX_OPERANDS = {
    "gather": lambda eqn: eqn.invars[1:2],
    "dynamic_slice": lambda eqn: eqn.invars[1:],
}

# call-like primitives whose single sub-jaxpr maps invars 1:1
_CALL_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


@dataclass(frozen=True)
class TaintHit:
    """A sliced read with a sort-tainted index inside a multi-partition
    shard_map body — the R1 pattern."""

    primitive: str
    path: tuple[str, ...]
    shard: ShardCtx

    @property
    def where(self) -> str:
        return "/".join(self.path) or "<top>"


def spmd_sort_tainted_slices(closed_jaxpr: Any, *,
                             require_multi_partition: bool = True
                             ) -> list[TaintHit]:
    """All R1 pattern instances in a traced computation.

    Taint = "derives from a ``sort`` output computed in traced code"
    (conservatively propagated: any tainted operand taints every output,
    carries reach a fixpoint through while/scan).  A hit is a ``gather`` /
    ``dynamic_slice`` whose *index* operands carry taint while inside a
    shard_map body mapped over an axis of size > 1.

    ``require_multi_partition=False`` reports hits inside *any* shard_map
    body regardless of mapped axis sizes — the property tests exercise the
    taint engine on single-device runtimes where no multi-partition mesh
    exists; R1 itself always uses the default.
    """
    hits: list[TaintHit] = []

    def sub_run(inner: Any, in_t: list[bool], path: tuple[str, ...],
                shard: ShardCtx | None, report: bool,
                eqn: Any) -> list[bool]:
        """Recurse into a call-like sub-jaxpr; conservative on mismatch."""
        j = unwrap(inner)
        if len(j.invars) != len(in_t):
            return [any(in_t)] * len(eqn.outvars)
        return run(j, in_t, path, shard, report)

    def run(jaxpr: Any, in_taint: list[bool], path: tuple[str, ...],
            shard: ShardCtx | None, report: bool) -> list[bool]:
        jaxpr = unwrap(jaxpr)
        env: dict[Any, bool] = {}

        def get(v: Any) -> bool:
            if isinstance(v, jcore.Literal):
                return False
            return env.get(v, False)

        for v, t in zip(jaxpr.invars, in_taint):
            env[v] = bool(t)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_t = [get(v) for v in eqn.invars]

            if report and shard is not None and \
                    (shard.multi_partition or not require_multi_partition):
                pick = _INDEX_OPERANDS.get(name)
                if pick is not None and any(get(v) for v in pick(eqn)):
                    hits.append(TaintHit(primitive=name, path=path,
                                         shard=shard))

            if name == "sort":
                out_t = [True] * len(eqn.outvars)
            elif name == "while":
                cn = eqn.params["cond_nconsts"]
                bn = eqn.params["body_nconsts"]
                body = eqn.params["body_jaxpr"]
                cond = eqn.params["cond_jaxpr"]
                consts_t = in_t[cn:cn + bn]
                carry_t = list(in_t[cn + bn:])
                for _ in range(len(carry_t) + 1):
                    out_c = sub_run(body, consts_t + carry_t, path, shard,
                                    False, eqn)
                    new = [a or b for a, b in zip(carry_t, out_c)]
                    if new == carry_t:
                        break
                    carry_t = new
                if report:
                    sub_run(body, consts_t + carry_t,
                            path + ("while.body",), shard, True, eqn)
                    sub_run(cond, list(in_t[:cn]) + carry_t,
                            path + ("while.cond",), shard, True, eqn)
                out_t = carry_t
            elif name == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                body = eqn.params["jaxpr"]
                consts_t = in_t[:nc]
                carry_t = list(in_t[nc:nc + ncar])
                xs_t = in_t[nc + ncar:]
                ys_t: list = []
                for _ in range(len(carry_t) + 1):
                    outs = sub_run(body, consts_t + carry_t + xs_t, path,
                                   shard, False, eqn)
                    new = [a or b for a, b in zip(carry_t, outs[:ncar])]
                    ys_t = list(outs[ncar:])
                    if new == carry_t:
                        break
                    carry_t = new
                if report:
                    outs = sub_run(body, consts_t + carry_t + xs_t,
                                   path + ("scan.body",), shard, True, eqn)
                    ys_t = list(outs[ncar:])
                out_t = carry_t + ys_t
            elif name == "cond":
                branches = eqn.params["branches"]
                op_t = in_t[1:]
                branch_outs = [sub_run(br, op_t,
                                       path + (f"cond.branches[{i}]",),
                                       shard, report, eqn)
                               for i, br in enumerate(branches)]
                out_t = [any(ts) for ts in zip(*branch_outs)] \
                    if branch_outs else [any(in_t)] * len(eqn.outvars)
            elif name == "shard_map":
                sub_shard = shard_ctx_of(eqn)
                out_t = sub_run(eqn.params["jaxpr"], in_t,
                                path + ("shard_map",), sub_shard, report,
                                eqn)
            elif name == "pallas_call":
                # Mosaic kernels are outside the XLA SPMD partitioner (the
                # miscompile class R1 targets); propagate conservatively
                # without descending
                out_t = [any(in_t)] * len(eqn.outvars)
            else:
                inner = next((eqn.params[k] for k in _CALL_JAXPR_PARAMS
                              if isinstance(eqn.params.get(k),
                                            (Jaxpr, ClosedJaxpr))), None)
                if inner is not None:
                    out_t = sub_run(inner, in_t, path + (name,), shard,
                                    report, eqn)
                else:
                    out_t = [any(in_t)] * len(eqn.outvars)

            for v, t in zip(eqn.outvars, out_t):
                env[v] = bool(t)
        return [get(v) for v in jaxpr.outvars]

    j = unwrap(closed_jaxpr)
    run(j, [False] * len(j.invars), (), None, True)
    return hits
