"""Abstract interpretation over pallas grids and jaxpr buffers (R6/R9 core).

Three analyses, each grounded in how the installed jax (0.9) actually lowers
this repo's kernels (probed, not guessed):

* an **affine domain over symbolic grid indices** —
  :func:`eval_index_map` evaluates a ``BlockSpec`` index-map jaxpr with the
  grid indices as symbolic unit affines and the scalar-prefetch operands as
  opaque table references; :func:`visit_verdict` then decides whether the
  output block coordinates are visited ``once`` over the whole grid,
  definitely ``revisit`` (some live grid axis never reaches any output
  coordinate — ``gather_nn``'s doubled column grid), are ``data``-dependent
  (the worklist sweep's ``mt[0, p]`` prefetch-table read), or ``unknown``.
* a **kernel-body write classifier** — :func:`classify_kernel_writes` runs
  a forward dataflow over the kernel jaxpr (reads are ``get`` eqns, writes
  are ``swap`` eqns; ``pl.when`` lowers to ``cond``) and classifies every
  write to an *output* ref: a read-modify-write through associative
  accumulate/merge ops only (``rmw-clean`` — safe on revisited blocks), an
  RMW whose old value crossed a non-whitelisted op (``rmw-dirty``), an
  overwrite under a grid/prefetch-pure guard (``overwrite-guarded`` — the
  first-visit init idiom), or a plain ``overwrite`` (lost-update on any
  revisited block: the R6 finding).
* a **live-buffer walker** — :func:`live_buffer_peak` bounds the
  simultaneously-live buffer bytes of a traced computation (last-use
  liveness over the eqn sequence, sub-jaxpr peaks stacked on the caller's
  live set), the dense-path half of R9's memory budget.

Everything here is pure jaxpr introspection: nothing executes, nothing
compiles, no device is touched.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Union

from jax._src import core as jcore

from .walker import _CALL_JAXPR_PARAMS, sub_jaxprs, unwrap

__all__ = [
    "Affine", "TOP", "DATA", "VInfo", "WriteSite",
    "eval_index_map", "visit_verdict", "classify_kernel_writes",
    "live_buffer_peak", "pallas_memory",
]

# enumeration ceiling for the exact small-grid visit check
ENUM_CAP = 1 << 16


# ------------------------------------------------------------ affine domain
@dataclasses.dataclass(frozen=True)
class Affine:
    """``const + sum(coeff_a * grid_index_a)`` — an affine map of the grid."""

    const: int
    coeffs: tuple[tuple[int, int], ...] = ()   # sorted (axis, coeff != 0)

    @property
    def axes(self) -> tuple[int, ...]:
        return tuple(a for a, _ in self.coeffs)

    def coeff(self, axis: int) -> int:
        for a, c in self.coeffs:
            if a == axis:
                return c
        return 0

    def eval(self, point: tuple[int, ...]) -> int:
        return self.const + sum(c * point[a] for a, c in self.coeffs)


# lattice companions of Affine: TOP = not affine (e.g. rem-folded column
# maps), DATA = derived from a scalar-prefetch table read, _REF = the
# table reference itself
TOP = "top"
DATA = "data"
_REF = "ref"

AbsVal = Union[Affine, str]


def _aff_add(a: Affine, b: Affine, sign: int = 1) -> Affine:
    coeffs = dict(a.coeffs)
    for ax, c in b.coeffs:
        coeffs[ax] = coeffs.get(ax, 0) + sign * c
    return Affine(a.const + sign * b.const,
                  tuple(sorted((ax, c) for ax, c in coeffs.items() if c)))


def _aff_scale(a: Affine, k: int) -> Affine:
    return Affine(a.const * k,
                  tuple(sorted((ax, c * k) for ax, c in a.coeffs if c * k)))


def _as_const(val: AbsVal) -> int | None:
    if isinstance(val, Affine) and not val.coeffs:
        return val.const
    return None


def _lit_val(v: Any) -> AbsVal:
    try:
        x = v.val
        if hasattr(x, "item"):
            x = x.item()
        if isinstance(x, bool):
            return TOP
        return Affine(int(x))
    except (TypeError, ValueError, AttributeError):
        return TOP


def _eval_jaxpr(jaxpr: Any, invals: list[AbsVal]) -> list[AbsVal]:
    jaxpr = unwrap(jaxpr)
    if len(jaxpr.invars) != len(invals):
        return [TOP] * len(jaxpr.outvars)
    env: dict[Any, AbsVal] = dict(zip(jaxpr.invars, invals))
    for v in jaxpr.constvars:
        env[v] = TOP

    def read(v: Any) -> AbsVal:
        if isinstance(v, jcore.Literal):
            return _lit_val(v)
        return env.get(v, TOP)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        vals = [read(v) for v in eqn.invars]
        outs: list[AbsVal] | None = None
        if any(v is _REF for v in vals) or any(v is DATA for v in vals):
            # a scalar-prefetch table read (get on a ref), or anything
            # derived from one: the block coordinate is data-dependent
            out: AbsVal = DATA
        elif name in ("add", "sub") and len(vals) == 2 \
                and all(isinstance(v, Affine) for v in vals):
            out = _aff_add(vals[0], vals[1], 1 if name == "add" else -1)
        elif name == "mul" and len(vals) == 2 \
                and all(isinstance(v, Affine) for v in vals) \
                and (_as_const(vals[0]) is not None
                     or _as_const(vals[1]) is not None):
            k = _as_const(vals[0])
            out = _aff_scale(vals[1], k) if k is not None \
                else _aff_scale(vals[0], _as_const(vals[1]) or 0)
        elif name == "neg" and isinstance(vals[0], Affine):
            out = _aff_scale(vals[0], -1)
        elif name in ("convert_element_type", "copy", "squeeze",
                      "broadcast_in_dim", "reshape") and vals \
                and isinstance(vals[0], Affine):
            # scalar plumbing around an affine value keeps it affine
            out = vals[0]
        else:
            inner = next((eqn.params[k] for k in _CALL_JAXPR_PARAMS
                          if isinstance(eqn.params.get(k),
                                        (jcore.Jaxpr, jcore.ClosedJaxpr))),
                         None)
            if inner is not None:
                outs = _eval_jaxpr(inner, vals)
            else:
                out = TOP
        if outs is None:
            outs = [out] * len(eqn.outvars)
        for ov, o in zip(eqn.outvars, outs):
            env[ov] = o
    return [read(v) for v in jaxpr.outvars]


def eval_index_map(index_map_jaxpr: Any, n_grid: int) -> list[AbsVal]:
    """Per-output-dim abstract block coordinates of a BlockSpec index map.

    ``index_map_jaxpr`` invars are the grid indices followed by the
    scalar-prefetch refs (jax 0.9 ``BlockMapping.index_map_jaxpr``
    layout).  Returns one :data:`AbsVal` per output dimension.
    """
    jaxpr = unwrap(index_map_jaxpr)
    invals: list[AbsVal] = [
        Affine(0, ((i, 1),)) if i < n_grid else _REF
        for i in range(len(jaxpr.invars))]
    return _eval_jaxpr(jaxpr, invals)


def visit_verdict(dims: list[AbsVal], grid: tuple[Any, ...],
                  enum_cap: int = ENUM_CAP) -> str:
    """Is each output block coordinate produced at most once over ``grid``?

    Returns ``"once"`` (proved unique), ``"revisit"`` (proved repeated),
    ``"data"`` (worklist/prefetch-dependent — uniqueness is a runtime
    property of the table), or ``"unknown"``.
    """
    if not all(isinstance(s, int) for s in grid):
        return "unknown"                  # dynamic grid bounds: R4 territory
    if any(d is DATA or d is _REF for d in dims):
        return "data"
    if not all(isinstance(d, Affine) for d in dims):
        return "unknown"
    affs = [d for d in dims if isinstance(d, Affine)]
    live = [a for a, s in enumerate(grid) if int(s) > 1]
    if not live:
        return "once"
    used: set[int] = set()
    for d in affs:
        used.update(d.axes)
    if any(a not in used for a in live):
        # a >1-sized grid axis never reaches any output coordinate: the
        # same block tuple recurs across that whole axis
        return "revisit"
    vol = 1
    for s in grid:
        vol *= max(int(s), 1)
    if vol <= enum_cap:
        seen: set[tuple[int, ...]] = set()
        for point in itertools.product(*[range(int(s)) for s in grid]):
            key = tuple(d.eval(point) for d in affs)
            if key in seen:
                return "revisit"
            seen.add(key)
        return "once"
    # sufficient condition for big grids: every live axis owns a distinct
    # output dim with a unit coefficient and no live-axis co-tenant
    owner: dict[int, int] = {}
    for i, d in enumerate(affs):
        axs = [a for a in d.axes if a in live]
        if len(axs) == 1 and abs(d.coeff(axs[0])) == 1:
            owner.setdefault(axs[0], i)
    if all(a in owner for a in live) \
            and len(set(owner.values())) == len(owner):
        return "once"
    return "unknown"


# ------------------------------------------------- kernel write classifier
# ops through which an accumulator's old value may legally flow back into
# its ref: associative accumulates (+ / min / max), the select/merge family
# and pure data movement — the building blocks of the kept-k lexicographic
# merge and the best-1 min update
_ACCUM_OK = frozenset({
    "add", "add_any", "sub", "max", "min",
    "reduce_max", "reduce_min", "reduce_sum",
    "select_n", "concatenate", "broadcast_in_dim", "reshape", "expand_dims",
    "squeeze", "transpose", "slice", "pad", "rev",
    "convert_element_type", "copy", "stop_gradient",
})
# predicate-producing ops: their result is control information, not a
# merged value — taint is deliberately killed (a comparison against the old
# accumulator is how min/merge updates decide, not how values flow)
_PREDICATE = frozenset({
    "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "xor", "not",
    "is_finite", "reduce_and", "reduce_or",
})
# sources that are pure functions of the grid position
_PURE_SOURCES = frozenset({"program_id", "num_programs", "iota"})


@dataclasses.dataclass(frozen=True)
class VInfo:
    """Abstract value state inside a kernel body.

    ``taint``: output-ref slots whose *stored value* flows into this value
    through accumulate-whitelisted ops; ``dirty``: some tainted operand
    crossed a non-whitelisted op on the way here; ``pure``: derived only
    from grid indices, scalar-prefetch reads and literals (guard purity).
    """

    taint: frozenset = frozenset()
    dirty: bool = False
    pure: bool = False


_PURE_V = VInfo(pure=True)
_OPAQUE_V = VInfo()


@dataclasses.dataclass(frozen=True)
class WriteSite:
    """One ``swap`` on an output ref, classified.

    ``slot`` is the output operand index (-1: a write inside an unmappable
    sub-jaxpr — conservatively matches every output).  ``kind`` is one of
    ``rmw-clean`` / ``rmw-dirty`` / ``overwrite-guarded`` / ``overwrite``.
    """

    slot: int
    kind: str
    path: str


def _join_v(infos: list[VInfo]) -> VInfo:
    if not infos:
        return _OPAQUE_V
    taint = frozenset().union(*[i.taint for i in infos])
    return VInfo(taint=taint, dirty=any(i.dirty for i in infos),
                 pure=all(i.pure for i in infos))


def classify_kernel_writes(body: Any, n_prefetch: int, n_inputs: int,
                           n_outputs: int
                           ) -> tuple[list[WriteSite], set[tuple[str, int]]]:
    """Classify every output-ref write in a pallas kernel body.

    ``body`` is the kernel jaxpr whose invars are, in order, the
    scalar-prefetch refs, the input refs, the output refs and the scratch
    refs (jax 0.9 ``pallas_call`` eqn ``jaxpr`` param layout).  Returns
    ``(writes, reads)`` where ``reads`` is the set of ref slots whose value
    is read anywhere (``("input", i)`` / ``("output", k)`` / ...).
    """
    jaxpr = unwrap(body)
    writes: list[WriteSite] = []
    reads: set[tuple[str, int]] = set()

    refs0: dict[Any, tuple[str, int]] = {}
    for i, v in enumerate(jaxpr.invars):
        if i < n_prefetch:
            refs0[v] = ("prefetch", i)
        elif i < n_prefetch + n_inputs:
            refs0[v] = ("input", i - n_prefetch)
        elif i < n_prefetch + n_inputs + n_outputs:
            refs0[v] = ("output", i - n_prefetch - n_inputs)
        else:
            refs0[v] = ("scratch", i - n_prefetch - n_inputs - n_outputs)

    def conservative_scan(jaxpr: Any, path: tuple[str, ...]) -> None:
        """A sub-jaxpr whose invars we could not map: any swap inside may
        target any output (slot -1, plain overwrite)."""
        for eqn in unwrap(jaxpr).eqns:
            if eqn.primitive.name == "swap":
                writes.append(WriteSite(slot=-1, kind="overwrite",
                                        path="/".join(path) or "<kernel>"))
            for key, sub in sub_jaxprs(eqn):
                conservative_scan(sub, path + (f"{eqn.primitive.name}.{key}",))

    def run(jaxpr: Any, refs: dict, invals: list[VInfo],
            guard_pure: bool, guarded: bool,
            path: tuple[str, ...]) -> list[VInfo]:
        jaxpr = unwrap(jaxpr)
        vals: dict[Any, VInfo] = dict(zip(jaxpr.invars, invals))
        for v in jaxpr.constvars:
            vals[v] = _OPAQUE_V

        def vinfo(v: Any) -> VInfo:
            if isinstance(v, jcore.Literal):
                return _PURE_V
            return vals.get(v, _OPAQUE_V)

        def refid(v: Any) -> tuple[str, int] | None:
            if isinstance(v, jcore.Literal):
                return None
            return refs.get(v)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            ins = [vinfo(v) for v in eqn.invars]
            outs: list[VInfo] | None = None
            out = _OPAQUE_V
            if name in ("get", "swap", "addupdate"):
                rid = refid(eqn.invars[0])
                if rid is not None:
                    reads.add(rid)
                idx_pure = all(vinfo(v).pure for v in eqn.invars[1:])
                if name in ("swap", "addupdate") and rid is not None \
                        and rid[0] == "output":
                    k = rid[1]
                    val = vinfo(eqn.invars[1])
                    if name == "addupdate" or k in val.taint:
                        kind = "rmw-dirty" if val.dirty else "rmw-clean"
                    elif guarded and guard_pure:
                        kind = "overwrite-guarded"
                    else:
                        kind = "overwrite"
                    writes.append(WriteSite(
                        slot=k, kind=kind,
                        path="/".join(path) or "<kernel>"))
                # the produced value is the ref's (old) stored value
                if rid is not None and rid[0] == "output":
                    out = VInfo(taint=frozenset({rid[1]}))
                elif rid is not None and rid[0] == "prefetch":
                    out = VInfo(pure=idx_pure)
                else:
                    out = _OPAQUE_V
            elif name == "cond":
                pred = ins[0]
                branches = tuple(eqn.params.get("branches", ()))
                op_vals = ins[1:]
                op_refs = {unwrap(br).invars[i]: refid(v)
                           for br in branches
                           for i, v in enumerate(eqn.invars[1:])
                           if len(unwrap(br).invars) == len(eqn.invars) - 1
                           and refid(v) is not None}
                per_branch: list[list[VInfo]] = []
                ok = True
                for bi, br in enumerate(branches):
                    sub = unwrap(br)
                    if len(sub.invars) != len(eqn.invars) - 1:
                        ok = False
                        break
                    sub_refs = {sv: refid(v) for sv, v in
                                zip(sub.invars, eqn.invars[1:])
                                if refid(v) is not None}
                    per_branch.append(run(
                        sub, sub_refs, op_vals,
                        guard_pure=guard_pure and pred.pure, guarded=True,
                        path=path + (f"cond[{bi}]",)))
                del op_refs
                if ok and per_branch:
                    outs = [_join_v(list(t)) for t in zip(*per_branch)]
                    if not outs:
                        outs = [_OPAQUE_V] * len(eqn.outvars)
                else:
                    for bi, br in enumerate(branches):
                        conservative_scan(br, path + (f"cond[{bi}]",))
                    outs = [_OPAQUE_V] * len(eqn.outvars)
            elif name in _PURE_SOURCES:
                out = _PURE_V
            elif name in _PREDICATE:
                out = VInfo(pure=all(i.pure for i in ins))
            elif name == "select_n":
                # the predicate selects; only the case operands' values flow
                cases = ins[1:]
                out = VInfo(
                    taint=frozenset().union(*[c.taint for c in cases])
                    if cases else frozenset(),
                    dirty=any(c.dirty for c in cases),
                    pure=all(i.pure for i in ins))
            elif name in _ACCUM_OK:
                out = _join_v(ins) if ins else _PURE_V
            else:
                inner = next((eqn.params[k] for k in _CALL_JAXPR_PARAMS
                              if isinstance(eqn.params.get(k),
                                            (jcore.Jaxpr, jcore.ClosedJaxpr))),
                             None)
                if inner is not None:
                    sub = unwrap(inner)
                    if len(sub.invars) == len(eqn.invars):
                        sub_refs = {sv: refid(v) for sv, v in
                                    zip(sub.invars, eqn.invars)
                                    if refid(v) is not None}
                        outs = run(sub, sub_refs, ins, guard_pure, guarded,
                                   path + (name,))
                        if len(outs) != len(eqn.outvars):
                            outs = [_join_v(ins)] * len(eqn.outvars)
                    else:
                        conservative_scan(sub, path + (name,))
                        outs = [_OPAQUE_V] * len(eqn.outvars)
                elif any(isinstance(val, (jcore.Jaxpr, jcore.ClosedJaxpr))
                         for val in eqn.params.values()) \
                        or any(isinstance(val, (tuple, list))
                               and any(isinstance(x, (jcore.Jaxpr,
                                                      jcore.ClosedJaxpr))
                                       for x in val)
                               for val in eqn.params.values()):
                    # while/scan/other sub-jaxpr carriers we do not model:
                    # conservative over every nested swap
                    for key, subj in sub_jaxprs(eqn):
                        conservative_scan(subj, path + (f"{name}.{key}",))
                    outs = [_OPAQUE_V] * len(eqn.outvars)
                else:
                    t = frozenset().union(*[i.taint for i in ins]) \
                        if ins else frozenset()
                    out = VInfo(taint=t,
                                dirty=any(i.dirty for i in ins) or bool(t),
                                pure=False)
            if outs is None:
                outs = [out] * len(eqn.outvars)
            for ov, o in zip(eqn.outvars, outs):
                vals[ov] = o
        return [vinfo(v) for v in jaxpr.outvars]

    run(jaxpr, refs0, [_OPAQUE_V] * len(jaxpr.invars),
        guard_pure=True, guarded=False, path=())
    return writes, reads


# ----------------------------------------------------- live-buffer walker
def _aval_bytes(v: Any) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for s in shape:
        if not isinstance(s, int):
            return 0
        size *= s
    try:
        return int(size) * int(dtype.itemsize)
    except (TypeError, AttributeError):
        return 0


def live_buffer_peak(closed: Any) -> int:
    """Upper bound on simultaneously-live buffer bytes of a traced
    computation.

    Last-use liveness over each jaxpr's eqn sequence; a sub-jaxpr's peak is
    stacked on top of the caller's live set at its call point (boundary
    values are counted on both sides — this is an upper bound, which is the
    useful direction for a budget).  ``pallas_call`` bodies are excluded:
    their on-chip footprint is :func:`pallas_memory`'s job, not HBM's.
    """
    memo: dict[int, int] = {}

    def peak(jaxpr: Any) -> int:
        jaxpr = unwrap(jaxpr)
        key = id(jaxpr)
        if key in memo:
            return memo[key]
        memo[key] = 0                    # cycle/diamond guard
        last: dict[Any, int] = {}
        n = len(jaxpr.eqns)
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if isinstance(v, jcore.Var):
                    last[v] = i
        for v in jaxpr.outvars:
            if isinstance(v, jcore.Var):
                last[v] = n
        live = 0
        alive: set = set()

        def birth(v: Any) -> int:
            if isinstance(v, jcore.Var) and v in last and v not in alive:
                alive.add(v)
                return _aval_bytes(v)
            return 0

        for v in (*jaxpr.invars, *jaxpr.constvars):
            live += birth(v)
        best = live
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.outvars:
                live += birth(v)
            sub_peak = 0
            if eqn.primitive.name != "pallas_call":
                sub_peak = max((peak(sub) for _, sub in sub_jaxprs(eqn)),
                               default=0)
            best = max(best, live + sub_peak)
            for v in set(x for x in eqn.invars if isinstance(x, jcore.Var)) \
                    | set(eqn.outvars):
                if v in alive and last.get(v, -1) <= i:
                    alive.discard(v)
                    live -= _aval_bytes(v)
        memo[key] = best
        return best

    return peak(closed)


# -------------------------------------------------- pallas memory estimate
def _ref_shape_dtype(aval: Any) -> tuple[tuple[int, ...], Any]:
    inner = getattr(aval, "inner_aval", None)
    shape = getattr(aval, "shape", None) or getattr(inner, "shape", None) \
        or ()
    dtype = getattr(aval, "dtype", None) or getattr(inner, "dtype", None)
    return tuple(int(s) for s in shape if isinstance(s, int)), dtype


def _nbytes(shape: tuple[int, ...], dtype: Any) -> int:
    size = 1
    for s in shape:
        size *= max(int(s), 1)
    try:
        return size * int(dtype.itemsize)
    except (TypeError, AttributeError):
        return size * 4


def _is_smem(aval: Any) -> bool:
    return "smem" in str(aval).lower()


def block_dims(bm: Any) -> tuple[int, ...]:
    """A block mapping's block shape as ints.  jax wraps each dimension
    in a ``Blocked``/``Element``/``Squeezed`` object carrying
    ``block_size``; a squeezed (``None``) dimension counts 1."""
    sizes = (getattr(b, "block_size", b)
             for b in tuple(getattr(bm, "block_shape", ()) or ()))
    return tuple(s if isinstance(s, int) else 1 for s in sizes)


def pallas_memory(eqn: Any) -> dict:
    """Peak VMEM/SMEM bytes one ``pallas_call`` launch needs, from its
    ``grid_mapping``: non-SMEM block mappings double-buffered, scalar
    prefetch + SMEM blocks + SMEM scratch resident for the whole launch,
    VMEM scratch single-buffered."""
    gm = eqn.params.get("grid_mapping")
    body = eqn.params.get("jaxpr")
    name_info = eqn.params.get("name_and_src_info")
    out = {"kernel": str(name_info) if name_info is not None else "<kernel>",
           "grid": [], "vmem_bytes": 0, "smem_bytes": 0}
    if gm is None or body is None:
        return out
    out["grid"] = [int(g) if isinstance(g, int) else str(g)
                   for g in tuple(getattr(gm, "grid", ()) or ())]
    vmem = smem = 0
    for bm in tuple(getattr(gm, "block_mappings", ()) or ()):
        block = block_dims(bm)
        dtype = getattr(getattr(bm, "array_aval", None), "dtype", None)
        nb = _nbytes(block, dtype)
        if _is_smem(getattr(bm, "block_aval", "")):
            smem += nb
        else:
            vmem += 2 * nb              # pipelined: double-buffered
    n_pf = int(getattr(gm, "num_index_operands", 0) or 0)
    n_in = int(getattr(gm, "num_inputs", 0) or 0)
    n_out = int(getattr(gm, "num_outputs", 0) or 0)
    invars = tuple(unwrap(body).invars)
    for v in invars[:n_pf]:
        shape, dtype = _ref_shape_dtype(v.aval)
        smem += _nbytes(shape, dtype)
    for v in invars[n_pf + n_in + n_out:]:
        shape, dtype = _ref_shape_dtype(v.aval)
        if _is_smem(v.aval):
            smem += _nbytes(shape, dtype)
        else:
            vmem += _nbytes(shape, dtype)
    out["vmem_bytes"] = int(vmem)
    out["smem_bytes"] = int(smem)
    return out
