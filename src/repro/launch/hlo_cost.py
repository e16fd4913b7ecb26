"""Trip-count-aware cost analysis of compiled (SPMD-partitioned) HLO text.

Why this exists: ``compiled.cost_analysis()`` counts a while-loop body ONCE,
not multiplied by its trip count, so any scan-based program (layer scans,
microbatch accumulation, chunked attention/CE — i.e. every real LM program)
is undercounted by orders of magnitude.  XLA annotates each ``while`` with
``backend_config={"known_trip_count":{"n":...}}``, so the fix is a recursive
walk of the computation graph that multiplies child-computation costs by
their trip counts.

Per instruction:
* flops:  dot = 2 * prod(batch) * M * N * K (from the dot dnums in the text);
          listed elementwise/reduce ops = result (or input) element count —
          the same convention as XLA's HloCostAnalysis.
* bytes:  operands + results of every top-level instruction except free ops
          (parameter/tuple/get-tuple-element/constant/bitcast).  Fusions are
          counted at the call boundary only — exactly the HBM-traffic view,
          since fused internals never round-trip to memory.
* collectives: payload bytes per kind (all-gather counts its gathered
          output; others their tensor size), multiplied through loops.

The result is a per-device cost (the partitioned module is the per-device
program).  Used by launch/dryrun.py and benchmarks/roofline.py.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
    "s4": 1, "u4": 1, "token": 0, "opaque": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# ops whose cost is ~1 flop per output element (XLA convention)
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "tanh", "sqrt", "rsqrt", "power",
    "floor", "ceil", "sign", "compare", "select", "and", "or", "not", "xor",
    "atan2", "expm1", "log1p", "logistic", "cbrt", "erf", "remainder",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "clamp", "round-nearest-afz", "round-nearest-even", "cosine", "sine",
    "tan",
}
_FREE = {"parameter", "tuple", "get-tuple-element", "constant", "bitcast",
         "after-all", "partition-id", "replica-id", "opt-barrier"}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*"
    r"((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*))\s+"
    r"([\w\-]+)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_CALLS_RE = re.compile(r"calls=(%[\w.\-]+)")
_WHILE_RE = re.compile(r"condition=(%[\w.\-]+),\s*body=(%[\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_OPERAND_RE = re.compile(r"(%[\w.\-]+)")
_DOT_DIMS = {
    "lhs_contracting_dims": re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}"),
    "rhs_contracting_dims": re.compile(r"rhs_contracting_dims=\{([0-9,]*)\}"),
    "lhs_batch_dims": re.compile(r"lhs_batch_dims=\{([0-9,]*)\}"),
    "rhs_batch_dims": re.compile(r"rhs_batch_dims=\{([0-9,]*)\}"),
}


def _shape_elems_bytes(type_str: str):
    elems, total = 0, 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        total += n * _DTYPE_BYTES[dtype]
    return elems, total


def _first_shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclass
class Cost:
    flops: float = 0.0
    dot_flops: float = 0.0
    bytes: float = 0.0
    coll: dict = field(default_factory=dict)
    unknown_trips: int = 0

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += mult * other.flops
        self.dot_flops += mult * other.dot_flops
        self.bytes += mult * other.bytes
        for k, v in other.coll.items():
            self.coll[k] = self.coll.get(k, 0.0) + mult * v
        self.unknown_trips += other.unknown_trips


@dataclass
class _Instr:
    name: str
    type_str: str
    opcode: str
    rest: str          # everything after the '('
    is_root: bool = False


def _parse_computations(text: str):
    comps: dict[str, list[_Instr]] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        hdr = (_COMP_HDR_RE.match(line)
               if "{" in line and not line.startswith(" ") else None)
        if hdr:
            cur = hdr.group(2)
            comps[cur] = []
            if hdr.group(1):
                entry = cur
            continue
        if line.startswith("}") or line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if m:
            comps[cur].append(_Instr(m.group(1), m.group(2), m.group(3),
                                     m.group(4),
                                     is_root="ROOT" in line[:12]))
    return comps, entry


def _dot_flops(instr: _Instr, types: dict) -> float:
    ops = _OPERAND_RE.findall(instr.rest.split("),")[0] + ")")
    if len(ops) < 2:
        return 0.0
    lhs_t = types.get(ops[0], "")
    lhs = _first_shape_dims(lhs_t)
    dims = {}
    for k, rx in _DOT_DIMS.items():
        m = rx.search(instr.rest)
        dims[k] = ([int(x) for x in m.group(1).split(",") if x] if m else [])
    out = _first_shape_dims(instr.type_str)
    contract = 1
    for i in dims["lhs_contracting_dims"]:
        if i < len(lhs):
            contract *= lhs[i]
    out_elems = 1
    for d in out:
        out_elems *= d
    return 2.0 * out_elems * contract


def _root_opcode(instrs) -> str | None:
    for ins in instrs:
        if ins.is_root:
            return ins.opcode
    return instrs[-1].opcode if instrs else None


def _operands(ins: _Instr):
    return _OPERAND_RE.findall(ins.rest.split("),")[0] + ")")


_SPARSE_READS = ("dynamic-slice", "slice", "gather")


def _fusion_bytes(inner_instrs, opnd_names, outer_types, result_type) -> float:
    """HBM traffic of one fusion, XLA-HloCostAnalysis style.

    Reads: each fusion operand is charged at full size UNLESS every internal
    use is a slice/gather (charged at sliced size) or the buffer operand of
    a dynamic-update-slice (in-place: no read).  Writes: the result, except
    a root DUS writes only its update region.  Internal intermediates stay
    in registers/VMEM and are free.
    """
    # DUS-emulation fusions: XLA CPU lowers a bf16 dynamic-update-slice as
    # convert(f32) -> DUS -> convert(bf16) over the WHOLE buffer.  On TPU
    # this is a native in-place row write, so charge only the update region
    # (2x: read update + write region).
    passthrough = {"convert", "copy", "bitcast", "reshape", "transpose",
                   "parameter", "constant"}
    nonfree = [i for i in inner_instrs if i.opcode not in passthrough]
    if (len(nonfree) == 1
            and nonfree[0].opcode == "dynamic-update-slice"):
        inner_types = {i.name: i.type_str for i in inner_instrs}
        ops_d = _operands(nonfree[0])
        if len(ops_d) >= 2:
            upd = _shape_elems_bytes(inner_types.get(ops_d[1], ""))[1]
            if upd:
                return 2.0 * upd

    params_by_idx = {}
    for ii in inner_instrs:
        if ii.opcode == "parameter":
            try:
                idx = int(ii.rest.split(")")[0])
            except ValueError:
                continue
            params_by_idx[idx] = ii.name

    read = 0.0
    for idx, opn in enumerate(opnd_names):
        full = _shape_elems_bytes(outer_types.get(opn, ""))[1]
        pname = params_by_idx.get(idx)
        if pname is None:
            read += full
            continue
        uses = [u for u in inner_instrs if pname in _operands(u)]
        sliced = bool(uses)
        part = 0.0
        for u in uses:
            ops_u = _operands(u)
            if u.opcode in _SPARSE_READS and ops_u and ops_u[0] == pname:
                part += _shape_elems_bytes(u.type_str)[1]
            elif (u.opcode == "dynamic-update-slice" and ops_u
                  and ops_u[0] == pname):
                part += 0.0          # in-place buffer: no read
            elif u.opcode in ("bitcast", "copy", "reshape", "transpose"):
                sliced = False       # full pass-through -> full read
                break
            else:
                sliced = False
                break
        read += part if sliced else full

    root = next((i for i in inner_instrs if i.is_root),
                inner_instrs[-1] if inner_instrs else None)
    write = _shape_elems_bytes(result_type)[1]
    if root is not None and root.opcode == "dynamic-update-slice":
        ops_r = _operands(root)
        if len(ops_r) >= 2:
            inner_types = {i.name: i.type_str for i in inner_instrs}
            write = _shape_elems_bytes(inner_types.get(ops_r[1], ""))[1]
    return read + write


def analyze(text: str) -> Cost:
    comps, entry = _parse_computations(text)
    if entry is None:
        # fall back: biggest computation named main
        entry = next((n for n in comps if "main" in n), None)
        if entry is None:
            return Cost()
    memo: dict[str, Cost] = {}

    def comp_cost(name: str) -> Cost:
        if name in memo:
            return memo[name]
        memo[name] = Cost()          # guard against cycles
        total = Cost()
        instrs = comps.get(name, [])
        types = {i.name: i.type_str for i in instrs}
        for ins in instrs:
            op = ins.opcode
            if op in _FREE:
                continue
            elems, byts = _shape_elems_bytes(ins.type_str)
            # operand bytes
            opnd_names = _OPERAND_RE.findall(ins.rest.split("),")[0] + ")")
            opnd_bytes = sum(_shape_elems_bytes(types.get(o, ""))[1]
                             for o in opnd_names)
            base = op.removesuffix("-start")
            is_coll = next((k for k in _COLLECTIVES
                            if base == k or base.startswith(k + ".")), None)
            if op.endswith("-done"):
                continue
            if is_coll:
                total.coll[is_coll] = total.coll.get(is_coll, 0.0) + byts
                total.bytes += byts + opnd_bytes
                continue
            if op == "while":
                m = _WHILE_RE.search(ins.rest)
                trip = None
                tm = _TRIP_RE.search(ins.rest)
                if tm:
                    trip = int(tm.group(1))
                if m:
                    body, cond = m.group(2), m.group(1)
                    if trip is None:
                        trip = 1
                        total.unknown_trips += 1
                    total.add(comp_cost(body), trip)
                    total.add(comp_cost(cond), trip + 1)
                continue
            if op == "fusion":
                cm = _CALLS_RE.search(ins.rest)
                if cm:
                    inner = comp_cost(cm.group(1))
                    total.flops += inner.flops
                    total.dot_flops += inner.dot_flops
                    for k, v in inner.coll.items():
                        total.coll[k] = total.coll.get(k, 0.0) + v
                    total.bytes += _fusion_bytes(
                        comps.get(cm.group(1), []), opnd_names, types,
                        ins.type_str)
                    continue
                # fusion bytes = call-boundary traffic only
                total.bytes += byts + opnd_bytes
                continue
            if op in ("call", "conditional", "sort", "map", "reduce",
                      "reduce-window", "scatter", "select-and-scatter"):
                for cm in re.finditer(
                        r"(?:to_apply|calls)=(%[\w.\-]+)", ins.rest):
                    # applied computations are per-element; charge once per
                    # output element for reduce-likes via the elementwise rule
                    pass
                if op == "conditional":
                    branches = re.search(
                        r"branch_computations=\{([^}]*)\}", ins.rest)
                    if branches:
                        subs = [comp_cost(b.strip()) for b in
                                branches.group(1).split(",")]
                        if subs:
                            big = max(subs, key=lambda c: c.flops + c.bytes)
                            total.add(big)
                if op == "call":
                    cm = re.search(r"to_apply=(%[\w.\-]+)", ins.rest)
                    if cm:
                        total.add(comp_cost(cm.group(1)))
                total.bytes += byts + opnd_bytes
                total.flops += elems
                continue
            if op == "dot" or op == "convolution":
                f = _dot_flops(ins, types)
                total.flops += f
                total.dot_flops += f
                total.bytes += byts + opnd_bytes
                continue
            if op in _ELEMENTWISE:
                total.flops += elems
                total.bytes += byts + opnd_bytes
                continue
            if op in ("dynamic-slice", "slice", "gather"):
                # sparse reads: only the produced elements are touched
                # (+ indices, negligible) — NOT the whole operand
                total.bytes += 2.0 * byts
                continue
            if op in ("dynamic-update-slice", "scatter"):
                # sparse writes: only the update region is read + written
                op_sizes = [_shape_elems_bytes(types.get(o, ""))[1]
                            for o in opnd_names]
                small = sum(op_sizes) - (max(op_sizes) if op_sizes else 0)
                total.bytes += 2.0 * small
                continue
            if op == "custom-call":
                # CPU oneDNN matmul rewrites etc.: charge bytes; flops only
                # if it looks like a matmul (documented limitation)
                total.bytes += byts + opnd_bytes
                continue
            # everything else (copy, broadcast, reshape, slice, dus, iota,
            # gather, concatenate, pad, reduce, transpose, rng, convert...)
            total.bytes += byts + opnd_bytes
        memo[name] = total
        return total

    return comp_cost(entry)


def analyze_compiled(compiled) -> dict:
    c = analyze(compiled.as_text())
    return {
        "flops": c.flops,
        "dot_flops": c.dot_flops,
        "bytes": c.bytes,
        "collectives": {"bytes": dict(c.coll),
                        "total_bytes": float(sum(c.coll.values()))},
        "unknown_trips": c.unknown_trips,
    }
