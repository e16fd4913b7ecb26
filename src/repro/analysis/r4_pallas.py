"""R4 pallas-legality: static checks on every ``pallas_call`` instantiation.

Three invariants every kernel launch in this tree is supposed to hold, all
checkable from the ``grid_mapping`` the eqn params carry (jax 0.9:
``GridMapping`` with ``grid``, ``block_mappings`` — each a ``BlockMapping``
with ``block_shape`` / ``array_aval`` / SMEM-typed
``index_map_avals`` — ``num_index_operands``, ``num_dynamic_grid_bounds``):

* **grid/block divisibility** — callers pad arrays to block multiples
  before launching (``_pad_inf`` / ``_pad_rows`` / the ops pads); a block
  mapping whose array extent is not a block multiple means a missed pad —
  out-of-bounds tile reads on TPU, silent zero-fill differences between
  interpret and compiled modes.
* **SMEM scalar-prefetch placement** — scalar-prefetch operands
  (``num_index_operands``: the worklist meta tables driving the 1-D sweep
  grid) must be SMEM references in the index-map avals, and small enough
  to live there; a worklist table accidentally routed through VMEM/HBM
  block mappings would compile on the interpreter and fail (or crawl) on
  Mosaic.
* **static grid** — a worklist's chunk length sizes the launch grid
  (``grid = (C,)``); ``num_dynamic_grid_bounds > 0`` means a traced value
  reached the grid, i.e. a worklist was sized under a tracer (the
  ``_require_host`` contract, enforced here statically too).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

from . import absint
from .rules import Finding, Rule, register_rule

RULE_NAME = "R4-pallas-legality"


def _check_pallas_eqn(target: str, site: Any) -> list[Finding]:
    eqn = site.eqn
    gm = eqn.params.get("grid_mapping")
    out: list[Finding] = []
    where = site.where + "/pallas_call"
    name_info = eqn.params.get("name_and_src_info")
    kernel = str(name_info) if name_info is not None else "<kernel>"

    def finding(msg: str) -> None:
        out.append(Finding(rule=RULE_NAME, severity="error", target=target,
                           message=f"{kernel}: {msg}", where=where))

    if gm is None:
        finding("pallas_call eqn carries no grid_mapping param (jax "
                "version drift? — re-probe the eqn layout)")
        return out

    if int(getattr(gm, "num_dynamic_grid_bounds", 0) or 0) > 0:
        finding("dynamic grid bounds: a traced value sizes the launch "
                "grid, i.e. a worklist was sized under a tracer "
                "(_require_host contract)")

    grid = tuple(getattr(gm, "grid", ()) or ())
    for g in grid:
        if isinstance(g, int) and g < 1:
            finding(f"degenerate grid {grid}: every launch dimension "
                    f"must be >= 1")
            break

    for bm in tuple(getattr(gm, "block_mappings", ()) or ()):
        shape = tuple(getattr(getattr(bm, "array_aval", None),
                              "shape", ()) or ())
        block = absint.block_dims(bm)
        origin = getattr(bm, "origin", "?")
        if len(shape) != len(block):
            continue                    # mapped/squeezed dims: skip
        for dim, (b, s) in enumerate(zip(block, shape)):
            if isinstance(b, int) and b > 0 and isinstance(s, int) \
                    and s % b != 0:
                finding(f"block mapping for {origin}: array extent "
                        f"{s} (dim {dim}) is not a multiple of block "
                        f"{b} — caller missed the pad-to-block-multiple "
                        f"contract")

    n_idx = int(getattr(gm, "num_index_operands", 0) or 0)
    if n_idx:
        from . import limits

        smem_budget = limits.limits_for_eqn(eqn).smem_bytes
        avals = tuple(getattr(gm, "index_map_avals", ()) or ())
        # index_map avals = grid indices followed by the prefetch refs
        prefetch = avals[len(avals) - n_idx:]
        for aval in prefetch:
            text = str(aval).lower()
            if "smem" not in text:
                finding(f"scalar-prefetch operand {aval} is not an SMEM "
                        f"reference — worklist meta tables must prefetch "
                        f"into SMEM, not ride the block mappings")
            inner = getattr(aval, "inner_aval", None)
            shape = tuple(getattr(aval, "shape", ()) or
                          getattr(inner, "shape", ()) or ())
            dtype = getattr(aval, "dtype", None) or \
                getattr(inner, "dtype", None)
            size = 1
            for s in shape:
                size *= int(s)
            nbytes = size * int(getattr(dtype, "itemsize", 4) or 4)
            if nbytes > smem_budget:
                finding(f"scalar-prefetch operand {aval} is {nbytes} "
                        f"bytes — over the "
                        f"{limits.limits_for_eqn(eqn).platform} SMEM "
                        f"budget of {smem_budget} bytes (shared table "
                        f"with R9; REPRO_LIMIT_SMEM_BYTES overrides)")
    return out


@dataclass(frozen=True)
class PallasLegalityRule(Rule):
    name: str = RULE_NAME
    description: str = ("pallas_call launches: block sizes divide padded "
                        "array extents, scalar-prefetch tables are SMEM "
                        "refs, grids are host-static")
    kind: str = "jaxpr"

    def check_jaxpr(self, target: str, closed_jaxpr: Any) -> list[Finding]:
        from .walker import iter_sites

        out: list[Finding] = []
        for site in iter_sites(closed_jaxpr):
            if site.eqn.primitive.name == "pallas_call":
                out.extend(_check_pallas_eqn(target, site))
        return out


register_rule(PallasLegalityRule())
