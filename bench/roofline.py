"""Bytes of the block-sparse sweep, from its worklist, and the chip's least
time for them.

The sweep visits each kept tile pair once: a row tile of ``block_n`` points
against a column tile of ``block_m`` points in ``d`` dimensions.  Each kept
pair streams its column tile from HBM, the coordinates twice (as
``(block_m, d)`` rows for the ranking and as ``(d, block_m)`` columns for
the direct-difference count), 4 bytes each; each row tile is read once (its
coordinates) and written once (its count and its ``topk`` nearest
candidates as value and index), 4 bytes each.  These are the bytes the
work needs; padding that a layout adds is not counted.

Only the memory side of the roofline is held against a peak.  Per point
pair the kernel's work is mostly float32 VPU work: the exactly rounded
squares of ``kernels/ref.sq_sum_parts`` (about ten operations a
dimension), the d_cut compare and count, the expanded form's sum, and
``topk`` rounds of extract-min over the tile for the kept candidates,
beside one ``(block_n, d) x (d, block_m)`` matmul at
``Precision.HIGHEST`` on the MXU.  No VPU peak is published for v5e, and
the FLOP/s in ``peaks.json`` is the MXU's bfloat16 rate, so the compute
side has no sound peak: the least time is that of the bytes alone.
"""
from __future__ import annotations


def sweep_bytes(kept_pairs: float, row_tiles: int, *, d: int, block_n: int,
                block_m: int, topk: int) -> float:
    col_bytes = 2 * block_m * d * 4
    row_bytes = block_n * (d + 1 + 2 * topk) * 4
    return kept_pairs * col_bytes + row_tiles * row_bytes


def least_time(nbytes: float, peaks: dict) -> float:
    """Seconds in which the chip's HBM moves ``nbytes``."""
    return nbytes / peaks["hbm_bytes_per_s"]
