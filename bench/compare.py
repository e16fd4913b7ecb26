"""The numbers that decide ``correct``: counts of outputs off the reference.

Each is compared with a limit of its own, set in the cell's file from the
readings of sound runs and of the control (``PERF.md`` gives both).
"""
from __future__ import annotations

import numpy as np

from bench import reference

CLUSTERING = ("rho_rows_off", "delta_rows_off", "parent_rows_off",
              "center_diff", "label_rows_off")
DELTA_RTOL = 1e-5   # a delta is "the same" within this share: the program
                    # and the reference sum the same squares in other orders


def clustering(prog: dict, ref: dict) -> dict:
    """One clustering (rho, delta, parent, centers, labels) against the
    reference's.  Labels are compared as a partition, so cluster ids need
    not be numbered alike.  An output of another length than the
    reference's has every row off."""
    n = len(ref["rho"])
    if any(np.shape(prog[k]) != np.shape(ref[k]) for k in ref):
        return dict.fromkeys(CLUSTERING, n)
    rho = np.asarray(prog["rho"]).astype(np.int64)
    delta = np.asarray(prog["delta"], np.float64)
    rdelta = ref["delta"].astype(np.float64)
    both_inf = np.isinf(delta) & np.isinf(rdelta)
    with np.errstate(invalid="ignore"):
        close = np.abs(delta - rdelta) <= DELTA_RTOL * np.abs(rdelta)
    return {
        "rho_rows_off": int(np.sum(rho != ref["rho"])),
        "delta_rows_off": int(np.sum(~(both_inf | close))),
        "parent_rows_off": int(np.sum(np.asarray(prog["parent"])
                                      != ref["parent"])),
        "center_diff": int(np.sum(np.asarray(prog["centers"], bool)
                                  != ref["centers"])),
        "label_rows_off": reference.rows_off_partition(
            np.asarray(prog["labels"]), ref["labels"]),
    }

