"""Kept tile pairs per fit: the program's ``worklist_len`` gauge at each
worklist build of a fit, summed over the fit, averaged over the window."""
UNIT = "pairs"


def read(run):
    fits = [c for c in run.calls if c["kind"] == "fit"]
    kept = [sum(c.get("worklist_len", [])) for c in fits]
    return sum(kept) / len(kept) if fits and any(kept) else None
