"""The comparisons that decide ``correct``: each returns
``{name: (value, limit)}`` with the limits of the cell's limits file
(``bench/limits/<workload>.json``).  A run is correct when every value
is finite and at most its limit.
"""

from __future__ import annotations

import statistics

# a leaf whose reference gradient is under this share of the median
# leaf's is nought to rounding (its change under Adam is round-off):
# it takes no part in the comparison of norms
NOUGHT = 1e-3


def norm_gap(prog: dict, ref: dict, keep) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = statistics.median(ref[n] for n in keep)
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in keep)


def kept_leaves(ref_grad: dict) -> list[str]:
    med = statistics.median(ref_grad.values())
    return [n for n, v in ref_grad.items() if v >= NOUGHT * med]


def train_readings(prog: dict, ref: dict) -> dict:
    """loss_gap: worst step's |loss - reference| / reference;
    grad_gap and change_gap: ``norm_gap`` of the first gradient and of
    the change over the checked steps."""
    keep = kept_leaves(ref["grad_norm"])
    return {
        "loss_gap": max(abs(p - r) / abs(r)
                        for p, r in zip(prog["loss"], ref["loss"])),
        "grad_gap": norm_gap(prog["grad_norm"], ref["grad_norm"], keep),
        "change_gap": norm_gap(prog["change_norm"], ref["change_norm"], keep),
    }


def compare_train(prog: dict, ref: dict, limits: dict) -> dict:
    readings = train_readings(prog, ref)
    return {k: (v, limits[k]) for k, v in readings.items() if k in limits}
