"""The comparison that decides ``correct``: each number the check compares,
beside the limit ``bench/limits/<cell>.json`` gives it.

Training (``training``), from readings of the program and of the reference
after the same first steps on the same batches:

    loss_gap    the largest |loss - reference loss| / |reference loss| over
                the checked steps
    grad_gap    the worst leaf's |norm - reference norm| of the first
                gradient as the optimizer got it
    change_gap  the worst leaf's |norm - reference norm| of its change over
                the checked steps

A leaf's gap is measured against the reference's norm of that leaf or of
the median leaf, whichever is larger. Leaves whose reference gradient is
under a thousandth of the median leaf's are left out of both: they move by
round-off alone. A leaf the program does not report counts as a gap of 1.

Serving (``serving``): for each checked served token, the gap by which the
reference's logit of that token lies below the reference's best logit at
its position; ``max_gap`` is the widest.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List

LEAF_FLOOR = 1e-3       # of the median leaf's reference gradient norm


def counted_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= LEAF_FLOOR * med]


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: List[str]) -> float:
    med = statistics.median(ref[k] for k in leaves)
    worst = 0.0
    for k in leaves:
        v = prog.get(k)
        gap = 1.0 if v is None else abs(v - ref[k]) / max(ref[k], med)
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
    return worst


def training_gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    leaves = counted_leaves(ref["grad"])
    pl, rl = prog["losses"], ref["losses"]
    loss = (max(abs(a - b) / abs(b) for a, b in zip(pl, rl))
            if len(pl) == len(rl) and all(map(math.isfinite, pl))
            else math.inf)
    return {"loss_gap": loss,
            "grad_gap": leaf_gap(prog["grad"], ref["grad"], leaves),
            "change_gap": leaf_gap(prog["change"], ref["change"], leaves)}


def with_limits(values: Dict[str, float],
                limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()
            if k in limits}


def training(prog: Dict, ref: Dict, limits: Dict[str, float]):
    return with_limits(training_gaps(prog, ref), limits)


def serving(max_gap: float, limits: Dict[str, float]):
    return with_limits({"max_gap": max_gap}, limits)
