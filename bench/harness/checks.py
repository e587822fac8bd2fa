"""The numbers the correctness check compares.

Training (the first three steps of the timed path against the float32
reference):

* ``loss_gap``   — the largest |program loss - reference loss| over the
  steps;
* ``grad_gap``   — the worst leaf's |‖g_program‖ - ‖g_reference‖| of the
  first gradient, over the larger of the reference's norm of that leaf and
  of the median leaf;
* ``change_gap`` — the same for each leaf's change over the steps, leaving
  out the leaves whose reference gradient is under a thousandth of the
  median leaf's (they move by round-off alone);
* ``frozen_moved`` — how many frozen leaves moved while frozen (exact: 0).

Serving (the served tokens against the float32 reference's logits):

* ``token_gap``  — the widest gap by which a served token's reference
  logit lies below the reference's best, over the sampled requests.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def norm_gap(prog_sq: Dict[str, float], ref_sq: Dict[str, float],
             keep=None) -> float:
    """Worst leaf of |‖a‖ - ‖b‖| / max(‖b‖, median leaf ‖b‖)."""
    paths = [p for p in ref_sq if keep is None or p in keep]
    if not paths:
        return float("inf")
    ref = {p: float(np.sqrt(ref_sq[p])) for p in paths}
    med = float(np.median(list(ref.values())))
    worst = 0.0
    for p in paths:
        if p not in prog_sq:
            return float("inf")
        gap = abs(float(np.sqrt(prog_sq[p])) - ref[p]) / max(ref[p], med,
                                                             1e-30)
        worst = max(worst, gap)
    return worst


def moving_leaves(ref_grad_sq: Dict[str, float]) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    norms = {p: float(np.sqrt(v)) for p, v in ref_grad_sq.items()}
    med = float(np.median(list(norms.values())))
    return {p for p, n in norms.items() if n >= 1e-3 * med}
