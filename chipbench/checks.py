"""The numbers the correctness check compares."""
from __future__ import annotations

import statistics
from typing import Dict

NEGLIGIBLE = 1e-3      # of the median leaf's reference gradient norm


def norm_gaps(prog: Dict[str, float], ref: Dict[str, float],
              grad_ref: Dict[str, float]) -> float:
    """Worst leaf's gap between the program's and the reference's norm,
    over the larger of that leaf's reference norm and the median leaf's.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out: they move under Adam by round-off alone."""
    if set(prog) != set(ref):
        raise ValueError("program and reference hold different leaves")
    g_med = statistics.median(grad_ref.values())
    r_med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], r_med)
               for k in ref if grad_ref[k] >= NEGLIGIBLE * g_med)


def logit_gap(ref_logits, tokens) -> float:
    """Widest gap by which a chosen token's reference logit lies below the
    reference's best at its position.  ref_logits (n, V), tokens (n,)."""
    import numpy as np
    lg = np.asarray(ref_logits, np.float64)
    pick = lg[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(lg.max(axis=1) - pick))
