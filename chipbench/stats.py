"""Statistics the metric readers share."""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile of every value (linear interpolation between
    order statistics); None when there is none."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def p95(values: Iterable[float]) -> Optional[float]:
    return percentile(values, 95)


def in_window(t: float, w) -> bool:
    return w[0] < t <= w[1]


def first_token_waits(rec) -> list:
    """Every request's time from its due moment to its first token on the
    host; a request that never got one counts as waiting until the run
    ended, so that it stays in the tail."""
    return [(q["times"][0] if q["times"] else rec["t_end"]) - q["due"]
            for q in rec["requests"]]
