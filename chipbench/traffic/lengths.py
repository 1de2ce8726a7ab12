"""Length sets shared by the traffic generators.

Every seed gets the same multiset of lengths and gaps, drawn at fixed
quantiles of the stated distribution; the seed only orders them and
fills the prompts.  So two seeds do the same work, and their spread is
that of the system, not of the draw.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """Generator for one named stream of one seed (any whole number)."""
    words = [int(seed) % (1 << 64)] + [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def lognormal_set(n: int, median: float, sigma: float, lo: int,
                  hi: int) -> np.ndarray:
    """n integer lengths at the quantiles (i + 1/2) / n of a lognormal of
    the given median and log-sigma, clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.round(median * np.exp(sigma * z))
    return np.clip(v, lo, hi).astype(np.int64)


def exponential_gaps(n: int, mean: float) -> np.ndarray:
    """n gaps at the quantiles (i + 1/2) / n of an exponential, rescaled
    so that they sum to exactly n * mean."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n * mean / g.sum())


def spec_set(spec: dict, n: int) -> np.ndarray:
    """``lognormal_set`` for a ``{"median", "sigma", "min", "max"}`` spec."""
    return lognormal_set(n, spec["median"], spec["sigma"], spec["min"],
                         spec["max"])
