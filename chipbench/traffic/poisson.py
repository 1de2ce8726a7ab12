"""Open-loop arrivals at a fixed rate over the window.

Parameters: ``rate`` (requests/s), ``prompt`` and ``output`` as
``{"median", "sigma", "min", "max"}`` of a lognormal.  The window of
``seconds`` holds round(rate * seconds) requests; their gaps are the
exponential's quantiles (so every seed's last request is due at the same
moment, half a mean gap before the window closes) and their lengths the
lognormals' quantiles, each set shuffled by the seed on its own.
"""
from __future__ import annotations

from typing import List

import numpy as np

from chipbench.traffic.lengths import exponential_gaps, rng, spec_set


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> List[dict]:
    n = max(1, int(round(traffic["rate"] * seconds)))
    r = rng(seed, "requests")
    gaps = r.permutation(exponential_gaps(n, 1.0 / traffic["rate"]))
    plen = r.permutation(spec_set(traffic["prompt"], n))
    gen = r.permutation(spec_set(traffic["output"], n))
    due = np.maximum(np.cumsum(gaps) - 0.5 / traffic["rate"], 0.0)
    toks = rng(seed, "prompts")
    return [{"rid": i, "due": float(due[i]), "gen": int(gen[i]),
             "prompt": toks.integers(0, config["vocab_size"], int(plen[i]),
                                     dtype=np.int32)}
            for i in range(n)]
