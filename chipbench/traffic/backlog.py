"""A backlog that never empties: every request is due at once.

Parameters: ``round`` (requests per round), ``rounds``, and ``prompt`` /
``output`` as in ``poisson``.  Each round holds the same lognormal
quantile lengths in an order of its own, so any stretch of the backlog
carries nearly the same work whatever the seed.
"""
from __future__ import annotations

from typing import List

import numpy as np

from chipbench.traffic.lengths import rng, spec_set


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> List[dict]:
    k = traffic["round"]
    r = rng(seed, "requests")
    toks = rng(seed, "prompts")
    plen0, gen0 = spec_set(traffic["prompt"], k), spec_set(traffic["output"], k)
    out = []
    for _ in range(traffic["rounds"]):
        order = r.permutation(k)
        for j in order:
            out.append({"rid": len(out), "due": 0.0, "gen": int(gen0[j]),
                        "prompt": toks.integers(0, config["vocab_size"],
                                                int(plen0[j]), dtype=np.int32)})
    return out
