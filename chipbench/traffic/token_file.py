"""Training batches for a token file: uniform ids over the vocabulary.

Parameters: ``batch``, ``seq``, ``batches`` (how many distinct batches the
file holds; the reader wraps round them).
"""
from __future__ import annotations

import numpy as np

from chipbench.traffic.lengths import rng


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> np.ndarray:
    """(batches, batch, seq + 1) int32 token ids."""
    shape = (traffic["batches"], traffic["batch"], traffic["seq"] + 1)
    return rng(seed, "tokens").integers(0, config["vocab_size"], shape,
                                        dtype=np.int32)
