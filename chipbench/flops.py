"""Operations and bytes of the work a cell needs, computed from shapes.

Counts are of the work the model requires, not of what the program
happens to do: padding, recomputation under remat and the paged path's
full-width gather are left out.  A multiply-add is two operations.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from chipbench.reference.dense import Dims


def matmul_params(dm: Dims) -> Dict[str, int]:
    """Parameters that take part in a matrix product, per token."""
    per_layer = (dm.d * (dm.h + 2 * dm.kh) * dm.hd + dm.h * dm.hd * dm.d
                 + 3 * dm.d * dm.f)
    return {"layers": dm.layers * per_layer, "unembed": dm.d * dm.vocab}


def param_count(dm: Dims) -> int:
    mp = matmul_params(dm)
    norms = (2 * dm.layers + 1) * dm.d
    embed = 0 if dm.tied else dm.vocab * dm.d
    return mp["layers"] + mp["unembed"] + norms + embed


def attn_pair_flops(dm: Dims) -> int:
    """Forward operations of one (query, key) pair over all layers:
    q.k and p.v, each 2 * hd per head."""
    return 4 * dm.h * dm.hd * dm.layers


def train_flops_per_token(dm: Dims, seq: int) -> float:
    """6 N plus causal attention forward and backward (3x the forward's
    (seq + 1) / 2 keys per query), no remat recomputation."""
    mp = matmul_params(dm)
    n = mp["layers"] + mp["unembed"]
    return 6.0 * n + 3.0 * attn_pair_flops(dm) * (seq + 1) / 2.0


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def flash_fwd(b: int, h: int, kh: int, s: int, hd: int,
              itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) of one causal flash forward call: q.k and p.v
    over the causal pairs; q, k, v read once, out written, lse written."""
    ops = 4.0 * b * h * hd * causal_pairs(s)
    by = (2 * b * h * s * hd + 2 * b * kh * s * hd) * itemsize + 4 * b * h * s
    return ops, float(by)


def flash_bwd(b: int, h: int, kh: int, s: int, hd: int,
              itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) of one causal flash backward: q.k recomputed,
    dO.v, p^T.dO, dS.k and dS^T.q (five products) over the causal pairs;
    q, k, v, dO read, dq, dk, dv written, lse and delta read."""
    ops = 10.0 * b * h * hd * causal_pairs(s)
    by = (3 * b * h * s * hd + 4 * b * kh * s * hd) * itemsize + 8 * b * h * s
    return ops, float(by)


def least_time(ops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    return max(ops / peak_flops, nbytes / peak_bw)


def decode_step(dm: Dims, ctx_lens: Iterable[int],
                weight_itemsize: int, kv_itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) one decode step needs for the active rows,
    each attending over ``ctx`` cached tokens plus its new one: every
    weight read once, each row's live keys and values read once."""
    ctx = list(ctx_lens)
    mp = matmul_params(dm)
    rows = len(ctx)
    keys = sum(c + 1 for c in ctx)
    ops = 2.0 * (mp["layers"] + mp["unembed"]) * rows + attn_pair_flops(dm) * keys
    wbytes = (mp["layers"] + mp["unembed"]) * weight_itemsize
    kvbytes = keys * 2 * dm.layers * dm.kh * dm.hd * kv_itemsize
    return ops, float(wbytes + kvbytes)


def prefill_flops(dm: Dims, plen: int) -> float:
    """Operations of a prefill at the prompt's true length, with logits
    for its last position only."""
    mp = matmul_params(dm)
    return (2.0 * mp["layers"] * plen + 2.0 * mp["unembed"]
            + attn_pair_flops(dm) * causal_pairs(plen))


def decode_token_flops(dm: Dims, ctx: int) -> float:
    """Operations of one decoded token attending over ``ctx`` + 1 keys."""
    mp = matmul_params(dm)
    return 2.0 * (mp["layers"] + mp["unembed"]) + attn_pair_flops(dm) * (ctx + 1)
