"""Pallas TPU flash-decode: one-token attention against a head-major cache.

Serving hot path: q (B, KH, G, hd) attends to a (B, KH, S, hd) cache (the
framework's head-major decode layout — no relayout between the cache DUS
and this kernel).  Grid (B, KH, ns) with the sequence dimension innermost:
the online-softmax carry (m, l, acc) persists in VMEM scratch across
sequence blocks, and blocks entirely past ``cur_len`` are skipped with
``pl.when`` — the §6 partitioning of the cache into EW stripes, walked
sequentially per (batch, kv-head).

``cur_len`` (tokens valid in the cache, including the just-inserted one)
arrives as a (1, 1) int32 array broadcast to every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

NEG_INF = -1e30


def _decode_kernel(cur_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block_s: int, num_blocks: int, scale: float,
                   window: int):
    j = pl.program_id(2)
    cur = cur_ref[0, 0]                                # valid entries

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = j * block_s
    run = base < cur                                   # §6 stripe skip
    if window > 0:
        run = jnp.logical_and(run, base + block_s > cur - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (block_s, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                   # (G, block_s)
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = pos < cur
        if window > 0:
            mask = jnp.logical_and(mask, pos >= cur - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 cur_len: jax.Array, *, window: int = 0,
                 block_s: int | None = None,
                 interpret: bool = False) -> jax.Array:
    """q: (B, KH, G, hd); caches: (B, KH, S, hd); cur_len: () int32.

    Returns (B, KH, G, hd_v).  cur_len counts valid cache entries
    (the new token must already be written at cur_len − 1).
    ``block_s=None`` asks the autotuner for a pow2 divisor of the cache
    length sized to the VMEM budget.
    """
    b, kh, g, hd = q.shape
    s = k_cache.shape[2]
    hd_v = v_cache.shape[-1]
    if block_s is None:
        block_s = autotune.plan_decode(
            s, g, hd, hd_v, q.dtype.itemsize * 8,
            backend="interpret" if interpret else "tpu")
    block_s = min(block_s, s)
    assert s % block_s == 0
    ns = s // block_s
    scale = 1.0 / np.sqrt(hd)
    cur = jnp.reshape(cur_len.astype(jnp.int32), (1, 1))

    kernel = functools.partial(_decode_kernel, block_s=block_s,
                               num_blocks=ns, scale=scale, window=window)
    return pl.pallas_call(
        kernel,
        grid=(b, kh, ns),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bb, hh, jj: (0, 0)),
            pl.BlockSpec((1, 1, g, hd), lambda bb, hh, jj: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda bb, hh, jj: (bb, hh, jj, 0)),
            pl.BlockSpec((1, 1, block_s, hd_v),
                         lambda bb, hh, jj: (bb, hh, jj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd_v),
                               lambda bb, hh, jj: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cur, q, k_cache, v_cache)
