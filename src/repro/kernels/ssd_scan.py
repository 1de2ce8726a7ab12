"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (B, H, nc) with the chunk dimension innermost (sequential): the
carried state (P × N) lives in VMEM scratch across chunk steps — the §6
"partition + carried event" pattern on the time axis.  Per chunk the
intra-block term is two MXU matmuls ((Q×N)·(N×Q) and (Q×Q)·(Q×P)) plus the
state in/out projections; all compute in fp32.

Layouts:
  x:  (B, H, S, P)    dt: (B, H, S)   A: (H,), whole in SMEM
  B/C: (B, S, N)      out: (B, H, S, P), final state (B, H, P, N)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, st_out_ref,
                state_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)      # (1, Q)
    a = a_ref[pl.program_id(1)]                     # scalar A_h (SMEM)
    bmat = b_ref[0].astype(jnp.float32)            # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)            # (Q, N)

    # Prefix sums of da = dt·A as masked row/column reductions over the
    # (Q, Q) tile (Mosaic has no cumsum): cum as a column (per query row)
    # and as a row (per key column), both ≤ 0 and decreasing.
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    dt_lanes = jnp.broadcast_to(dt_row, (chunk, chunk))      # [q, t] = dt_t
    dt_col = jnp.sum(jnp.where(rows == cols, dt_lanes, 0.0), axis=1,
                     keepdims=True)                          # (Q, 1)
    dt_subl = jnp.broadcast_to(dt_col, (chunk, chunk))       # [s, t] = dt_s
    cum_col = a * jnp.sum(jnp.where(causal, dt_lanes, 0.0), axis=1,
                          keepdims=True)                     # (Q, 1)
    cum_row = a * jnp.sum(jnp.where(causal, 0.0, dt_subl), axis=0,
                          keepdims=True) + a * dt_row        # (1, Q)
    total = a * jnp.sum(dt_row, axis=1, keepdims=True)       # (1, 1)

    # intra-chunk: att[q, t] = (C_q · B_t) * exp(cum_q - cum_t) * dt_t, t ≤ q
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    decay = jnp.exp(cum_col - cum_row)
    att = jnp.where(causal, cb * decay * dt_row, 0.0)
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # off-diagonal: y += exp(cum_q) * C_q @ state_prev^T   (state: (P, N))
    prev = state_ref[...]                           # (P, N)
    y = y + jnp.exp(cum_col) * jax.lax.dot_general(
        cmat, prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: state = exp(total) * prev + Σ_t exp(total - cum_t) dt_t x_t B_t
    w = jnp.exp(total - cum_col) * dt_col           # (Q, 1)
    xw = x * w                                      # (Q, P)
    new_contrib = jax.lax.dot_general(xw, bmat, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(total) * prev + new_contrib     # (P, N)

    o_ref[0, 0] = y.astype(o_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _finalize():
        st_out_ref[0, 0] = state_ref[...].astype(st_out_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B,H,S,P); dt: (B,H,S); A: (H,); B/C: (B,S,N).

    Returns (y (B,H,S,P), final_state (B,H,P,N)).
    """
    b, h, s, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    dt3 = dt.reshape(b, h, 1, s)                    # 2D-iota-friendly block
    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)

    y, st = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bb, hh, cc: (bb, hh, 0, cc)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt3, A.astype(jnp.float32), B, C)
    return y, st
