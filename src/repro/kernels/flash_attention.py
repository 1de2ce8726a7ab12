"""Pallas TPU flash attention (causal, GQA, sliding window) — differentiable.

TPU adaptation of the paper's §6 data-block partitioning at the memory
hierarchy: the (S × S) attention computation is partitioned into disjoint
(block_q × block_k) tiles; each grid step acquires its q-tile "EW" in VMEM
while streaming k/v tiles HBM→VMEM.  The online-softmax carry (m, l, acc)
lives in VMEM scratch and persists across the sequential innermost grid
dimension (TPU grids execute in order), exactly the inter-chunk state carry
pattern the paper expresses with partitions + events.

Block sizes are no longer constants: every call plans its tiles through
``kernels.autotune.plan_attention`` (VMEM footprint + edge-tile waste +
grid-step cost), unless the caller pins them.  Two structural choices ride
the plan:

* **GQA head folding** — queries live in a (B, KH, G, S, hd) layout and a
  grid step loads ``g_fold`` query heads of one kv head as a single
  (gf·bq, hd) tile, so the folded heads share the streamed k/v tile and
  their MACs batch into one dot.
* **Fused backward** — when dk/dv for the whole (padded) kv sequence fit
  the VMEM budget, backward is ONE kernel on grid (B, KH, nq, nk)
  computing dq, dk and dv per tile visit: dq accumulates in scratch
  (flushed when the k loop finishes), dk/dv accumulate into full-length
  revisited output blocks.  This recomputes the probability tile once
  instead of once per pass — ~30 % fewer MACs than the dq-pass + dkv-pass
  split, which remains as the fallback for long sequences.

All kernels take the global ``q_offset`` as a scalar-prefetch operand (the
context-parallel stripe origin under ``repro.dist.flash``'s shard_map —
a traced ``axis_index`` product), so the causal/window masks and the
block-level ``pl.when`` skips stay globally positioned in both directions.

Layouts (chosen for MXU alignment):
  q:    (B, H, S, hd) public → (B, KH, G, S, hd) internal
  k, v: (B, KH, S, hd)
  lse, delta: (B, KH, G, S, 1) — one value per row, kept on sublanes so a
  (g, bq, 1) block merges into the (g·bq, 1) column the tiles broadcast
  against without a lane→sublane relayout (Mosaic refuses that cast).
Causal tiles with j·bk > (i+1)·bq are skipped with ``pl.when`` — no wasted
MXU work, unlike the masked jnp oracle.  Sequence lengths that do not
divide the block sizes are zero-padded at the edge and masked via the
static ``kv_len`` bound (the §6 masked-edge-tile treatment
``multi_partition_copy`` uses for ragged ranges).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.autotune import AttnPlan

NEG_INF = -1e30


def _tile_mask(q_start, k_start, gf: int, block_q: int, block_k: int,
               causal: bool, window: int, kv_len: int, sk_padded: int):
    """(gf·block_q, block_k) boolean mask for one folded tile, or None
    when every element is live.  ``q_start`` is the tile's *global* first
    row (traced: it includes the scalar-prefetched stripe offset); the
    ``gf`` folded heads share row positions, so the (block_q, block_k)
    mask tiles along the fold axis."""
    if not (causal or window > 0 or kv_len < sk_padded):
        return None
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = cols <= rows
    if window > 0:
        mask = jnp.logical_and(mask, rows - cols < window)
    if kv_len < sk_padded:
        mask = jnp.logical_and(mask, cols < kv_len)
    if gf > 1:
        mask = jnp.tile(mask, (gf, 1))
    return mask


def _tile_run(q_start, k_start, block_q: int, block_k: int,
              causal: bool, window: int, kv_len: int, sk_padded: int):
    """Block-level ``pl.when`` predicate: False only if the whole tile is
    provably masked (the §6 tile-skip — no wasted MXU work)."""
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window > 0:
        run = jnp.logical_and(run,
                              q_start - (k_start + block_k - 1) < window)
    if kv_len < sk_padded:
        run = jnp.logical_and(run, jnp.bool_(k_start < kv_len))
    return run


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _additive_mask(offs, gf: int, block_q: int, block_k: int, causal: bool,
                   window: int, kv_len: int, sk_padded: int):
    """Precomputed additive mask (0 / NEG_INF) for single-tile grids,
    built OUTSIDE the kernel: one (gf·bq, bk) f32 array shared by every
    grid step (and constant-folded by XLA when the offset is static)
    replaces the per-step iota/compare/select chain.  Masked lanes then
    vanish through exp underflow — ``exp(x + NEG_INF − m) == 0`` — the
    same convention the jnp twin uses."""
    rows = offs[0] + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = cols <= rows
    if window > 0:
        mask = jnp.logical_and(mask, rows - cols < window)
    if kv_len < sk_padded:
        mask = jnp.logical_and(mask, cols < kv_len)
    amask = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
    if gf > 1:
        amask = jnp.tile(amask, (gf, 1))
    return amask


# ---------------------------------------------------------------- forward

def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, *rest,
                gf: int, block_q: int, block_k: int, num_kv_blocks: int,
                causal: bool, window: int, scale: float, kv_len: int,
                with_lse: bool, premask: bool):
    single = num_kv_blocks == 1
    if premask:
        mask_ref, *rest = rest
    o_ref, *rest = rest
    if single:
        lse_ref = rest[0] if with_lse else None
    elif with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    i = pl.program_id(2)
    j = pl.program_id(3)
    sk_padded = num_kv_blocks * block_k
    rows = gf * block_q
    hd_v = v_ref.shape[-1]

    q_start = i * block_q + off_ref[0]          # global row of tile row 0
    k_start = j * block_k

    def _tile_s():
        # fold scale into the q tile: (gf·bq, hd) multiplies instead of
        # (gf·bq, bk) on the logits
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1]).astype(
            jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        s = _dot(q, k, ((1,), (1,)))                   # (gf·bq, bk)
        if premask:
            s = s + mask_ref[...]
        else:
            mask = _tile_mask(q_start, k_start, gf, block_q, block_k,
                              causal, window, kv_len, sk_padded)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
        return s

    if single:
        # one kv tile: plain softmax, no carry scratch, no rescale.
        # Masked lanes vanish via exp underflow (twin convention).
        s = _tile_s()
        v = v_ref[0, 0].astype(jnp.float32)
        m = s.max(axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(p.sum(axis=1, keepdims=True), 1e-37)
        o = _dot(p, v, ((1,), (0,))) / l
        o_ref[0, 0] = o.reshape(gf, block_q, hd_v).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = (m + jnp.log(l)).reshape(gf, block_q, 1)
        return

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = _tile_run(q_start, k_start, block_q, block_k, causal, window,
                    kv_len, sk_padded)

    @pl.when(run)
    def _compute():
        s = _tile_s()
        v = v_ref[0, 0].astype(jnp.float32)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(p, v, ((1,), (0,)))
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l).reshape(
            gf, block_q, hd_v).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).reshape(
                gf, block_q, 1)


# ---- megakernels: grid (1,), whole arrays as blocks, one batched dot
# over (B, KH) per matmul.  One flat XLA computation, so the softmax
# elementwise chain runs at flat speed instead of the ~4x in-loop
# penalty a multi-step interpret grid pays, and the (B, KH) slices
# batch into single dot_generals instead of a grid dimension.  The
# planner picks this at shapes where the full (padded+masked) matrix
# costs less than the grid's per-step overheads.

def _mega_amask(off_ref, g: int, sq: int, sk: int, causal: bool,
                window: int, kv_len: int):
    """(g·sq, sk) additive mask shared by every (batch, kv head) slice
    (rows are global: stripe offset applies), or None when everything is
    live."""
    if not (causal or window > 0 or kv_len < sk):
        return None
    return _additive_mask(off_ref, g, sq, sk, causal, window, kv_len, sk)


def _bdot(a, b, contract):
    """dot_general batched over the leading dim — the (B, KH) slices merged
    into one, since Mosaic's matmul takes a single batch dimension."""
    return jax.lax.dot_general(a, b, (contract, ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _fwd_mega_kernel(off_ref, q_ref, k_ref, v_ref, *rest, g: int,
                     causal: bool, window: int, scale: float,
                     kv_len: int, with_lse: bool):
    o_ref = rest[0]
    lse_ref = rest[1] if with_lse else None
    b, kh, _, sq, hd = q_ref.shape
    sk = k_ref.shape[2]
    hd_v = v_ref.shape[-1]
    amask = _mega_amask(off_ref, g, sq, sk, causal, window, kv_len)
    bh = b * kh
    q = q_ref[...].reshape(bh, g * sq, hd).astype(jnp.float32) * scale
    kt = k_ref[...].reshape(bh, sk, hd).astype(jnp.float32)
    vt = v_ref[...].reshape(bh, sk, hd_v).astype(jnp.float32)
    s = _bdot(q, kt, ((2,), (2,)))                     # (b·kh, g·sq, sk)
    if amask is not None:
        s = s + amask
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-37)
    o = _bdot(p, vt, ((2,), (1,))) / l
    o_ref[...] = o.reshape(b, kh, g, sq, hd_v).astype(o_ref.dtype)
    if with_lse:
        lse_ref[...] = (m + jnp.log(l)).reshape(b, kh, g, sq, 1)


def _whole(shape):
    n = len(shape)
    return pl.BlockSpec(shape, lambda i, off, _n=n: (0,) * _n)


def _bt(shape):
    """Batch-tiled spec: one batch row per grid step, everything else
    whole.  The mega kernel bodies read ``b`` from the ref shape, so the
    same bodies run unchanged with b=1 blocks."""
    n = len(shape)
    return pl.BlockSpec((1,) + tuple(shape[1:]),
                        lambda i, off, _n=n: (i,) + (0,) * (_n - 1))


def _fwd_mega_call(q, k, v, offs, *, causal: bool, window: int,
                   kv_len: int, interpret: bool, with_lse: bool,
                   batch_tiled: bool = False):
    b, kh, g, sq, hd = q.shape
    hd_v = v.shape[-1]
    spec = _bt if batch_tiled else _whole
    kernel = functools.partial(
        _fwd_mega_kernel, g=g, causal=causal, window=window,
        scale=1.0 / np.sqrt(hd), kv_len=kv_len, with_lse=with_lse)
    out_shape = [jax.ShapeDtypeStruct((b, kh, g, sq, hd_v), q.dtype)]
    out_specs = [spec((b, kh, g, sq, hd_v))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((b, kh, g, sq, 1),
                                              jnp.float32))
        out_specs.append(spec((b, kh, g, sq, 1)))
    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,) if batch_tiled else (1,),
            in_specs=[spec(q.shape), spec(k.shape), spec(v.shape)],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("parallel",) if batch_tiled else ("arbitrary",))),
        interpret=interpret,
    )(offs, q, k, v)
    return (res[0], res[1]) if with_lse else (res[0], None)


def _bwd_mega_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, dk_ref, dv_ref, *, g: int,
                     causal: bool, window: int, scale: float, kv_len: int):
    b, kh, _, sq, hd = q_ref.shape
    sk = k_ref.shape[2]
    hd_v = v_ref.shape[-1]
    amask = _mega_amask(off_ref, g, sq, sk, causal, window, kv_len)
    bh = b * kh
    q = q_ref[...].reshape(bh, g * sq, hd).astype(jnp.float32)
    kt = k_ref[...].reshape(bh, sk, hd).astype(jnp.float32)
    vt = v_ref[...].reshape(bh, sk, hd_v).astype(jnp.float32)
    do = do_ref[...].reshape(bh, g * sq, hd_v).astype(jnp.float32)
    lse = lse_ref[...].reshape(bh, g * sq, 1)
    delta = delta_ref[...].reshape(bh, g * sq, 1)
    s = _bdot(q * scale, kt, ((2,), (2,)))
    if amask is not None:
        s = s + amask
    p = jnp.exp(s - lse)                               # (b·kh, g·sq, sk)
    # contraction over the g·sq rows IS the GQA group sum
    dv = _bdot(p, do, ((1,), (1,)))                    # (b·kh, sk, hd_v)
    dp = _bdot(do, vt, ((2,), (2,)))
    ds = p * (dp - delta) * scale
    dq = _bdot(ds, kt, ((2,), (1,)))
    dk = _bdot(ds, q, ((1,), (1,)))
    dq_ref[...] = dq.reshape(b, kh, g, sq, hd).astype(dq_ref.dtype)
    dk_ref[...] = dk.reshape(b, kh, sk, hd).astype(dk_ref.dtype)
    dv_ref[...] = dv.reshape(b, kh, sk, hd_v).astype(dv_ref.dtype)


def _bwd_mega_call(q, k, v, do, lse, delta, offs, *, causal: bool,
                   window: int, kv_len: int, interpret: bool,
                   batch_tiled: bool = False):
    b, kh, g, sq, hd = q.shape
    sk = k.shape[2]
    hd_v = v.shape[-1]
    spec = _bt if batch_tiled else _whole
    kernel = functools.partial(
        _bwd_mega_kernel, g=g, causal=causal, window=window,
        scale=1.0 / np.sqrt(hd), kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,) if batch_tiled else (1,),
            in_specs=[spec(q.shape), spec(k.shape), spec(v.shape),
                      spec(do.shape), spec(lse.shape),
                      spec(delta.shape)],
            out_specs=[spec((b, kh, g, sq, hd)),
                       spec((b, kh, sk, hd)),
                       spec((b, kh, sk, hd_v))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, g, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((b, kh, sk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, kh, sk, hd_v), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("parallel",) if batch_tiled else ("arbitrary",))),
        interpret=interpret,
    )(offs, q, k, v, do, lse, delta)


def _fwd_call(q, k, v, offs, *, causal: bool, window: int, plan: AttnPlan,
              kv_len: int, interpret: bool, with_lse: bool):
    if plan.mega_fwd or plan.mega_fwd_bt:
        return _fwd_mega_call(q, k, v, offs, causal=causal, window=window,
                              kv_len=kv_len, interpret=interpret,
                              with_lse=with_lse,
                              batch_tiled=plan.mega_fwd_bt)
    block_q, block_k, g_fold = plan.block_q, plan.block_k, plan.g_fold
    b, kh, g, sq, hd = q.shape
    sk = k.shape[2]
    hd_v = v.shape[-1]
    gf = g_fold if g % g_fold == 0 else 1
    ngf = g // gf
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / np.sqrt(hd)

    # single-tile grids get the additive mask precomputed outside the
    # kernel — one shared array instead of per-step iota/select chains
    premask = (nq == 1 and nk == 1
               and (causal or window > 0 or kv_len < sk))
    kernel = functools.partial(
        _fwd_kernel, gf=gf, block_q=block_q, block_k=block_k,
        num_kv_blocks=nk, causal=causal, window=window, scale=scale,
        kv_len=kv_len, with_lse=with_lse, premask=premask)
    out_shape = [jax.ShapeDtypeStruct((b, kh, g, sq, hd_v), q.dtype)]
    out_specs = [pl.BlockSpec(
        (1, 1, gf, block_q, hd_v),
        lambda bb, hh, ii, jj, off: (bb, hh // ngf, hh % ngf, ii, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((b, kh, g, sq, 1),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, 1, gf, block_q, 1),
            lambda bb, hh, ii, jj, off: (bb, hh // ngf, hh % ngf, ii, 0)))

    scratch = []
    if nk > 1:
        scratch = [
            pltpu.VMEM((gf * block_q, 1), jnp.float32),
            pltpu.VMEM((gf * block_q, 1), jnp.float32),
            pltpu.VMEM((gf * block_q, hd_v), jnp.float32),
        ]
    in_specs = [
        pl.BlockSpec((1, 1, gf, block_q, hd),
                     lambda bb, hh, ii, jj, off:
                     (bb, hh // ngf, hh % ngf, ii, 0)),
        pl.BlockSpec((1, 1, block_k, hd),
                     lambda bb, hh, ii, jj, off: (bb, hh // ngf, jj, 0)),
        pl.BlockSpec((1, 1, block_k, hd_v),
                     lambda bb, hh, ii, jj, off: (bb, hh // ngf, jj, 0)),
    ]
    operands = [offs, q, k, v]
    if premask:
        in_specs.append(pl.BlockSpec(
            (gf * block_q, block_k), lambda bb, hh, ii, jj, off: (0, 0)))
        operands.append(_additive_mask(offs, gf, block_q, block_k, causal,
                                       window, kv_len, block_k))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh * ngf, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*operands)
    return (res[0], res[1]) if with_lse else (res[0], None)


# --------------------------------------------------------------- backward

def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, gf: int, block_q: int, block_k: int,
                   num_kv_blocks: int, causal: bool, window: int,
                   scale: float, kv_len: int):
    i = pl.program_id(2)
    j = pl.program_id(3)
    sk_padded = num_kv_blocks * block_k
    rows = gf * block_q
    hd = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = i * block_q + off_ref[0]
    k_start = j * block_k
    run = _tile_run(q_start, k_start, block_q, block_k, causal, window,
                    kv_len, sk_padded)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].reshape(rows, hd).astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd_v)
        do = do_ref[0, 0].reshape(rows, v.shape[-1]).astype(jnp.float32)
        lse = lse_ref[0, 0].reshape(rows, 1)
        delta = delta_ref[0, 0].reshape(rows, 1)
        s = _dot(q, k, ((1,), (1,)))
        mask = _tile_mask(q_start, k_start, gf, block_q, block_k, causal,
                          window, kv_len, sk_padded)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                           # (gf·bq, bk)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * scale
        dq_acc[...] += _dot(ds, k, ((1,), (0,)))

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].reshape(
            gf, block_q, hd).astype(dq_ref.dtype)


def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, gf: int, block_q: int,
                    block_k: int, num_q_blocks: int, num_groups: int,
                    causal: bool, window: int, scale: float, kv_len: int,
                    sk_padded: int):
    j = pl.program_id(2)                               # k block
    gg = pl.program_id(3)                              # folded-head group
    i = pl.program_id(4)                               # q block
    rows = gf * block_q

    @pl.when(jnp.logical_and(gg == 0, i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = i * block_q + off_ref[0]
    k_start = j * block_k
    run = _tile_run(q_start, k_start, block_q, block_k, causal, window,
                    kv_len, sk_padded)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1]).astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd_v)
        do = do_ref[0, 0].reshape(rows, v.shape[-1]).astype(jnp.float32)
        lse = lse_ref[0, 0].reshape(rows, 1)
        delta = delta_ref[0, 0].reshape(rows, 1)
        s = _dot(q * scale, k, ((1,), (1,)))
        mask = _tile_mask(q_start, k_start, gf, block_q, block_k, causal,
                          window, kv_len, sk_padded)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                           # (gf·bq, bk)
        # dv += pᵀ · do — the contraction over the gf·bq rows IS the
        # GQA group sum for the folded heads
        dv_acc[...] += _dot(p, do, ((0,), (0,)))
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * scale
        dk_acc[...] += _dot(ds, q, ((0,), (0,)))

    @pl.when(jnp.logical_and(gg == num_groups - 1, i == num_q_blocks - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, *refs, g: int, block_q: int, block_k: int,
                      num_q_blocks: int, num_kv_blocks: int, causal: bool,
                      window: int, scale: float, kv_len: int,
                      premask: bool):
    """Fused dq+dk+dv: grid (B, KH, nq, nk), nk innermost.  dq rides VMEM
    scratch (flushed when the k loop finishes); dk/dv accumulate into
    whole-kv revisited output blocks — the probability tile is recomputed
    once per (i, j) visit instead of once per backward pass."""
    if premask:
        mask_ref, *refs = refs
    if len(refs) == 4:
        dq_ref, dk_ref, dv_ref, dq_acc = refs
    else:
        (dq_ref, dk_ref, dv_ref), dq_acc = refs, None
    i = pl.program_id(2)
    j = pl.program_id(3)
    sk_padded = num_kv_blocks * block_k
    rows = g * block_q
    hd = q_ref.shape[-1]
    single = num_q_blocks == 1 and num_kv_blocks == 1

    if not single:
        @pl.when(jnp.logical_and(i == 0, j == 0))
        def _init_kv():
            dk_ref[...] = jnp.zeros_like(dk_ref)
            dv_ref[...] = jnp.zeros_like(dv_ref)

        @pl.when(j == 0)
        def _init_q():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = i * block_q + off_ref[0]
    k_start = j * block_k

    def _compute():
        q = q_ref[0, 0].reshape(rows, hd).astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd_v)
        do = do_ref[0, 0].reshape(rows, v.shape[-1]).astype(jnp.float32)
        lse = lse_ref[0, 0].reshape(rows, 1)
        delta = delta_ref[0, 0].reshape(rows, 1)
        s = _dot(q * scale, k, ((1,), (1,)))
        if premask:
            s = s + mask_ref[...]
        else:
            mask = _tile_mask(q_start, k_start, g, block_q, block_k,
                              causal, window, kv_len, sk_padded)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                           # (g·bq, bk)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * scale
        dq = _dot(ds, k, ((1,), (0,)))
        dv = _dot(p, do, ((0,), (0,)))
        dk = _dot(ds, q, ((0,), (0,)))
        if single:
            # one tile: write grads straight through, no RMW/scratch
            dq_ref[0, 0] = dq.reshape(g, block_q, hd).astype(dq_ref.dtype)
            dk_ref[0, 0] = dk
            dv_ref[0, 0] = dv
        else:
            dq_acc[...] += dq
            dv_ref[0, 0, pl.ds(j * block_k, block_k)] += dv
            dk_ref[0, 0, pl.ds(j * block_k, block_k)] += dk

    if single:
        _compute()
        return

    run = _tile_run(q_start, k_start, block_q, block_k, causal, window,
                    kv_len, sk_padded)
    pl.when(run)(_compute)

    @pl.when(j == num_kv_blocks - 1)
    def _flush_dq():
        dq_ref[0, 0] = dq_acc[...].reshape(
            g, block_q, hd).astype(dq_ref.dtype)


def _bwd_call(q, k, v, do, lse, delta, offs, plan: AttnPlan, *,
              causal: bool, window: int, kv_len: int, interpret: bool):
    b, kh, g, sq, hd = q.shape
    sk = k.shape[2]
    hd_v = v.shape[-1]
    scale = 1.0 / np.sqrt(hd)

    if plan.mega_bwd or plan.mega_bwd_bt:
        return _bwd_mega_call(q, k, v, do, lse, delta, offs, causal=causal,
                              window=window, kv_len=kv_len,
                              interpret=interpret,
                              batch_tiled=plan.mega_bwd_bt)

    if plan.fused_bwd:
        bq, bk = plan.dq_block_q, plan.dq_block_k
        nq, nk = sq // bq, sk // bk
        single = nq == 1 and nk == 1
        premask = single and (causal or window > 0 or kv_len < sk)
        kernel = functools.partial(
            _bwd_fused_kernel, g=g, block_q=bq, block_k=bk,
            num_q_blocks=nq, num_kv_blocks=nk, causal=causal, window=window,
            scale=scale, kv_len=kv_len, premask=premask)
        in_specs = [
            pl.BlockSpec((1, 1, g, bq, hd),
                         lambda bb, hk, ii, jj, off:
                         (bb, hk, 0, ii, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bb, hk, ii, jj, off:
                         (bb, hk, jj, 0)),
            pl.BlockSpec((1, 1, bk, hd_v),
                         lambda bb, hk, ii, jj, off:
                         (bb, hk, jj, 0)),
            pl.BlockSpec((1, 1, g, bq, hd_v),
                         lambda bb, hk, ii, jj, off:
                         (bb, hk, 0, ii, 0)),
            pl.BlockSpec((1, 1, g, bq, 1),
                         lambda bb, hk, ii, jj, off:
                         (bb, hk, 0, ii, 0)),
            pl.BlockSpec((1, 1, g, bq, 1),
                         lambda bb, hk, ii, jj, off:
                         (bb, hk, 0, ii, 0)),
        ]
        operands = [offs, q, k, v, do, lse, delta]
        if premask:
            in_specs.append(pl.BlockSpec(
                (g * bq, bk), lambda bb, hk, ii, jj, off: (0, 0)))
            operands.append(_additive_mask(offs, g, bq, bk, causal,
                                           window, kv_len, bk))
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, kh, nq, nk),
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec((1, 1, g, bq, hd),
                                 lambda bb, hk, ii, jj, off:
                                 (bb, hk, 0, ii, 0)),
                    # whole-kv revisited blocks: constant index per
                    # (batch, kv head) so the accumulator stays resident
                    pl.BlockSpec((1, 1, sk, hd),
                                 lambda bb, hk, ii, jj, off:
                                 (bb, hk, 0, 0)),
                    pl.BlockSpec((1, 1, sk, hd_v),
                                 lambda bb, hk, ii, jj, off:
                                 (bb, hk, 0, 0)),
                ],
                scratch_shapes=[] if single else
                [pltpu.VMEM((g * bq, hd), jnp.float32)],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, kh, g, sq, hd), q.dtype),
                jax.ShapeDtypeStruct((b, kh, sk, hd), jnp.float32),
                jax.ShapeDtypeStruct((b, kh, sk, hd_v), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary")),
            interpret=interpret,
        )(*operands)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)

    # --- two-call fallback -----------------------------------------------
    gf = plan.g_fold if g % plan.g_fold == 0 else 1
    ngf = g // gf
    bq, bk = plan.dq_block_q, plan.dq_block_k
    nq, nk = sq // bq, sk // bk

    # dq pass: grid (B, KH·ngf, nq, nk), nk innermost reduction
    dq_kernel = functools.partial(
        _bwd_dq_kernel, gf=gf, block_q=bq, block_k=bk, num_kv_blocks=nk,
        causal=causal, window=window, scale=scale, kv_len=kv_len)
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh * ngf, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, gf, bq, hd),
                             lambda bb, hh, ii, jj, off:
                             (bb, hh // ngf, hh % ngf, ii, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda bb, hh, ii, jj, off:
                             (bb, hh // ngf, jj, 0)),
                pl.BlockSpec((1, 1, bk, hd_v),
                             lambda bb, hh, ii, jj, off:
                             (bb, hh // ngf, jj, 0)),
                pl.BlockSpec((1, 1, gf, bq, hd_v),
                             lambda bb, hh, ii, jj, off:
                             (bb, hh // ngf, hh % ngf, ii, 0)),
                pl.BlockSpec((1, 1, gf, bq, 1),
                             lambda bb, hh, ii, jj, off:
                             (bb, hh // ngf, hh % ngf, ii, 0)),
                pl.BlockSpec((1, 1, gf, bq, 1),
                             lambda bb, hh, ii, jj, off:
                             (bb, hh // ngf, hh % ngf, ii, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, gf, bq, hd),
                lambda bb, hh, ii, jj, off:
                (bb, hh // ngf, hh % ngf, ii, 0)),
            scratch_shapes=[pltpu.VMEM((gf * bq, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, sq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(offs, q, k, v, do, lse, delta)

    # dk/dv pass: grid (B, KH, nk, ngf, nq); the folded-group sum and the
    # q-block reduction both ride the innermost sequential dims, so dk/dv
    # accumulate per *kv* head directly in scratch
    dbq, dbk = plan.dkv_block_q, plan.dkv_block_k
    dnq, dnk = sq // dbq, sk // dbk
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, gf=gf, block_q=dbq, block_k=dbk, num_q_blocks=dnq,
        num_groups=ngf, causal=causal, window=window, scale=scale,
        kv_len=kv_len, sk_padded=dnk * dbk)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh, dnk, ngf, dnq),
            in_specs=[
                pl.BlockSpec((1, 1, gf, dbq, hd),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, gg, ii, 0)),
                pl.BlockSpec((1, 1, dbk, hd),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, jj, 0)),
                pl.BlockSpec((1, 1, dbk, hd_v),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, jj, 0)),
                pl.BlockSpec((1, 1, gf, dbq, hd_v),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, gg, ii, 0)),
                pl.BlockSpec((1, 1, gf, dbq, 1),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, gg, ii, 0)),
                pl.BlockSpec((1, 1, gf, dbq, 1),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, gg, ii, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, dbk, hd),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, jj, 0)),
                pl.BlockSpec((1, 1, dbk, hd_v),
                             lambda bb, hk, jj, gg, ii, off:
                             (bb, hk, jj, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((dbk, hd), jnp.float32),
                pltpu.VMEM((dbk, hd_v), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, sk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, kh, sk, hd_v), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
    )(offs, q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- custom VJP

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, q_offset, causal, window, plan, kv_len, interpret):
    """Primal (non-differentiated) call: no residual output.  ``q`` is the
    internal 5-d (B, KH, G, S, hd) layout; ``plan`` is the (hashable)
    ``AttnPlan`` carrying every block decision."""
    offs = jnp.reshape(q_offset.astype(jnp.int32), (1,))
    out, _ = _fwd_call(q, k, v, offs, causal=causal, window=window,
                       plan=plan, kv_len=kv_len,
                       interpret=interpret, with_lse=False)
    return out


def _flash_fwd_rule(q, k, v, q_offset, causal, window, plan, kv_len,
                    interpret):
    offs = jnp.reshape(q_offset.astype(jnp.int32), (1,))
    out, lse = _fwd_call(q, k, v, offs, causal=causal, window=window,
                         plan=plan, kv_len=kv_len,
                         interpret=interpret, with_lse=True)
    return out, (q, k, v, out, lse, offs)


def _flash_bwd_rule(causal, window, plan, kv_len, interpret, res, do):
    q, k, v, out, lse, offs = res
    # delta_i = rowsum(do · out), elementwise on the unblocked arrays (see
    # models.attention._flash_bwd for why not a blocked dot)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)             # (B, KH, G, S, 1)
    dq, dk, dv = _bwd_call(q, k, v, do, lse, delta, offs, plan,
                           causal=causal, window=window, kv_len=kv_len,
                           interpret=interpret)
    return dq, dk, dv, jnp.zeros((), jnp.float32)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ----------------------------------------------------------------- public

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_offset=0.0, *, causal: bool = True, window: int = 0,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False,
                    plan: AttnPlan | None = None) -> jax.Array:
    """q: (B, H, S, hd); k, v: (B, KH, S, hd) → (B, H, S, hd_v).

    Differentiable: the backward runs the Pallas kernels (fused or
    dq/dkv two-call, per the plan) from the saved logsumexp (O(S)
    memory), matching the jnp twin
    (``models.attention.flash_attention_jnp``) to fp32 tolerance.

    Block sizes come from ``kernels.autotune.plan_attention`` unless
    ``block_q``/``block_k`` pin them (or a full ``plan`` is supplied).
    ``q_offset`` is the global position of q row 0 (a traced
    ``axis_index`` product under context-parallel shard_map); its
    cotangent is zero.  Sequence lengths need not divide the block sizes:
    edges are zero-padded and masked like the forward's causal tiles.
    """
    b, h, sq, hd = q.shape
    _, kh, sk, _ = k.shape
    hd_v = v.shape[-1]
    g = h // kh
    if plan is None:
        # a traced q_offset (context-parallel stripe) means no tile is
        # provably dead at trace time — plan with every tile live
        static_off = isinstance(q_offset, (int, float, np.integer,
                                           np.floating))
        plan = autotune.plan_attention(
            sq, sk, hd, hd_v, g, kh, b, np.dtype(q.dtype).itemsize * 8,
            bool(causal), int(window), int(sk), diag_aligned=static_off,
            backend="interpret" if interpret else "tpu",
            block_q=block_q, block_k=block_k)
    sq_p = -(-sq // plan.block_q) * plan.block_q
    sk_p = -(-sk // plan.block_k) * plan.block_k
    off = jnp.asarray(q_offset).astype(jnp.float32)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    q5 = q.reshape(b, kh, g, sq_p, hd)
    out = _flash(q5, k, v, off, causal, window, plan, int(sk), interpret)
    out = out.reshape(b, h, sq_p, hd_v)
    return out[:, :, :sq] if sq_p != sq else out
