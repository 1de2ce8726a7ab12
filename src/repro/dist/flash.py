"""Mesh-strategy dispatch for attention compute.

One place decides how attention parallelizes, so the model blocks never
mention the mesh:

* **head-parallel** — when the (kv-)head count divides the "model" axis,
  heads shard and every device runs the plain local kernel on its heads;
  no collective at all (attention is independent per head).
* **context/sequence-parallel** — otherwise, when the sequence divides the
  "model" axis: q shards over sequence, k/v stay whole, and each device
  computes its q stripe against the full context (``q_offset`` keeps the
  causal mask globally correct).  Used for training/prefill.
* **lse-combine flash decode** — one-token decode against a cache whose
  *sequence* dim shards over "model": every device computes a partial
  softmax over its §6 stripe of the cache and the partials combine with a
  global max + psum (the log-sum-exp trick), two scalarish collectives.
* **single device** — no mesh (or ``pure_dp``): the existing kernels.
  Long-sequence training/prefill runs the differentiable Pallas flash
  kernel (custom-VJP backward kernels; compiled on TPU, interpret mode
  on CPU); the decode hot path routes to the Pallas flash-decode kernel
  on a TPU backend.

The §6 reading: a decode cache is one data block; the sequence stripes the
lse-combine path walks are exactly the disjoint EW partitions
``partition_tree_of`` emits for the cache's ``kv_seq`` sharding.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kernel_ops
from repro.models.attention import (decode_attention, flash_min_seq,
                                    full_attention)
from .sharding import current_ctx

NEG_INF = -1e30


def _blocks(cfg) -> Tuple[Optional[int], Optional[int], int]:
    """Config tile overrides (None = let the trace-time autotuner pick)
    and the flash threshold.  ``flash_min_seq`` derives its floor from
    ``autotune.min_block()`` when no override pins a tile, so the
    threshold and the planner can never disagree about the smallest
    sequence worth tiling — fwd and bwd alike."""
    return (getattr(cfg, "attn_block_q", None),
            getattr(cfg, "attn_block_k", None),
            flash_min_seq(cfg))


def _attn_local(q: jax.Array, k: jax.Array, v: jax.Array, *, window: int,
                block_q: Optional[int], block_k: Optional[int],
                min_seq: int = 2048, q_offset=0) -> jax.Array:
    """Single-shard causal attention: the differentiable Pallas flash
    kernel for long sequences (O(S) memory, custom-VJP backward kernels —
    training and inference take the same path), dense reference for short
    ones.  Ragged sequence lengths are edge-padded inside the kernel, so
    the flash branch is purely length-thresholded."""
    sq = q.shape[1]
    if sq > min_seq:
        return kernel_ops.flash_attention(
            q, k, v, jnp.asarray(q_offset).astype(jnp.float32),
            causal=True, window=window, block_q=block_q, block_k=block_k)
    return full_attention(q, k, v, causal=True, window=window,
                          q_offset=q_offset)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, cfg=None,
                     window: int = 0) -> jax.Array:
    """Causal (optionally sliding-window) attention, mesh-dispatched.

    q: (B, S, H, hd); k, v: (B, S, KH, hd) → (B, S, H, hd_v).
    """
    ctx = current_ctx()
    b, s, h, _ = q.shape
    kh = k.shape[2]
    m = ctx.model_size
    bq, bk, min_seq = _blocks(cfg)

    if not ctx.active or ctx.pure_dp or m <= 1:
        return _attn_local(q, k, v, window=window, block_q=bq, block_k=bk,
                           min_seq=min_seq)

    dp = ctx.resolve("dp", b)
    if h % m == 0 and kh % m == 0:
        # head-parallel: no collective, local kernel per head shard
        spec = P(dp, None, "model", None)

        def inner(ql, kl, vl):
            return _attn_local(ql, kl, vl, window=window,
                               block_q=bq, block_k=bk, min_seq=min_seq)

        return jax.shard_map(inner, mesh=ctx.mesh,
                             in_specs=(spec, spec, spec), out_specs=spec,
                             check_vma=False)(q, k, v)

    if s % m == 0:
        # context-parallel: q stripes over "model", k/v whole; q_offset
        # keeps each stripe's causal mask globally positioned — through
        # the Pallas kernel's scalar-prefetched offset in fwd AND bwd
        chunk = s // m
        qspec = P(dp, "model", None, None)
        kvspec = P(dp, None, None, None)

        def inner(ql, kl, vl):
            off = jax.lax.axis_index("model") * chunk
            return _attn_local(ql, kl, vl, window=window, block_q=bq,
                               block_k=bk, min_seq=min_seq, q_offset=off)

        return jax.shard_map(inner, mesh=ctx.mesh,
                             in_specs=(qspec, kvspec, kvspec),
                             out_specs=qspec, check_vma=False)(q, k, v)

    return _attn_local(q, k, v, window=window, block_q=bq, block_k=bk,
                       min_seq=min_seq)


# ------------------------------------------------------------------- decode

def _decode_local(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  valid: jax.Array, window: int) -> jax.Array:
    """One-token attention against head-major caches on one shard.

    q: (B, 1, H, hd); caches: (B, KH, S, hd); valid: scalar int32 count of
    valid cache entries.  Routes to the Pallas flash-decode kernel on a
    TPU backend (decode is never differentiated), jnp oracle elsewhere.
    A cache length off the kernel's 128-entry tiling is zero-padded: the
    padding lies past ``valid`` and is masked like any unwritten entry.
    """
    b, _, h, hd = q.shape
    kh = k_cache.shape[1]
    g = h // kh
    smax = k_cache.shape[2]
    if jax.default_backend() == "tpu":
        from repro.kernels.flash_decode import flash_decode
        pad = ((0, 0), (0, 0), (0, (-smax) % 128), (0, 0))
        if smax % 128:
            k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)
        qg = q[:, 0].reshape(b, kh, g, hd)
        out = flash_decode(qg, k_cache, v_cache, valid, window=window)
        return out.reshape(b, 1, h, v_cache.shape[-1])
    kt = jnp.transpose(k_cache, (0, 2, 1, 3))
    vt = jnp.transpose(v_cache, (0, 2, 1, 3))
    return decode_attention(q, kt, vt, cur_len=valid, window=window)


def decode_update_and_attend(q: jax.Array, k_new: jax.Array,
                             v_new: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, cur_len, *, cfg=None,
                             window: int = 0):
    """Insert the new token at ``cur_len`` and attend over ``cur_len + 1``.

    q, k_new, v_new: (B, 1, H|KH, hd); caches head-major (B, KH, S, hd);
    cur_len: scalar int32 tokens already cached.  Returns
    (out (B, 1, H, hd_v), k_cache', v_cache').
    """
    ctx = current_ctx()
    b, _, h, hd = q.shape
    kh, smax = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    m = ctx.model_size
    cur = jnp.asarray(cur_len, jnp.int32)

    kn = jnp.transpose(k_new, (0, 2, 1, 3)).astype(k_cache.dtype)
    vn = jnp.transpose(v_new, (0, 2, 1, 3)).astype(v_cache.dtype)
    k_cache = jax.lax.dynamic_update_slice(k_cache, kn, (0, 0, cur, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, vn, (0, 0, cur, 0))

    if not ctx.active or ctx.pure_dp or m <= 1:
        out = _decode_local(q, k_cache, v_cache, cur + 1, window)
        return out, k_cache, v_cache

    dp = ctx.resolve("dp", b)
    if h % m == 0 and kh % m == 0:
        qspec = P(dp, None, "model", None)
        cspec = P(dp, "model", None, None)

        def inner(c, ql, kcl, vcl):
            return _decode_local(ql, kcl, vcl, c + 1, window)

        out = jax.shard_map(inner, mesh=ctx.mesh,
                            in_specs=(P(), qspec, cspec, cspec),
                            out_specs=qspec,
                            check_vma=False)(cur, q, k_cache, v_cache)
        return out, k_cache, v_cache

    if smax % m == 0:
        # lse-combine: each device scans its §6 stripe of the cache,
        # partial softmaxes merge through a global max + psum
        chunk = smax // m
        scale = 1.0 / np.sqrt(hd)
        qspec = P(dp, None, None, None)
        cspec = P(dp, None, "model", None)

        def inner(c, ql, kcl, vcl):
            bl = ql.shape[0]
            r = jax.lax.axis_index("model")
            pos = r * chunk + jnp.arange(chunk)
            qg = ql[:, 0].reshape(bl, kh, g, hd).astype(jnp.float32)
            s = jnp.einsum("bkgh,bksh->bkgs", qg,
                           kcl.astype(jnp.float32)) * scale
            valid = pos < c + 1
            if window > 0:
                valid &= pos >= jnp.maximum(c + 1 - window, 0)
            s = jnp.where(valid[None, None, None, :], s, NEG_INF)
            m_loc = jnp.max(s, axis=-1)
            m_all = jax.lax.pmax(m_loc, "model")
            p = jnp.exp(s - m_all[..., None])
            p = jnp.where(valid[None, None, None, :], p, 0.0)
            num = jnp.einsum("bkgs,bksh->bkgh", p,
                             vcl.astype(jnp.float32))
            num = jax.lax.psum(num, "model")
            den = jax.lax.psum(jnp.sum(p, axis=-1), "model")
            out = num / jnp.maximum(den, 1e-37)[..., None]
            return out.reshape(bl, 1, h, -1).astype(ql.dtype)

        out = jax.shard_map(inner, mesh=ctx.mesh,
                            in_specs=(P(), qspec, cspec, cspec),
                            out_specs=qspec,
                            check_vma=False)(cur, q, k_cache, v_cache)
        return out, k_cache, v_cache

    out = _decode_local(q, k_cache, v_cache, cur + 1, window)
    return out, k_cache, v_cache


# ------------------------------------------------------------- paged decode

def _write_tokens(pools: jax.Array, layer, phys: jax.Array, slot: jax.Array,
                  new: jax.Array) -> jax.Array:
    """Write each row's token (B, 1, KH, hd) at pool page ``phys`` slot
    ``slot`` of ``layer``; a page past the pool drops.  A pool row holds
    ``r`` tokens, so the row is read, the token put in its place and the
    whole row written back: a scatter of whole rows, which updates the
    pool in place in one pass (a window at a lane offset would instead
    be expanded into a loop of one-row writes).  With one token to a row
    the read is dead and the compiler drops it."""
    b, row_shape = new.shape[0], pools.shape[3:]
    tw = new.shape[2] * new.shape[3]
    r = int(np.prod(row_shape)) // tw
    row = pools[layer, phys, slot // r].reshape(b, r, tw)
    mine = (jnp.arange(r)[None] == (slot % r)[:, None])[..., None]
    row = jnp.where(mine, new.reshape(b, 1, tw).astype(pools.dtype), row)
    return pools.at[layer, phys, slot // r].set(row.reshape(b, *row_shape),
                                                mode="drop")


def paged_update_and_attend(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                            k_pools: jax.Array, v_pools: jax.Array, layer,
                            page_table: jax.Array, cur_lens: jax.Array,
                            active: jax.Array, *, window: int = 0):
    """Per-request paged decode over §6 pages of a shared cache pool.

    q, k_new, v_new: (B, 1, H|KH, hd); pools (L, P, page // r, *row) —
    every layer's KV lives in fixed-size pages of one pool, each page
    ``page`` tokens stored ``r`` to a row, a row being (KH, hd) or
    (r·KH·hd,) as ``repro.serve.steps.pool_shape`` picks from the shapes,
    indexed through ``page_table`` (B, max_pages) int32 (entries past a
    row's page count are ignored).  ``layer`` is the
    (traced) layer whose pages are read and written; ``cur_lens`` (B,)
    int32 tokens already cached per row; ``active`` (B,) bool — inactive
    rows write nothing and output zeros.

    The whole pools are taken and returned so that a caller carrying them
    through its layer loop updates them in place: the only pool bytes
    written are the new tokens', the only ones read the pages gathered.

    The attention is one masked softmax over each row's gathered pages,
    in f32, with the masking and window of the cache-stripe decode path.
    Returns (out (B,1,H,hd), k_pools', v_pools').
    """
    b, _, h, hd = q.shape
    kh = k_new.shape[2]
    npages, rows_pp = k_pools.shape[1:3]
    r = int(np.prod(k_pools.shape[3:])) // (kh * hd)  # tokens per pool row
    page = rows_pp * r
    g = h // kh
    scale = 1.0 / np.sqrt(hd)
    cur = jnp.asarray(cur_lens, jnp.int32)

    # scatter the new token: row i writes page_table[i, cur//page] slot
    # cur%page; inactive rows aim past the pool and drop
    phys = page_table[jnp.arange(b), cur // page]
    phys = jnp.where(active, phys, npages)
    slot = cur % page
    k_pools = _write_tokens(k_pools, layer, phys, slot, k_new)
    v_pools = _write_tokens(v_pools, layer, phys, slot, v_new)

    # gather each row's pages straight from the pool as (B, n, KR, WR):
    # n pool rows of KR heads of WR lanes, a lane row holding r tokens of
    # hr heads.  The query is spread block-diagonally over a lane row, so
    # scores and weighted values are matmuls over the lanes as the pool
    # stores them (bf16; the cast to f32 fuses in): nothing is laid out
    # again, whatever the pool's layout.
    row = k_pools.shape[3:]
    kr, wr = (row[0] if len(row) == 2 else 1), row[-1]
    hr = kh // kr
    kg = k_pools[layer, page_table].reshape(b, -1, kr, wr)
    vg = v_pools[layer, page_table].reshape(b, -1, kr, wr)
    n = kg.shape[1]
    eye_r = jnp.eye(r, dtype=jnp.float32)
    eye_h = jnp.eye(hr, dtype=jnp.float32)
    qg = q[:, 0].reshape(b, kr, hr, g, hd).astype(jnp.float32)
    qd = jnp.einsum("bjigh,ts,iu->bjtihsug", qg, eye_r, eye_h).reshape(
        b, kr, wr, r * hr * g)
    s = jnp.einsum("bjwc,bnjw->bjcn", qd, kg.astype(jnp.float32)) * scale
    s = s.reshape(b, kr, r, hr, g, n)
    pos = jnp.arange(n)[None, :] * r + jnp.arange(r)[:, None]    # (r, n)
    valid = pos[None] < (cur + 1)[:, None, None]                 # (B, r, n)
    if window > 0:
        valid &= pos[None] >= jnp.maximum(cur + 1 - window, 0)[:, None, None]
    valid = valid[:, None, :, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    m_all = jnp.max(s, axis=(2, 5), keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m_all), 0.0)
    den = jnp.sum(p, axis=(2, 5))                                # (B, KR, hr, g)
    pv = jnp.einsum("bjcn,bnjw->bjcw", p.reshape(b, kr, -1, n),
                    vg.astype(jnp.float32))
    num = jnp.einsum("bjtigsuh,ts,iu->bjigh",
                     pv.reshape(b, kr, r, hr, g, r, hr, hd), eye_r, eye_h)
    out = num / jnp.maximum(den, 1e-37)[..., None]
    out = out * active[:, None, None, None, None]
    return (out.reshape(b, 1, h, -1).astype(q.dtype), k_pools, v_pools)


# ---------------------------------------------------------------- MLA decode

def mla_decode_attend(q_latent: jax.Array, q_rope: jax.Array,
                      c_new: jax.Array, kr_new: jax.Array,
                      c_kv: jax.Array, k_rope: jax.Array, cur_len, *,
                      scale: float):
    """Absorbed-matrix MLA decode in the compressed latent space.

    q_latent: (B, 1, H, rkv); q_rope: (B, 1, H, dr); new latents
    c_new (B, 1, rkv) / kr_new (B, 1, dr); caches c_kv (B, S, rkv) /
    k_rope (B, S, dr).  Returns (out_latent (B, 1, H, rkv), c_kv',
    k_rope').  Heads shard over "model" when they divide it (the caches
    are head-shared latents, so head-parallel needs no collective);
    otherwise the compute is latent-rank-bound and runs replicated.
    """
    ctx = current_ctx()
    b, _, h, _ = q_latent.shape
    m = ctx.model_size
    cur = jnp.asarray(cur_len, jnp.int32)

    c_kv = jax.lax.dynamic_update_slice(
        c_kv, c_new.astype(c_kv.dtype), (0, cur, 0))
    k_rope = jax.lax.dynamic_update_slice(
        k_rope, kr_new.astype(k_rope.dtype), (0, cur, 0))

    def attend(ql, qr, ckv, kr, c):
        smax = ckv.shape[1]
        s = (jnp.einsum("bshr,btr->bhst", ql, ckv)
             + jnp.einsum("bshk,btk->bhst", qr, kr)).astype(jnp.float32)
        s = s * scale
        valid = jnp.arange(smax) < c + 1
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(ql.dtype)
        return jnp.einsum("bhst,btr->bshr", probs, ckv)

    if ctx.active and not ctx.pure_dp and m > 1 and h % m == 0:
        dp = ctx.resolve("dp", b)
        qspec = P(dp, None, "model", None)
        cspec = P(dp, None, None)

        def inner(ql, qr, ckv, kr, c):
            return attend(ql, qr, ckv, kr, c)

        out = jax.shard_map(inner, mesh=ctx.mesh,
                            in_specs=(qspec, qspec, cspec, cspec, P()),
                            out_specs=qspec, check_vma=False)(
                                q_latent, q_rope, c_kv, k_rope, cur)
        return out, c_kv, k_rope

    out = attend(q_latent, q_rope, c_kv, k_rope, cur)
    return out, c_kv, k_rope
