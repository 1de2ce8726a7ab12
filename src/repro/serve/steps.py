"""Serving steps: prefill and single-token decode (the shapes the
``decode_*`` / ``long_*`` dry-run cells lower), plus the paged variants
the continuous-batching engine runs.

The paged steps keep the whole KV cache in per-layer page pools
(``pool_shape``) indexed through a (B, max_pages) page table — §6's
disjoint-partition decomposition applied to serving: every request owns a
disjoint set of fixed-size pages of one shared cache block, appended as
it decodes.  Positions are carried as traced (B,) arrays — steps never
retrace across decode lengths or batch compositions.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.dist.flash import paged_update_and_attend
from repro.models import blocks
from repro.models.layers import apply_rope, cast_params, mlp, rmsnorm, _dtype
from repro.models.attention import gqa_qkv
from repro.models.model import LanguageModel


def make_prefill_step(model: LanguageModel):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step


def make_decode_step(model: LanguageModel):
    def decode_step(params, cache, token, cur_len):
        return model.decode_step(params, cache, token, cur_len)
    return decode_step


# -------------------------------------------------------------- paged steps

def pool_shape(num_layers: int, pool_pages: int, page_size: int,
               num_kv_heads: int, head_dim: int) -> tuple:
    """The KV pool's shape, picked from the shapes so that the chip keeps
    the pool row-major and the layer loop updates it in place.

    The chip tiles an array's two minor dims by 8 rows of 128 lanes.  When
    a token's (KH, hd) fills those tiles to within an eighth, the pool is
    (L, P, page, KH, hd): one token to a row.  Otherwise (5 heads of 64
    pad to 3.2 times their bytes, and the compiler then gives a pool
    argument a layout of its own and lays every pool layer out again for
    each token write) the pool is (L, P, page // r, r·KH·hd): each row
    holds ``r`` tokens, the fewest whose values fill whole lane tiles, so
    nothing pads; when ``page`` is not a multiple of that ``r``, one
    token to a row.
    """
    def up(n, m):
        return -(-n // m) * m

    width = num_kv_heads * head_dim
    if 8 * up(num_kv_heads, 8) * up(head_dim, 128) <= 9 * width:
        return (num_layers, pool_pages, page_size, num_kv_heads, head_dim)
    r = 128 // math.gcd(width, 128)
    if page_size % r:
        r = 1
    return (num_layers, pool_pages, page_size // r, r * width)


def make_paged_prefill_step(model: LanguageModel, page_size: int):
    """Prefill one request straight into its pages.

    Returned step signature:
      serve_prefill(params, k_pools, v_pools, tokens, plen, pages)
        k_pools, v_pools: in ``pool_shape``'s layout, donated: the step
          writes the request's pages in place and the caller must drop
          its references and rebind to the result;
        tokens: (1, Spad) int32, right-padded — Spad must be a multiple of
          ``page_size`` and is a static bucket (one trace per bucket);
        plen: () int32 true prompt length (logits read position plen-1;
          pad positions write KV that stays masked behind ``cur_lens``);
        pages: (Spad//page_size,) int32 physical page ids for this request
          (unused tail entries point one past the pool and drop).
      -> (next_token () int32, logits (V,) f32, k_pools', v_pools')

    Dense-family GQA only — the engine's paged path; other families keep
    the contiguous-cache decode.
    """
    cfg = model.cfg
    if cfg.family not in ("dense", "vlm") or getattr(cfg, "use_mla", False):
        raise ValueError(f"paged serving supports dense GQA, not {cfg.family}")

    @jax.named_scope("serve_prefill")
    def serve_prefill(params, k_pools, v_pools, tokens, plen, pages):
        x = jnp.take(params["embedding"], tokens, axis=0).astype(_dtype(cfg.dtype))
        positions = jnp.arange(tokens.shape[1])[None, :]

        def body(xx, p_l):
            return blocks.decoder_layer_prefill(p_l, xx, cfg, positions,
                                                "dense")

        x, cache = jax.lax.scan(body, x, params["layers"])
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        h_last = jax.lax.dynamic_index_in_dim(h[0], plen - 1, axis=0,
                                              keepdims=False)
        logits = jnp.einsum("d,dv->v", h_last,
                            model._unembed_weight(params).astype(h.dtype))
        # cache k/v: (L, 1, KH, Spad, hd) head-major -> the pools' pages
        nlayers, _, kh, spad, hd = cache["k"].shape
        npg = spad // page_size

        def paged(c, pools):
            c = jnp.transpose(c[:, 0], (0, 2, 1, 3))        # (L, Spad, KH, hd)
            return c.reshape(nlayers, npg, *pools.shape[2:]).astype(
                pools.dtype)                                # row-major pages

        k_pools = k_pools.at[:, pages].set(paged(cache["k"], k_pools),
                                           mode="drop")
        v_pools = v_pools.at[:, pages].set(paged(cache["v"], v_pools),
                                           mode="drop")
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits.astype(jnp.float32), k_pools, v_pools

    return jax.jit(serve_prefill, donate_argnums=(1, 2))


def make_paged_decode_step(model: LanguageModel):
    """One continuous-batching decode step over the paged pools.

    Returned step signature:
      serve_decode(params, k_pools, v_pools, page_table, cur_lens, active,
                   tokens)
        k_pools, v_pools: ``pool_shape``, donated and updated in place
          (see ``make_paged_prefill_step``);
        page_table: (B, max_pages) int32; cur_lens: (B,) int32 tokens
        already cached per row; active: (B,) bool; tokens: (B,) int32 last
        sampled token per row.
      -> (next_tokens (B,) int32, logits (B, V) f32, k_pools', v_pools',
          cur_lens')

    Every array is traced — the step compiles once per (B, max_pages)
    shape and the position state never round-trips through Python ints.
    The pools ride the layer loop's carry, never its scanned inputs or
    outputs: each layer writes its new token into the pool and gathers
    its pages from it by layer index, so no layer of the pool is sliced
    out, laid out again or stacked back.
    """
    cfg = model.cfg
    if cfg.family not in ("dense", "vlm") or getattr(cfg, "use_mla", False):
        raise ValueError(f"paged serving supports dense GQA, not {cfg.family}")

    @jax.named_scope("serve_decode")
    def serve_decode(params, k_pools, v_pools, page_table, cur_lens, active,
                     tokens):
        x = jnp.take(params["embedding"], tokens[:, None],
                     axis=0).astype(_dtype(cfg.dtype))      # (B, 1, D)
        pos = cur_lens[:, None]                             # (B, 1) per row

        def body(carry, inp):
            xx, kp, vp = carry
            p_l, layer = inp
            p_l = cast_params(p_l, cfg.dtype)
            h = rmsnorm(p_l["ln1"], xx, cfg.norm_eps)
            q, k, v = gqa_qkv(p_l["attn"], h, cfg)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
            out, kp, vp = paged_update_and_attend(
                q, k, v, kp, vp, layer, page_table, cur_lens, active,
                window=cfg.sliding_window)
            xx = xx + jnp.einsum("bshk,hkd->bsd", out, p_l["attn"]["w_o"])
            h = rmsnorm(p_l["ln2"], xx, cfg.norm_eps)
            xx = xx + mlp(p_l["mlp"], h)
            return (xx, kp, vp), None

        (x, k_pools, v_pools), _ = jax.lax.scan(
            body, (x, k_pools, v_pools),
            (params["layers"], jnp.arange(k_pools.shape[0])))
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, 0],
                            model._unembed_weight(params).astype(h.dtype))
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cur_new = cur_lens + active.astype(jnp.int32)
        return (next_tok, logits.astype(jnp.float32), k_pools, v_pools,
                cur_new)

    return jax.jit(serve_decode, donate_argnums=(1, 2))
