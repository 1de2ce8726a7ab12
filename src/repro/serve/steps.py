"""Serving steps: prefill and single-token decode (the shapes the
``decode_*`` / ``long_*`` dry-run cells lower), plus the paged variants
the continuous-batching engine runs.

The paged steps keep the whole KV cache in per-layer page pools
(L, P, KH, page, hd) indexed through a (B, max_pages) page table — §6's
disjoint-partition decomposition applied to serving: every request owns a
disjoint set of fixed-size pages of one shared cache block, appended as
it decodes.  Positions are carried as traced (B,) arrays — steps never
retrace across decode lengths or batch compositions.
"""
from __future__ import annotations

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

def make_paged_prefill_step(model: LanguageModel, page_size: int):
    """Prefill one request straight into its pages.

    Returned step signature:
      serve_prefill(params, k_pools, v_pools, tokens, plen, pages)
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
        # cache k/v: (L, 1, KH, Spad, hd) head-major -> page-major scatter
        nlayers, _, kh, spad, hd = cache["k"].shape
        npg = spad // page_size
        kc = cache["k"][:, 0].reshape(nlayers, kh, npg, page_size, hd)
        vc = cache["v"][:, 0].reshape(nlayers, kh, npg, page_size, hd)
        kc = jnp.transpose(kc, (0, 2, 1, 3, 4))
        vc = jnp.transpose(vc, (0, 2, 1, 3, 4))
        k_pools = k_pools.at[:, pages].set(kc.astype(k_pools.dtype),
                                           mode="drop")
        v_pools = v_pools.at[:, pages].set(vc.astype(v_pools.dtype),
                                           mode="drop")
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits.astype(jnp.float32), k_pools, v_pools

    return jax.jit(serve_prefill)


def make_paged_decode_step(model: LanguageModel):
    """One continuous-batching decode step over the paged pools.

    Returned step signature:
      serve_decode(params, k_pools, v_pools, page_table, cur_lens, active,
                   tokens)
        page_table: (B, max_pages) int32; cur_lens: (B,) int32 tokens
        already cached per row; active: (B,) bool; tokens: (B,) int32 last
        sampled token per row.
      -> (next_tokens (B,) int32, logits (B, V) f32, k_pools', v_pools',
          cur_lens')

    Every array is traced — the step compiles once per (B, max_pages)
    shape and the position state never round-trips through Python ints.
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

        def body(xx, inp):
            p_l, kp, vp = inp
            p_l = cast_params(p_l, cfg.dtype)
            h = rmsnorm(p_l["ln1"], xx, cfg.norm_eps)
            q, k, v = gqa_qkv(p_l["attn"], h, cfg)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
            out, kp, vp = paged_update_and_attend(
                q, k, v, kp, vp, page_table, cur_lens, active,
                window=cfg.sliding_window)
            xx = xx + jnp.einsum("bshk,hkd->bsd", out, p_l["attn"]["w_o"])
            h = rmsnorm(p_l["ln2"], xx, cfg.norm_eps)
            xx = xx + mlp(p_l["mlp"], h)
            return xx, (kp, vp)

        x, (k_pools, v_pools) = jax.lax.scan(
            body, x, (params["layers"], k_pools, v_pools))
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, 0],
                            model._unembed_weight(params).astype(h.dtype))
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cur_new = cur_lens + active.astype(jnp.int32)
        return (next_tok, logits.astype(jnp.float32), k_pools, v_pools,
                cur_new)

    return jax.jit(serve_decode)
