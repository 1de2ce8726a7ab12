"""Mamba2 (state-space duality) block: chunked training scan + O(1) decode.

Follows arXiv:2405.21060 (SSD): the sequence is split into chunks of
``ssm_chunk``; intra-chunk contributions are dense matmuls (MXU-friendly),
inter-chunk state is carried by a short ``lax.scan`` over chunks.  The
Pallas kernel (`repro.kernels.ssd_scan`) implements the same algorithm with
explicit VMEM tiling; this module is its jnp oracle and the dry-run path.

Decode is the recurrent form: state (B, H, P, N) updated per token — cache
size independent of sequence length (why SSM archs run ``long_500k``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Params, _dtype, dense_init, rmsnorm, rmsnorm_init


def mamba_init(key, cfg) -> Params:
    """Projections are stored *separately* per component (z, x, B, C, dt)
    rather than as one fused in_proj: the x/z parts are head-aligned and
    shard over the "model" axis, while B/C/dt are head-shared and stay
    replicated — a fused layout would interleave both (see DESIGN.md §6).
    """
    d = cfg.d_model
    di = cfg.d_inner
    n = cfg.ssm_state
    h = cfg.ssm_heads
    ck = cfg.conv_kernel
    dt = _dtype(cfg.param_dtype)
    keys = jax.random.split(key, 7)
    return {
        "w_z": dense_init(keys[0], d, (di,), dt),
        "w_x": dense_init(keys[1], d, (di,), dt),
        "w_B": dense_init(keys[2], d, (n,), dt),
        "w_C": dense_init(keys[3], d, (n,), dt),
        "w_dt": dense_init(keys[4], d, (h,), dt),
        "conv_x": (jax.random.normal(keys[5], (ck, di), jnp.float32)
                   / np.sqrt(ck)).astype(dt),
        "conv_b_x": jnp.zeros((di,), dtype=dt),
        "conv_B": (jax.random.normal(keys[6], (ck, n), jnp.float32)
                   / np.sqrt(ck)).astype(dt),
        "conv_b_B": jnp.zeros((n,), dtype=dt),
        "conv_C": (jax.random.normal(jax.random.fold_in(key, 7), (ck, n),
                                     jnp.float32) / np.sqrt(ck)).astype(dt),
        "conv_b_C": jnp.zeros((n,), dtype=dt),
        "A_log": jnp.zeros((h,), dtype=jnp.float32),
        "D": jnp.ones((h,), dtype=jnp.float32),
        "dt_bias": jnp.zeros((h,), dtype=jnp.float32),
        "norm": rmsnorm_init(di, dt),
        "out_proj": dense_init(jax.random.fold_in(key, 8), di, (d,), dt),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d.  xbc: (B, S, C); w: (K, C)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    s = xbc.shape[1]
    for i in range(k):
        out = out + pad[:, i: i + s].astype(jnp.float32) * w[i].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    return jax.nn.silu(out).astype(xbc.dtype)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array,
                B: jax.Array, C: jax.Array, chunk: int,
                initial_state: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x:  (b, s, h, p)   — per-head inputs
    dt: (b, s, h)      — positive step sizes (already softplus'd)
    A:  (h,)           — negative decay rates
    B:  (b, s, n)      — input projections (single group, shared over heads)
    C:  (b, s, n)      — output projections
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:
        # pad with dt=0 steps: decay exp(0)=1 and no input contribution, so
        # the carried state and real outputs are unaffected
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        y, st = ssd_chunked(x, dt, A, B, C, chunk, initial_state)
        return y[:, :s], st
    nc = s // chunk

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)

    dA = dtc.astype(jnp.float32) * A.astype(jnp.float32)       # (b,nc,q,h) ≤ 0
    cum = jnp.cumsum(dA, axis=2)                               # running log-decay
    total = cum[:, :, -1, :]                                   # (b,nc,h)

    # ---- intra-chunk (diagonal block): attention-like masked matmul
    CB = jnp.einsum("bcqn,bckn->bcqk", Cc.astype(jnp.float32),
                    Bc.astype(jnp.float32))                    # (b,nc,q,q)
    # decay from position k to q (q >= k): exp(cum_q - cum_k)
    decay = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])  # (b,nc,q,k,h)
    mask = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
    att = CB[..., None] * jnp.where(mask[None, None, :, :, None], decay, 0.0)
    att = att * dtc[:, :, None, :, :]                          # weight by dt_k
    y_diag = jnp.einsum("bcqkh,bckhp->bcqhp", att, xc.astype(jnp.float32))

    # ---- chunk states: contribution of chunk c to the carried state
    # state_c = sum_k exp(total_c - cum_k) * dt_k * B_k ⊗ x_k   (b,h,p,n)
    w = jnp.exp(total[:, :, None, :] - cum) * dtc               # (b,nc,q,h)
    states = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", w, Bc.astype(jnp.float32),
                        xc.astype(jnp.float32))

    # ---- inter-chunk recurrence
    if initial_state is None:
        init = jnp.zeros((b, h, p, n), dtype=jnp.float32)
    else:
        init = initial_state.astype(jnp.float32)

    decay_chunk = jnp.exp(total)                                # (b,nc,h)

    def carry_fn(state, inp):
        st_c, dec_c = inp                                       # (b,h,p,n), (b,h)
        prev = state
        new = prev * dec_c[:, :, None, None] + st_c
        return new, prev

    (final_state, prevs) = jax.lax.scan(
        carry_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decay_chunk, 1, 0)))
    prev_states = jnp.moveaxis(prevs, 0, 1)                     # (b,nc,h,p,n)

    # ---- off-diagonal: y_off = C_q · (exp(cum_q) * prev_state)
    outw = jnp.exp(cum)                                         # (b,nc,q,h)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc.astype(jnp.float32),
                       prev_states, outw)

    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_reference(x, dt, A, B, C, initial_state=None):
    """O(S) sequential-scan oracle for :func:`ssd_chunked` (tests only)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    state = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        dA = jnp.exp(dt_t.astype(jnp.float32) * A)              # (b,h)
        dBx = jnp.einsum("bh,bn,bhp->bhpn", dt_t.astype(jnp.float32),
                         B_t.astype(jnp.float32), x_t.astype(jnp.float32))
        state = state * dA[:, :, None, None] + dBx
        y_t = jnp.einsum("bhpn,bn->bhp", state, C_t.astype(jnp.float32))
        return state, y_t

    state, ys = jax.lax.scan(
        step, state,
        (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
         jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), state


def _mamba_proj(params: Params, x: jax.Array, cfg):
    """Shared projection + conv for train/prefill paths."""
    z = jnp.einsum("bsd,de->bse", x, params["w_z"])
    xr = jnp.einsum("bsd,de->bse", x, params["w_x"])
    Br = jnp.einsum("bsd,dn->bsn", x, params["w_B"])
    Cr = jnp.einsum("bsd,dn->bsn", x, params["w_C"])
    dt_raw = jnp.einsum("bsd,dh->bsh", x, params["w_dt"])
    xs = _causal_conv(xr, params["conv_x"], params["conv_b_x"])
    B = _causal_conv(Br, params["conv_B"], params["conv_b_B"])
    C = _causal_conv(Cr, params["conv_C"], params["conv_b_C"])
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    return z, xs, B, C, dt, A, (xr, Br, Cr)


def _mamba_out(params: Params, y_heads: jax.Array, xh: jax.Array, z: jax.Array,
               cfg, lead_shape) -> jax.Array:
    y = y_heads + params["D"].astype(jnp.float32)[:, None] * xh.astype(jnp.float32)
    y = y.reshape(*lead_shape, cfg.d_inner).astype(z.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(z.dtype)
    y = rmsnorm(params["norm"], y, cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, params["out_proj"])


def mamba_train(params: Params, x: jax.Array, cfg) -> jax.Array:
    """Full-sequence Mamba2 block (training / prefill compute): the Pallas
    SSD kernel on TPU, the jnp chunked scan elsewhere."""
    h, pdim = cfg.ssm_heads, cfg.ssm_head_dim
    z, xs, B, C, dt, A, _ = _mamba_proj(params, x, cfg)
    xh = xs.reshape(*xs.shape[:-1], h, pdim)
    if jax.default_backend() == "tpu":
        from repro.kernels import ops as kops
        y, _ = kops.ssd_scan(xh, dt, A, B, C, chunk=cfg.ssm_chunk)
    else:
        y, _ = ssd_chunked(xh, dt, A, B, C, cfg.ssm_chunk)
    return _mamba_out(params, y, xh, z, cfg, xs.shape[:-1])


def mamba_prefill(params: Params, x: jax.Array, cfg
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill returning the recurrent cache (conv tails + SSD state)."""
    h, pdim, ck = cfg.ssm_heads, cfg.ssm_head_dim, cfg.conv_kernel
    z, xs, B, C, dt, A, (xr, Br, Cr) = _mamba_proj(params, x, cfg)
    xh = xs.reshape(*xs.shape[:-1], h, pdim)
    y, state = ssd_chunked(xh, dt, A, B, C, cfg.ssm_chunk)
    out = _mamba_out(params, y, xh, z, cfg, xs.shape[:-1])
    cache = {
        "conv_x": xr[:, -(ck - 1):, :],     # pre-activation conv tails
        "conv_B": Br[:, -(ck - 1):, :],
        "conv_C": Cr[:, -(ck - 1):, :],
        "state": state.astype(jnp.float32),
    }
    return out, cache


def _conv_step(tail: jax.Array, new: jax.Array, w: jax.Array, b: jax.Array):
    """One-token causal conv: tail (B, K-1, C), new (B, 1, C)."""
    win = jnp.concatenate([tail, new], axis=1)                  # (B, K, C)
    out = jnp.einsum("bkc,kc->bc", win.astype(jnp.float32), w.astype(jnp.float32))
    out = jax.nn.silu(out + b.astype(jnp.float32))
    return out.astype(new.dtype), win[:, 1:, :]


def mamba_decode(params: Params, x: jax.Array, cfg,
                 cache: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token recurrent step.  x: (B, 1, D); O(1) in sequence length."""
    di, n, h, pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z = jnp.einsum("bsd,de->bse", x, params["w_z"])
    xr = jnp.einsum("bsd,de->bse", x, params["w_x"])
    Br = jnp.einsum("bsd,dn->bsn", x, params["w_B"])
    Cr = jnp.einsum("bsd,dn->bsn", x, params["w_C"])
    dt_raw = jnp.einsum("bsd,dh->bsh", x, params["w_dt"])

    xs, conv_x = _conv_step(cache["conv_x"], xr, params["conv_x"], params["conv_b_x"])
    B1, conv_B = _conv_step(cache["conv_B"], Br, params["conv_B"], params["conv_b_B"])
    C1, conv_C = _conv_step(cache["conv_C"], Cr, params["conv_C"], params["conv_b_C"])

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])[:, 0]  # (B,H)
    A = -jnp.exp(params["A_log"])
    xh = xs.reshape(xs.shape[0], h, pdim)                        # (B,H,P)
    dA = jnp.exp(dt * A)                                         # (B,H)
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt, B1.astype(jnp.float32),
                     xh.astype(jnp.float32))
    state = cache["state"] * dA[:, :, None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", state, C1.astype(jnp.float32))
    y = y[:, None]                                               # (B,1,H,P)
    out = _mamba_out(params, y, xh[:, None], z, cfg, (x.shape[0], 1))
    return out, {"conv_x": conv_x, "conv_B": conv_B, "conv_C": conv_C,
                 "state": state}
