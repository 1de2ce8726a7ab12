"""Mixture-of-Experts layer with capacity-bucketed all-to-all dispatch.

Expert parallelism (the default, ``cfg.moe_dispatch="a2a"``): tokens are
sharded over the "model" mesh axis alongside the expert banks.  Each shard
packs its local routed (token, choice) pairs into per-destination-expert
buckets of capacity ``C`` (drop on overflow, stats recorded), a single
``lax.all_to_all`` hands every peer exactly the §6-disjoint bucket ranges
bound for its local experts, the sort-based grouped-GEMM runs on purely
local experts, and the reverse all-to-all returns results to the source
shard for the gate-weighted combine.  Per shard this moves
``2 · E · C · D`` bucket bytes — independent of the model-axis width —
where the old replicate-over-"model" + psum combine moved the *full* token
set twice per shard (O(E) wasted bytes at production expert counts; see
``benchmarks/bench_moe.py``).  The exchange rides a custom VJP whose
backward is the *reverse* exchange, never a psum.

The legacy path (``moe_dispatch="psum"``) replicates activations over
"model", computes local experts against all tokens, and psums the combine.
It remains the fallback when the sequence does not divide the model axis,
and the baseline the a2a path is benchmarked against.

Token dropping: per-expert capacity ``C = ceil(k·T·capacity_factor / E)``
over the tokens T that route *together* (per source shard under a2a —
total expert capacity ``m·C`` matches the psum path's global ``C``).
Dropped (token, choice) pairs fall through on the residual path; drops are
deterministic — the pack is a stable sort, so the earliest tokens keep
their slots.

This mirrors the paper's §6 *data block partitioning* twice over: the
expert weight bank is one logical block partitioned E-ways, and each
shard's bucket buffer is one block whose per-destination ranges are the
disjoint §6 partitions the all-to-all exchanges (see
``repro.dist.sharding.moe_bucket_ranges`` for the lowering).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Params, dense_init, mlp, mlp_init, _dtype


def moe_init(key, cfg) -> Params:
    d = cfg.d_model
    e = cfg.num_experts
    f = cfg.moe_d_ff or cfg.d_ff
    dt = _dtype(cfg.param_dtype)
    keys = jax.random.split(key, 6)

    def expert_bank(k, shape_in, shape_out):
        ws = jax.random.normal(k, (e, shape_in, shape_out), dtype=jnp.float32)
        return (ws / np.sqrt(shape_in)).astype(dt)

    p: Params = {
        "router": dense_init(keys[0], d, (e,), jnp.float32),
        "w_gate": expert_bank(keys[1], d, f),
        "w_up": expert_bank(keys[2], d, f),
        "w_down": expert_bank(keys[3], f, d),
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = mlp_init(keys[4], d, f * cfg.num_shared_experts, dt)
    if cfg.moe_dense_residual:
        p["dense_residual"] = mlp_init(keys[5], d, cfg.d_ff, dt)
    return p


def _route(logits: jax.Array, k: int, renormalize: bool = True
           ) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing.  logits: (T, E) fp32 → (gates (T,k) fp32, idx (T,k) i32)."""
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    if renormalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def load_balance_loss(logits: jax.Array, idx: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss over ALL k routed choices: E · Σ_e f_e·P_e.

    ``f_e`` is the fraction of (token, choice) dispatch slots assigned to
    expert e — scoring only the top-1 choice (the old behaviour) let a hot
    expert hide in everyone's 2nd..k-th slots while the actual dispatch
    distribution overloaded it.  At k=1 this is the classic Switch loss.
    """
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    f = jnp.mean(jax.nn.one_hot(idx.reshape(-1), num_experts,
                                dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def zero_aux() -> Dict[str, jax.Array]:
    """Zero MoE aux pytree (dense layers / non-MoE backbones)."""
    z = jnp.zeros((), jnp.float32)
    return {"loss": z, "dropped": z, "routed": z, "a2a_bytes": z}


def _expert_positions(flat_e: jax.Array, n: int) -> jax.Array:
    """Rank of each (token, choice) within its expert, in token order:
    stable-sort by expert id, then position = index - start_of_run,
    where start_of_run propagates via a running maximum."""
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    arange_n = jnp.arange(n, dtype=jnp.int32)
    new_run = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                               sorted_e[1:] != sorted_e[:-1]])
    starts = jnp.where(new_run, arange_n, 0)
    starts = jax.lax.associative_scan(jnp.maximum, starts)
    pos_sorted = arange_n - starts
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)


def _grouped_experts(x_flat: jax.Array, gates: jax.Array, idx: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     capacity: int, e_offset: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Sort-based grouped-GEMM dispatch for one shard's local experts.

    x_flat: (T, D); gates/idx: (T, k); w_*: (E_loc, D, F)/(E_loc, F, D).
    Returns ``(y, kept)``: (T, D) sum of local-expert contributions
    (token-dropped beyond ``capacity``) and the per-token count of routed
    choices that landed in this shard's window *and* kept their slot.
    """
    t, d = x_flat.shape
    k = idx.shape[1]
    e_loc = w_gate.shape[0]
    n = t * k

    flat_e = idx.reshape(n)                                   # global expert ids
    flat_g = gates.reshape(n)
    tok_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    pos = _expert_positions(flat_e, n)

    local_e = flat_e - e_offset
    valid = (local_e >= 0) & (local_e < e_loc) & (pos < capacity) & (flat_g > 0)
    safe_e = jnp.where(valid, local_e, 0).astype(jnp.int32)
    safe_pos = jnp.where(valid, pos, capacity).astype(jnp.int32)  # row C = trash

    w = (flat_g * valid).astype(jnp.float32)
    x_grouped = _dispatch(x_flat, safe_e, safe_pos, tok_ids, w,
                          e_loc, capacity, str(x_flat.dtype), t)

    g = jnp.einsum("ecd,edf->ecf", x_grouped, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x_grouped, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x_flat.dtype) * u
    y_grouped = jnp.einsum("ecf,efd->ecd", h, w_down)         # (E_loc, C, D)

    y = _combine(y_grouped, safe_e, safe_pos, tok_ids, w, t)
    kept = jnp.sum(valid.reshape(t, k), axis=1).astype(jnp.float32)
    return y.astype(x_flat.dtype), kept


def _chunks(n: int, target: int = 16384) -> int:
    c = min(n, target)
    while n % c:
        c //= 2
    return c


def _chunked(arrs, c):
    return tuple(a.reshape(a.shape[0] // c, c, *a.shape[1:]) for a in arrs)


# Dispatch and combine are (bi)linear scatter/gathers over the routing
# tables.  They run as chunked scans so the (T·k, D) gather never
# materializes, and carry custom VJPs so the *backward* is the mirror-image
# chunked scan (plain autodiff of the scan would stack per-chunk gather
# residuals — O(T·k·D) again).

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _dispatch(x_flat, e, p, t, w, e_loc, capacity, dtype_name, t_total):
    d = x_flat.shape[1]
    c = _chunks(e.shape[0])

    def step(acc, inp):
        e_i, p_i, t_i, w_i = inp
        xc = x_flat[t_i] * (w_i > 0)[:, None].astype(x_flat.dtype)
        return acc.at[e_i, p_i].add(xc, mode="drop"), None

    acc0 = jnp.zeros((e_loc, capacity + 1, d), dtype=x_flat.dtype)
    acc, _ = jax.lax.scan(step, acc0, _chunked((e, p, t, w), c))
    return acc[:, :capacity]


def _dispatch_fwd(x_flat, e, p, t, w, e_loc, capacity, dtype_name, t_total):
    out = _dispatch(x_flat, e, p, t, w, e_loc, capacity, dtype_name, t_total)
    return out, (e, p, t, w)


def _dispatch_bwd(e_loc, capacity, dtype_name, t_total, res, g_out):
    (e, p, t, w) = res
    d = g_out.shape[-1]
    g_ext = jnp.concatenate(
        [g_out, jnp.zeros((e_loc, 1, d), g_out.dtype)], axis=1)
    c = _chunks(e.shape[0])

    def step(acc, inp):
        e_i, p_i, t_i, w_i = inp
        gc = g_ext[e_i, p_i] * (w_i > 0)[:, None].astype(g_ext.dtype)
        return acc.at[t_i].add(gc, mode="drop"), None

    dx0 = jnp.zeros((t_total, d), dtype=g_out.dtype)
    dx, _ = jax.lax.scan(step, dx0, _chunked((e, p, t, w), c))
    return (dx.astype(dtype_name), None, None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(y_grouped, e, p, t, w, t_total):
    d = y_grouped.shape[-1]
    y_ext = jnp.concatenate(
        [y_grouped, jnp.zeros((y_grouped.shape[0], 1, d), y_grouped.dtype)],
        axis=1)
    c = _chunks(e.shape[0])

    def step(acc, inp):
        e_i, p_i, t_i, w_i = inp
        yc = y_ext[e_i, p_i].astype(jnp.float32) * w_i[:, None]
        return acc.at[t_i].add(yc, mode="drop"), None

    y0 = jnp.zeros((t_total, d), dtype=jnp.float32)
    y, _ = jax.lax.scan(step, y0, _chunked((e, p, t, w), c))
    return y


def _combine_fwd(y_grouped, e, p, t, w, t_total):
    return _combine(y_grouped, e, p, t, w, t_total), (y_grouped, e, p, t, w)


def _combine_bwd(t_total, res, dy):
    y_grouped, e, p, t, w = res
    e_loc, cap, d = y_grouped.shape
    c = _chunks(e.shape[0])

    def step(carry, inp):
        dg_acc, dw_parts = carry
        e_i, p_i, t_i, w_i = inp
        dy_rows = dy[t_i]                                    # (c, D) f32
        dg_acc = dg_acc.at[e_i, p_i].add(
            (dy_rows * w_i[:, None]).astype(dg_acc.dtype), mode="drop")
        yg = jnp.concatenate(
            [y_grouped, jnp.zeros((e_loc, 1, d), y_grouped.dtype)], axis=1
        )[e_i, p_i].astype(jnp.float32)
        dw_i = jnp.sum(yg * dy_rows, axis=-1)                # (c,)
        return (dg_acc, dw_parts), dw_i

    dg0 = jnp.zeros((e_loc, cap + 1, d), dtype=jnp.float32)
    (dg, _), dws = jax.lax.scan(step, (dg0, 0.0), _chunked((e, p, t, w), c))
    dw = dws.reshape(-1)
    return (dg[:, :cap].astype(y_grouped.dtype), None, None, None, dw)


_combine.defvjp(_combine_fwd, _combine_bwd)


# ------------------------------------------------------ all-to-all exchange

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _exchange(buckets: jax.Array, axis: str) -> jax.Array:
    """Bucket exchange over ``axis`` (inside shard_map): leading dim m is
    the per-peer split — peer j receives our block j, we receive every
    peer's block i at position i (source-major)."""
    return jax.lax.all_to_all(buckets, axis, split_axis=0, concat_axis=0)


def _exchange_fwd(buckets, axis):
    return _exchange(buckets, axis), None


def _exchange_bwd(axis, _res, g):
    # the transpose of the bucket exchange is the REVERSE exchange (the
    # peer-block permutation is an involution) — dispatch mirrors to
    # combine without ever widening to a psum
    return (jax.lax.all_to_all(g, axis, split_axis=0, concat_axis=0),)


_exchange.defvjp(_exchange_fwd, _exchange_bwd)


def _a2a_experts(x_flat: jax.Array, gates: jax.Array, idx: jax.Array,
                 w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                 capacity: int, m: int, axis: str
                 ) -> Tuple[jax.Array, jax.Array]:
    """Capacity-bucketed all-to-all dispatch (inside shard_map).

    x_flat: (T_loc, D) — this shard's *disjoint* tokens.  Packs the routed
    (token, choice) pairs into per-destination-expert buckets (E, C, D)
    via the same stable-sort tables as :func:`_grouped_experts`, exchanges
    the per-destination §6 ranges with the peers over ``axis``, runs the
    local experts on the received (E_loc, m·C, D), and reverse-exchanges
    the results for the gate-weighted combine back on the source shard.
    Returns ``(y (T_loc, D), kept (T_loc,))``.
    """
    t, d = x_flat.shape
    k = idx.shape[1]
    e_loc = w_gate.shape[0]
    e = e_loc * m                                             # global experts
    n = t * k

    flat_e = idx.reshape(n).astype(jnp.int32)
    flat_g = gates.reshape(n)
    tok_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    pos = _expert_positions(flat_e, n)
    valid = (pos < capacity) & (flat_g > 0)
    safe_pos = jnp.where(valid, pos, capacity).astype(jnp.int32)  # row C = trash
    w = (flat_g * valid).astype(jnp.float32)

    # pack: bucket (g, c) holds this shard's c-th surviving token for
    # global expert g; overflow lands in the trash row and is dropped
    buckets = _dispatch(x_flat, flat_e, safe_pos, tok_ids, w,
                        e, capacity, str(x_flat.dtype), t)

    # exchange: reshaped (m, E_loc, C, D), peer j's slice is exactly the
    # contiguous §6 range covering its experts [j·E_loc, (j+1)·E_loc)
    recv = _exchange(buckets.reshape(m, e_loc, capacity, d), axis)
    x_grouped = jnp.moveaxis(recv, 0, 1).reshape(e_loc, m * capacity, d)

    g = jnp.einsum("ecd,edf->ecf", x_grouped, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x_grouped, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x_flat.dtype) * u
    y_grouped = jnp.einsum("ecf,efd->ecd", h, w_down)     # (E_loc, m·C, D)

    # reverse exchange: source shard gets back its own bucket layout
    back = _exchange(jnp.moveaxis(
        y_grouped.reshape(e_loc, m, capacity, d), 1, 0), axis)
    y = _combine(back.reshape(e, capacity, d), flat_e, safe_pos, tok_ids,
                 w, t)
    kept = jnp.sum(valid.reshape(t, k), axis=1).astype(jnp.float32)
    return y.astype(x_flat.dtype), kept


def _capacity(cfg, tokens: int) -> int:
    """Per-expert bucket capacity over ``tokens`` routing together: the
    psum path rounds up to 8 (lane-friendly grouped GEMM over (E, C));
    the a2a path calls :func:`_a2a_capacity` instead — its GEMM batches
    m·C rows, so tiny per-source buckets stay tight."""
    c = int(np.ceil(cfg.experts_per_token * tokens * cfg.capacity_factor
                    / cfg.num_experts))
    return max(8, int(np.ceil(c / 8) * 8))


def _a2a_capacity(cfg, tokens: int) -> int:
    c = int(np.ceil(cfg.experts_per_token * tokens * cfg.capacity_factor
                    / cfg.num_experts))
    return max(1, c)


def moe_ffn(params: Params, x: jax.Array, cfg
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """MoE feed-forward.  x: (B, S, D) → (y, aux dict).

    The aux dict carries the balance loss plus dispatch stats
    (``dropped`` / ``routed`` (token, choice) counts and the per-device
    ``a2a_bytes`` the exchange moves per layer); layers sum it through the
    backbone scan and the trainer surfaces it as Stats gauges.

    Routing (cheap, (T,E)) runs in global view; expert compute runs under
    ``shard_map`` when a mesh with a "model" axis is ambient, expert banks
    sharded E→"model" (EP) and D→"data" (FSDP, re-gathered per layer).
    ``cfg.moe_dispatch`` picks the EP combine:

    * ``"a2a"`` (default): tokens shard S→"model"; each shard packs
      per-destination-expert capacity buckets and two ``all_to_all``s
      exchange exactly the §6-disjoint routed ranges (see module docs).
    * ``"psum"``: tokens replicate over "model"; every shard computes its
      local experts against all tokens and a full-width psum combines —
      the O(E)-wasteful baseline, kept for fallback (S not divisible by
      the model axis) and for ``bench_moe``'s comparison.
    """
    from repro.dist.sharding import current_ctx, moe_bucket_ranges
    from jax.sharding import PartitionSpec as P

    ctx = current_ctx()
    b, s, d = x.shape
    t = b * s

    m = ctx.model_size
    use_shmap = (ctx.active and m > 1 and cfg.num_experts % m == 0
                 and not ctx.pure_dp)
    dispatch = getattr(cfg, "moe_dispatch", "a2a")
    use_a2a = (use_shmap and dispatch == "a2a"
               and ctx.resolve("sp", s) is not None)

    # a2a keeps tokens sharded over "model" (matching the blocks' sp
    # constraint — no gather at shard_map entry); psum replicates them
    x = ctx.constrain(x, "dp", "sp" if use_a2a else None, None)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["router"])
    logits = ctx.constrain(logits, "dp", "sp" if use_a2a else None, None)
    gates, idx = _route(logits.reshape(t, cfg.num_experts),
                        cfg.experts_per_token)
    aux = load_balance_loss(logits.reshape(t, cfg.num_experts), idx,
                            cfg.num_experts)
    gates_b = gates.reshape(b, s, -1)
    idx_b = idx.reshape(b, s, -1)
    routed = jnp.asarray(float(t * cfg.experts_per_token), jnp.float32)
    a2a_bytes = jnp.zeros((), jnp.float32)

    dp_b = ctx.resolve("dp", b) if use_shmap else None
    # FSDP axes the expert banks are sharded over (may span pod+data)
    fs = ctx.resolve("fsdp", d) if use_shmap else None

    def _gather_banks(wg, wu, wd):
        if fs is not None:
            wg = jax.lax.all_gather(wg, fs, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fs, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fs, axis=2, tiled=True)
        return wg, wu, wd

    if not use_shmap:
        y, kept = _grouped_experts(
            x.reshape(t, d), gates, idx,
            params["w_gate"], params["w_up"], params["w_down"],
            _capacity(cfg, t), 0)
        y = y.reshape(b, s, d)
        kept_b = kept.reshape(b, s)
    elif use_a2a:
        dp_names = dp_b if isinstance(dp_b, tuple) else \
            ((dp_b,) if dp_b else ())
        dp_size = 1
        for a_ in dp_names:
            dp_size *= ctx.axis_sizes[a_]
        cap = _a2a_capacity(cfg, t // (dp_size * m))   # == inner tl
        # per-device bucket bytes per layer: two exchanges over the §6
        # destination ranges of one shard's (E, C, D) bucket block
        ranges = moe_bucket_ranges(cfg.num_experts, cap, d,
                                   x.dtype.itemsize, ctx)
        a2a_bytes = jnp.asarray(2.0 * sum(sz for _, sz in ranges),
                                jnp.float32)

        def inner_a2a(xx, gg, ii, wg, wu, wd):
            wg, wu, wd = _gather_banks(wg, wu, wd)
            bl, sl, _ = xx.shape
            tl = bl * sl
            y, kept = _a2a_experts(xx.reshape(tl, d), gg.reshape(tl, -1),
                                   ii.reshape(tl, -1), wg, wu, wd,
                                   _a2a_capacity(cfg, tl), m, "model")
            return y.reshape(bl, sl, d), kept.reshape(bl, sl)

        xspec = P(dp_b, "model", None)
        fn = jax.shard_map(
            inner_a2a, mesh=ctx.mesh,
            in_specs=(xspec, xspec, xspec,
                      P("model", fs, None), P("model", fs, None),
                      P("model", None, fs)),
            out_specs=(xspec, P(dp_b, "model")), check_vma=False)
        y, kept_b = fn(x, gates_b, idx_b.astype(jnp.int32),
                       params["w_gate"], params["w_up"], params["w_down"])
    else:
        e_loc = cfg.num_experts // m

        def inner(xx, gg, ii, wg, wu, wd):
            wg, wu, wd = _gather_banks(wg, wu, wd)
            bl, sl, _ = xx.shape
            tl = bl * sl
            e_off = jax.lax.axis_index("model") * e_loc
            y, kept = _grouped_experts(xx.reshape(tl, d), gg.reshape(tl, -1),
                                       ii.reshape(tl, -1), wg, wu, wd,
                                       _capacity(cfg, tl), e_off)
            y = jax.lax.psum(y, "model")
            # each choice is kept by exactly one owning shard (or dropped)
            kept = jax.lax.psum(kept, "model")
            return y.reshape(bl, sl, d), kept.reshape(bl, sl)

        xspec = P(dp_b, None, None)
        fn = jax.shard_map(
            inner, mesh=ctx.mesh,
            in_specs=(xspec, xspec, xspec,
                      P("model", fs, None), P("model", fs, None),
                      P("model", None, fs)),
            out_specs=(xspec, P(dp_b, None)), check_vma=False)
        y, kept_b = fn(x, gates_b, idx_b.astype(jnp.int32),
                       params["w_gate"], params["w_up"], params["w_down"])

    dropped = routed - jnp.sum(kept_b)
    auxd = {"loss": aux, "dropped": dropped, "routed": routed,
            "a2a_bytes": a2a_bytes}

    if "shared" in params:
        y = y + mlp(params["shared"], x)
    if "dense_residual" in params:
        y = y + mlp(params["dense_residual"], x)
    return y, auxd
