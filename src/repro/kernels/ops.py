"""jit'd wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU (this container) and False on TPU, so
the same call sites work in tests and on hardware.  Layout adaptation from
model conventions (B, S, H, hd) to kernel conventions (B, H, S, hd) lives
here, not in model code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import flash_decode as _fd
from . import partition_copy as _pc
from . import ssd_scan as _ssd
from ..core.objects import spans_overlap


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, q_offset=0.0, *, causal=True, window=0,
                    block_q=None, block_k=None, interpret=None):
    """Model layout: q (B,S,H,hd), k/v (B,S,KH,hd) → (B,S,H,hd_v).

    Differentiable (custom-VJP backward kernels); ``q_offset`` is the
    global position of q row 0 under context-parallel stripes — a traced
    operand, not a static argument, so shard_map `axis_index` products
    trace through.  ``block_q``/``block_k`` default to the trace-time
    autotuner (``repro.kernels.autotune``); ints pin the tiles.
    """
    interpret = _default_interpret() if interpret is None else interpret
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = _fa.flash_attention(qt, kt, vt, q_offset, causal=causal,
                              window=window, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return jnp.transpose(out, (0, 2, 1, 3))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=None):
    """Model layout: x (B,S,H,P), dt (B,S,H), B/C (B,S,N).

    Returns (y (B,S,H,P), state (B,H,P,N)).  A length that is not a
    multiple of the chunk is padded with dt=0 steps: no decay and no input
    contribution, so the state and the real outputs are unaffected.
    """
    interpret = _default_interpret() if interpret is None else interpret
    s = x.shape[1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    xt = jnp.transpose(x, (0, 2, 1, 3))
    dtt = jnp.transpose(dt, (0, 2, 1))
    y, st = _ssd.ssd_scan(xt, dtt, A, B, C, chunk=chunk, interpret=interpret)
    return jnp.transpose(y, (0, 2, 1, 3))[:, :s], st


@functools.partial(jax.jit, static_argnames=("dst_off", "src_off", "size",
                                             "interpret"))
def partition_copy_bytes(dst, src, *, dst_off, src_off, size, interpret=None):
    """§6.3 fallback copy on flat byte buffers.

    dst/src: (N,) uint8.  Returns new dst with src[src_off:src_off+size]
    written at dst_off.  Offsets/size need only be lane-aligned (128 B);
    32 KiB-aligned copies keep the tile-per-grid-step fast path, anything
    else routes through the fused masked-edge kernel as a single range.
    """
    interpret = _default_interpret() if interpret is None else interpret
    lanes = _pc.LANES
    block = 256 * lanes
    assert dst.shape[0] % lanes == 0 and src.shape[0] % lanes == 0
    assert dst_off % lanes == 0 and src_off % lanes == 0 and size % lanes == 0
    d2 = dst.reshape(-1, lanes)
    s2 = src.reshape(-1, lanes)
    if dst_off % block == 0 and src_off % block == 0 and size % block == 0:
        out = _pc.partition_copy(d2, s2, dst_off // lanes, src_off // lanes,
                                 size // lanes, interpret=interpret)
    else:
        out = _pc.multi_partition_copy(
            d2, s2, ((dst_off // lanes, src_off // lanes, size // lanes),),
            interpret=interpret)
    return out.reshape(-1)


def multi_partition_copy_bytes(dst, src, ranges, *, block_rows=256,
                               interpret=None):
    """Fused §6.3 copy of a whole partition set in one kernel launch.

    dst/src: (N,) uint8 byte buffers.  ``ranges`` is a sequence of
    ``(dst_off, src_off, size)`` byte triples, each a multiple of 128
    (lane granularity — NOT the 32 KiB tile granularity of
    :func:`partition_copy_bytes`).  Destination ranges must be mutually
    disjoint; overlap raises ``ValueError`` (§6.2 partitions are disjoint
    by construction, so an overlap is a caller bug).  Returns the new dst.
    """
    interpret = _default_interpret() if interpret is None else interpret
    lanes = _pc.LANES
    nd, ns = int(dst.shape[0]), int(src.shape[0])
    row_ranges = []
    for (d_off, s_off, size) in ranges:
        if size <= 0:
            raise ValueError(f"empty copy range ({d_off},{s_off},{size})")
        if d_off % lanes or s_off % lanes or size % lanes:
            raise ValueError(
                f"range ({d_off},{s_off},{size}) not 128-byte aligned")
        if d_off + size > nd or s_off + size > ns or d_off < 0 or s_off < 0:
            raise ValueError(
                f"range ({d_off},{s_off},{size}) out of bounds "
                f"(dst {nd}, src {ns})")
        row_ranges.append((d_off // lanes, s_off // lanes, size // lanes))
    if spans_overlap((d, d + n) for d, _, n in row_ranges):
        raise ValueError("destination ranges overlap")
    pad_d = (-nd) % lanes
    pad_s = (-ns) % lanes
    d2 = (jnp.pad(dst, (0, pad_d)) if pad_d else jnp.asarray(dst)) \
        .reshape(-1, lanes)
    s2 = (jnp.pad(src, (0, pad_s)) if pad_s else jnp.asarray(src)) \
        .reshape(-1, lanes)
    out = _pc.multi_partition_copy(d2, s2, tuple(row_ranges),
                                   block_rows=block_rows,
                                   interpret=interpret)
    return out.reshape(-1)[:nd]


@functools.partial(jax.jit, static_argnames=("window", "block_s",
                                             "interpret"))
def flash_decode(q, k_cache, v_cache, cur_len, *, window=0, block_s=None,
                 interpret=None):
    """Serving layout: q (B,1,H,hd), head-major caches (B,KH,S,hd).

    Returns (B, 1, H, hd_v).  cur_len = valid entries incl. the new
    token.  ``block_s`` defaults to ``autotune.plan_decode``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    b, one, h, hd = q.shape
    kh = k_cache.shape[1]
    g = h // kh
    qg = q.reshape(b, kh, g, hd)
    out = _fd.flash_decode(qg, k_cache, v_cache, cur_len, window=window,
                           block_s=block_s, interpret=interpret)
    return out.reshape(b, 1, h, out.shape[-1])
