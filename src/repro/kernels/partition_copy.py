"""Pallas TPU kernels materializing §6.3 ``ocrDbCopy(DB_COPY_PARTITION)``.

When the zero-copy view path is unavailable (partition crosses a device
boundary, or the runtime chose to materialize), the copy itself is the
fallback.  Two kernels implement it:

* :func:`partition_copy` — one contiguous tile-aligned range, one grid step
  per (rows × 128) tile staged through VMEM.
* :func:`multi_partition_copy` — a whole *partition set* in one
  ``pallas_call``: N disjoint ranges at lane (128 B) granularity, driven by
  scalar-prefetched per-block source/dest row tables.  Range lengths need
  not be block-aligned; edge tiles are handled by a masked read-modify-write
  so untouched destination rows are preserved bit-exactly.

Mosaic slices and DMAs the (rows, 128) uint8 view only at row offsets that
are multiples of its sublane tiling (:data:`ALIGN` rows).  A range may start
at any row, so every block moves an ``ALIGN``-aligned window one tile longer
than the block: the source window is rotated by the two offsets' residues
(widened to 32 bits, the only width Mosaic rotates) so its rows line up with
the destination window, and the valid rows are merged under a mask.

Above :data:`DMA_STAGE_BYTES` of buffer, the batched kernel's
whole-buffer VMEM residency stops being a plan (a 32 MiB spill buffer
doesn't fit a 16 MiB VMEM), so ``multi_partition_copy`` re-stages: the
buffers stay in HBM (``memory_space=pl.ANY``) and each grid step
moves one autotuner-sized chunk through a double-buffered VMEM stage
with explicit ``pltpu.make_async_copy`` DMAs — the next chunk's source
fetch is in flight while the current chunk merges.  Same tables, same
table order, same masked-RMW edge handling, so arrival-order/hazard
semantics are identical to the batched path.

dst/src are 2-D (N, 128) views of the flat byte buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

LANES = 128
# Row tiling of the (rows, LANES) uint8 view: dynamic row offsets of slices
# and DMAs must be multiples of it.
ALIGN = 8

# Buffer size above which multi_partition_copy switches from whole-buffer
# VMEM residency to the HBM-staged chunked-DMA kernel.
DMA_STAGE_BYTES = 16 * 2 ** 20


def dma_staged(dst_bytes: int, src_bytes: int) -> bool:
    """True when a copy over buffers this large takes the DMA-staged
    path (either buffer too big for whole-buffer VMEM residency)."""
    return max(dst_bytes, src_bytes) > DMA_STAGE_BYTES


def _aligned(row):
    """(row rounded down to ALIGN, residue) for a traced row index."""
    base = pl.multiple_of((row // ALIGN) * ALIGN, ALIGN)
    return base, row - base


def _merge_window(val, cur, shift, lo, n):
    """Rotate the source window ``val`` down by ``shift`` rows and keep its
    rows ``[lo, lo + n)`` over the destination window ``cur``."""
    win = val.shape[0]
    rolled = pltpu.roll(val.astype(jnp.uint32), shift, 0).astype(val.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (win, LANES), 0)
    keep = jnp.logical_and(rows >= lo, rows < lo + n)
    return jnp.where(keep, rolled, cur)


def _window_shift(d_res, s_res, win):
    """Roll amount that moves source-window row ``s_res`` to ``d_res``."""
    return (d_res - s_res + win) % win


def _copy_kernel(src_ref, dst_in_ref, o_ref):
    del dst_in_ref  # aliased with o_ref; untouched tiles keep dst contents
    o_ref[...] = src_ref[...]


def partition_copy(dst: jax.Array, src: jax.Array, dst_off_rows: int,
                   src_off_rows: int, rows: int, *, block_rows: int = 256,
                   interpret: bool = False) -> jax.Array:
    """Copy ``rows`` rows of ``src`` (from src_off_rows) into ``dst`` at
    dst_off_rows.  Rows are (·, 128) lanes.  Returns the new dst.

    Offsets and length must be multiples of ``block_rows`` (the §6.2
    partition-granularity constraint, tile-aligned on TPU); ops.py pads.
    """
    assert dst.shape[1] == LANES and src.shape[1] == LANES
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    assert dst_off_rows % block_rows == 0 and src_off_rows % block_rows == 0
    nb = rows // block_rows
    d_base = dst_off_rows // block_rows
    s_base = src_off_rows // block_rows

    return pl.pallas_call(
        _copy_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, LANES),
                               lambda i: (s_base + i, 0)),
                  pl.BlockSpec((block_rows, LANES),
                               lambda i: (d_base + i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES),
                               lambda i: (d_base + i, 0)),
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(src, dst)


def _block_tables(ranges, block_rows: int):
    """Flatten row ranges into per-grid-block (dst, src, valid-rows) tables."""
    d_tab, s_tab, n_tab = [], [], []
    for (d0, s0, rows) in ranges:
        nb = -(-rows // block_rows)
        for b in range(nb):
            d_tab.append(d0 + b * block_rows)
            s_tab.append(s0 + b * block_rows)
            n_tab.append(min(block_rows, rows - b * block_rows))
    return (np.asarray(d_tab, np.int32), np.asarray(s_tab, np.int32),
            np.asarray(n_tab, np.int32))


def multi_partition_copy(dst: jax.Array, src: jax.Array,
                         ranges, *, block_rows: int = 256,
                         interpret: bool = False) -> jax.Array:
    """Execute N disjoint-range copies in a single ``pallas_call``.

    ``ranges`` is a tuple of ``(dst_row, src_row, rows)`` row triples
    (a row is one 128-byte lane).  Offsets are lane-granular — no block
    alignment required; each range's edge tile is masked.  The grid has
    one step per ``block_rows`` tile of any range; the tile's source/dest
    rows come from scalar-prefetched tables, so the whole partition set
    costs one kernel launch.  Destination ranges must be disjoint
    (callers validate); results are bit-exact vs range-by-range numpy
    assignment.

    The offset tables are runtime operands: only the block *count* (their
    length) and buffer shapes key the jit cache, so flushes with new
    offsets but the same number of tiles reuse the compiled kernel.
    """
    assert dst.shape[1] == LANES and src.shape[1] == LANES
    if dma_staged(dst.shape[0] * LANES * dst.dtype.itemsize,
                  src.shape[0] * LANES * src.dtype.itemsize):
        total = sum(r for (_, _, r) in ranges)
        chunk = autotune.plan_copy_chunk(int(total))
        d_tab, s_tab, n_tab = _block_tables(ranges, chunk)
        if d_tab.shape[0] == 0:
            return dst
        return _multi_partition_copy_dma(
            dst, src, jnp.asarray(d_tab), jnp.asarray(s_tab),
            jnp.asarray(n_tab), chunk=chunk, interpret=interpret)
    d_tab, s_tab, n_tab = _block_tables(ranges, block_rows)
    if d_tab.shape[0] == 0:
        return dst
    return _multi_partition_copy_impl(
        dst, src, jnp.asarray(d_tab), jnp.asarray(s_tab), jnp.asarray(n_tab),
        block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _multi_partition_copy_impl(dst: jax.Array, src: jax.Array,
                               d_tab: jax.Array, s_tab: jax.Array,
                               n_tab: jax.Array, *, block_rows: int,
                               interpret: bool) -> jax.Array:
    total_blocks = int(d_tab.shape[0])
    nd = dst.shape[0]
    win = block_rows + ALIGN
    # pad by one window so edge tiles can load/store win full rows;
    # masked RMW keeps the pad rows' (and any untouched rows') contents
    dst_p = jnp.pad(dst, ((0, win), (0, 0)))
    src_p = jnp.pad(src, ((0, win), (0, 0)))

    def kernel(d_ref, s_ref, n_ref, src_ref, dst_in_ref, o_ref):
        del dst_in_ref  # aliased with o_ref; read through o_ref for RMW
        i = pl.program_id(0)
        da, d_res = _aligned(d_ref[i])
        sa, s_res = _aligned(s_ref[i])
        val = src_ref[pl.ds(sa, win), :]
        cur = o_ref[pl.ds(da, win), :]
        o_ref[pl.ds(da, win), :] = _merge_window(
            val, cur, _window_shift(d_res, s_res, win), d_res, n_ref[i])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(total_blocks,),
        in_specs=[pl.BlockSpec(src_p.shape, lambda i, *_: (0, 0)),
                  pl.BlockSpec(dst_p.shape, lambda i, *_: (0, 0))],
        out_specs=pl.BlockSpec(dst_p.shape, lambda i, *_: (0, 0)),
    )
    # whole src + dst blocks, double-buffered by the pipeline, plus the
    # widened rotate temporaries
    vmem = 2 * (src_p.size + dst_p.size) + 16 * win * LANES + 2 ** 20
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_p.shape, dst_p.dtype),
        # operand indices include the 3 scalar-prefetch tables: dst_in is 4
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem, 32 * 2 ** 20)),
        interpret=interpret,
    )(jnp.asarray(d_tab), jnp.asarray(s_tab), jnp.asarray(n_tab),
      src_p, dst_p)
    return out[:nd]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _multi_partition_copy_dma(dst: jax.Array, src: jax.Array,
                              d_tab: jax.Array, s_tab: jax.Array,
                              n_tab: jax.Array, *, chunk: int,
                              interpret: bool) -> jax.Array:
    """HBM-staged variant: buffers never become VMEM-resident blocks.

    src/dst live in ``pl.ANY`` (HBM on hardware); each grid step
    DMAs one ``chunk``-row table entry (as an ALIGN-aligned window one
    tile longer) through a two-slot VMEM stage —
    while chunk *i* merges, chunk *i+1*'s source fetch is already in
    flight (started one step ahead on the other slot/semaphore pair).
    The destination chunk is fetched, merged under the valid-row mask
    (same edge treatment as the batched kernel), and DMA'd back before
    the step ends, so table order — and therefore hazard/arrival
    semantics — matches the batched path exactly.
    """
    total_blocks = int(d_tab.shape[0])
    nd = dst.shape[0]
    win = chunk + ALIGN
    # pad by one window so edge tiles can move full-window DMAs; the
    # masked merge keeps pad-row (and untouched-row) contents
    dst_p = jnp.pad(dst, ((0, win), (0, 0)))
    src_p = jnp.pad(src, ((0, win), (0, 0)))

    def kernel(d_ref, s_ref, n_ref, src_ref, dst_in_ref, o_ref,
               scr, sdst, sem_a, sem_b, sem_d, sem_o):
        del dst_in_ref  # aliased with o_ref; RMW goes through o_ref
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def _src_copy(blk, slot, sem):
            sa, _ = _aligned(s_ref[blk])
            return pltpu.make_async_copy(
                src_ref.at[pl.ds(sa, win)], scr.at[slot], sem)

        @pl.when(i == 0)
        def _first():
            _src_copy(0, 0, sem_a).start()

        @pl.when(jnp.logical_and(i + 1 < n, (i + 1) % 2 == 0))
        def _prefetch_even():
            _src_copy(i + 1, 0, sem_a).start()

        @pl.when(jnp.logical_and(i + 1 < n, (i + 1) % 2 == 1))
        def _prefetch_odd():
            _src_copy(i + 1, 1, sem_b).start()

        def _merge(slot, sem):
            _src_copy(i, slot, sem).wait()
            da, d_res = _aligned(d_ref[i])
            _, s_res = _aligned(s_ref[i])
            dcp = pltpu.make_async_copy(
                o_ref.at[pl.ds(da, win)], sdst, sem_d)
            dcp.start()
            dcp.wait()
            scr[slot] = _merge_window(scr[slot], sdst[...],
                                      _window_shift(d_res, s_res, win),
                                      d_res, n_ref[i])
            ocp = pltpu.make_async_copy(
                scr.at[slot], o_ref.at[pl.ds(da, win)], sem_o)
            ocp.start()
            ocp.wait()

        @pl.when(i % 2 == 0)
        def _even():
            _merge(0, sem_a)

        @pl.when(i % 2 == 1)
        def _odd():
            _merge(1, sem_b)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(total_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, win, LANES), dst.dtype),
            pltpu.VMEM((win, LANES), dst.dtype),
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_p.shape, dst_p.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(d_tab, s_tab, n_tab, src_p, dst_p)
    return out[:nd]
