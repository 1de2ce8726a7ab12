"""Trace-time block autotuning for the Pallas kernels (§6 partitioning).

The paper's §6 data-block partitioning promises tile-granular accesses at
hardware speed — but a *fixed* tile size can't deliver that across shapes:
a 512-row q block on a 68-row context-parallel stripe is 87 % edge-tile
waste, while 64-row blocks on a 4096-token sequence pay 4096 grid-step
overheads for work 8× fewer steps could do.  So the partition size is
chosen at **trace time** from the static shape, via a cost model with
three terms:

* **VMEM footprint** — every candidate is rejected unless its resident
  tiles (double-buffered streamed operands + f32 scratch carries) fit the
  per-kernel budget.  This is a hard constraint, not a cost term.
* **edge-tile waste** — padded tiles do masked work on dead rows/cols;
  the model charges the *padded* MAC count, so a block that divides the
  sequence beats one that overhangs it.
* **grid-step count** — each grid step pays a fixed overhead (pipeline
  bookkeeping on TPU, interpreter dispatch in interpret mode) plus the
  k/v tile re-fetch.  Fewer, larger steps amortize it; the VMEM budget
  caps how far that goes.

Beyond (block_q, block_k) the planner picks two structural knobs the
fixed-constant path never had:

* ``g_fold`` — how many GQA query heads of one kv head share a grid
  step.  Folded heads reuse the streamed k/v tile (G× fewer k/v fetches)
  and batch their MACs into one dot; the q tile grows gf×, so VMEM
  decides.
* ``fused`` backward — when dk/dv for the whole (padded) kv sequence fit
  in VMEM, the backward runs as ONE kernel computing dq, dk and dv per
  tile visit, recomputing the probability tile once instead of once per
  pass (~30 % fewer MACs than the dq-pass + dkv-pass split).

Plans are pure functions of static ints — cached, deterministic, no
runtime measurement — so they never retrace and behave identically on
every host.  Config overrides (``attn_block_q/k``) win over the model
when set.
"""
from __future__ import annotations

import dataclasses
import functools
import os

__all__ = [
    "AttnPlan", "plan_attention", "plan_decode", "plan_copy_chunk",
    "min_block", "edge_waste", "live_tiles", "vmem_budget_bytes",
    "MIN_BLOCK", "MAX_BLOCK", "DEFAULT_VMEM_BUDGET", "LANES",
]

MIN_BLOCK = 16               # smallest tile the planner will choose
MAX_BLOCK = 2048             # largest tile the planner will consider
LANES = 128

# Default per-kernel VMEM budget: sized for a TPU v4-ish core (16 MiB
# VMEM) with headroom for the Mosaic pipeline's own buffers.
DEFAULT_VMEM_BUDGET = 12 * 2 ** 20
# Interpret-mode "VMEM" is host RAM: a larger per-kernel working set is
# harmless, and the 512-row tiles it admits are the measured winners at
# hd=128 (a 12 MiB budget rejects them and forces losing 128/256 tiles).
INTERPRET_VMEM_BUDGET = 32 * 2 ** 20
# Largest *grid-path* tile per backend.  Interpret stops at 512: every
# committed bench shape was measured at 128/256/512 and 512 wins, while
# >512 tiles blow up the in-loop transients without measured benefit.
GRID_BLOCK_CAP = {"interpret": 512, "tpu": MAX_BLOCK}

# Per-grid-step fixed overhead, in MAC-equivalents (1 MAC ≈ 0.015 ns on
# the ~65 GMAC/s single-core interpret baseline; ~100 GMAC/s/core TPU).
STEP_COST = {"interpret": 500_000, "tpu": 100_000}
# Cost per streamed byte, in MAC-equivalents (HBM→VMEM ~1 MAC/byte at
# TPU roofline; interpret's slicing traffic is modeled by
# STEP_BYTE_COST below instead).
BYTE_COST = {"interpret": 0.0, "tpu": 1.0}
# Interpret's dominant per-step cost: the interpreter touches the WHOLE
# operand buffers on every grid step (block gather/scatter over the
# full arrays), so each step costs ~0.17 ns/byte of total pass
# footprint (~6 GB/s memcpy) — fitted from the committed sweep at
# S ∈ {1024, 4096}: 0.63 ms/step @ 4 MB operands, 2.6 ms/step @ 16 MB.
# Steps skipped by ``pl.when`` still pay about half (gather/scatter
# happens; the body doesn't).  A compiled TPU pipeline streams only the
# tiles (BYTE_COST) — this term is zero there.
STEP_BYTE_COST = {"interpret": 11.0, "tpu": 0.0}
# Cost per softmax-matrix element (the exp/where/max chain), in
# MAC-equivalents.  Fitted from the sweep: a live in-loop tile costs
# ~8.2 ns/elem *including* its MACs → ~390 MACs/elem of pure
# elementwise; the flat (single-step) computation runs the same chain
# at ~5.3 ns/elem → ~340.  The in-loop/flat gap is what lets the
# single-step megakernel win at small shapes.  TPU pipelines the VPU
# chain behind the MXU: near-free.
ELEM_COST = {"interpret": 390.0, "interpret_flat": 340.0, "tpu": 2.0,
             "tpu_flat": 2.0}
# Feasibility gate for the single-step megakernels.  On TPU the whole
# problem must genuinely sit in VMEM, so the regular budget applies
# (None = use the VMEM budget).  In interpret mode "VMEM" is host RAM
# and the gate only bounds the materialized (B·KH·G·S·S) softmax
# transients.
MEGA_BUDGET = {"interpret": 192 * 2 ** 20, "tpu": None}
# What Mosaic holds in VMEM beyond the pipelined blocks, per grid kernel:
# f32 score-sized (gf·bq, bk) tiles the body keeps live at once (s, p, the
# mask, dp, ds) and f32 (gf·bq, 1) row columns (m/l scratch, lse/delta
# blocks, double-buffered).  On TPU a minor dim is padded to the 128-lane
# width, so a column costs 128 floats per row.  Counts fitted to the v5e
# compiler, which refuses the fused backward at gf·bq=1536, bk=512 and the
# forward at 3072×1024.  Interpret-mode transients are host arrays that
# its budget does not bound.
TILE_TRANSIENTS = {"tpu": {"fwd": (4, 4), "dq": (5, 4), "dkv": (5, 4),
                           "fused": (6, 4)}}
LANE_WIDTH = {"tpu": 128}


def _transients(backend: str, kernel: str, rows: int, bk: int) -> int:
    tiles, cols = TILE_TRANSIENTS.get(backend, {}).get(kernel, (0, 0))
    lanes = LANE_WIDTH.get(backend, 1)
    return (tiles * max(bk, lanes) + cols * lanes) * rows * 4


def vmem_budget_bytes(backend: str = "tpu") -> int:
    """Per-kernel VMEM budget (bytes); ``REPRO_VMEM_BUDGET`` overrides."""
    env = os.environ.get("REPRO_VMEM_BUDGET")
    if env is not None:
        return int(env)
    if backend.startswith("interpret"):
        return INTERPRET_VMEM_BUDGET
    return DEFAULT_VMEM_BUDGET


def min_block() -> int:
    """Smallest block the planner can pick — the floor consumers like
    ``flash_min_seq`` derive thresholds from (a sequence of
    ``2·min_block()`` is the shortest that can fill two q tiles)."""
    return MIN_BLOCK


def edge_waste(seq: int, block: int) -> float:
    """Dead fraction of the padded sequence: (padded − live) / live.

    Monotone non-increasing in ``seq`` between multiples of ``block``
    (more live rows amortize the same pad), zero exactly at multiples.
    """
    if seq <= 0:
        return 0.0
    padded = -(-seq // block) * block
    return (padded - seq) / seq


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _pow2s(lo: int, hi: int):
    b = lo
    while b <= hi:
        yield b
        b *= 2


def live_tiles(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
               window: int, kv_len: int, diag_aligned: bool = True) -> int:
    """Tiles the kernel actually computes (the ``pl.when`` skip count).

    ``diag_aligned``: the q rows end at the kv end (the local
    sq == kv_len case — offset statically known to be kv_len − sq).
    Under context-parallel stripes the offset is a *traced*
    ``axis_index`` product, so no tile is provably dead at trace time
    and every tile counts.
    """
    nq, nk = _ceil_div(sq, block_q), _ceil_div(sk, block_k)
    if not diag_aligned:
        if kv_len < nk * block_k:
            nk_live = _ceil_div(kv_len, block_k)
            return nq * nk_live
        return nq * nk
    off = max(kv_len - sq, 0)
    live = 0
    for i in range(nq):
        for j in range(nk):
            k0 = j * block_k
            if k0 >= kv_len:
                continue
            q_last = off + (i + 1) * block_q - 1
            if causal and k0 > q_last:
                continue
            if window > 0:
                q0 = off + i * block_q
                if q0 - (k0 + block_k - 1) >= window:
                    continue
            live += 1
    return live


def _divisors(n: int):
    return [d for d in range(1, n + 1) if n % d == 0]


@dataclasses.dataclass(frozen=True)
class AttnPlan:
    """Blocks chosen for one flash-attention shape — fwd and both
    backward structures.  Hashable (it rides ``custom_vjp`` nondiff
    args and jit static args)."""
    block_q: int                 # forward q tile rows
    block_k: int                 # forward k tile rows
    g_fold: int                  # query heads per grid step (divides G)
    fused_bwd: bool              # one fused dq+dk+dv kernel?
    # dq-pass blocks (two-call backward; also the fused kernel's tiles)
    dq_block_q: int
    dq_block_k: int
    # dk/dv-pass blocks; dkv_block_q is the q-reduction block riding the
    # innermost sequential grid dim
    dkv_block_q: int
    dkv_block_k: int
    vmem_bytes: int              # worst per-kernel footprint estimate
    # single-step folded kernels: the whole (B, KH) problem in one grid
    # step, batch/kv-head loop unrolled in the body.  Escapes the
    # interpret backend's in-loop elementwise penalty; only chosen when
    # the single-tile footprint fits the budget.
    mega_fwd: bool = False
    mega_bwd: bool = False
    # batch-tiled mega: grid over B only, one batch row per step.  The
    # softmax transient shrinks by 1/B, so serving-size batches keep the
    # flat elementwise chain when the full-batch transient blows
    # MEGA_BUDGET; each extra grid step costs one STEP_COST.
    mega_fwd_bt: bool = False
    mega_bwd_bt: bool = False

    @property
    def padded_q(self):
        """Pad target for sq: lcm-free — every pass block divides
        blocks chosen as divisors of the fwd-padded length."""
        return self.block_q

    def describe(self) -> str:
        fb = "fused" if self.fused_bwd else \
            f"dq{self.dq_block_q}x{self.dq_block_k}/" \
            f"dkv{self.dkv_block_q}x{self.dkv_block_k}"
        mega = "".join([" mega_fwd" if self.mega_fwd else "",
                        " mega_bwd" if self.mega_bwd else "",
                        " mega_fwd_bt" if self.mega_fwd_bt else "",
                        " mega_bwd_bt" if self.mega_bwd_bt else ""])
        return (f"bq{self.block_q} bk{self.block_k} gf{self.g_fold} "
                f"bwd={fb} vmem={self.vmem_bytes // 1024}KiB{mega}")


def _fwd_vmem(bq: int, bk: int, gf: int, hd: int, hd_v: int,
              in_bytes: int) -> int:
    # streamed k/v tiles are double-buffered by the pipeline; q/out/lse
    # change only with the outer q index but budget them buffered too
    tiles = 2 * (bk * (hd + hd_v)) * in_bytes \
        + 2 * (gf * bq * (hd + hd_v + 1)) * in_bytes
    scratch = gf * bq * (hd_v + 2) * 4          # acc, m, l (f32)
    return tiles + scratch


def _dq_vmem(bq: int, bk: int, gf: int, hd: int, hd_v: int,
             in_bytes: int) -> int:
    tiles = 2 * (bk * (hd + hd_v)) * in_bytes \
        + 2 * (gf * bq * (hd + hd_v + 2 + hd)) * in_bytes
    scratch = gf * bq * hd * 4                  # dq accumulator
    return tiles + scratch


def _dkv_vmem(bq: int, bk: int, gf: int, hd: int, hd_v: int,
              in_bytes: int) -> int:
    tiles = 2 * (gf * bq * (hd + hd_v + 2)) * in_bytes \
        + 2 * (bk * (hd + hd_v)) * in_bytes * 2     # k/v in + dk/dv out
    scratch = bk * (hd + hd_v) * 4              # dk, dv accumulators
    return tiles + scratch


def _fused_vmem(bq: int, bk: int, g: int, sk_p: int, hd: int, hd_v: int,
                in_bytes: int) -> int:
    tiles = 2 * (bk * (hd + hd_v)) * in_bytes \
        + 2 * (g * bq * (hd + hd_v + 2 + hd)) * in_bytes
    resident = sk_p * (hd + hd_v) * in_bytes    # dk/dv whole-kv out blocks
    scratch = g * bq * hd * 4                   # dq accumulator
    return tiles + resident + scratch


def _pass_cost(sq: int, sk: int, bq: int, bk: int, gf: int, g: int,
               kh: int, batch: int, hd_work: int, causal: bool,
               window: int, kv_len: int, diag_aligned: bool,
               step_cost: float, byte_cost: float, elem_cost: float,
               step_byte_cost: float, pass_bytes: int,
               in_bytes: int) -> float:
    """One kernel pass: padded MACs + softmax-matrix elementwise chain +
    per-step overhead (fixed + whole-pass-footprint gather/scatter) +
    streamed tile bytes.  ``pass_bytes`` is the TOTAL operand footprint
    of the pass (all batch/head slices) — interpret touches all of it
    on every grid step."""
    nq, nk = _ceil_div(sq, bq), _ceil_div(sk, bk)
    live = live_tiles(sq, sk, bq, bk, causal, window, kv_len, diag_aligned)
    groups = _ceil_div(g, gf)
    seq_steps = nq * nk * groups                 # per (batch, kv head)
    live_steps = live * groups
    tile_elems = gf * bq * bk
    macs = live_steps * tile_elems * hd_work
    kv_bytes = seq_steps * bk * hd_work * in_bytes
    per_bh = (macs + live_steps * tile_elems * elem_cost
              + live_steps * step_cost
              + (seq_steps - live_steps) * 0.25 * step_cost
              + kv_bytes * byte_cost)
    dead_steps = seq_steps - live_steps
    step_traffic = ((live_steps + 0.5 * dead_steps) * kh * batch
                    * pass_bytes * step_byte_cost)
    return per_bh * kh * batch + step_traffic


@functools.lru_cache(maxsize=4096)
def plan_attention(sq: int, sk: int, hd: int, hd_v: int, g: int, kh: int,
                   batch: int, dtype_bits: int, causal: bool, window: int,
                   kv_len: int, diag_aligned: bool = True,
                   backend: str = "interpret",
                   vmem_budget: int | None = None,
                   block_q: int | None = None,
                   block_k: int | None = None) -> AttnPlan:
    """Choose blocks for one flash-attention shape (trace-time, cached).

    ``block_q`` / ``block_k`` are the config *overrides*: when given
    they pin the forward AND backward tiles (clamped to the sequence),
    bypassing the search — the knob configs keep for reproducing a
    hand-tuned layout.  Everything else — g_fold, the fused-backward
    choice — is still planned, but under the pinned tiles.
    """
    budget = vmem_budget_bytes(backend) if vmem_budget is None else vmem_budget
    step_cost = STEP_COST.get(backend, STEP_COST["tpu"])
    byte_cost = BYTE_COST.get(backend, BYTE_COST["tpu"])
    sbc = STEP_BYTE_COST.get(backend, STEP_BYTE_COST["tpu"])
    elem_in = ELEM_COST.get(backend, ELEM_COST["tpu"])
    elem_flat = ELEM_COST.get(backend + "_flat", elem_in)
    block_cap = GRID_BLOCK_CAP.get(backend, MAX_BLOCK)
    in_bytes = max(dtype_bits // 8, 1)
    hd_work = hd + hd_v
    lanes = LANE_WIDTH.get(backend, 1)
    hd_l, hd_vl = max(hd, lanes), max(hd_v, lanes)   # VMEM footprint dims

    # Overrides pin their axis verbatim (clamped to the sequence, the
    # historical ``min(block, seq)`` behavior); the other axis is still
    # searched.  ``pinned`` relaxes the VMEM rejection so an explicit
    # choice is always honored.
    if block_q is not None:
        q_cands = [max(min(block_q, sq), 1)]
    else:
        hi_q = min(block_cap, _ceil_div(sq, MIN_BLOCK) * MIN_BLOCK)
        q_cands = list(_pow2s(MIN_BLOCK, hi_q)) or [MIN_BLOCK]
    if block_k is not None:
        k_cands = [max(min(block_k, sk), 1)]
    else:
        hi_k = min(block_cap, _ceil_div(sk, MIN_BLOCK) * MIN_BLOCK)
        k_cands = list(_pow2s(MIN_BLOCK, hi_k)) or [MIN_BLOCK]
        # bk is the score tile's lane dim: narrower tiles pad to the lane
        # width anyway, so they only add grid steps
        k_cands = [c for c in k_cands if c >= lanes] or k_cands
    pinned = block_q is not None or block_k is not None

    gf_cands = _divisors(g)

    # total operand footprints (bytes): what interpret's per-step block
    # gather/scatter walks — q/out/lse vs the backward passes' extras
    pb_fwd = batch * kh * (g * sq * (hd + hd_v + 1)
                           + sk * (hd + hd_v)) * in_bytes

    # ---- forward: minimize cost over (bq, bk, gf) under the budget ----
    best = None
    for bq in q_cands:
        for bk in k_cands:
            for gf in gf_cands:
                vm = _fwd_vmem(bq, bk, gf, hd_l, hd_vl, in_bytes) \
                    + _transients(backend, "fwd", gf * bq, bk)
                if vm > budget and not (pinned and gf == 1):
                    continue
                c = _pass_cost(sq, sk, bq, bk, gf, g, kh, batch, hd_work,
                               causal, window, kv_len, diag_aligned,
                               step_cost, byte_cost, elem_in,
                               sbc, pb_fwd, in_bytes)
                key = (c, -bq * bk, -gf)
                if best is None or key < best[0]:
                    best = (key, bq, bk, gf, vm)
    _, bq, bk, gf, vm_fwd = best
    sq_p = _ceil_div(sq, bq) * bq
    sk_p = _ceil_div(sk, bk) * bk

    # ---- backward candidates must tile the fwd-padded sequence ----
    if pinned:
        bwd_q_cands = [bq]
        bwd_k_cands = [bk]
    else:
        bwd_q_cands = [b for b in _pow2s(MIN_BLOCK, min(block_cap, sq_p))
                       if sq_p % b == 0] or [bq]
        bwd_k_cands = [b for b in _pow2s(MIN_BLOCK, min(block_cap, sk_p))
                       if sk_p % b == 0 and b >= min(lanes, bk)] or [bk]

    # q/do/dq + lse/delta, k/v in; dk/dv whole-kv RMW counts twice
    pb_fused = batch * kh * (g * sq_p * (2 * hd + hd_v + 2)
                             + 3 * sk_p * (hd + hd_v)) * in_bytes
    pb_dq = batch * kh * (g * sq_p * (2 * hd + hd_v + 2)
                          + sk_p * (hd + hd_v)) * in_bytes
    pb_dkv = batch * kh * (g * sq_p * (hd + hd_v + 2)
                           + 2 * sk_p * (hd + hd_v)) * in_bytes

    # fused: one kernel, dk/dv resident for the whole padded kv length;
    # ~10 MAC-units per tile element instead of 6 (dq pass) + 8 (dkv)
    best_fused = None
    for fbq in bwd_q_cands:
        for fbk in bwd_k_cands:
            vm = _fused_vmem(fbq, fbk, g, sk_p, hd_l, hd_vl, in_bytes) \
                + _transients(backend, "fused", g * fbq, fbk)
            if vm > budget:
                continue
            c = _pass_cost(sq_p, sk_p, fbq, fbk, g, g, kh, batch,
                           int(hd_work * 2.5), causal, window, kv_len,
                           diag_aligned, step_cost, byte_cost,
                           2 * elem_in, sbc, pb_fused, in_bytes)
            key = (c, -fbq * fbk)
            if best_fused is None or key < best_fused[0]:
                best_fused = (key, fbq, fbk, vm)

    # two-call: dq pass (grid like fwd) + dkv pass (q-reduction block)
    best_dq = None
    for dbq in bwd_q_cands:
        for dbk in bwd_k_cands:
            for dgf in gf_cands:
                vm = _dq_vmem(dbq, dbk, dgf, hd_l, hd_vl, in_bytes) \
                    + _transients(backend, "dq", dgf * dbq, dbk)
                if vm > budget and not (pinned and dgf == 1):
                    continue
                c = _pass_cost(sq_p, sk_p, dbq, dbk, dgf, g, kh, batch,
                               int(hd_work * 1.5), causal, window, kv_len,
                               diag_aligned, step_cost, byte_cost,
                               2 * elem_in, sbc, pb_dq, in_bytes)
                key = (c, -dbq * dbk, -dgf)
                if best_dq is None or key < best_dq[0]:
                    best_dq = (key, dbq, dbk, dgf, vm)
    best_dkv = None
    for dbq in bwd_q_cands:
        for dbk in bwd_k_cands:
            for dgf in gf_cands:
                vm = _dkv_vmem(dbq, dbk, dgf, hd_l, hd_vl, in_bytes) \
                    + _transients(backend, "dkv", dgf * dbq, dbk)
                if vm > budget and not (pinned and dgf == 1):
                    continue
                c = _pass_cost(sq_p, sk_p, dbq, dbk, dgf, g, kh, batch,
                               hd_work * 2, causal, window, kv_len,
                               diag_aligned, step_cost, byte_cost,
                               2 * elem_in, sbc, pb_dkv, in_bytes)
                key = (c, -dbq * dbk, -dgf)
                if best_dkv is None or key < best_dkv[0]:
                    best_dkv = (key, dbq, dbk, dgf, vm)

    two_call_cost = best_dq[0][0] + best_dkv[0][0]
    use_fused = best_fused is not None and best_fused[0][0] <= two_call_cost

    # ---- mega: grid (1,), the whole (B, KH) problem in one step, one
    # batched dot per matmul.  One flat XLA computation: elementwise
    # runs at flat speed (no in-loop penalty) but every masked element
    # is computed.  Gated on the materialized softmax-matrix transients
    # (host RAM in interpret mode, real VMEM on TPU).
    mega_fwd = mega_bwd = False
    mega_fwd_bt = mega_bwd_bt = False
    vm_mf = vm_mb = 0
    if not pinned:
        mega_budget = MEGA_BUDGET.get(backend) or budget
        full = batch * kh * g * sq_p * sk_p
        vm_mf = 2 * full * 4
        vm_mb = 4 * full * 4
        c_mf = full * (hd_work + elem_flat) + step_cost
        c_mb = full * (hd_work * 2.5 + 2 * elem_flat) + step_cost
        mega_fwd = vm_mf <= mega_budget and c_mf < best[0][0]
        bwd_cost = best_fused[0][0] if use_fused else two_call_cost
        mega_bwd = vm_mb <= mega_budget and c_mb < bwd_cost
        # batch-tiled fallback: when the full-batch transient is what
        # killed the mega (serving batch sizes), grid over B alone — the
        # per-step transient is 1/B of the full one and the flat
        # elementwise chain survives, at B·STEP_COST extra
        if batch > 1:
            c_mf_bt = full * (hd_work + elem_flat) + batch * step_cost
            c_mb_bt = full * (hd_work * 2.5 + 2 * elem_flat) \
                + batch * step_cost
            mega_fwd_bt = (not mega_fwd and vm_mf // batch <= mega_budget
                           and c_mf_bt < best[0][0])
            mega_bwd_bt = (not mega_bwd and vm_mb // batch <= mega_budget
                           and c_mb_bt < bwd_cost)

    if use_fused:
        _, fbq, fbk, vm_f = best_fused
        plan = AttnPlan(block_q=bq, block_k=bk, g_fold=gf, fused_bwd=True,
                        dq_block_q=fbq, dq_block_k=fbk,
                        dkv_block_q=fbq, dkv_block_k=fbk,
                        vmem_bytes=max(vm_fwd, vm_f,
                                       vm_mf if mega_fwd else 0,
                                       vm_mb if mega_bwd else 0,
                                       vm_mf // batch if mega_fwd_bt else 0,
                                       vm_mb // batch if mega_bwd_bt else 0),
                        mega_fwd=mega_fwd, mega_bwd=mega_bwd,
                        mega_fwd_bt=mega_fwd_bt, mega_bwd_bt=mega_bwd_bt)
    else:
        _, dqq, dqk, dqgf, vm_dq = best_dq
        _, dkq, dkk, dkgf, vm_dkv = best_dkv
        del dqgf, dkgf   # two-call passes re-derive their fold below
        plan = AttnPlan(block_q=bq, block_k=bk, g_fold=gf, fused_bwd=False,
                        dq_block_q=dqq, dq_block_k=dqk,
                        dkv_block_q=dkq, dkv_block_k=dkk,
                        vmem_bytes=max(vm_fwd, vm_dq, vm_dkv,
                                       vm_mf if mega_fwd else 0,
                                       vm_mb if mega_bwd else 0,
                                       vm_mf // batch if mega_fwd_bt else 0,
                                       vm_mb // batch if mega_bwd_bt else 0),
                        mega_fwd=mega_fwd, mega_bwd=mega_bwd,
                        mega_fwd_bt=mega_fwd_bt, mega_bwd_bt=mega_bwd_bt)
    return plan


@functools.lru_cache(maxsize=1024)
def plan_decode(seq: int, g: int, hd: int, hd_v: int, dtype_bits: int,
                backend: str = "interpret",
                vmem_budget: int | None = None,
                block_s: int | None = None) -> int:
    """Sequence block for the flash-decode kernel.  The cache length
    must divide the block, so candidates are pow2 divisors of ``seq``;
    cost is steps + streamed cache bytes under the VMEM budget."""
    if block_s is not None:
        return min(block_s, seq)
    budget = vmem_budget_bytes(backend) if vmem_budget is None else vmem_budget
    step_cost = STEP_COST.get(backend, STEP_COST["tpu"])
    in_bytes = max(dtype_bits // 8, 1)
    best = None
    for b in _pow2s(MIN_BLOCK, min(seq, MAX_BLOCK * 4)):
        if seq % b:
            continue
        vm = 2 * b * (hd + hd_v) * in_bytes + g * (hd_v + 2) * 4
        if vm > budget and best is not None:
            continue
        steps = seq // b
        c = steps * (step_cost + g * b * (hd + hd_v))
        if best is None or c < best[0]:
            best = (c, b)
    return best[1] if best else min(seq, 512)


@functools.lru_cache(maxsize=64)
def plan_copy_chunk(total_rows: int, vmem_budget: int | None = None) -> int:
    """Rows per DMA chunk for the HBM-staged ``multi_partition_copy``
    path: double-buffered source stage + RMW stage must fit the budget,
    and at least a few chunks should exist so the prefetch overlaps."""
    budget = vmem_budget_bytes() if vmem_budget is None else vmem_budget
    # 2 src slots + 1 rmw slot, each chunk×LANES bytes
    cap = max(budget // (3 * LANES), MIN_BLOCK)
    chunk = MIN_BLOCK
    while chunk * 2 <= cap and chunk * 2 <= 8192 and \
            chunk * 4 <= max(total_rows, MIN_BLOCK * 4):
        chunk *= 2
    return chunk
