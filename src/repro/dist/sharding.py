"""Mesh sharding as paper-§6 data-block partitioning.

The distributed layer treats per-device shards of one logical array the way
the paper treats partitions of one data block: disjoint ranges of a single
buffer that multiple tasks (devices) want to access at once.  Everything
here is organized around that bridge:

* :class:`ShardCtx` wraps a ``jax.sharding.Mesh`` and translates *logical*
  axis names ("dp", "tp", "fsdp", "sp", "ep", "vocab", "kv_seq") into the
  physical mesh axes, dropping any axis whose size does not divide the
  dimension (a sharding that does not divide is not a valid §6 partition,
  so it silently degrades to replication rather than emitting one).
* :func:`_resolve_with_priority` maps a parameter's key path to a
  ``PartitionSpec`` via suffix rules — the most specific (longest) matching
  suffix wins, so ``("moe", "w_gate")`` (an expert bank, expert-parallel)
  beats the generic ``("w_gate",)`` dense rule.
* :func:`param_shardings` applies those rules to a whole params tree.
* :func:`use_mesh` / :func:`current_ctx` install an ambient context so
  model code can constrain intermediates without threading a ctx argument.
* :func:`partition_tree_of` lowers a ``NamedSharding`` to the disjoint
  ``(offset, size)`` byte ranges of §6 — the ranges a ``db_partition``
  call accepts (tests prove it by handing them to the core runtime).

Logical → physical axis mapping:

  ==========  =====================================================
  logical     physical
  ==========  =====================================================
  dp          ("pod", "data") — every axis in ``pure_dp`` mode
  fsdp        ("pod", "data") — disabled in ``pure_dp`` mode
  tp / model  ("model",)      — tensor / head parallel
  ep          ("model",)      — expert banks (MoE)
  sp          ("model",)      — sequence dim of activations
  kv_seq      ("model",)      — sequence dim of decode caches
  vocab       ("model",)      — vocab dim of logits
  ==========  =====================================================
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]


# --------------------------------------------------------------------- context

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Ambient sharding context: a mesh plus the logical-axis dictionary."""

    mesh: Optional[Mesh] = None
    pure_dp: bool = False

    @property
    def active(self) -> bool:
        return self.mesh is not None and self.mesh.size > 1

    @property
    def axis_sizes(self) -> Dict[str, int]:
        if self.mesh is None:
            return {}
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def model_size(self) -> int:
        return self.axis_sizes.get("model", 1) if self.active else 1

    # -- logical axes ------------------------------------------------------

    def _physical(self, logical: str) -> Tuple[str, ...]:
        """Mesh axes backing one logical name (existing axes only)."""
        sizes = self.axis_sizes
        if logical == "dp":
            if self.pure_dp:
                return tuple(self.mesh.axis_names)
            return tuple(a for a in ("pod", "data") if a in sizes)
        if logical == "fsdp":
            if self.pure_dp:
                return ()
            return tuple(a for a in ("pod", "data") if a in sizes)
        if logical in ("tp", "model", "ep", "sp", "kv_seq", "vocab"):
            if self.pure_dp:
                return ()
            return tuple(a for a in ("model",) if a in sizes)
        raise KeyError(f"unknown logical axis {logical!r}")

    def resolve(self, logical: Optional[str], dim: int) -> Axes:
        """Physical axes for ``logical`` on a dimension of size ``dim``.

        Returns a single axis name, a tuple of names, or None when the
        logical axis is unmapped or its total size does not divide ``dim``
        (an indivisible sharding is not a valid §6 partition).
        """
        if logical is None or not self.active:
            return None
        axes = self._physical(logical)
        if not axes:
            return None
        sizes = self.axis_sizes
        total = 1
        for a in axes:
            total *= sizes[a]
        if total <= 1 or dim % total != 0:
            # try a prefix that still divides (e.g. batch 4 on pod×data=8)
            for cut in range(len(axes) - 1, 0, -1):
                t = 1
                for a in axes[:cut]:
                    t *= sizes[a]
                if t > 1 and dim % t == 0:
                    axes = axes[:cut]
                    total = t
                    break
            else:
                return None
        return axes[0] if len(axes) == 1 else tuple(axes)

    def spec(self, shape: Sequence[int], *logical: Optional[str]) -> P:
        """PartitionSpec for ``shape`` with one logical name per dim."""
        assert len(logical) == len(shape), (tuple(shape), logical)
        return P(*(self.resolve(l, d) for l, d in zip(logical, shape)))

    def constrain(self, x: jax.Array, *logical: Optional[str]) -> jax.Array:
        """Sharding-constrain ``x`` (no-op without an active mesh)."""
        if not self.active:
            return x
        spec = self.spec(x.shape, *logical)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


_NULL_CTX = ShardCtx()
_CTX_STACK: List[ShardCtx] = []


def current_ctx() -> ShardCtx:
    """The innermost :func:`use_mesh` context (inactive ctx outside any)."""
    return _CTX_STACK[-1] if _CTX_STACK else _NULL_CTX


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], pure_dp: bool = False):
    """Install ``mesh`` as the ambient sharding context.

    ``mesh=None`` installs an *inactive* ctx (single-device semantics), so
    callers can pass an optional mesh through unconditionally.  In
    ``pure_dp`` mode the batch shards over every mesh axis and weights
    stay replicated (no TP/SP/FSDP) — the recipe small models prefer.
    """
    ctx = ShardCtx(mesh=mesh, pure_dp=pure_dp)
    _CTX_STACK.append(ctx)
    try:
        yield ctx
    finally:
        _CTX_STACK.pop()


# ------------------------------------------------------- param sharding rules

# (key-path suffix) -> logical axes for the *trailing* dims.  Leading stack
# dims (vmap-init layer stacking) are padded with None.  Ordered by
# specificity: the longest matching suffix wins (`_resolve_with_priority`).
_PARAM_RULES: Tuple[Tuple[Tuple[str, ...], Tuple[Optional[str], ...]], ...] = (
    # MoE expert banks: expert dim is the §6 partition axis (EP); the
    # d_model/d_ff dim re-gathers per layer (FSDP)
    (("moe", "w_gate"), ("ep", "fsdp", None)),
    (("moe", "w_up"), ("ep", "fsdp", None)),
    (("moe", "w_down"), ("ep", None, "fsdp")),
    (("moe", "router"), (None, None)),          # fp32, tiny: replicated
    # attention projections: heads over TP, d_model over FSDP
    (("w_q",), ("fsdp", "tp", None)),
    (("w_k",), ("fsdp", "tp", None)),
    (("w_v",), ("fsdp", "tp", None)),
    (("w_o",), ("tp", None, "fsdp")),
    (("b_q",), ("tp", None)),
    (("b_k",), ("tp", None)),
    (("b_v",), ("tp", None)),
    # MLA low-rank factors
    (("w_dq",), ("fsdp", None)),
    (("w_dkv",), ("fsdp", None)),
    (("w_uq",), (None, "tp", None)),
    (("w_uk",), (None, "tp", None)),
    (("w_uv",), (None, "tp", None)),
    # dense MLPs (SwiGLU + GELU): hidden over TP, d_model over FSDP
    (("w_gate",), ("fsdp", "tp")),
    (("w_up",), ("fsdp", "tp")),
    (("w_down",), ("tp", "fsdp")),
    (("w_in",), ("fsdp", "tp")),
    (("w_out",), ("tp", "fsdp")),
    (("b_in",), ("tp",)),
    # mamba projections: d_inner / heads are TP-aligned, B/C/dt head-shared
    (("w_z",), ("fsdp", "tp")),
    (("w_x",), ("fsdp", "tp")),
    (("out_proj",), ("tp", "fsdp")),
    (("conv_x",), (None, "tp")),
    (("conv_b_x",), ("tp",)),
    # embeddings / unembedding: vocab over TP (vocab-parallel CE loss)
    (("embedding",), ("tp", "fsdp")),
    (("lm_head",), ("fsdp", "tp")),
)


def _path_keys(path: Sequence[Any]) -> Tuple[str, ...]:
    return tuple(p.key if hasattr(p, "key") else str(p) for p in path)


def _resolve_with_priority(keys: Tuple[str, ...], shape: Tuple[int, ...],
                           ctx: ShardCtx) -> P:
    """PartitionSpec for one param leaf by key-path suffix priority.

    The longest rule suffix that matches the end of ``keys`` wins; its
    logical axes apply to the trailing ``len(rule)`` dims (leading stack
    dims replicate).  Unmatched leaves (norms, biases, scalars) replicate.
    Every resolved axis is divisibility-checked, so the emitted spec is
    always a valid §6 partitioning of the leaf.
    """
    best: Optional[Tuple[Optional[str], ...]] = None
    best_len = 0
    for suffix, logical in _PARAM_RULES:
        if len(suffix) > best_len and len(suffix) <= len(keys) \
                and keys[-len(suffix):] == suffix:
            best, best_len = logical, len(suffix)
    if best is None or len(best) > len(shape):
        return P(*([None] * len(shape)))
    pad = len(shape) - len(best)
    logical_full = (None,) * pad + best
    return ctx.spec(shape, *logical_full)


def param_shardings(shapes: Any, ctx: ShardCtx) -> Any:
    """NamedSharding tree for a params(-like) tree of ShapeDtypeStructs.

    Works for params, optimizer moments (same tree structure ⇒ same key
    paths ⇒ same shardings), and real arrays alike.
    """
    if ctx.mesh is None:
        raise ValueError("param_shardings requires a ShardCtx with a mesh")

    def leaf_sh(path, leaf):
        spec = _resolve_with_priority(_path_keys(path), tuple(leaf.shape), ctx)
        return NamedSharding(ctx.mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_sh, shapes)


# ----------------------------------------------------- §6 partition lowering

def device_ranges_of(shape: Tuple[int, ...], itemsize: int,
                     sharding: NamedSharding
                     ) -> List[Tuple[Any, List[Tuple[int, int]]]]:
    """Per-device §6 byte ranges of one row-major buffer under a sharding.

    Each device's shard is a hyperrectangle of the row-major buffer; it
    lowers to one byte range per contiguous run (one run when only leading
    dims shard, many when an inner dim shards), emitted in the shard's own
    row-major order — so a shard's host bytes split into equal run-sized
    pieces correspond 1:1, in order, with that device's ranges.  Devices
    are visited in ``mesh.devices.flat`` order; replicated devices repeat
    ranges.  This is the §6 range map the sharded checkpoint writer uses
    to make each node write exactly its own bytes.
    """
    shape = tuple(int(d) for d in shape)
    if not shape:
        # scalar: a single range owned by the first device (all replicate)
        return [(sharding.mesh.devices.flat[0], [(0, itemsize)])]
    nelems = int(np.prod(shape))
    total = nelems * itemsize
    if nelems == 0:
        return []
    # row-major strides in bytes
    strides = [itemsize] * len(shape)
    for i in range(len(shape) - 2, -1, -1):
        strides[i] = strides[i + 1] * shape[i + 1]

    out: List[Tuple[Any, List[Tuple[int, int]]]] = []
    indices_map = sharding.devices_indices_map(shape)
    for dev in sharding.mesh.devices.flat:
        idx = indices_map[dev]
        starts = []
        lens = []
        for d, sl in enumerate(idx):
            start = 0 if sl.start is None else int(sl.start)
            stop = shape[d] if sl.stop is None else int(sl.stop)
            starts.append(start)
            lens.append(stop - start)
        # innermost contiguous run: trailing dims that are whole
        k = len(shape)
        while k > 0 and lens[k - 1] == shape[k - 1]:
            k -= 1
        if k == 0:
            out.append((dev, [(0, total)]))
            continue
        run = lens[k - 1] * strides[k - 1]   # bytes per contiguous run
        base = starts[k - 1] * strides[k - 1]
        # iterate the outer (non-run) dims
        outer = [range(s, s + l) for s, l in zip(starts[:k - 1],
                                                 lens[:k - 1])]
        ranges = []
        for combo in itertools.product(*outer):
            off = base + sum(c * strides[d] for d, c in enumerate(combo))
            ranges.append((off, run))
        out.append((dev, ranges))
    return out


def partition_tree_of(shape: Tuple[int, ...], itemsize: int,
                      sharding: NamedSharding) -> List[Tuple[int, int]]:
    """Lower a sharding to the §6 ``(offset, size)`` byte ranges per device.

    Flat view of :func:`device_ranges_of`: ranges in device order,
    replicated devices repeating theirs — deduplicated, the distinct
    ranges are mutually disjoint and tile the buffer exactly, which is
    precisely what ``db_partition`` (§6.2) accepts.  Lane alignment: a
    run's byte size is a multiple of the trailing-dims byte count, so
    whenever the innermost *sharded* dim leaves ≥ 32 f32 (128 B) of
    trailing extent, every range is lane-aligned for the fused-copy
    kernel (``partition_copy_bytes``).
    """
    return [r for _dev, ranges in device_ranges_of(shape, itemsize, sharding)
            for r in ranges]


def moe_bucket_ranges(num_experts: int, capacity: int, width: int,
                      itemsize: int, ctx: ShardCtx) -> List[Tuple[int, int]]:
    """§6 destination ranges of one shard's ``(E, C, width)`` a2a bucket.

    The capacity-bucketed MoE dispatch packs each source shard's tokens
    into per-destination-expert buckets; the ``all_to_all`` then hands
    destination shard *j* exactly the contiguous range covering its
    experts ``[j·E/m, (j+1)·E/m)`` — the same NamedSharding →
    disjoint-``(offset, size)`` lowering the expert banks use, so the
    exchanged buckets are literally a §6 partitioning of the bucket block
    (tests hand these ranges to ``db_partition``).  Distinct ranges only
    (replicated mesh axes deduplicated), in offset order; without an
    active expert-parallel axis the whole block is one local range.
    """
    shape = (num_experts, capacity, width)
    total = num_experts * capacity * width * itemsize
    ep = ctx.resolve("ep", num_experts) if ctx.mesh is not None else None
    if ep is None:
        return [(0, total)]
    sharding = NamedSharding(ctx.mesh, P(ep, None, None))
    seen = set()
    out: List[Tuple[int, int]] = []
    for r in partition_tree_of(shape, itemsize, sharding):
        if r not in seen:
            seen.add(r)
            out.append(r)
    return sorted(out)
