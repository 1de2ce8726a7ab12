"""Mesh construction: the one place that decides a mesh's axis types.

Every mesh in the repository is built by :func:`make_mesh`.  Its axes are
``Auto``: the model code places intermediates with
``with_sharding_constraint`` (``dist.sharding.ShardCtx.constrain``), which
only accepts Auto axes, while a bare ``jax.make_mesh`` defaults to Explicit.

Functions, not module-level constants, so importing this module never
touches JAX device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and then calls these.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of ``shape`` over ``devices`` (default: all of them), Auto axes."""
    kw = {} if devices is None else {"devices": list(devices)}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """("data", "model") mesh over the available devices: ``model`` of
    them per model group (clamped to the count), the rest as data."""
    n = len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))
