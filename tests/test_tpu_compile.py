"""Main-path Pallas kernels compile for a TPU v5e chip, at real widths.

The TPU compiler is installed even where no chip is attached: it compiles
for a described topology.  Interpret-mode tests cannot see what these
catch — Mosaic refusing a block shape, an unaligned dynamic slice or a
reshape it cannot lay out, or a kernel that outgrows VMEM.  Nothing runs,
so these say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Single-chip sharding; the persistent cache is off meanwhile (an
    entry compiled for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("h,kh,hd", [(15, 5, 64),      # smollm-360m
                                     (24, 8, 128)])    # llama3.2-3b
def test_flash_fwd_and_grad(one_chip, h, kh, hd):
    b, s = 1, 4096
    q = _spec(one_chip, (b, s, h, hd), jnp.bfloat16)
    kv = _spec(one_chip, (b, s, kh, hd), jnp.bfloat16)

    def fwd_grad(q_, k_, v_):
        def loss(*a):
            return ops.flash_attention(*a, interpret=False).astype(
                jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

    assert "tpu_custom_call" in _hlo(
        lambda *a: ops.flash_attention(*a, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in _hlo(fwd_grad, q, kv, kv)


def test_flash_decode(one_chip):
    q = _spec(one_chip, (8, 1, 15, 64), jnp.bfloat16)
    cache = _spec(one_chip, (8, 5, 1024, 64), jnp.bfloat16)
    cur = _spec(one_chip, (), jnp.int32)
    assert "tpu_custom_call" in _hlo(
        lambda *a: ops.flash_decode(*a, interpret=False), q, cache, cache,
        cur)


@pytest.mark.parametrize("mib", [1, 64])    # whole-buffer VMEM / HBM-DMA
def test_multi_partition_copy(one_chip, mib):
    n = mib << 20
    slot = n // 64
    # row offsets off the 8-row tiling, ragged lengths, permuted sources
    ranges = tuple((i * slot + 128 * (1 + i % 7), ((i * 37) % 64) * slot,
                    slot - 128 * (9 + i % 3)) for i in range(64))
    buf = _spec(one_chip, (n,), jnp.uint8)
    assert "tpu_custom_call" in _hlo(
        lambda d, s: ops.multi_partition_copy_bytes(d, s, ranges,
                                                    interpret=False),
        buf, buf)


@pytest.mark.parametrize("dst_off,src_off,size", [
    (32768, 0, 65536),          # 32 KiB tiles: the single-range kernel
    (384, 128, 4096)])          # lane-aligned: the masked multi-range one
def test_partition_copy_bytes(one_chip, dst_off, src_off, size):
    buf = _spec(one_chip, (1 << 20,), jnp.uint8)
    assert "tpu_custom_call" in _hlo(
        lambda d, s: ops.partition_copy_bytes(
            d, s, dst_off=dst_off, src_off=src_off, size=size,
            interpret=False), buf, buf)


def test_ssd_scan(one_chip):
    b, s, h, p, n = 1, 2048, 64, 64, 128          # mamba2-1.3b heads
    assert "tpu_custom_call" in _hlo(
        lambda *a: ops.ssd_scan(*a, interpret=False),
        _spec(one_chip, (b, s, h, p), jnp.bfloat16),
        _spec(one_chip, (b, s, h), jnp.float32),
        _spec(one_chip, (h,), jnp.float32),
        _spec(one_chip, (b, s, n), jnp.bfloat16),
        _spec(one_chip, (b, s, n), jnp.bfloat16))


# (registry model, pool pages, page, b_cap, max_pages, prompt_pad): the
# serve benchmark cells' shapes, with 2 layers to keep the compile short
@pytest.mark.parametrize("name,pool,page,b_cap,max_pages,pad", [
    ("smollm-360m", 3072, 16, 32, 128, 1536),        # 5 KV heads of 64
    ("h2o-danube-3-4b", 1800, 16, 11, 192, 2048)])   # 8 KV heads of 120
def test_paged_steps_update_pools_in_place(one_chip, name, pool, page, b_cap,
                                           max_pages, pad):
    """Both paged steps alias the donated pools, and nothing of a pool's or
    a pool layer's size is copied, sliced or stacked: the only such
    instructions are the loop's own plumbing and the in-place scatters of
    the new tokens or pages.  The decode's gathered pages are never laid
    out again either."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models.model import LanguageModel
    from repro.serve.steps import (make_paged_decode_step,
                                   make_paged_prefill_step, pool_shape)

    cfg = dataclasses.replace(get_config(name), num_layers=2,
                              dtype="bfloat16", param_dtype="bfloat16")
    model = LanguageModel(cfg)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    shape = pool_shape(2, pool, page, cfg.num_kv_heads, cfg.head_dim)
    kv = _spec(one_chip, shape, jnp.bfloat16)
    i32 = _spec(one_chip, (b_cap,), jnp.int32)
    programs = {
        "decode": make_paged_decode_step(model).lower(
            params, kv, kv, _spec(one_chip, (b_cap, max_pages), jnp.int32),
            i32, _spec(one_chip, (b_cap,), jnp.bool_), i32).compile(),
        "prefill": make_paged_prefill_step(model, page).lower(
            params, kv, kv, _spec(one_chip, (1, pad), jnp.int32),
            _spec(one_chip, (), jnp.int32),
            _spec(one_chip, (pad // page,), jnp.int32)).compile()}

    pool_elems = 2 * pool * cfg.num_kv_heads * page * cfg.head_dim
    pool_sized = {pool_elems, pool_elems // 2}
    gathered = b_cap * max_pages * page * cfg.num_kv_heads * cfg.head_dim
    plumbing = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}
    for kind, compiled in programs.items():
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            2 * pool_elems * 2, kind
        body, roots, comp = [], {}, None
        for line in compiled.as_text().splitlines():
            if not line.startswith(" "):
                comp = line.split(" ")[0]
                continue
            m = re.match(r"\s*(ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                         r"([\w\-]+)\(", line)
            if m:
                if m.group(1):
                    roots[comp] = m.group(4)
                body.append((comp, m.group(2), m.group(3), m.group(4), line))
        fused = set(re.findall(r"calls=(%[\w.\-]+)", compiled.as_text()))
        for comp, dtype, dims, op, line in body:
            n = int(np.prod([int(d) for d in dims.split(",") if d]))
            if comp in fused or dtype != "bf16":
                continue
            assert not (n == gathered and op == "copy"), (kind, line)
            if n not in pool_sized:
                continue
            if op == "fusion":
                called = re.search(r"calls=(%[\w.\-]+)", line).group(1)
                assert roots[called] == "scatter", (kind, line)
            else:
                assert op in plumbing, (kind, line)
