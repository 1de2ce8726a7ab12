"""Differentiable Pallas flash attention: gradcheck vs the jnp twin.

The Pallas custom-VJP kernels (``repro.kernels.flash_attention``) must
match the jnp oracles — fwd and grad — across ragged sequence lengths
(block-edge padding), sliding windows, GQA groupings, and the
context-parallel stripe path (``q_offset`` global causal positioning in
*both* directions).  Sharded cases run in subprocesses with 8 forced host
devices, like ``tests/test_dist.py``.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.models.attention import flash_attention_jnp, full_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk(key, b, sq, sk, h, kh, hd):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, sq, h, hd)),
            jax.random.normal(ks[1], (b, sk, kh, hd)),
            jax.random.normal(ks[2], (b, sk, kh, hd)))


def _grads_match(loss_a, loss_b, args, atol=3e-4):
    la, lb = loss_a(*args), loss_b(*args)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               atol=atol, rtol=atol)
    ga = jax.grad(loss_a, argnums=tuple(range(len(args))))(*args)
    gb = jax.grad(loss_b, argnums=tuple(range(len(args))))(*args)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=atol)


# ------------------------------------------------- deterministic gradcheck

def _check_vs_twin(seed, sq, g, kh, window, block_q, block_k):
    """Gradcheck: Pallas VJP vs flash_attention_jnp at ragged lengths.

    The jnp twin asserts block divisibility, so it runs whole-sequence
    tiles; the Pallas kernel runs the requested (non-dividing) blocks with
    zero-padded masked edge tiles — results must still agree to fp32
    tolerance, fwd and grad.
    """
    h, hd = g * kh, 16
    q, k, v = _mk(jax.random.PRNGKey(seed), 1, sq, sq, h, kh, hd)

    def loss_pallas(q_, k_, v_):
        out = kops.flash_attention(q_, k_, v_, causal=True, window=window,
                                   block_q=block_q, block_k=block_k)
        return jnp.sum(jnp.sin(out))

    def loss_twin(q_, k_, v_):
        out = flash_attention_jnp(q_, k_, v_, jnp.zeros((), jnp.float32),
                                  True, window, sq, sq)
        return jnp.sum(jnp.sin(out))

    _grads_match(loss_pallas, loss_twin, (q, k, v))


def _check_q_offset_stripe(seed, sq, off, window):
    """A q stripe at global offset ``off`` against a longer context: the
    scalar-prefetched offset must position the causal/window masks in the
    backward kernels exactly as the dense oracle does."""
    sk = sq + off
    q, k, v = _mk(jax.random.PRNGKey(seed), 2, sq, sk, 4, 2, 16)

    def loss_pallas(q_, k_, v_):
        out = kops.flash_attention(q_, k_, v_, jnp.float32(off),
                                   causal=True, window=window,
                                   block_q=16, block_k=16)
        return jnp.sum(jnp.sin(out))

    def loss_dense(q_, k_, v_):
        out = full_attention(q_, k_, v_, causal=True, window=window,
                             q_offset=off)
        return jnp.sum(jnp.sin(out))

    _grads_match(loss_pallas, loss_dense, (q, k, v))


@pytest.mark.parametrize("seed,sq,g,kh,window,block_q,block_k", [
    (0, 100, 2, 2, 0, 32, 32),      # ragged vs both block sizes, GQA
    (1, 65, 1, 2, 0, 16, 48),       # sq % block_k != 0, MQA-ish
    (2, 96, 3, 1, 24, 32, 32),      # sliding window, MHA group 3
    (3, 50, 2, 2, 13, 16, 32),      # window + ragged
])
def test_pallas_vjp_matches_jnp_twin(seed, sq, g, kh, window, block_q,
                                     block_k):
    _check_vs_twin(seed, sq, g, kh, window, block_q, block_k)


@pytest.mark.parametrize("seed,sq,off,window", [
    (0, 32, 64, 0), (1, 24, 40, 9), (2, 17, 32, 0),
])
def test_pallas_vjp_q_offset_stripe(seed, sq, off, window):
    _check_q_offset_stripe(seed, sq, off, window)


# ------------------------------------------------------- hypothesis sweep

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 1000),
           sq=st.integers(17, 96),        # rarely a block multiple
           g=st.sampled_from([1, 2, 3]),
           kh=st.sampled_from([1, 2]),
           window=st.sampled_from([0, 0, 7, 20]),
           block_q=st.sampled_from([16, 32]),
           block_k=st.sampled_from([16, 32, 48]))
    def test_pallas_vjp_hypothesis_sweep(seed, sq, g, kh, window, block_q,
                                         block_k):
        _check_vs_twin(seed, sq, g, kh, window, block_q, block_k)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 1000), sq=st.integers(8, 48),
           off=st.integers(0, 64), window=st.sampled_from([0, 9]))
    def test_pallas_vjp_q_offset_hypothesis_sweep(seed, sq, off, window):
        _check_q_offset_stripe(seed, sq, off, window)
else:                                                  # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_pallas_vjp_hypothesis_sweep():
        pass


# --------------------------------------------------------- branch boundary

@pytest.mark.parametrize("window", [0, 12])
def test_attn_local_branches_agree_at_boundary(window):
    """`_attn_local` flips between the Pallas flash kernel and the dense
    reference on a length threshold: both branches must agree (fwd and
    grad) at the boundary, windowed or not.  This also locks in the ragged
    fix — the flash branch no longer falls back to the dense O(S²) path
    when the stripe length doesn't divide the block sizes."""
    from repro.dist.flash import _attn_local
    min_seq = 64
    bq = bk = 16
    for sq in (min_seq, min_seq + 1):          # dense side, flash side
        q, k, v = _mk(jax.random.PRNGKey(sq + window), 2, sq, sq, 4, 2, 32)

        def loss_local(q_, k_, v_):
            out = _attn_local(q_, k_, v_, window=window, block_q=bq,
                              block_k=bk, min_seq=min_seq)
            return jnp.sum(jnp.sin(out))

        def loss_dense(q_, k_, v_):
            out = full_attention(q_, k_, v_, causal=True, window=window)
            return jnp.sum(jnp.sin(out))

        _grads_match(loss_local, loss_dense, (q, k, v))


# ------------------------------------------------------- sharded (8 dev)

def _run(code: str):
    full = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys\nsys.path.insert(0, 'src')\n" + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", full], capture_output=True,
                         text=True, cwd=ROOT, timeout=560)
    assert out.returncode == 0 and "PASS" in out.stdout, \
        (out.stdout[-1500:], out.stderr[-3000:])


def test_context_parallel_stripes_run_pallas_vjp():
    """Context-parallel causal_attention on the Pallas kernel: per-stripe
    ``q_offset`` flows into the backward kernels through scalar prefetch;
    sharded grads must equal the single-device Pallas grads."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_config
    from repro.dist.flash import causal_attention
    from repro.dist.sharding import use_mesh
    from repro.models.attention import flash_min_seq

    cfg = get_config("qwen2-7b").reduced()   # 6 % 4 != 0 → seq strategy
    cfg = dataclasses.replace(cfg, num_heads=6, num_kv_heads=2,
                              attn_block_q=8, attn_block_k=8,
                              attn_flash_min_seq=8, sliding_window=24)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, hd = 2, 128, cfg.head_dim
    # every 32-row stripe (s / model axis 4) must clear the threshold, or
    # this test silently degrades to the dense fallback
    assert s // 4 > flash_min_seq(cfg), (s // 4, flash_min_seq(cfg))
    q = jax.random.normal(ks[0], (b, s, 6, hd))
    k = jax.random.normal(ks[1], (b, s, 2, hd))
    v = jax.random.normal(ks[2], (b, s, 2, hd))

    def loss(a, b_, c):
        return jnp.sum(jnp.sin(causal_attention(
            a, b_, c, cfg=cfg, window=cfg.sliding_window)))

    ref = causal_attention(q, k, v, cfg=cfg, window=cfg.sliding_window)
    g_ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        got = jax.jit(lambda a, b_, c: causal_attention(
            a, b_, c, cfg=cfg, window=cfg.sliding_window))(q, k, v)
        g_got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               atol=2e-4, rtol=2e-4)
    for a, b_ in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)
    print("PASS")
    """)


def test_use_mesh_train_step_runs_pallas_vjp():
    """End-to-end acceptance: a ``use_mesh`` train step whose attention
    length clears ``attn_flash_min_seq`` differentiates through the Pallas
    kernels and matches the single-device step."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_config
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.train.steps import init_train_state, make_train_step
    from repro.dist.sharding import use_mesh
    from repro.data import SyntheticTokens
    from repro.models.attention import flash_min_seq

    cfg = get_config("llama3.2-3b").reduced()
    cfg = dataclasses.replace(cfg, attn_block_q=8, attn_block_k=8,
                              attn_flash_min_seq=8)
    # seq 68: kv_heads 2 % model 4 != 0 → context-parallel stripes of 17
    # (> flash_min_seq 16, and ragged vs the 8-row blocks) on the mesh
    # side; 68 > 16 on the single-device side — both run the Pallas VJP
    seq = 68
    assert seq // 4 > flash_min_seq(cfg), (seq // 4, flash_min_seq(cfg))
    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50)
    data = SyntheticTokens(cfg.vocab_size, batch=4, seq=seq, seed=5)
    step = make_train_step(model, oc)

    s1 = init_train_state(model, jax.random.PRNGKey(0), oc)
    b = {k: jnp.asarray(v) for k, v in data.get(0).items()}
    s1b, m1 = jax.jit(step)(s1, b)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    s2 = init_train_state(model, jax.random.PRNGKey(0), oc)
    with use_mesh(mesh):
        s2b, m2 = jax.jit(step)(s2, b)

    assert abs(float(m1["ce_loss"]) - float(m2["ce_loss"])) < 1e-3
    for a, c in zip(jax.tree_util.tree_leaves(s1b["params"]),
                    jax.tree_util.tree_leaves(s2b["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=3e-4, rtol=3e-4)
    print("PASS")
    """)
