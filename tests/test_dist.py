"""Distributed parity: sharded paths must equal the single-device oracle.

These run in subprocesses with ``--xla_force_host_platform_device_count=8``
so the main test session keeps seeing one device (per the dry-run contract).
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    full = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys\nsys.path.insert(0, 'src')\n" + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", full], capture_output=True,
                         text=True, cwd=ROOT, timeout=560)
    assert out.returncode == 0 and "PASS" in out.stdout, \
        (out.stdout[-1500:], out.stderr[-3000:])


def test_moe_shardmap_parity():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import moe as M
    from repro.dist.sharding import use_mesh
    import dataclasses

    cfg = get_config("deepseek-v2-236b").reduced()
    cfg = dataclasses.replace(cfg, num_experts=8, experts_per_token=2)
    params = M.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

    y_ref, aux_ref = M.moe_ffn(params, x, cfg)          # no mesh

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        y_sh, aux_sh = jax.jit(lambda p, xx: M.moe_ffn(p, xx, cfg))(params, x)

    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sh),
                               atol=2e-4, rtol=2e-4)
    assert abs(float(aux_ref["loss"]) - float(aux_sh["loss"])) < 1e-5
    assert float(aux_sh["dropped"]) == float(aux_ref["dropped"]) == 0.0

    # gradients too
    def loss(p, xx):
        y, a = M.moe_ffn(p, xx, cfg)
        return jnp.sum(y ** 2) + 0.01 * a["loss"]
    g_ref = jax.grad(loss)(params, x)
    with use_mesh(mesh):
        g_sh = jax.jit(jax.grad(loss))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3)
    print("PASS")
    """)


def test_seq_parallel_attention_parity():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_config
    from repro.dist.flash import causal_attention
    from repro.dist.sharding import use_mesh

    cfg = get_config("qwen2-7b").reduced()   # 4 heads → seq strategy on 8
    cfg = dataclasses.replace(cfg, num_heads=6, num_kv_heads=2,
                              attn_block_q=16, attn_block_k=16)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, hd = 2, 64, cfg.head_dim
    q = jax.random.normal(ks[0], (b, s, 6, hd))
    k = jax.random.normal(ks[1], (b, s, 2, hd))
    v = jax.random.normal(ks[2], (b, s, 2, hd))

    ref = causal_attention(q, k, v, cfg=cfg)            # no mesh

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))     # 6 % 4 != 0 → seq
    with use_mesh(mesh):
        got = jax.jit(lambda a, b_, c: causal_attention(a, b_, c, cfg=cfg))(
            q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               atol=2e-4, rtol=2e-4)

    # grads through the shard_map path
    def loss(a, b_, c):
        return jnp.sum(jnp.sin(causal_attention(a, b_, c, cfg=cfg)))
    g_ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with use_mesh(mesh):
        g_got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)
    print("PASS")
    """)


def test_flash_decode_lse_combine_parity():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs import get_config
    from repro.dist.flash import decode_update_and_attend
    from repro.dist.sharding import use_mesh

    cfg = get_config("llama3.2-3b").reduced()
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, smax, h, kh, hd = 4, 64, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    kn = jax.random.normal(ks[1], (b, 1, kh, hd))
    vn = jax.random.normal(ks[2], (b, 1, kh, hd))
    kc = jax.random.normal(ks[3], (b, kh, smax, hd))   # head-major caches
    vc = jax.random.normal(ks[4], (b, kh, smax, hd))
    cur = jnp.asarray(37, jnp.int32)

    o_ref, kc_ref, vc_ref = decode_update_and_attend(
        q, kn, vn, kc, vc, cur, cfg=cfg)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        o, kc2, vc2 = jax.jit(lambda *a: decode_update_and_attend(
            *a, cfg=cfg))(q, kn, vn, kc, vc, cur)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(kc_ref), np.asarray(kc2),
                               atol=1e-6)
    print("PASS")
    """)


def test_param_shardings_cover_all_archs():
    _run("""
    import jax
    from repro.configs import all_arch_names, get_config
    from repro.dist.sharding import ShardCtx, param_shardings, use_mesh
    from repro.launch.specs import params_only_specs

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ShardCtx(mesh)
    for arch in all_arch_names():
        cfg = get_config(arch)
        shapes = params_only_specs(cfg)
        sh = param_shardings(shapes, ctx)
        # every leaf gets a sharding whose spec divides its shape
        def check(path, leaf, s):
            for dim, axes in zip(leaf.shape, s.spec):
                if axes is None:
                    continue
                names = axes if isinstance(axes, tuple) else (axes,)
                total = 1
                for n in names:
                    total *= mesh.shape[n]
                assert dim % total == 0, (arch, path, leaf.shape, s.spec)
        jax.tree_util.tree_map_with_path(
            lambda p, l, s: check(p, l, s), shapes, sh)
    print("PASS")
    """)


def test_train_step_sharded_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.train.steps import init_train_state, make_train_step
    from repro.dist.sharding import use_mesh
    from repro.data import SyntheticTokens

    cfg = get_config("llama3.2-3b").reduced()
    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50)
    data = SyntheticTokens(cfg.vocab_size, batch=4, seq=32, seed=5)
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
