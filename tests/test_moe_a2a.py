"""Capacity-bucketed all-to-all MoE dispatch (repro.models.moe).

Four angles: (1) the a2a path, the legacy psum path and the single-device
oracle agree — outputs, aux loss AND gradients — on 8 forced host
devices; (2) the bucket pack/unpack custom VJPs are the true transposes
(checked against plain-autodiff references and numerically); (3) bucket
slots are disjoint and capacity-bounded for arbitrary routings
(hypothesis), and ``moe_bucket_ranges`` emits §6 partitions that
``db_partition`` accepts; (4) overflow drops are deterministic and keep
the earliest tokens (stable sort).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
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


def test_a2a_psum_oracle_parity():
    """a2a == psum == single-device oracle: y, balance loss, grads."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.dist.sharding import use_mesh
    from repro.models import moe as M

    cfg = get_config("deepseek-v2-236b").reduced()   # cf=8.0: no drops
    cfg = dataclasses.replace(cfg, num_experts=8, experts_per_token=2)
    params = M.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

    def loss(cfg_):
        def f(p, xx):
            y, a = M.moe_ffn(p, xx, cfg_)
            return jnp.sum(y ** 2) + 0.01 * a["loss"], (y, a)
        return f

    (l_ref, (y_ref, a_ref)), g_ref = jax.value_and_grad(
        loss(cfg), has_aux=True)(params, x)          # no mesh: oracle

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    outs = {}
    for dispatch in ("a2a", "psum"):
        c = dataclasses.replace(cfg, moe_dispatch=dispatch)
        with use_mesh(mesh):
            outs[dispatch] = jax.jit(jax.value_and_grad(
                loss(c), has_aux=True))(params, x)

    for dispatch, ((l, (y, a)), g) in outs.items():
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                                   atol=2e-4, rtol=2e-4, err_msg=dispatch)
        np.testing.assert_allclose(float(l_ref), float(l), rtol=1e-5,
                                   err_msg=dispatch)
        assert float(a["dropped"]) == 0.0, dispatch
        for pa, pb in zip(jax.tree_util.tree_leaves(g_ref),
                          jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       atol=5e-3, rtol=5e-3,
                                       err_msg=dispatch)
    # the a2a gauge is live only on the a2a path
    assert float(outs["a2a"][0][1][1]["a2a_bytes"]) > 0
    assert float(outs["psum"][0][1][1]["a2a_bytes"]) == 0
    print("PASS")
    """)


def _routing_tables(key, t, e, k, capacity):
    from repro.models import moe as M
    kg, ki = jax.random.split(key)
    logits = jax.random.normal(kg, (t, e))
    gates, idx = M._route(logits, k)
    n = t * k
    flat_e = idx.reshape(n).astype(jnp.int32)
    flat_g = gates.reshape(n)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    pos = M._expert_positions(flat_e, n)
    valid = (pos < capacity) & (flat_g > 0)
    safe_pos = jnp.where(valid, pos, capacity).astype(jnp.int32)
    w = (flat_g * valid).astype(jnp.float32)
    return flat_e, safe_pos, tok, w, valid


def test_dispatch_combine_custom_vjp_gradcheck():
    """The chunked-scan custom VJPs equal plain autodiff of the direct
    scatter/gather formulation, and pass numerical gradcheck."""
    from repro.models import moe as M
    t, e, k, cap, d = 12, 4, 2, 3, 8
    key = jax.random.PRNGKey(7)
    fe, sp, tok, w, _ = _routing_tables(key, t, e, k, cap)
    x = jax.random.normal(jax.random.PRNGKey(8), (t, d))
    yg = jax.random.normal(jax.random.PRNGKey(9), (e, cap, d))

    def ref_dispatch(xx, ww):
        acc = jnp.zeros((e, cap + 1, d))
        acc = acc.at[fe, sp].add(xx[tok] * (ww > 0)[:, None], mode="drop")
        return acc[:, :cap]

    def ref_combine(yy, ww):
        y_ext = jnp.concatenate([yy, jnp.zeros((e, 1, d))], axis=1)
        out = jnp.zeros((t, d))
        return out.at[tok].add(y_ext[fe, sp] * ww[:, None], mode="drop")

    co = jax.random.normal(jax.random.PRNGKey(10), (e, cap, d))

    def f_cust(xx):
        return jnp.sum(M._dispatch(xx, fe, sp, tok, w, e, cap,
                                   str(x.dtype), t) * co)

    def f_ref(xx):
        return jnp.sum(ref_dispatch(xx, w) * co)

    np.testing.assert_allclose(f_cust(x), f_ref(x), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(jax.grad(f_cust)(x)),
                               np.asarray(jax.grad(f_ref)(x)), rtol=1e-5)

    ct = jax.random.normal(jax.random.PRNGKey(11), (t, d))

    def g_cust(yy, ww):
        return jnp.sum(M._combine(yy, fe, sp, tok, ww, t) * ct)

    def g_ref(yy, ww):
        return jnp.sum(ref_combine(yy, ww) * ct)

    np.testing.assert_allclose(g_cust(yg, w), g_ref(yg, w), rtol=1e-5)
    for a, b in zip(jax.grad(g_cust, argnums=(0, 1))(yg, w),
                    jax.grad(g_ref, argnums=(0, 1))(yg, w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)

    # numerical check through the full pack → unpack round trip
    from jax.test_util import check_grads

    def roundtrip(xx):
        xx = jnp.asarray(xx)     # check_grads perturbs with numpy arrays
        buckets = M._dispatch(xx, fe, sp, tok, w, e, cap, str(x.dtype), t)
        return jnp.sum(M._combine(buckets, fe, sp, tok, w, t) ** 2)

    check_grads(roundtrip, (x,), order=1, modes=("rev",),
                atol=1e-3, rtol=1e-3)


def test_bucket_slots_disjoint_and_capacity_bounded():
    """Hypothesis: for arbitrary routings, every kept (token, choice) pair
    gets a unique (expert, slot) with slot < capacity; per-expert kept
    counts saturate at capacity; dropped pairs are exactly the overflow."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.models import moe as M

    @st.composite
    def cases(draw):
        t = draw(st.integers(2, 24))
        e = draw(st.sampled_from((2, 4, 8, 16)))
        k = draw(st.integers(1, min(4, e)))
        cap = draw(st.integers(1, 8))
        seed = draw(st.integers(0, 2 ** 16))
        return t, e, k, cap, seed

    @settings(max_examples=60, deadline=None)
    @given(cases())
    def prop(case):
        t, e, k, cap, seed = case
        fe, sp, tok, w, valid = _routing_tables(
            jax.random.PRNGKey(seed), t, e, k, cap)
        fe_, sp_, valid_ = (np.asarray(fe), np.asarray(sp),
                            np.asarray(valid))
        kept = [(int(a), int(b)) for a, b, v in zip(fe_, sp_, valid_) if v]
        # disjoint: each (expert, slot) used at most once
        assert len(kept) == len(set(kept))
        # capacity-bounded
        assert all(0 <= s < cap for _, s in kept)
        # per-expert saturation: kept == min(assigned, capacity)
        for ex in range(e):
            assigned = int((fe_ == ex).sum())
            got = sum(1 for a, _ in kept if a == ex)
            assert got == min(assigned, cap), (ex, assigned, got, cap)

    prop()


def test_bucket_ranges_are_section6_partitions():
    """``moe_bucket_ranges`` under an EP mesh: disjoint ranges tiling the
    (E, C, D) bucket block, accepted by the core ``db_partition``."""
    _run("""
    import jax
    import numpy as np
    from repro.core import NULL_GUID, Runtime, spawn_main
    from repro.dist.sharding import ShardCtx, moe_bucket_ranges

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ShardCtx(mesh)
    checked = 0
    for e, cap, d, item in ((8, 3, 16, 4), (64, 5, 128, 4),
                            (128, 1, 32, 2), (160, 7, 8, 4)):
        ranges = moe_bucket_ranges(e, cap, d, item, ctx)
        total = e * cap * d * item
        assert len(ranges) == 4, ranges       # one per "model" shard
        off = 0
        for o, s in ranges:                   # disjoint + exact tiling
            assert o == off and s == total // 4, ranges
            off += s
        assert off == total
        rt = Runtime()
        res = {}

        def main(paramv, depv, api, _total=total, _ranges=ranges):
            db, _ = api.db_create(_total)
            api.db_release(db)
            api.db_partition(db, _ranges)     # §6.2 invariants enforced
            res["ok"] = True
            return NULL_GUID

        spawn_main(rt, main)
        rt.run()
        assert res.get("ok"), (e, cap, ranges)
        checked += 1
    assert checked == 4

    # no active EP axis: the whole block is one local range
    assert moe_bucket_ranges(8, 3, 16, 4, ShardCtx(None)) == [(0, 8*3*16*4)]
    print("PASS")
    """)


def test_overflow_drops_deterministic_and_earliest_win():
    """With a starved capacity factor, repeated runs are bitwise identical
    and the stable sort keeps the earliest tokens' slots."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import moe as M

    cfg = get_config("deepseek-v2-236b").reduced()
    cfg = dataclasses.replace(cfg, num_experts=4, experts_per_token=2,
                              capacity_factor=0.25, num_shared_experts=0)
    params = M.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))

    fn = jax.jit(lambda p, xx: M.moe_ffn(p, xx, cfg))
    y1, a1 = fn(params, x)
    y2, a2 = fn(params, x)
    assert float(a1["dropped"]) > 0           # starved: drops must occur
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert float(a1["dropped"]) == float(a2["dropped"])

    # earliest-token-wins: slots go to the first `capacity` pairs of each
    # expert in token order (stable argsort)
    t, e, k, cap = 16, 4, 2, 2
    fe, sp, tok, w, valid = _routing_tables(
        jax.random.PRNGKey(3), t, e, k, cap)
    fe_, valid_, tok_ = np.asarray(fe), np.asarray(valid), np.asarray(tok)
    for ex in range(e):
        rows = np.where(fe_ == ex)[0]         # already in token order
        expect = set(rows[:cap].tolist())
        got = set(rows[valid_[rows]].tolist())
        assert got == expect, (ex, expect, got)


def test_a2a_sharded_drop_determinism():
    """The sharded a2a path with drops: two executions bitwise agree."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.dist.sharding import use_mesh
    from repro.models import moe as M

    cfg = get_config("deepseek-v2-236b").reduced()
    cfg = dataclasses.replace(cfg, num_experts=8, experts_per_token=2,
                              capacity_factor=0.5, num_shared_experts=0)
    params = M.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        fn = jax.jit(lambda p, xx: M.moe_ffn(p, xx, cfg))
        y1, a1 = fn(params, x)
        y2, a2 = fn(params, x)
    assert float(a1["dropped"]) > 0
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert float(a1["dropped"]) == float(a2["dropped"])
    print("PASS")
    """)
