"""The §6 bridge: every sharding this framework emits is a valid paper-§6
partitioning — its per-device (offset, size) ranges are accepted by the
core runtime's ``db_partition`` (which enforces the §6.2 invariants).
"""
import os
import subprocess
import sys
import textwrap

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


def test_param_shardings_are_valid_section6_partitions():
    _run("""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.dist.sharding import ShardCtx, param_shardings, partition_tree_of
    from repro.launch.specs import params_only_specs
    from repro.core import NULL_GUID, Runtime, spawn_main

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ShardCtx(mesh)

    checked = [0]
    for arch in ("llama3.2-3b", "deepseek-v2-236b", "mamba2-1.3b"):
        cfg = get_config(arch).reduced()
        shapes = params_only_specs(cfg)
        shardings = param_shardings(shapes, ctx)

        leaves = list(zip(jax.tree_util.tree_leaves(shapes),
                          jax.tree_util.tree_leaves(shardings)))
        for leaf, sh in leaves:
            parts = partition_tree_of(tuple(leaf.shape),
                                      np.dtype(leaf.dtype).itemsize, sh)
            uniq = sorted(set(parts))
            total = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            # replicated dims repeat ranges; distinct ranges must tile the
            # buffer disjointly — proven by handing them to db_partition
            if len(uniq) == 1:
                continue
            rt = Runtime()
            res = {}

            def main(paramv, depv, api):
                db, _ = api.db_create(total)
                api.db_release(db)
                api.db_partition(db, uniq)      # §6.2 invariants enforced
                res["ok"] = True
                return NULL_GUID

            spawn_main(rt, main)
            rt.run()
            assert res.get("ok"), (arch, leaf.shape, sh.spec, uniq[:4])
            # and they cover the buffer exactly when the leading dim shards
            assert sum(s for _, s in uniq) == total
            checked[0] += 1
    assert checked[0] >= 3, checked
    print("PASS")
    """)


def test_partition_tree_of_properties_hypothesis():
    """Property test: for random shapes × meshes × specs, the emitted
    ranges are mutually disjoint, tile the buffer exactly, pass the §6.2
    invariant checks of ``db_partition``, and are lane-aligned (128 B)
    whenever the sharded dim's contiguous run allows it."""
    import pytest
    pytest.importorskip("hypothesis")
    _run("""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from hypothesis import given, settings, strategies as st
    from repro.core import NULL_GUID, Runtime, spawn_main
    from repro.dist.sharding import partition_tree_of

    MESHES = (((8,), ("model",)),
              ((2, 4), ("data", "model")),
              ((4, 2), ("data", "model")),
              ((2, 2, 2), ("pod", "data", "model")))

    @st.composite
    def cases(draw):
        mi = draw(st.integers(0, len(MESHES) - 1))
        mesh_shape, axes = MESHES[mi]
        ndim = draw(st.integers(1, 3))
        dims = tuple(draw(st.sampled_from((1, 2, 3, 4, 6, 8, 16, 32, 48)))
                     for _ in range(ndim))
        spec = [None] * ndim
        used = set()
        for ax, size in zip(axes, mesh_shape):
            d = draw(st.integers(-1, ndim - 1))
            if d >= 0 and d not in used and dims[d] % size == 0:
                spec[d] = ax
                used.add(d)
        itemsize = draw(st.sampled_from((1, 2, 4)))
        return mi, dims, tuple(spec), itemsize

    @settings(max_examples=80, deadline=None)
    @given(cases())
    def prop(case):
        mi, dims, spec, itemsize = case
        mesh_shape, axes = MESHES[mi]
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(mesh_shape, axes)
        sizes = dict(zip(axes, mesh_shape))
        sh = NamedSharding(mesh, P(*spec))
        parts = partition_tree_of(dims, itemsize, sh)
        assert len(parts) >= mesh.size      # >= one range per device
        total = int(np.prod(dims)) * itemsize
        uniq = sorted(set(parts))
        # disjoint + exact tiling: sorted distinct ranges chain perfectly
        off = 0
        for o, s in uniq:
            assert o == off and s > 0, (uniq, dims, spec)
            off += s
        assert off == total, (uniq, dims, spec)
        # accepted by the core runtime's db_partition (§6.2 invariants)
        if len(uniq) > 1:
            rt = Runtime()
            res = {}

            def main(paramv, depv, api):
                db, _ = api.db_create(total)
                api.db_release(db)
                api.db_partition(db, uniq)
                res["ok"] = True
                return NULL_GUID

            spawn_main(rt, main)
            rt.run()
            assert res.get("ok"), (dims, spec, uniq[:4])
        # lane alignment where the sharded dim allows: every range is a
        # multiple of the innermost contiguous run, so when that run is a
        # multiple of 128 B all offsets/sizes are lane-aligned
        sharded = [i for i, a in enumerate(spec) if a is not None]
        if sharded:
            k = sharded[-1]
            run = (dims[k] // sizes[spec[k]]) * itemsize
            run *= int(np.prod(dims[k + 1:], dtype=np.int64))
            if run % 128 == 0:
                assert all(o % 128 == 0 and s % 128 == 0 for o, s in uniq)

    prop()
    print("PASS")
    """)


def test_pure_dp_train_parity():
    """pure_dp mode must produce the same step as single-device."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.train.steps import init_train_state, make_train_step
    from repro.dist.sharding import use_mesh
    from repro.data import SyntheticTokens

    cfg = get_config("smollm-360m").reduced()
    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50)
    data = SyntheticTokens(cfg.vocab_size, batch=8, seq=32, seed=5)
    step = make_train_step(model, oc)
    b = {k: jnp.asarray(v) for k, v in data.get(0).items()}

    s1 = init_train_state(model, jax.random.PRNGKey(0), oc)
    s1b, m1 = jax.jit(step)(s1, b)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    s2 = init_train_state(model, jax.random.PRNGKey(0), oc)
    with use_mesh(mesh, pure_dp=True):
        s2b, m2 = jax.jit(step)(s2, b)

    assert abs(float(m1["ce_loss"]) - float(m2["ce_loss"])) < 1e-3
    for a, c in zip(jax.tree_util.tree_leaves(s1b["params"]),
                    jax.tree_util.tree_leaves(s2b["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=3e-4, rtol=3e-4)
    print("PASS")
    """)
