"""Checkpoint layer (§5/§6): roundtrip, dirty-skip, commit, elasticity,
async, crash consistency, corrupt-manifest resilience, sharded ranges."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import ckpt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            "embedding": rng.normal(size=(32, 8)).astype(np.float32),
            "layers": {"w": rng.normal(size=(4, 8, 8)).astype(np.float32),
                       "b": np.zeros((4, 8), np.float32)},
        },
        "opt": {"m": {"w": np.zeros((4, 8, 8), np.float32)},
                "step": np.asarray(7, np.int32)},
    }


def _assert_tree_equal(a, b):
    if isinstance(a, dict):
        assert sorted(a) == sorted(b)
        for k in a:
            _assert_tree_equal(a[k], b[k])
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_roundtrip(tmp_path):
    t = _tree()
    stats = ckpt.save(str(tmp_path), t, 3, chunk_bytes=256)
    assert stats.chunks_written == stats.chunks_total
    got, step = ckpt.restore(str(tmp_path))
    assert step == 3
    _assert_tree_equal(t, got)


def test_dirty_skip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), t, 1, chunk_bytes=128)
    s2 = ckpt.save(str(tmp_path), t, 2, chunk_bytes=128)
    assert s2.chunks_written == 0
    assert s2.chunks_skipped == s2.chunks_total
    # change ONE leaf: only its chunks rewrite
    t["params"]["layers"]["w"][2, 3, 4] = 99.0
    s3 = ckpt.save(str(tmp_path), t, 3, chunk_bytes=128)
    assert 0 < s3.chunks_written < s3.chunks_total
    got, step = ckpt.restore(str(tmp_path))
    assert step == 3
    _assert_tree_equal(t, got)


def test_manifest_commit_protects_partial_saves(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), t, 5)
    # a crashed save leaves a .tmp dir without manifest — must be ignored
    os.makedirs(tmp_path / "step_9.tmp")
    with open(tmp_path / "step_9.tmp" / "leaf_0.bin", "wb") as f:
        f.write(b"garbage")
    assert ckpt.latest_step(str(tmp_path)) == 5
    got, step = ckpt.restore(str(tmp_path))
    assert step == 5


def test_elastic_reader_count(tmp_path):
    """Restore must reassemble identically for any reader parallelism."""
    t = _tree(seed=4)
    ckpt.save(str(tmp_path), t, 1, chunk_bytes=64, num_writers=3)
    for readers in (1, 2, 7):
        got, _ = ckpt.restore(str(tmp_path), num_readers=readers)
        _assert_tree_equal(t, got)


def test_async_save(tmp_path):
    t = _tree(seed=9)
    th = ckpt.async_save(str(tmp_path), t, 11)
    # mutate after issue: snapshot semantics (§3 issue-now/resolve-later)
    t["params"]["embedding"][:] = -1
    th.join()
    got, step = ckpt.restore(str(tmp_path))
    assert step == 11
    assert not np.allclose(got["params"]["embedding"], -1)


def test_restore_specific_step(tmp_path):
    a, b = _tree(1), _tree(2)
    ckpt.save(str(tmp_path), a, 1)
    ckpt.save(str(tmp_path), b, 2)
    got, step = ckpt.restore(str(tmp_path), step=1)
    assert step == 1
    _assert_tree_equal(a, got)


def test_crash_mid_flush_preserves_previous(tmp_path):
    """A save killed with coalesced writes pending must not commit, and
    the previous step must still round-trip; the .tmp dir is ignored."""
    a = _tree(3)
    ckpt.save(str(tmp_path), a, 1)
    b = _tree(4)
    stats = ckpt.save(str(tmp_path), b, 2, crash_at=0.5)
    assert not stats.committed
    assert os.path.isdir(tmp_path / "step_2.tmp")      # dead weight, ignored
    assert ckpt.latest_step(str(tmp_path)) == 1
    got, step = ckpt.restore(str(tmp_path))
    assert step == 1
    _assert_tree_equal(a, got)
    # a later save is unaffected by the wreckage
    s3 = ckpt.save(str(tmp_path), b, 3)
    assert s3.committed
    got, step = ckpt.restore(str(tmp_path))
    assert step == 3
    _assert_tree_equal(b, got)


def test_corrupt_prev_manifest_skips_dirty_tracking(tmp_path):
    """A corrupt previous manifest only disables the dirty skip (warn)."""
    t = _tree(5)
    ckpt.save(str(tmp_path), t, 1, chunk_bytes=128)
    with open(tmp_path / "step_1" / "manifest.json", "w") as f:
        f.write("{definitely not json")
    with pytest.warns(UserWarning, match="dirty-range skipping disabled"):
        s2 = ckpt.save(str(tmp_path), t, 2, chunk_bytes=128)
    assert s2.committed
    assert s2.chunks_written == s2.chunks_total       # full write, no skip
    got, step = ckpt.restore(str(tmp_path), step=2)
    _assert_tree_equal(t, got)


def test_host_tree_reports_no_gathers(tmp_path):
    stats = ckpt.save(str(tmp_path), _tree(), 1)
    assert stats.host_gathers == 0


def _run_devices(code: str):
    full = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys\nsys.path.insert(0, 'src')\n" + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", full], capture_output=True,
                         text=True, cwd=ROOT, timeout=560)
    assert out.returncode == 0 and "PASS" in out.stdout, \
        (out.stdout[-1500:], out.stderr[-3000:])


def test_sharded_save_reshard_on_restore():
    """Save under an 8-device mesh; restore under 2- and 1-device meshes
    and pure_dp — bit-exact via the §6 range manifest, zero gathers."""
    _run_devices("""
    import json, os, tempfile, shutil
    import numpy as np
    import jax
    from repro.launch.mesh import make_mesh
    from repro import ckpt
    from repro.dist.sharding import ShardCtx, param_shardings

    rng = np.random.default_rng(0)
    tree = {"params": {
        "w_q": rng.normal(size=(32, 8, 16)).astype(np.float32),
        "w_down": rng.normal(size=(64, 32)).astype(np.float32),
        "norm": rng.normal(size=(32,)).astype(np.float32)},
        "opt": {"step": np.asarray(11, np.int32)}}
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    mesh8 = make_mesh((2, 4), ("data", "model"))
    sh8 = param_shardings(shapes, ShardCtx(mesh=mesh8))
    dev = jax.tree_util.tree_map(jax.device_put, tree, sh8)
    tmp = tempfile.mkdtemp()
    st = ckpt.save(tmp, dev, 1, num_writers=8)
    assert st.host_gathers == 0, st
    assert st.committed

    # the manifest carries per-range (node, offset, size) entries
    with open(os.path.join(tmp, "step_1", "manifest.json")) as f:
        man = json.load(f)
    sharded_leaves = [l for l in man["leaves"] if "ranges" in l]
    assert sharded_leaves, man["leaves"]
    for l in sharded_leaves:
        assert all(len(r) == 3 for r in l["ranges"])
        spans = sorted((off, off + size) for _n, off, size in l["ranges"])
        assert spans[0][0] == 0 and spans[-1][1] == l["nbytes"]
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))

    def check(shardings):
        got, step = ckpt.restore(tmp, shardings=shardings)
        assert step == 1
        for k in tree["params"]:
            np.testing.assert_array_equal(
                tree["params"][k], np.asarray(got["params"][k]))
        np.testing.assert_array_equal(
            tree["opt"]["step"], np.asarray(got["opt"]["step"]))

    check(None)                                        # plain host restore
    mesh2 = make_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2])
    check(param_shardings(shapes, ShardCtx(mesh=mesh2)))
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    check(param_shardings(shapes, ShardCtx(mesh=mesh1)))
    check(param_shardings(shapes, ShardCtx(mesh=mesh2, pure_dp=True)))

    # dirty-skip across identical sharded saves
    st2 = ckpt.save(tmp, dev, 2, num_writers=8)
    assert st2.chunks_written == 0 and st2.chunks_skipped == st2.chunks_total
    shutil.rmtree(tmp)
    print("PASS")
    """)


def test_sharded_save_restores_on_other_writer_count():
    """§6 range manifest is elastic in the writer/reader dimension too."""
    _run_devices("""
    import tempfile, shutil
    import numpy as np
    import jax
    from repro.launch.mesh import make_mesh
    from repro import ckpt
    from repro.dist.sharding import ShardCtx, param_shardings

    rng = np.random.default_rng(2)
    tree = {"w_up": rng.normal(size=(16, 64)).astype(np.float32)}
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    mesh = make_mesh((2, 4), ("data", "model"))
    dev = jax.tree_util.tree_map(
        jax.device_put, tree, param_shardings(shapes, ShardCtx(mesh=mesh)))
    tmp = tempfile.mkdtemp()
    ckpt.save(tmp, dev, 1, num_writers=3)       # writers != devices
    for readers in (1, 2, 7):
        got, _ = ckpt.restore(tmp, num_readers=readers)
        np.testing.assert_array_equal(tree["w_up"], np.asarray(got["w_up"]))
    shutil.rmtree(tmp)
    print("PASS")
    """)
