"""Trainer on the core runtime: descent, fault tolerance, stragglers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import SyntheticTokens
from repro.models.model import LanguageModel
from repro.optim import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=1e-3, warmup_steps=5, total_steps=60)
    data = SyntheticTokens(cfg.vocab_size, batch=4, seq=32, seed=7,
                           mode="markov")
    return cfg, model, oc, data


def test_descent(setup):
    cfg, model, oc, data = setup
    tr = Trainer(model, oc, data, TrainerConfig())
    state = tr.init_or_restore(jax.random.PRNGKey(0))
    tr.run(state, 10)
    losses = [h["ce_loss"] for h in tr.history]
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    # steps ran in order through the §4 labeled step map
    assert [h["step"] for h in tr.history] == list(range(10))


def test_failure_restart_bit_exact(setup, tmp_path):
    """Fail-stop at step 8, restart from the step-5 manifest, finish — final
    params must equal an uninterrupted run bit-for-bit."""
    cfg, model, oc, data = setup

    tr_a = Trainer(model, oc, data, TrainerConfig())
    state_a = tr_a.init_or_restore(jax.random.PRNGKey(0))
    state_a = tr_a.run(state_a, 12)

    tc = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                       async_ckpt=False, fail_at_step=8)
    tr_b = Trainer(model, oc, data, tc)
    state_b = tr_b.init_or_restore(jax.random.PRNGKey(0))
    tr_b.run(state_b, 12)
    assert max(h["step"] for h in tr_b.history) == 7   # died at 8

    tc2 = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                        async_ckpt=False)
    tr_c = Trainer(model, oc, data, tc2)
    state_c = tr_c.init_or_restore(jax.random.PRNGKey(99))  # key unused
    assert tr_c.start_step == 5
    state_c = tr_c.run(state_c, 12 - tr_c.start_step)

    for a, b in zip(jax.tree_util.tree_leaves(state_a["params"]),
                    jax.tree_util.tree_leaves(state_c["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_watchdog(setup, monkeypatch):
    cfg, model, oc, data = setup
    tr = Trainer(model, oc, data, TrainerConfig(straggler_factor=1.8))
    state = tr.init_or_restore(jax.random.PRNGKey(0))

    orig_get = data.get
    import time as _t

    def slow_get(step):
        if step == 9:
            _t.sleep(1.0)       # inject a straggler
        return orig_get(step)

    monkeypatch.setattr(data, "get", slow_get)
    tr.run(state, 11)
    assert 9 in tr.straggler_steps


def test_trainer_with_file_tokens(setup, tmp_path):
    """§5 file-backed data source feeding the trainer end-to-end."""
    import numpy as np
    from repro.data import FileTokens
    from repro.data.pipeline import write_token_file

    cfg, model, oc, _ = setup
    rng = np.random.default_rng(0)
    batch, seq, nb = 4, 32, 6
    raw = rng.integers(0, cfg.vocab_size,
                       size=(nb * batch * (seq + 1),), dtype=np.int32)
    path = str(tmp_path / "tokens.bin")
    write_token_file(path, raw)
    data = FileTokens(path, cfg.vocab_size, batch, seq)

    tr = Trainer(model, oc, data, TrainerConfig())
    state = tr.init_or_restore(jax.random.PRNGKey(0))
    tr.run(state, 5)
    assert len(tr.history) == 5
    assert all(np.isfinite(h["ce_loss"]) for h in tr.history)


def test_init_under_mesh_places_state_on_the_mesh(setup):
    """With a mesh, the initial state is built in the mesh layout (it never
    has to fit one device) and matches the unsharded initialization: the
    same random draws, up to the rounding of jit's fused init scaling."""
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_host_mesh
    cfg, model, oc, data = setup
    mesh = make_host_mesh()
    sharded = Trainer(model, oc, data, TrainerConfig(),
                      mesh=mesh).init_or_restore(jax.random.PRNGKey(0))
    plain = Trainer(model, oc, data,
                    TrainerConfig()).init_or_restore(jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(sharded)
    assert all(isinstance(a.sharding, NamedSharding)
               and a.sharding.mesh == mesh for a in leaves)
    for a, b in zip(leaves, jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
