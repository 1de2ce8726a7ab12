"""End-to-end system test: train through the OCR-runtime trainer with §5
chunked checkpoints, restore, then serve tokens from the trained model."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import ckpt
from repro.configs import get_config
from repro.data import SyntheticTokens
from repro.models.model import LanguageModel
from repro.optim import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def test_train_checkpoint_serve_roundtrip(tmp_path):
    cfg = get_config("llama3.2-3b").reduced()
    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=5e-3, warmup_steps=10, total_steps=400,
                         weight_decay=0.0)
    data = SyntheticTokens(cfg.vocab_size, batch=16, seq=32, seed=11,
                           mode="markov")

    tc = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=20,
                       async_ckpt=False)
    tr = Trainer(model, oc, data, tc)
    state = tr.init_or_restore(jax.random.PRNGKey(0))
    state = tr.run(state, 60)

    losses = [h["ce_loss"] for h in tr.history]
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses

    # the model learned the markov chain: greedy decode follows it
    tree, step = ckpt.restore(str(tmp_path))
    assert step == 60
    params = jax.tree_util.tree_map(jnp.asarray, tree)["params"]

    tokens = jnp.asarray([[7, (7 * 31 + 7) % cfg.vocab_size]], jnp.int32)
    logits, cache = jax.jit(model.prefill)(params, {"tokens": tokens})
    want = (int(tokens[0, -1]) * 31 + 7) % cfg.vocab_size
    top5 = np.argsort(np.asarray(logits[0]))[-5:]
    assert want in top5, (want, top5)
    pred = want

    # decode two more steps following the chain
    # grow the seq axis (axis -2 of head-major (L,B,K,S,hd)) by 4 tokens
    cache = jax.tree_util.tree_map(
        lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 2)
                          + [(0, 4), (0, 0)]),
        cache)
    cur = jnp.asarray(tokens.shape[1], jnp.int32)
    tok = jnp.asarray([[pred]], jnp.int32)
    hits = 0
    for i in range(2):
        logits2, cache = jax.jit(model.decode_step)(params, cache, tok,
                                                    cur + i)
        want_i = (int(tok[0, 0]) * 31 + 7) % cfg.vocab_size
        top5_i = np.argsort(np.asarray(logits2[0]))[-5:]
        if want_i in top5_i:
            hits += 1
        tok = jnp.asarray([[want_i]], jnp.int32)
    assert hits >= 1


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache sits at JAX_COMPILATION_CACHE_DIR when
    that is set, else at a fixed path inside the checkout."""
    import os
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
