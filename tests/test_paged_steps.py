"""The paged serve steps against the contiguous-cache decode.

``serve_prefill`` / ``serve_decode`` keep every request's KV in scattered
pages of two donated pools (``pool_shape``'s layout) and update them in
place; ``model.decode_step`` keeps one contiguous cache per request.  On
a reduced fp32 config the two must give the same logits and tokens, and
a row that is not active must leave the pools as they were.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.flash import paged_update_and_attend
from repro.models.model import LanguageModel
from repro.serve.steps import (make_paged_decode_step,
                               make_paged_prefill_step, pool_shape)

PAGE, POOL, PAD, MAX_PAGES = 4, 16, 8, 5
# scattered, non-contiguous page ids per row; row 1 stops after STOP steps
PAGES = [[9, 2, 14, 6, 1], [5, 11, 3, 15, 8], [0, 13, 7, 10, 4]]
PROMPTS = (6, 3, 7)
STEPS, STOP = 8, 3


def _model(kh, hd, window):
    cfg = dataclasses.replace(get_config("smollm-360m").reduced(),
                              num_heads=2 * kh, num_kv_heads=kh, head_dim=hd,
                              sliding_window=window)
    return LanguageModel(cfg)


def _pools(cfg):
    shape = pool_shape(cfg.num_layers, POOL, PAGE, cfg.num_kv_heads,
                       cfg.head_dim)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def _reference(model, params, seq, plen):
    """Logits after each of seq[plen-1:] on one contiguous cache."""
    decode = jax.jit(model.decode_step)
    cache = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                   model.cache_spec(1, 32))
    out = []
    for i in range(len(seq)):
        logits, cache = decode(params, cache,
                               jnp.asarray([[seq[i]]], jnp.int32),
                               jnp.asarray(i, jnp.int32))
        if i >= plen - 1:
            out.append(np.asarray(logits[0]))
    return out


@pytest.mark.parametrize("kh,hd,window", [
    (2, 32, 0),      # 2 heads of 32: two tokens to a pool row
    (5, 64, 0),      # the chat cell's 5 heads of 64: two tokens to a row
    (8, 128, 0),     # 8 heads of 128: one token of (KH, hd) to a row
    (2, 32, 5),      # a sliding window shorter than the contexts
])
def test_paged_steps_match_contiguous_decode(kh, hd, window):
    model = _model(kh, hd, window)
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPTS]
    prefill = make_paged_prefill_step(model, PAGE)
    decode = make_paged_decode_step(model)
    kp, vp = _pools(cfg)

    logits = [[] for _ in prompts]
    seqs = [list(p) for p in prompts]
    for row, p in enumerate(prompts):
        tk = np.zeros((1, PAD), np.int32)
        tk[0, :len(p)] = p
        pg = np.full(PAD // PAGE, POOL, np.int32)
        used = -(-len(p) // PAGE)
        pg[:used] = PAGES[row][:used]
        nt, lg, kp, vp = prefill(params, kp, vp, jnp.asarray(tk),
                                 jnp.int32(len(p)), jnp.asarray(pg))
        logits[row].append(np.asarray(lg))
        seqs[row].append(int(nt))

    table = np.asarray(PAGES, np.int32)
    cur = np.asarray(PROMPTS, np.int32)
    for step in range(STEPS):
        active = np.asarray([True, step < STOP, True])
        tokens = np.asarray([s[-1] for s in seqs], np.int32)
        before = (np.asarray(kp), np.asarray(vp))
        nt, lg, kp, vp, cur_new = decode(
            params, kp, vp, jnp.asarray(table), jnp.asarray(cur),
            jnp.asarray(active), jnp.asarray(tokens))
        if not active[1]:
            # the inactive row's pages are as they were
            for old, new in zip(before, (kp, vp)):
                np.testing.assert_array_equal(
                    np.asarray(new)[:, table[1]], old[:, table[1]])
        for row in np.nonzero(active)[0]:
            logits[row].append(np.asarray(lg[row]))
            seqs[row].append(int(nt[row]))
        np.testing.assert_array_equal(np.asarray(cur_new), cur + active)
        cur = np.asarray(cur_new)

    assert cur.max() > 2 * PAGE          # rows crossed page boundaries
    for row, p in enumerate(prompts):
        ref = _reference(model, params, seqs[row][:-1], len(p))
        assert len(ref) == len(logits[row])
        for got, want in zip(logits[row], ref):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
            assert int(np.argmax(got)) == int(np.argmax(want))


def test_paged_attend_inactive_rows_write_nothing_and_output_zeros():
    model = _model(2, 32, 0)
    cfg = model.cfg
    b, h, kh, hd = 3, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(b, 1, n, hd), jnp.float32)
               for n in (h, kh, kh))
    kp, vp = (jnp.asarray(rng.randn(*s.shape), jnp.float32)
              for s in _pools(cfg))
    active = jnp.asarray([True, False, True])
    out, kp2, vp2 = paged_update_and_attend(
        q, k, v, kp, vp, 1, jnp.asarray(PAGES, jnp.int32),
        jnp.asarray([5, 6, 9], jnp.int32), active)
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    assert np.abs(np.asarray(out[0])).max() > 0
    for old, new in ((kp, kp2), (vp, vp2)):
        old, new = np.asarray(old), np.asarray(new)
        changed = np.argwhere((old != new).reshape(old.shape[0],
                                                   old.shape[1], -1).any(-1))
        # one page of layer 1 per active row: token 5 of row 0 lies in
        # its logical page 1, token 9 of row 2 in its logical page 2
        assert sorted(map(tuple, changed)) == [(1, PAGES[0][1]),
                                               (1, PAGES[2][2])]


@pytest.mark.parametrize("kh,hd", [(2, 32), (8, 128)])
def test_paged_steps_donate_both_pools(kh, hd):
    model = _model(kh, hd, 0)
    cfg = model.cfg
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    kp, vp = _pools(cfg)
    i32 = jnp.int32
    dec = make_paged_decode_step(model).lower(
        params, kp, vp, jnp.zeros((3, MAX_PAGES), i32), jnp.zeros(3, i32),
        jnp.zeros(3, bool), jnp.zeros(3, i32)).compile()
    pre = make_paged_prefill_step(model, PAGE).lower(
        params, kp, vp, jnp.zeros((1, PAD), i32), i32(1),
        jnp.zeros(PAD // PAGE, i32)).compile()
    for compiled in (dec, pre):
        assert compiled.memory_analysis().alias_size_in_bytes == \
            kp.nbytes + vp.nbytes
