#!/usr/bin/env python3
"""Smoke test of the main path on a TPU chip, in one process.

    python chip_smoke.py              # one chip: kernels, train, serve, copy
    python chip_smoke.py --chips 4    # four chips: sharded training only

One chip, at smollm-360m's full published width with random weights:

* ``kernels`` — the Pallas flash forward and gradients at the model's
  attention widths (B=1, S=4096, H=15, KH=5, hd=64) against the jnp twin in
  float32.
* ``train`` — ``repro.train.trainer.Trainer`` (the path
  ``repro.launch.train`` runs: §4 labeled step map, one §5 checkpoint) for
  a few steps at seq 4096, where attention takes the Pallas flash kernels
  forward and backward.  The batch is the largest candidate whose compiled
  step fits the device.  Step-0 loss must sit within 0.5 of ln(vocab) + 1/2
  (the random init's logit variance is ~1), loss must fall by more than
  the batch-to-batch spread, and the checkpoint must restore bit-exact.
* ``serve`` — ``ServeEngine`` over ``ModelBackend`` (``repro.launch.serve``
  without ``--smoke``) answering Poisson requests; every generated token is
  checked against a contiguous-cache reference (``model.decode_step``,
  which runs ``flash_decode``).
* ``copy`` — a ``Runtime(copy_backend="pallas")`` graph of same-timestamp
  §6.3 partition copies over a VMEM-resident block and an HBM/DMA-staged
  one, bit-exact against ``copy_backend="numpy"``.

Four chips (``--chips 4``): llama3.2-3b, whose float32 training state does
not fit one chip, trains a few steps on a ("data", "model") mesh; its step-0
loss is compared with a single-device bf16 forward of the same initial
parameters, each device must hold about a quarter of the parameter bytes,
and a sharded checkpoint of trained parameters must restore bit-exact.

Each phase prints its compile seconds, host-clock step or request times
(around ``block_until_ready``), the device's peak bytes in use, and its
check's numbers.  Any failed check raises, so the exit code is non-zero;
the last line of a passing run is the JSON device record.  Without a TPU
the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
TRAIN_SEQ = 4096
TRAIN_BATCHES = (12, 8, 4)      # tried largest first; first that fits runs
TRAIN_STEPS = 5
# the markov chain runs over the first DATA_VOCAB token ids: structure the
# model picks up within a few updates, unlike a chain over all 49152
DATA_VOCAB = 4096
LOSS_FALL = 0.1                 # > the 0.06 batch-to-batch spread at init
FIT_FRACTION = 0.92             # of the device's bytes_limit
FLASH_TOL = 1e-2                # max |err| / max |ref|, flash vs f32 twin
LOGIT_TOL = 2e-2                # near-tie margin, relative to max |logit|
SHARDED_LOSS_TOL = 2e-2         # |loss_mesh - loss_single|, both bf16


class SmokeError(AssertionError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def need_and_limit(compiled, dev) -> tuple:
    """(bytes the compiled program needs, the device's bytes_limit)."""
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return need, int(dev.memory_stats()["bytes_limit"])


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def summary(ts) -> str:
    import numpy as np
    t = np.asarray(ts) * 1e3
    return (f"n={len(t)} median={np.median(t):.3f}ms min={t.min():.3f}ms "
            f"max={t.max():.3f}ms")


# ------------------------------------------------------------------ phases

def phase_kernels(dev, b=1, s=TRAIN_SEQ, h=15, kh=5, hd=64):
    """Pallas flash fwd + grads vs the jnp twin, float32."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models.attention import flash_attention_jnp

    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32)
    w = jax.random.normal(ks[3], (b, s, h, hd), jnp.float32)

    def pallas(q_, k_, v_):
        return ops.flash_attention(q_, k_, v_, causal=True)

    def twin(q_, k_, v_):
        return flash_attention_jnp(q_, k_, v_, jnp.zeros((), jnp.float32),
                                   True, 0, 512, 1024)

    def fwd_and_grads(attn):
        def loss(q_, k_, v_):
            return jnp.sum(attn(q_, k_, v_) * w)
        return jax.jit(lambda q_, k_, v_: (
            attn(q_, k_, v_), jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)))

    t0 = time.perf_counter()
    compiled = fwd_and_grads(pallas).lower(q, k, v).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text()
          or jax.default_backend() != "tpu",
          "flash fwd/bwd did not compile to a Mosaic kernel")
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(q, k, v))
        times.append(time.perf_counter() - t0)
    with jax.default_matmul_precision("highest"):
        ref = jax.block_until_ready(fwd_and_grads(twin)(q, k, v))
    errs = {"out": rel_err(got[0], ref[0])}
    for name, g_, r_ in zip(("dq", "dk", "dv"), got[1], ref[1]):
        errs[name] = rel_err(g_, r_)
    log("kernels", f"flash B={b} S={s} H={h} KH={kh} hd={hd} f32: "
        f"compile {compile_s:.2f}s; fwd+bwd {summary(times)}")
    log("kernels", "max|err|/max|ref| vs jnp twin: " + " ".join(
        f"{k_}={v_:.3e}" for k_, v_ in errs.items())
        + f" (tol {FLASH_TOL:g}); peak {peak_bytes(dev)} B")
    check(all(e <= FLASH_TOL for e in errs.values()),
          f"flash kernels disagree with the jnp twin: {errs}")


def phase_train(dev, cfg, seq=TRAIN_SEQ, batches=TRAIN_BATCHES,
                steps=TRAIN_STEPS):
    """Trainer at full width; largest fitting batch; loss + ckpt checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import ckpt
    from repro.data import SyntheticTokens
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.train.steps import init_train_state, make_train_step
    from repro.train.trainer import Trainer, TrainerConfig

    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=3e-3, warmup_steps=1, total_steps=steps,
                         state_dtype=cfg.optimizer_state_dtype)
    # shapes on the default device, as the Trainer's own arrays are: the
    # Trainer's compile of the same step then hits the compile cache
    shapes = jax.eval_shape(lambda k_: init_train_state(model, k_, oc),
                            jax.random.PRNGKey(SEED))
    step = jax.jit(make_train_step(model, oc), donate_argnums=(0,))
    compile_s = 0.0
    for b in batches:
        tok = jax.ShapeDtypeStruct((b, seq), jnp.int32)
        t0 = time.perf_counter()
        compiled = step.lower(shapes, {"tokens": tok,
                                       "targets": tok}).compile()
        compile_s += time.perf_counter() - t0
        need, limit = need_and_limit(compiled, dev)
        ok = need <= FIT_FRACTION * limit
        log("train", f"B={b} S={seq}: compiled step needs {need} B "
            f"({'fits' if ok else 'does not fit'} {FIT_FRACTION:g} of "
            f"{limit} B)")
        if ok:
            break
    check(ok, "no candidate batch fits the device")
    n_kernels = compiled.as_text().count("tpu_custom_call")
    check(n_kernels > 0 or jax.default_backend() != "tpu",
          "train step holds no Pallas kernel")
    log("train", f"{cfg.name} full width: compile {compile_s:.2f}s, "
        f"B={b} S={seq}, {n_kernels} Pallas kernel calls in the step HLO")

    data = SyntheticTokens(min(DATA_VOCAB, cfg.vocab_size), b, seq,
                           seed=SEED, mode="markov")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ck:
        tr = Trainer(model, oc, data,
                     TrainerConfig(ckpt_dir=ck, ckpt_every=steps,
                                   async_ckpt=False))
        state = tr.init_or_restore(jax.random.PRNGKey(SEED))
        state = tr.run(state, steps)
        losses = [h_["loss"] for h_ in tr.history]
        times = [h_["step_time"] for h_ in tr.history]
        toks = b * seq
        log("train", "losses " + " ".join(f"{x:.4f}" for x in losses))
        log("train", "step times " + " ".join(f"{t:.3f}s" for t in times)
            + f"; steps 1.. {summary(times[1:])}, "
            f"{toks / float(np.median(times[1:])):.0f} tok/s; "
            f"peak {peak_bytes(dev)} B")
        # random init: embeddings have std 1/sqrt(d) and the final norm
        # gives unit-rms hidden states, so tied-unembedding logits have
        # variance ~1 and the expected loss is ln(V) + 1/2
        expect = math.log(cfg.vocab_size) + 0.5
        check(abs(losses[0] - expect) <= 0.5,
              f"step-0 loss {losses[0]:.4f} not within 0.5 of "
              f"ln(V) + 1/2 = {expect:.4f}")
        check(losses[-1] < losses[0] - LOSS_FALL,
              f"loss did not fall by {LOSS_FALL}: {losses}")
        t0 = time.perf_counter()
        restored, at = ckpt.restore(ck)
        restore_s = time.perf_counter() - t0
        host = jax.tree_util.tree_map(np.asarray, state)
        flat_a = jax.tree_util.tree_leaves(host)
        flat_b = jax.tree_util.tree_leaves(restored)
        exact = (len(flat_a) == len(flat_b) and all(
            a.dtype == np.asarray(b_).dtype and np.array_equal(a, b_)
            for a, b_ in zip(flat_a, flat_b)))
        nbytes = sum(a.nbytes for a in flat_a)
        log("train", f"checkpoint step {at}: {len(flat_a)} leaves, "
            f"{nbytes} B, restore {restore_s:.2f}s, bit-exact={exact}")
        check(at == steps, f"checkpoint at step {at}, expected {steps}")
        check(exact, "checkpoint did not restore bit-exact")
    del state, host, restored


def phase_serve(dev, cfg, n_requests=8, rate=200.0, prompt_len=(16, 96),
                gen=(8, 24), page=16, b_cap=8, pool_pages=64, max_pages=8):
    """ServeEngine + ModelBackend vs the contiguous-cache reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import LanguageModel
    from repro.serve.engine import ModelBackend, ServeEngine, poisson_workload

    cfg = dataclasses.replace(cfg, param_dtype=cfg.dtype)
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    prompt_pad = -(-prompt_len[1] // page) * page
    bk = ModelBackend(model, params, pool_pages=pool_pages, page_size=page,
                      prompt_pad=prompt_pad)
    reqs = poisson_workload(n_requests, rate, prompt_len=prompt_len,
                            gen=gen, vocab=cfg.vocab_size, seed=SEED)

    t0 = time.perf_counter()
    tk = jnp.zeros((1, prompt_pad), jnp.int32)
    pg = jnp.full((prompt_pad // page,), pool_pages, jnp.int32)
    bk._prefill.lower(params, bk.k_pools, bk.v_pools, tk, jnp.int32(1),
                      pg).compile()
    zb = jnp.zeros((b_cap,), jnp.int32)
    bk._decode.lower(params, bk.k_pools, bk.v_pools,
                     jnp.zeros((b_cap, max_pages), jnp.int32), zb,
                     jnp.zeros((b_cap,), bool), zb).compile()
    compile_s = time.perf_counter() - t0

    timings = {"prefill": [], "decode": []}

    def timed(fn, key):
        def call(*a):
            t = time.perf_counter()
            out = fn(*a)       # returns host ints/arrays: already synced
            timings[key].append(time.perf_counter() - t)
            return out
        return call

    bk.prefill = timed(bk.prefill, "prefill")
    bk.decode_step = timed(bk.decode_step, "decode")
    eng = ServeEngine(bk, b_cap=b_cap, pool_pages=pool_pages,
                      max_pages=max_pages)
    t0 = time.perf_counter()
    m = eng.run(reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in reqs)
    log("serve", f"{cfg.name} full width bf16: compile {compile_s:.2f}s; "
        f"{n_requests} requests, {n_tok} tokens in {wall:.2f}s wall")
    log("serve", f"prefill {summary(timings['prefill'])}; "
        f"decode step {summary(timings['decode'])}; peak {peak_bytes(dev)} B")
    check(all(len(r.out) == r.gen for r in reqs), "a request came back short")

    # reference: token-by-token decode on a contiguous cache whose length
    # is a multiple of 128, so each step runs flash_decode on the chip
    smax = -(-(prompt_len[1] + gen[1]) // 128) * 128
    decode = jax.jit(model.decode_step)
    worst, exact, total = 0.0, 0, 0
    for r in reqs:
        cache = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), model.cache_spec(1, smax))
        seq = list(r.prompt) + r.out
        plen = len(r.prompt)
        for i in range(plen + len(r.out) - 1):
            logits, cache = decode(params, cache,
                                   jnp.asarray([[seq[i]]], jnp.int32),
                                   jnp.asarray(i, jnp.int32))
            if i + 1 >= plen:
                lg = np.asarray(logits[0], np.float64)
                got = seq[i + 1]
                margin = (lg.max() - lg[got]) / np.abs(lg).max()
                worst = max(worst, margin)
                exact += int(np.argmax(lg) == got)
                total += 1
    log("serve", f"reference (contiguous cache {smax}, flash_decode): "
        f"{exact}/{total} tokens are its argmax; worst near-tie margin "
        f"{worst:.3e} of max|logit| (tol {LOGIT_TOL:g})")
    check(worst <= LOGIT_TOL, "served tokens disagree with the reference")


def phase_copy(dev, sizes=(4 << 20, 64 << 20), parts=(16, 64)):
    """§6.3 same-timestamp partition copies: Pallas vs numpy, bit-exact."""
    import numpy as np
    from repro.core import NULL_GUID, Runtime, spawn_main
    from repro.kernels import partition_copy as pc

    for size, n in zip(sizes, parts):
        rng = np.random.default_rng(SEED + n)
        src_bytes = rng.integers(0, 256, size, dtype=np.uint8)
        dst_bytes = rng.integers(0, 256, size, dtype=np.uint8)
        slot = size // n
        # ragged 128-byte-granular ranges: odd row offsets and lengths,
        # sources permuted, gaps between destinations left untouched
        ranges = []
        for i in range(n):
            d = i * slot + 128 * (1 + i % 7)
            s = ((i * 37) % n) * slot + 128 * (i % 5)
            ln = slot - 128 * (9 + i % 3)
            ranges.append((d, s, ln))

        def run(backend):
            rt = Runtime(copy_backend=backend)
            out = {}

            def main(paramv, depv, api):
                block, ptr = api.db_create(size)
                ptr[:] = src_bytes
                api.db_release(block)
                shadow, sp = api.db_create(size)
                sp[:] = dst_bytes
                api.db_release(shadow)
                for d, s, ln in ranges:
                    api.db_copy(shadow, d, block, s, ln)
                out["shadow"] = shadow
                return NULL_GUID

            spawn_main(rt, main)
            t0 = time.perf_counter()
            rt.run()
            wall = time.perf_counter() - t0
            return np.array(rt.lookup(out["shadow"]).buffer), rt.stats, wall

        got, st, wall = run("pallas")
        ref, _, wall_np = run("numpy")
        path = "dma" if pc.dma_staged(size, size) else "vmem"
        same = bool(np.array_equal(got, ref))
        log("copy", f"{size} B block, {n} ranges, {path} path: "
            f"fused_copies={st.fused_copies} bytes_copied={st.bytes_copied} "
            f"run {wall:.3f}s (numpy {wall_np:.3f}s) bit-exact={same}; "
            f"peak {peak_bytes(dev)} B")
        check(st.fused_copies > 0, "copies did not take the Pallas kernel")
        check(same, f"pallas copy differs from numpy on the {path} path")


def phase_sharded(cfg, seq=4096, batch=2, steps=3, mesh_model=2):
    """llama3.2-3b on a ("data", "model") mesh vs a single-device forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from repro import ckpt
    from repro.data import SyntheticTokens
    from repro.dist.sharding import ShardCtx, param_shardings, use_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.train.trainer import Trainer, TrainerConfig

    mesh = make_host_mesh(model=mesh_model)
    devs = list(mesh.devices.flat)
    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=1e-3, warmup_steps=1, total_steps=steps,
                         state_dtype=cfg.optimizer_state_dtype)
    data = SyntheticTokens(min(DATA_VOCAB, cfg.vocab_size), batch, seq,
                           seed=SEED, mode="markov")
    key = jax.random.PRNGKey(SEED)

    # reference first, while only the parameters exist: the same initial
    # parameters in bf16 on one device, forward on step 0's batch
    pshapes = jax.eval_shape(model.init, key)
    psh = param_shardings(pshapes, ShardCtx(mesh=mesh))
    params = jax.jit(model.init, out_shardings=psh)(key)
    p16 = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), p))(params)
    del params
    one = SingleDeviceSharding(devs[0])
    p16 = jax.device_put(p16, one)
    b0 = {k_: jax.device_put(v_, one) for k_, v_ in data.get(0).items()}
    t0 = time.perf_counter()
    ref_loss = float(jax.block_until_ready(
        jax.jit(model.train_loss)(p16, b0)[0]))
    ref_s = time.perf_counter() - t0
    del p16, b0

    tr = Trainer(model, oc, data, TrainerConfig(), mesh=mesh)
    state = tr.init_or_restore(key)
    per_dev = {d.id: 0 for d in devs}
    total = 0
    for leaf in jax.tree_util.tree_leaves(state["params"]):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    share = {d: n / total for d, n in per_dev.items()}
    state = tr.run(state, steps)
    losses = [h_["loss"] for h_ in tr.history]
    times = [h_["step_time"] for h_ in tr.history]
    log("sharded", f"{cfg.name} mesh {dict(mesh.shape)} B={batch} "
        f"S={seq}: losses " + " ".join(f"{x:.4f}" for x in losses))
    log("sharded", "step times " + " ".join(f"{t:.3f}s" for t in times)
        + "; peak per device "
        + " ".join(str(peak_bytes(d)) for d in devs) + " B")
    log("sharded", f"step-0 loss {losses[0]:.5f} vs single-device bf16 "
        f"forward {ref_loss:.5f} ({ref_s:.2f}s incl. compile): "
        f"|diff|={abs(losses[0] - ref_loss):.3e} (tol {SHARDED_LOSS_TOL:g})")
    log("sharded", f"param bytes {total}; per-device share " + " ".join(
        f"{d}:{s_:.4f}" for d, s_ in sorted(share.items())))
    check(abs(losses[0] - ref_loss) <= SHARDED_LOSS_TOL,
          "sharded step-0 loss disagrees with the single-device forward")
    check(all(abs(s_ - 1 / len(devs)) <= 0.01 for s_ in share.values()),
          f"parameters are not spread evenly: {share}")

    # trained parameters through the §6-range sharded checkpoint (the path
    # Trainer takes under a mesh): each device writes its own ranges, the
    # restore places them back onto the mesh.  A range is one contiguous
    # run of a shard, 1.75 M of them for the whole model on this mesh —
    # far too slow through the runtime — so this round-trips layer 0's
    # q and o projections: both inner-sharded orientations, 12k ranges.
    attn = state["params"]["layers"]["attn"]
    params = {"w_q": attn["w_q"][0], "w_o": attn["w_o"][0]}
    total_ck = sum(a.nbytes for a in params.values())
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ck:
        t0 = time.perf_counter()
        with use_mesh(mesh):
            st = ckpt.save(ck, params, steps)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        restored, at = ckpt.restore(ck, shardings=param_shardings(
            jax.eval_shape(lambda: params), ShardCtx(mesh=mesh)))
        restore_s = time.perf_counter() - t0
        exact = all(
            np.asarray(a).dtype == np.asarray(b_).dtype
            and np.array_equal(np.asarray(a), np.asarray(b_))
            for a, b_ in zip(jax.tree_util.tree_leaves(params),
                             jax.tree_util.tree_leaves(restored)))
    log("sharded", f"sharded checkpoint of {total_ck} B: save {save_s:.2f}s "
        f"(host gathers {st.host_gathers}), restore {restore_s:.2f}s onto "
        f"the mesh, bit-exact={exact}")
    check(at == steps and exact and st.host_gathers == 0,
          "sharded checkpoint did not restore bit-exact")


# -------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded llama3.2-3b path")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); this script never runs on the CPU",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    devs = devs[:args.chips]

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    dev = devs[0]
    log("setup", f"{len(devs)} x {dev.device_kind}; compile cache {cache}")

    t_all = time.perf_counter()
    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(
            get_config("llama3.2-3b")))]
    else:
        cfg = get_config("smollm-360m")
        phases = [("kernels", lambda: phase_kernels(dev)),
                  ("train", lambda: phase_train(dev, cfg)),
                  ("serve", lambda: phase_serve(dev, cfg)),
                  ("copy", lambda: phase_copy(dev))]
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        log(name, f"passed in {time.perf_counter() - t0:.2f}s")
    log("setup", f"all phases passed in {time.perf_counter() - t_all:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
