"""Training cells: ``repro.train.trainer.Trainer`` over §5 ``FileTokens``.

Set-up writes the token file from the seed, makes the float32 master
weights on the device in one jitted call, builds one ``Trainer`` and
drives it through its first steps: the first compiles the step, and the
first three give the readings the correctness check compares.  The window
then hands the same trainer and state on for as many steps as fill
``--seconds`` (from the set-up's step time) and times the whole
``Trainer.run`` call on the host clock.  Once the window has closed and
the program's state is freed, the float32 reference follows the same
first steps from the same weights and batches.

A mix with a ``mesh`` key (``{"data": d, "model": m}``, d * m the cell's
chips) runs the trainer on that ("data", "model") mesh over the cell's
devices: the weights are made straight into the program's own parameter
layout, and the reference runs on the same placement.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

from chipbench import flops
from chipbench.checks import norm_gaps
from chipbench.program import check_layout, model_config
from chipbench.reference import dense

FIRST_STEPS = 3


class TimedTokens:
    """The data object the trainer reads, with a host span around each
    ``get``."""

    def __init__(self, inner):
        self.inner = inner
        self.spans: List[tuple] = []

    def get(self, step: int) -> Dict[str, np.ndarray]:
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("cb:input"):
            out = self.inner.get(step)
        self.spans.append((t0, time.perf_counter()))
        return out


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for _, x in flat]
    vals = jax.device_get(vals)
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in zip(flat, vals)}


def diff_norms(a, b) -> Dict[str, float]:
    import jax
    return leaf_norms(jax.tree_util.tree_map(lambda x, y: x - y, a, b))


def reference_run(dm, adam, tokens, steps: int, init, mode: str) -> dict:
    """The reference's readings over the first ``steps`` steps: each
    step's loss, the first clipped gradient's leaf norms, and the leaf
    norms of the parameters' change."""
    import jax
    import jax.numpy as jnp
    step = dense.make_train_step(dm, adam, mode)
    p = init()
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grads = [], None
    for i in range(steps):
        tk = jnp.asarray(tokens[i % tokens.shape[0]])
        p, m, v, loss, g = step(p, m, v, jnp.int32(i), tk[:, :-1], tk[:, 1:])
        losses.append(float(loss))
        if i == 0:
            grads = leaf_norms(g)
        del g
    del m, v
    p0 = init()
    delta = diff_norms(p, p0)
    del p, p0
    return {"losses": losses, "grads": grads, "delta": delta}


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The three numbers the check compares (see chipbench/checks.py)."""
    return {
        "loss_gap": max(abs(a - b) for a, b in
                        zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": norm_gaps(prog["grads"], ref["grads"], ref["grads"]),
        "update_norm_gap": norm_gaps(prog["delta"], ref["delta"],
                                     ref["grads"]),
    }


def mesh_for(ctx):
    """The cell's ("data", "model") mesh from the mix's ``mesh`` key, or
    None on one chip."""
    shape = ctx.traffic.get("mesh")
    if not shape:
        return None
    from jax.sharding import Mesh
    d, m = shape["data"], shape["model"]
    if d * m != ctx.chips:
        raise ValueError(f"mesh {d}x{m} does not match the cell's "
                         f"{ctx.chips} chips")
    return Mesh(np.array(ctx.devices).reshape(d, m), ("data", "model"))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.data import FileTokens
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig, init_opt_state
    from repro.train.trainer import Trainer, TrainerConfig

    tf, lim = ctx.traffic, ctx.limits
    b, s = tf["batch"], tf["seq"]
    cfg = model_config(ctx.config, ctx.config["run"]["train"])
    model = LanguageModel(cfg)
    dm = dense.Dims.from_config(ctx.config)
    adam = dense.Adam(**tf["optimizer"])
    oc = OptimizerConfig(**tf["optimizer"],
                         state_dtype=cfg.optimizer_state_dtype)

    tokens = ctx.generate()
    path = os.path.join(ctx.workdir, "tokens.bin")
    tokens.reshape(-1, s + 1).astype(np.int32).tofile(path)
    data = TimedTokens(FileTokens(path, cfg.vocab_size, b, s))

    mesh = mesh_for(ctx)
    shardings = None
    if mesh is not None:
        from repro.dist.sharding import ShardCtx, param_shardings
        shardings = param_shardings(
            jax.eval_shape(lambda: dense.init_params(dm, 0, jnp.float32)),
            ShardCtx(mesh=mesh))

    def init():
        return dense.init_params(dm, ctx.seed32, jnp.float32, shardings)

    params = init()
    check_layout(params, model)
    state = {"params": params, "opt": init_opt_state(params, oc)}
    del params
    trainer = Trainer(model, oc, data, TrainerConfig(), mesh=mesh)

    # first steps, through the window's own call and feed
    state = trainer.run(state, 1, start_step=0)
    g_prog = leaf_norms(jax.tree_util.tree_map(
        lambda m: m / (1 - oc.b1), state["opt"]["m"]))
    delta_prog = {}
    for i in range(1, FIRST_STEPS):
        state = trainer.run(state, 1, start_step=i)
        p0 = init()
        delta_prog[i + 1] = diff_norms(state["params"], p0)
        del p0
    losses_prog = [h["loss"] for h in trainer.history]
    step_s = float(np.median([h["step_time"] for h in trainer.history[1:]]))
    n_steps = max(1, int(round(ctx.seconds / step_s)))

    window_start = time.perf_counter()
    setup_s = window_start - ctx.t_start
    data.spans.clear()
    first = len(trainer.history)
    with ctx.window():
        t0 = time.perf_counter()
        state = trainer.run(state, n_steps, start_step=FIRST_STEPS)
        window_s = time.perf_counter() - t0
    hist = trainer.history[first:]
    peak = ctx.memory_peak()
    holding = len({d for leaf in jax.tree_util.tree_leaves(state["params"])
                   for d in leaf.devices()})
    del state, trainer

    # reference: the same first steps in float32 from the same weights
    ref_steps = lim.get("reference_steps", FIRST_STEPS)
    t_ref = time.perf_counter()
    readings = {m: reference_run(dm, adam, tokens, ref_steps, init, m)
                for m in ctx.modes}
    ref_s = time.perf_counter() - t_ref
    ref = readings["f32"]
    prog = {"losses": losses_prog[:ref_steps], "grads": g_prog,
            "delta": delta_prog[ref_steps]}
    compared = compare(prog, ref)
    # a number the limits file leaves out has no control or fault that
    # separates it from sound runs (PERF.md): read, not judged
    checks = [{"name": k, "value": v, "limit": lim[k]}
              for k, v in compared.items() if k in lim]
    control = {m: compare(r, ref) for m, r in readings.items() if m != "f32"}
    tok_per_step = b * s
    return {
        "kind": "train",
        "setup_s": setup_s,
        "window_s": window_s,
        "steps": len(hist),
        "tokens": len(hist) * tok_per_step,
        "step_times": [h["step_time"] for h in hist],
        "input_waits": [e - s_ for s_, e in data.spans],
        "flops_per_token": flops.train_flops_per_token(dm, s),
        "attn": {"b": b, "s": s, "h": dm.h, "kh": dm.kh, "hd": dm.hd,
                 "layers": dm.layers, "itemsize": 2},
        "dims": dm,
        "memory_peak_bytes": peak,
        "attempted": len(hist),
        "failed": 0,
        "checks": checks,
        "control": control,
        "readings": {"mesh": dict(mesh.shape) if mesh is not None else None,
                     "devices_holding_params": holding,
                     "losses_prog": losses_prog,
                     "losses_ref": ref["losses"], "reference_s": ref_s,
                     **{k: v for k, v in compared.items() if k not in lim}},
    }
