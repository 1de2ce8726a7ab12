"""Training step: loss + grad + AdamW update, with gradient accumulation.

``TrainState`` is a plain dict pytree (checkpoint-friendly):
  {"params": ..., "opt": {"m","v","step"}}

The step function is pure and jit/pjit-able; donation of the state buffers
(zero-copy in-place semantics — the paper's §6.3 ``DB_COPY_PARTITION``
degenerate case) is applied by the caller via ``donate_argnums=(0,)``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.model import LanguageModel
from repro.optim import OptimizerConfig, adamw_update, init_opt_state

TrainState = Dict[str, Any]


def init_train_state(model: LanguageModel, key, oc: OptimizerConfig
                     ) -> TrainState:
    params = model.init(key)
    return {"params": params, "opt": init_opt_state(params, oc)}


def make_train_step(model: LanguageModel, oc: OptimizerConfig):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def loss_fn(params, batch):
        return model.train_loss(params, batch)

    def grads_of(params, batch):
        if oc.accum_steps <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, metrics

        a = oc.accum_steps

        acc_dt = jnp.bfloat16 if oc.accum_dtype == "bfloat16" else jnp.float32

        def micro(carry, mb):
            g_acc, m_acc = carry
            (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a_, g_: a_ + g_.astype(a_.dtype), g_acc, g)
            m_acc = jax.tree_util.tree_map(jnp.add, m_acc, m)
            return (g_acc, m_acc), None

        micro_batch = jax.tree_util.tree_map(
            lambda x: x.reshape(a, x.shape[0] // a, *x.shape[1:]), batch)
        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dt), params)
        m0 = {k: jnp.zeros((), jnp.float32)
              for k in ("loss", "ce_loss", "z_loss", "accuracy", "tokens",
                        "aux_loss", "moe_dropped_tokens",
                        "moe_overflow_rate", "moe_a2a_bytes")}
        (g, m), _ = jax.lax.scan(micro, (g0, m0), micro_batch)
        g = jax.tree_util.tree_map(lambda x: x / a, g)
        summed = ("tokens", "moe_dropped_tokens", "moe_a2a_bytes")
        m = {k: v / a if k not in summed else v for k, v in m.items()}
        return g, m

    # named in the device trace, beside the jitted program's own name
    @jax.named_scope("train_step")
    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        grads, metrics = grads_of(state["params"], batch)
        params, opt, opt_metrics = adamw_update(
            oc, grads, state["params"], state["opt"])
        metrics = {**metrics, **opt_metrics}
        return {"params": params, "opt": opt}, metrics

    return train_step
