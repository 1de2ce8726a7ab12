"""Fault-tolerant trainer orchestrated through the OCR core runtime.

The step sequence is built with the paper's §4 labeled-GUID map: a map of
step tasks indexed by step number whose creator wires step *i* to depend on
step *i−1*'s output event — the 1-D degenerate case of the paper's 2-D
wavefront.  Checkpoint tasks hang off every k-th step event and write
through the §5 chunked file layer (async, off the step critical path, §3
issue-now/resolve-later).

Fault tolerance: ``run`` stops cleanly at a simulated failure step; a new
``Trainer`` with the same config resumes from the last *committed* manifest
and — because the data pipeline is stateless-per-step — replays exactly the
batches the lost steps would have seen (tested bit-exact in
``tests/test_trainer.py``).  A step-time watchdog flags stragglers.

Attention in the jitted step routes through ``repro.dist.flash``: above
``cfg.attn_flash_min_seq`` the differentiable Pallas flash kernel runs the
forward *and* both backward passes (compiled on TPU, interpret mode on
CPU), under ``use_mesh`` included — training no longer falls back to the
jnp flash twin.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro import ckpt
from repro.core import (DbMode, EDT_PROP_MAPPED, NULL_GUID,
                        Runtime, UNINITIALIZED_GUID, spawn_main)
from repro.dist.sharding import use_mesh
from repro.models.model import LanguageModel
from repro.monitoring import spans
from repro.optim import OptimizerConfig
from .steps import init_train_state, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str = ""
    ckpt_every: int = 0              # 0 → no checkpoints
    async_ckpt: bool = True
    fail_at_step: int = -1           # inject a failure (tests)
    straggler_factor: float = 3.0    # watchdog threshold × median step time
    log_every: int = 10


class Trainer:
    def __init__(self, model: LanguageModel, oc: OptimizerConfig,
                 data, tc: TrainerConfig, mesh=None):
        self.model = model
        self.oc = oc
        self.data = data
        self.tc = tc
        self.mesh = mesh
        self._step_fn = None
        self.history: List[Dict[str, float]] = []
        self.straggler_steps: List[int] = []
        self._ckpt_threads: List[Any] = []

    # ------------------------------------------------------------ lifecycle

    def _build(self):
        if self._step_fn is None:
            step = make_train_step(self.model, self.oc)
            self._step_fn = jax.jit(step, donate_argnums=(0,))
        return self._step_fn

    def init_or_restore(self, key) -> Dict[str, Any]:
        tc = self.tc
        if tc.ckpt_dir and ckpt.latest_step(tc.ckpt_dir) is not None:
            tree, step = ckpt.restore(tc.ckpt_dir)
            if self.mesh is not None:
                # reshard-on-restore: the §6 range manifest reassembles
                # full leaves whatever mesh wrote them; place them onto
                # *this* run's mesh via the suffix param rules
                from repro.dist.sharding import ShardCtx, param_shardings
                shapes = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape,
                                                   np.asarray(a).dtype), tree)
                shardings = param_shardings(shapes, ShardCtx(mesh=self.mesh))
                state = jax.tree_util.tree_map(jax.device_put, tree, shardings)
            else:
                state = jax.tree_util.tree_map(jax.numpy.asarray, tree)
            self.start_step = step
            return state
        self.start_step = 0
        if self.mesh is None:
            return init_train_state(self.model, key, self.oc)
        # initialize straight into the mesh layout: the whole state never
        # sits on one device (a 3B fp32 state does not fit one chip)
        from repro.dist.sharding import ShardCtx, param_shardings

        def init(k):
            return init_train_state(self.model, k, self.oc)
        shapes = jax.eval_shape(init, key)
        shardings = param_shardings(shapes, ShardCtx(mesh=self.mesh))
        return jax.jit(init, out_shardings=shardings)(key)

    # ----------------------------------------------------------------- run

    def run(self, state: Dict[str, Any], num_steps: int,
            start_step: Optional[int] = None) -> Dict[str, Any]:
        start = self.start_step if start_step is None else start_step
        step_fn = self._build()
        tc = self.tc
        holder = {"state": state}
        durations: List[float] = []

        rt = Runtime(num_nodes=2)
        smap_holder: Dict[str, Any] = {}

        def step_body(paramv, depv, api):
            idx = paramv[0]
            i = start + idx
            if tc.fail_at_step >= 0 and i == tc.fail_at_step:
                api.rt.kill_node(0)      # fail-stop: nothing after this runs
                return NULL_GUID
            with spans.span("train.step", step=i):
                self._step(i, step_fn, holder, durations, rt.registry,
                           rt._mon is not None)
            # the paper's wavefront pattern: this task satisfies the next
            # step task's pre-slot via the §4 labeled map
            if idx + 1 < num_steps:
                nxt = api.map_get(smap_holder["map"], idx + 1)
                api.add_dependence(NULL_GUID, nxt, 0, DbMode.NULL)
            return NULL_GUID

        def creator(ctx_api, object_lid, index, paramv, guidv):
            deps = [NULL_GUID] if index == 0 else [UNINITIALIZED_GUID]
            ctx_api.edt_create(guidv[0], paramv=[index], depv=deps,
                               props=EDT_PROP_MAPPED, mapped_id=object_lid)

        def main(paramv, depv, api):
            tmpl = api.edt_template_create(step_body, 1, 1)
            smap = api.map_create(num_steps, creator, guidv=[tmpl])
            smap_holder["map"] = smap
            api.map_get(smap, 0)     # seed the chain
            return NULL_GUID

        spawn_main(rt, main)
        rt.run()
        for t in self._ckpt_threads:
            t.join()
        if self.history:
            last = self.history[-1]
            rt.stats.moe_dropped_tokens = int(
                last.get("moe_dropped_tokens", 0))
            rt.stats.moe_overflow_rate = float(
                last.get("moe_overflow_rate", 0.0))
            rt.stats.moe_a2a_bytes = int(last.get("moe_a2a_bytes", 0))
        self.last_runtime_stats = rt.stats
        self.registry = rt.registry
        return holder["state"]

    def _step(self, i: int, step_fn, holder: Dict[str, Any],
              durations: List[float], reg, histograms: bool) -> None:
        """One step's body: ``train.input``, ``train.h2d``,
        ``train.dispatch``, ``train.wait`` and ``train.record`` spans
        (``train.ckpt`` when a save runs) while the profiler traces."""
        tc = self.tc
        t0 = time.perf_counter()
        with spans.span("train.input"):
            batch = self.data.get(i)
        with spans.span("train.h2d"):
            batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
        with spans.span("train.dispatch"), use_mesh(self.mesh):
            out = step_fn(holder["state"], batch)
        # the clock stops when the step's results exist, not when the
        # step was enqueued: step_time and the watchdog see the device
        with spans.span("train.wait"):
            holder["state"], metrics = jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        with spans.span("train.record"):
            durations.append(dt)
            med = float(np.median(durations))
            if len(durations) > 5 and dt > tc.straggler_factor * med:
                self.straggler_steps.append(i)
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = i
            m["step_time"] = dt
            self.history.append(m)
            # stamp step metrics into the monitoring registry: the train.*
            # namespace is live alongside the runtime's io.*/spill.*
            # gauges, which is what an elastic supervisor would watch
            reg.set("train.step", float(i))
            reg.set("train.loss", m.get("loss", 0.0))
            reg.set("train.step_time_s", dt)
            reg.inc("train.steps")
            if histograms:
                reg.histogram("train.step_wall_s").observe(dt)
        if tc.ckpt_every and tc.ckpt_dir and (i + 1) % tc.ckpt_every == 0:
            with spans.span("train.ckpt"):
                self._save(holder["state"], i + 1)

    def _save(self, state, step: int) -> None:
        # checkpoint hangs off this step's event; §5 chunked write,
        # §3 issue-now/resolve-later.  async_ckpt snapshots at issue
        # time and overlaps inside the runtime's IO queue (virtual
        # time); the call itself completes before the next step runs.
        # Under a mesh the NamedShardings ride along and ckpt.save takes
        # the §6 sharded path: each node writes exactly its own byte
        # ranges, no host-side gather.
        tc = self.tc
        if self.mesh is not None:
            with use_mesh(self.mesh):
                if tc.async_ckpt:
                    self._ckpt_threads.append(ckpt.async_save(
                        tc.ckpt_dir, state, step))
                else:
                    ckpt.save(tc.ckpt_dir, state, step)
        else:
            host = jax.tree_util.tree_map(np.asarray, state)
            if tc.async_ckpt:
                self._ckpt_threads.append(
                    ckpt.async_save(tc.ckpt_dir, host, step))
            else:
                ckpt.save(tc.ckpt_dir, host, step)
