"""§4 — labeled GUIDs: concurrent-creation storms and the wavefront grid.

Measures creator-call counts under racing ``map_get`` (must equal the map
size — the exactly-once guarantee), message totals, and wavefront makespan
scaling.  Also one sharded train-step row (the trainer's step chain is the
§4 map's 1-D wavefront, and the sharded step exercises the ``repro.dist``
bridge on 8 forced host devices) so the dist subsystem shows up in the
perf trajectory (``BENCH_map.json``).
"""
import json
import os
import subprocess
import sys
import textwrap
import time

from repro.core import (DbMode, EDT_PROP_MAPPED, NULL_GUID, Runtime,
                        UNINITIALIZED_GUID, spawn_main)

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


def _storm(size: int, gets_per_index: int, nodes: int = 6):
    rt = Runtime(num_nodes=nodes, seed=1, jitter=2.0)

    def creator(ctx, lid, index, paramv, guidv):
        ctx.edt_create(guidv[0], paramv=[index], depv=[UNINITIALIZED_GUID],
                       props=EDT_PROP_MAPPED)

    def noop(paramv, depv, api):
        return NULL_GUID

    def main(paramv, depv, api):
        tmpl = api.edt_template_create(noop, 1, 1)
        m = api.map_create(size, creator, guidv=[tmpl])
        for i in range(size):
            for _ in range(gets_per_index):
                api.map_get(m, i)
        return NULL_GUID

    spawn_main(rt, main)
    return rt.run()


def _wavefront(w: int, h: int):
    from tests.test_core_runtime import run_wavefront
    return run_wavefront(w, h, num_nodes=8)


_sharded_cache = {}


def _sharded_step(arch: str = "smollm-360m", steps: int = 3):
    """Per-step wall time of a sharded train step on 8 forced host devices.

    Runs in a subprocess (XLA_FLAGS must be set before any jax import).
    Cached so ``run()`` and ``summary()`` pay the compile once.
    """
    if arch in _sharded_cache:
        return _sharded_cache[arch]
    # a CPU compile by design: the child must never reach for a chip the
    # parent may hold
    code = ("import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            f"import sys\nsys.path.insert(0, {_SRC!r})\n"
            + textwrap.dedent(f"""
        import json, time
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.data import SyntheticTokens
        from repro.dist.sharding import use_mesh
        from repro.models.model import LanguageModel
        from repro.optim import OptimizerConfig
        from repro.train.steps import init_train_state, make_train_step

        cfg = get_config("{arch}").reduced()
        model = LanguageModel(cfg)
        oc = OptimizerConfig()
        data = SyntheticTokens(cfg.vocab_size, batch=8, seq=32, seed=0)
        state = init_train_state(model, jax.random.PRNGKey(0), oc)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        b = {{k: jnp.asarray(v) for k, v in data.get(0).items()}}
        with use_mesh(mesh):
            fn = jax.jit(make_train_step(model, oc))
            state, _ = fn(state, b)
            jax.block_until_ready(state)            # compile
            t0 = time.perf_counter()
            for _ in range({steps}):
                state, _ = fn(state, b)
            jax.block_until_ready(state)
        dt = (time.perf_counter() - t0) / {steps}
        print(json.dumps({{"step_ms": dt * 1e3,
                           "devices": jax.device_count()}}))
    """))
    out = None
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=560)
        rec = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # report (with the subprocess's own failure,
        rec = {"error": f"{type(e).__name__}: {e}"}   # not just ours)
        if out is not None and out.returncode != 0:
            rec["error"] = (f"exit={out.returncode}: "
                            + out.stderr.strip()[-500:].replace("\n", " | "))
    _sharded_cache[arch] = rec
    return rec


def run():
    rows = []
    for size, gets in ((16, 4), (64, 8), (256, 4)):
        t0 = time.perf_counter()
        stats = _storm(size, gets)
        us = (time.perf_counter() - t0) / (size * gets) * 1e6
        rows.append((
            f"map.storm_s{size}_g{gets}", f"{us:.1f}",
            f"creator_calls={stats.creator_calls}(expect {size});"
            f"msgs={stats.messages_sent}"))
    for w, h in ((4, 4), (8, 8)):
        t0 = time.perf_counter()
        executed, stats = _wavefront(w, h)
        us = (time.perf_counter() - t0) / (w * h) * 1e6
        rows.append((
            f"map.wavefront_{w}x{h}", f"{us:.1f}",
            f"tasks={len(executed)};makespan={stats.makespan:.0f};"
            f"critical_path={w + h - 1}"))
    sh = _sharded_step()
    if "step_ms" in sh:
        rows.append(("map.sharded_step_smollm360m_8dev",
                     f"{sh['step_ms'] * 1e3:.0f}",
                     f"devices={sh['devices']};mesh=2x4"))
    else:
        rows.append(("map.sharded_step_smollm360m_8dev.SKIP", "0",
                     sh.get("error", "")))
    return rows


def summary():
    """Machine-readable snapshot for BENCH_map.json (perf trajectory)."""
    t0 = time.perf_counter()
    stats = _storm(64, 8)
    executed, wf = _wavefront(8, 8)
    sh = _sharded_step()
    wall = time.perf_counter() - t0
    return {
        "storm_creator_calls": stats.creator_calls,
        "storm_messages": stats.messages_sent,
        "wavefront_tasks": len(executed),
        "makespan_wavefront_8x8": wf.makespan,
        "sharded_step_ms": sh.get("step_ms", -1.0),
        "sharded_devices": sh.get("devices", 0),
        "wall_time_s": wall,
    }
