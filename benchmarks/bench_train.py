"""Wall-clock microbenchmarks of the step functions on reduced configs
(CPU; the real targets are AOT artifacts — see bench_roofline), plus
real AOT dry-run cells for the production MoE configs at TRUE expert
counts (deepseek-v2-236b E=160, arctic-480b E=128): full-size train
step lowered + compiled on a 16-device mesh matching the production
"model"-axis width, with ``hlo_cost``-parsed collective bytes per cell
— the capacity-bucketed all-to-all shows up as ``all-to-all`` traffic
in the compiled SPMD HLO (the 16×16 production mesh compiles the same
cells but takes ~10 min/cell on CPU; 1×16 keeps the per-device expert
and bucket layout identical at bench-friendly compile times)."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import SyntheticTokens
from repro.models.model import LanguageModel
from repro.optim import OptimizerConfig
from repro.train.steps import init_train_state, make_train_step

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

_dryrun_cache = {}


def _dryrun_cell(arch: str):
    """Lower + compile the FULL config's train step (no reduced()) on a
    (1, 16) mesh and parse collective traffic from the SPMD HLO."""
    if arch in _dryrun_cache:
        return _dryrun_cache[arch]
    # a CPU compile by design: the child must never reach for a chip the
    # parent may hold
    code = ("import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=16'\n"
            f"import sys\nsys.path.insert(0, {_SRC!r})\n"
            + textwrap.dedent(f"""
        import json, time
        import jax
        from repro.configs import get_config
        from repro.configs.base import shape_by_name
        from repro.dist.sharding import use_mesh
        from repro.launch import hlo_cost
        from repro.launch.dryrun import lower_cell

        cfg = get_config("{arch}")
        shape = shape_by_name("train_4k")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 16), ("data", "model"))
        t0 = time.time()
        with use_mesh(mesh) as ctx:
            lowered, _ = lower_cell(cfg, shape, mesh, ctx)
            compiled = lowered.compile()
            cost = hlo_cost.analyze(compiled.as_text())
        print(json.dumps({{
            "compile_s": time.time() - t0,
            "flops": cost.flops,
            "coll_bytes": cost.coll_total,
            "per_kind": {{k: v for k, v in cost.coll_bytes.items() if v}},
            "num_experts": cfg.num_experts,
        }}))
    """))
    out = None
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=560)
        rec = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:
        rec = {"error": f"{type(e).__name__}: {e}"}
        if out is not None and out.returncode != 0:
            rec["error"] = (f"exit={out.returncode}: "
                            + out.stderr.strip()[-500:].replace("\n", " | "))
    _dryrun_cache[arch] = rec
    return rec


def _bench_arch(arch: str, steps: int = 8):
    cfg = get_config(arch).reduced()
    model = LanguageModel(cfg)
    oc = OptimizerConfig()
    data = SyntheticTokens(cfg.vocab_size, batch=4, seq=64, seed=0)
    step = jax.jit(make_train_step(model, oc), donate_argnums=(0,))
    st = init_train_state(model, jax.random.PRNGKey(0), oc)
    b = {k: jnp.asarray(v) for k, v in data.get(0).items()}
    st, _ = step(st, b)                       # compile
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in data.get(i + 1).items()}
        st, m = step(st, b)
    jax.block_until_ready(st)
    dt = (time.perf_counter() - t0) / steps
    toks = 4 * 64
    return dt * 1e6, toks / dt


def run():
    rows = []
    for arch in ("smollm-360m", "mamba2-1.3b", "deepseek-v2-236b",
                 "zamba2-1.2b"):
        us, tps = _bench_arch(arch)
        rows.append((f"train.step_{arch}-smoke", f"{us:.0f}",
                     f"tokens_per_s={tps:.0f} (reduced cfg, CPU)"))
    # the production MoE configs as real AOT cells at true expert counts
    for arch in ("deepseek-v2-236b", "arctic-480b"):
        rec = _dryrun_cell(arch)
        name = f"train.dryrun_{arch}_train4k_1x16"
        if "error" in rec:
            rows.append((name + ".SKIP", "0", rec["error"]))
            continue
        kinds = ";".join(f"{k}={v:.3e}"
                         for k, v in sorted(rec["per_kind"].items()))
        rows.append((name, f"{rec['compile_s'] * 1e6:.0f}",
                     f"E={rec['num_experts']};flops={rec['flops']:.3e};"
                     f"coll_bytes={rec['coll_bytes']:.3e};{kinds}"))
    return rows
