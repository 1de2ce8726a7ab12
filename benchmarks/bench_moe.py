"""MoE dispatch: capacity-bucketed all-to-all vs replicate-and-psum.

Compares the two EP combines of ``repro.models.moe`` at expert counts
E ∈ {8, 64, 128} on 8 forced host devices (mesh 1×8, tokens/experts over
"model").  Per cell: collective traffic parsed out of the compiled SPMD
HLO by ``repro.launch.hlo_cost`` (per-device operand bytes for one
fwd+bwd step — deterministic, noise-free) and wall step time.

The point of the a2a path: its exchange moves ``2·E·C·D`` bucket bytes
per device regardless of the model-axis width, while the psum combine
moves the *full* (T, D) token block per psum — so the byte gap widens
with E (capacity C shrinks as 1/E while the psum stays fixed).  The
acceptance line, asserted in CI via BENCH_moe.json + bench_diff's
``*_bytes`` lower-is-better rule: strictly fewer bytes than psum at
E ≥ 64, no step-time regression at E = 8.

Cells run in subprocesses (XLA_FLAGS must be set before jax imports),
cached so ``run()`` and ``summary()`` compile each once.
"""
import json
import os
import subprocess
import sys
import textwrap

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

EXPERT_COUNTS = (8, 64, 128)
_cache = {}


def _cell(num_experts: int, dispatch: str, steps: int = 5):
    """One (E, dispatch) cell: HLO collective bytes + wall step time."""
    key = (num_experts, dispatch)
    if key in _cache:
        return _cache[key]
    # a CPU compile by design: the child must never reach for a chip the
    # parent may hold
    code = ("import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            f"import sys\nsys.path.insert(0, {_SRC!r})\n"
            + textwrap.dedent(f"""
        import dataclasses, json, time
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.dist.sharding import use_mesh
        from repro.launch import hlo_cost
        from repro.models import moe as M

        cfg = get_config("deepseek-v2-236b").reduced()
        cfg = dataclasses.replace(
            cfg, num_experts={num_experts}, experts_per_token=2,
            capacity_factor=1.25, num_shared_experts=0,
            moe_dispatch="{dispatch}")
        params = M.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 128, cfg.d_model))

        def loss(p, xx):
            y, aux = M.moe_ffn(p, xx, cfg)
            return jnp.sum(y ** 2) + 0.01 * aux["loss"], aux

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 8), ("data", "model"))
        with use_mesh(mesh):
            fn = jax.jit(jax.value_and_grad(loss, has_aux=True))
            lowered = fn.lower(params, x)
            compiled = lowered.compile()
            cost = hlo_cost.analyze(compiled.as_text())
            (l0, aux), g = compiled(params, x)
            jax.block_until_ready(g)                 # compile + warm
            t0 = time.perf_counter()
            for _ in range({steps}):
                (l0, aux), g = compiled(params, x)
            jax.block_until_ready(g)
        dt = (time.perf_counter() - t0) / {steps}
        print(json.dumps({{
            "step_ms": dt * 1e3,
            "coll_bytes": cost.coll_total,
            "per_kind": {{k: v for k, v in cost.coll_bytes.items() if v}},
            "dropped": float(aux["dropped"]),
            "overflow_rate": float(aux["dropped"])
                             / max(float(aux["routed"]), 1.0),
            "a2a_bytes_gauge": float(aux["a2a_bytes"]),
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
    _cache[key] = rec
    return rec


def run():
    rows = []
    for e in EXPERT_COUNTS:
        for dispatch in ("a2a", "psum"):
            rec = _cell(e, dispatch)
            name = f"moe.step_E{e}_{dispatch}_8dev"
            if "error" in rec:
                rows.append((name + ".SKIP", "0", rec["error"]))
                continue
            kinds = ";".join(f"{k}={v:.0f}"
                             for k, v in sorted(rec["per_kind"].items()))
            rows.append((name, f"{rec['step_ms'] * 1e3:.0f}",
                         f"coll_bytes={rec['coll_bytes']:.0f};"
                         f"dropped={rec['dropped']:.0f};{kinds}"))
    return rows


def summary():
    """BENCH_moe.json: per-E bytes for both dispatches + the ratios the
    acceptance line and bench_diff's ``*_bytes`` rule watch."""
    out = {}
    for e in EXPERT_COUNTS:
        a2a, psum = _cell(e, "a2a"), _cell(e, "psum")
        if "error" in a2a or "error" in psum:
            out[f"E{e}_error"] = a2a.get("error") or psum.get("error")
            continue
        out[f"a2a_coll_bytes_E{e}"] = a2a["coll_bytes"]
        out[f"psum_coll_bytes_E{e}"] = psum["coll_bytes"]
        out[f"a2a_step_ms_E{e}"] = a2a["step_ms"]
        out[f"psum_step_ms_E{e}"] = psum["step_ms"]
        out[f"bytes_ratio_a2a_over_psum_E{e}"] = (
            a2a["coll_bytes"] / max(psum["coll_bytes"], 1.0))
        out[f"overflow_rate_E{e}"] = a2a["overflow_rate"]
        out[f"a2a_bytes_gauge_E{e}"] = a2a["a2a_bytes_gauge"]
    return out
