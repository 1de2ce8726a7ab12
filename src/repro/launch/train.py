"""End-to-end training driver.

Runs any assigned architecture (reduced or full config) through the
OCR-runtime trainer: §4 labeled step map, §5 chunked checkpoints, §3 async
checkpoint write-back, straggler watchdog.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
import argparse

import jax

from repro.configs import get_config
from repro.data import FileTokens, SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import LanguageModel
from repro.optim import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default="synthetic",
                    help="synthetic | markov | path to int32 token file")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel size over local devices")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = LanguageModel(cfg)
    oc = OptimizerConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                         total_steps=args.steps,
                         state_dtype=cfg.optimizer_state_dtype)

    if args.data in ("synthetic", "markov"):
        data = SyntheticTokens(cfg.vocab_size, args.batch, args.seq,
                               seed=0, mode="markov" if args.data == "markov"
                               else "uniform")
    else:
        data = FileTokens(args.data, cfg.vocab_size, args.batch, args.seq)

    mesh = make_host_mesh(model=args.tp) if args.tp > 1 else None
    tc = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every
                       if args.ckpt_dir else 0)
    tr = Trainer(model, oc, data, tc, mesh=mesh)
    state = tr.init_or_restore(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} params(dev)="
          f"{sum(l.size for l in jax.tree_util.tree_leaves(state['params'])):,}"
          f" start_step={tr.start_step}")
    state = tr.run(state, args.steps - tr.start_step)
    for h in tr.history[:3] + tr.history[-3:]:
        print(f"  step {h['step']:5d} loss={h['ce_loss']:.4f} "
              f"acc={h['accuracy']:.3f} {h['step_time']*1e3:.0f}ms")
    if tr.straggler_steps:
        print("stragglers:", tr.straggler_steps)
    rs = tr.last_runtime_stats
    print(f"runtime: tasks={rs.tasks_executed} msgs={rs.messages_sent} "
          f"creator_calls={rs.creator_calls}")


if __name__ == "__main__":
    main()
