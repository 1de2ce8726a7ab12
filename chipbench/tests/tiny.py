"""A whole run of a cell at a size the CPU holds, through the harness with
its look for a chip skipped."""
from chipbench import run as R

PEAKS = {"devices": {"cpu": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11,
                             "hbm_bytes": 1e10}}}
MODEL = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "num_hidden_layers": 2, "vocab_size": 256}
SERVE = {"b_cap": 4, "prompt_pad": 64, "max_pages": 6, "pool_pages": 24,
         "prompt": {"median": 20, "sigma": 0.7, "min": 4, "max": 64},
         "output": {"median": 8, "sigma": 0.7, "min": 2, "max": 32}}
# Limits at this size, set by the rule the cells' own follow (PERF.md):
# over four seeds the sound runs, the float8 control and the faults read,
# train: grad 0.0011 / 0.0096 / 0.059-1, update 0.0014 / 0.0035 /
# 0.009-1 (only the unchanged state reads ten times the sound runs, so
# the update's limit sits far up); serve: 0.037 / 0.23 / 3.7.  The loss
# gap (0.0027 / 0.0057 / 0.011-0.10) has no upper reading and is not
# judged, as in the cell.
TRAIN_LIMITS = {"grad_norm_gap": 0.004, "update_norm_gap": 0.07}
SERVE_LIMITS = {"served_logit_gap": 0.08}
OVERRIDES = {
    "train.smollm-360m.s4096": {
        "config": MODEL, "traffic": {"batch": 4, "seq": 64, "batches": 8},
        "limits": TRAIN_LIMITS},
    "serve.smollm-360m.chat": {
        "config": MODEL, "traffic": dict(SERVE, rate=20.0, drain_s=30),
        "limits": SERVE_LIMITS},
    "serve.h2o-danube3-4b.batch": {
        "config": MODEL, "traffic": dict(SERVE, round=8, rounds=40),
        "limits": SERVE_LIMITS},
}


def execute(cell, seed, seconds=0.5, trace=False, modes=("f32",)):
    return R.execute(cell, seed, seconds, trace, require_chip=False,
                     overrides=OVERRIDES[cell], peaks=PEAKS, modes=modes)
