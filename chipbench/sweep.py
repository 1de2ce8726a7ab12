#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: its traffic at a few fixed
rates, in one process, on the chip.

    python3 chipbench/sweep.py --workload serve.smollm-360m.chat \
        --seconds 20 --seed 5 --rates 4 6 8 10 12

For each rate: the TTFT median and 95th percentile, the queue wait of the
first and last thirds of the requests (a backlog that grows shows as a
last third that waits far longer), and how long the run drained past the
window.  The knee is the highest rate whose backlog does not grow.  The
benchmark's own runs never call this; it records how a cell's rate was
chosen.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def summary(rec, readings) -> dict:
    from chipbench.stats import first_token_waits
    reqs = sorted(rec["requests"], key=lambda q: q["due"])
    ttft = first_token_waits(rec)
    wait = [q["start"] - q["due"] for q in reqs if q["start"] is not None]
    third = max(1, len(wait) // 3)
    return {"requests": len(reqs),
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "wait_first_third_ms": 1e3 * float(np.mean(wait[:third])),
            "wait_last_third_ms": 1e3 * float(np.mean(wait[-third:])),
            "drained_s": rec["t_end"] - rec["window"][1],
            "readings": readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from chipbench import run as R
    jax.config.update("jax_compilation_cache_dir", R.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for rate in args.rates:
        out, rec = R.execute(args.workload, args.seed, args.seconds, False,
                             overrides={"traffic": {"rate": rate}})
        print(json.dumps({"rate": rate, "failed": out["failed"],
                          "correct": out["correct"],
                          **summary(rec, out["readings"])}),
              flush=True)
        del rec
        gc.collect()     # the engine and its backend hold each other
    return 0


if __name__ == "__main__":
    sys.exit(main())
