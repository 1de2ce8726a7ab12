#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 11 12 13
    python3 chipbench/control.py ... --fault half_batch

For each seed, in one process, one run of the cell with its reference in
float32 (the program's reading, the lower end of a limit) and again in
float8 put in the program's place (the control, the upper end).  With
``--fault`` the run has that fault planted under the timed path
(chipbench/faults.py).  One JSON line per seed: the run's own verdict,
and the control's readings put through the same limits with the verdict
they give (``control_correct``, which has to read false).  The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def judged(readings: dict, checks: dict) -> dict:
    """A control's readings against the run's limits, as ``correct``
    judges the program's: each number beside its limit, and the verdict."""
    compared = {k: {"value": v, "limit": checks[k]["limit"]}
                for k, v in readings.items() if k in checks}
    return {"checks": compared,
            "correct": all(c["value"] <= c["limit"]
                           for c in compared.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default="")
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from chipbench import faults, run as R
    jax.config.update("jax_compilation_cache_dir", R.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    modes = ("f32",) if args.no_control else ("f32", "fp8")
    for seed in args.seeds:
        plant = (faults.FAULTS[args.fault]() if args.fault
                 else contextlib.nullcontext())
        with plant:
            out, rec = R.execute(args.workload, seed, args.seconds, False,
                                 modes=modes)
        control = {m: judged(r, out["checks"])
                   for m, r in rec.get("control", {}).items()}
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": out["correct"], "checks": out["checks"],
                          "control": control,
                          "control_correct": {m: c["correct"]
                                              for m, c in control.items()},
                          "metrics": out["metrics"],
                          "readings": rec.get("readings", {})}), flush=True)
        del rec
        gc.collect()     # the engine and its backend hold each other
    return 0


if __name__ == "__main__":
    sys.exit(main())
