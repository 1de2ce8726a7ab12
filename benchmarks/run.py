"""Benchmark driver: one section per paper extension + roofline + steps.

Prints ``name,us_per_call,derived`` CSV.  §3/§4/§6 makespans are in
deterministic virtual time (noise-free); file IO does real disk IO; the
roofline section reads the AOT dry-run artifact.

Modules exposing ``summary()`` also emit a machine-readable
``BENCH_<name>.json`` (makespan, messages_sent, wall-time, counters) into
``$BENCH_JSON_DIR`` (default: cwd) so the perf trajectory is tracked
across PRs.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


_SECTIONS = ("bench_lid", "bench_map", "bench_guidtable", "bench_fileio",
             "bench_partition", "bench_contention", "bench_serve",
             "bench_flash", "bench_moe", "bench_train", "bench_roofline")


def main() -> None:
    import importlib

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--sections", default=None, metavar="NAME[,NAME...]",
        help="comma-separated subset to run (short names, e.g. "
             "'partition,contention'); default: all")
    opts = ap.parse_args()
    sections = _SECTIONS
    if opts.sections is not None:
        wanted = [s.strip() for s in opts.sections.split(",") if s.strip()]
        unknown = [s for s in wanted
                   if f"bench_{s}" not in _SECTIONS and s not in _SECTIONS]
        if unknown:
            ap.error(f"unknown section(s) {unknown}; choose from "
                     f"{[s[len('bench_'):] for s in _SECTIONS]}")
        sections = tuple(s if s in _SECTIONS else f"bench_{s}"
                         for s in wanted)

    mods, failed = [], []
    print("name,us_per_call,derived")
    for name in sections:
        # a section that fails to import or run is reported, the others
        # still run, and the exit status is non-zero at the end
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us},{derived}")
        except Exception as e:
            print(f"# {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed.append(name)
            continue
        mods.append(mod)

    out_dir = os.environ.get("BENCH_JSON_DIR", ".")
    for mod in mods:
        summary = getattr(mod, "summary", None)
        if summary is None:
            continue
        name = mod.__name__.rsplit("bench_", 1)[-1]
        path = os.path.join(out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(summary(), f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {path}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
