#!/usr/bin/env python3
"""Chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration in ``chipbench/configs/<config>.json``, its traffic mix in
``chipbench/traffic/<traffic>.json`` (whose ``kind`` names the generator
``chipbench/traffic/<kind>.py`` and whose ``path`` names the runner
``chipbench/paths/<path>.py``), the limits of its correctness check in
``chipbench/limits/<cell>.json``, and each metric's reader in
``chipbench/metrics/<metric>.py``.  With ``--trace 0`` the run reports the
cell's end-to-end metrics; with ``--trace 1`` it takes a profiler trace of
the window and reports the per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks`` last); the numbers compared, each beside its limit,
are also the last lines on standard error.  Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WORK_DIR = os.path.join(ROOT, ".chipbench_work")


class NoChip(RuntimeError):
    """No accelerator, too few of them, or one the peaks table lacks."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "chipbench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Dict[str, Any]:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = os.path.join(root, "chipbench")
    traffic = _json(os.path.join(bench, "traffic", cell["traffic"] + ".json"))
    return {
        "cell": cell,
        "config": _json(os.path.join(root, configs[cell["config"]]["file"])),
        "traffic": traffic,
        "generator": os.path.join(bench, "traffic", traffic["kind"] + ".py"),
        "path": os.path.join(bench, "paths", traffic["path"] + ".py"),
        "limits": _json(os.path.join(bench, "limits", workload + ".json")),
        "end_to_end": [m for m in spec["end_to_end"] if _applies(m, workload)],
        "per_layer": [m for m in spec["per_layer"] if _applies(m, workload)],
        "run_seconds": spec["run_seconds"],
    }


def devices_for(chips: int, peaks: dict, require_chip: bool = True):
    """The first ``chips`` devices, refusing anything but a known TPU."""
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU found: JAX platform {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"cell asks for {chips} chips, JAX sees {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json")
    return devs[:chips], peaks["devices"][kind]


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class Ctx:
    """What a path's runner gets: the cell's data, the seed, the devices,
    and the trace window."""

    def __init__(self, res: dict, seed: int, seconds: float, trace: bool,
                 devices, peak: dict, workdir: str, modes=("f32",)):
        self.cell, self.config = res["cell"], res["config"]
        self.traffic, self.limits = res["traffic"], res["limits"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.peak = devices, peak
        self.chips = len(devices)
        self.workdir = workdir
        self.t_start = T_START
        self._generator = res["generator"]
        self.trace_events: Optional[List[dict]] = None
        self.trace_window_s = 0.0
        self.compiles_in_window = 0
        self.gc_pauses: List[tuple] = []   # (generation, seconds) in window
        self.jax_events: Dict[str, list] = {}  # name -> [count, seconds]
        self.memory_change: Dict[str, int] = {}
        # precisions the reference runs in: "f32" decides ``correct``;
        # the control reading adds "fp8" (chipbench/control.py)
        self.modes = tuple(modes)

    @property
    def seed32(self) -> int:
        import numpy as np
        return int(np.random.SeedSequence(self.seed % (1 << 64))
                   .generate_state(1)[0] & 0x7FFFFFFF)

    def generate(self):
        gen = load_module(self._generator)
        return gen.generate(self.traffic, self.config, self.seed, self.seconds)

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip so far."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    @contextlib.contextmanager
    def window(self):
        """The measured window: counts the programs compiled or loaded in
        it (``compiles_in_window``; there should be none), times the
        interpreter's garbage collections in it, and takes the profiler
        trace in a traced run."""
        import jax
        count = [0]
        started = [0.0]

        def on_compile(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                count[0] += 1
            seen = self.jax_events.setdefault(event, [0, 0.0])
            seen[0] += 1
            seen[1] += duration

        def on_gc(phase, info):
            if phase == "start":
                started[0] = time.perf_counter()
            else:
                self.gc_pauses.append((info["generation"],
                                       time.perf_counter() - started[0]))

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        gc.callbacks.append(on_gc)
        before = self._memory_stats()
        try:
            with self._traced():
                yield
        finally:
            gc.callbacks.remove(on_gc)
            jax.monitoring.unregister_event_duration_listener(on_compile)
            self.compiles_in_window = count[0]
            after = self._memory_stats()
            self.memory_change = {k: after[k] - before.get(k, 0)
                                  for k in after if after[k] != before.get(k)}

    def _memory_stats(self) -> Dict[str, int]:
        stats = self.devices[0].memory_stats() or {}
        return {k: int(v) for k, v in stats.items()
                if isinstance(v, (int, float))}

    def gc_readings(self) -> dict:
        """The window's collections: how many of the oldest generation,
        and the longest and total pause of all."""
        pauses = [s for _, s in self.gc_pauses]
        return {"gc_full_collections": sum(1 for g, _ in self.gc_pauses
                                           if g == 2),
                "gc_pause_max_ms": 1e3 * max(pauses, default=0.0),
                "gc_pause_total_ms": 1e3 * sum(pauses)}

    @contextlib.contextmanager
    def _traced(self):
        if not self.trace:
            yield
            return
        import jax
        from chipbench import trace as tr
        tdir = os.path.join(self.workdir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir, profiler_options=_profile_options())
        t0 = time.perf_counter()
        try:
            yield
        finally:
            for d in self.devices:
                jax.device_put(0.0, d).block_until_ready()
            self.trace_window_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
        self.trace_events = tr.load_xplane(tdir)
        shutil.rmtree(tdir, ignore_errors=True)


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: str = ROOT, require_chip: bool = True,
            overrides: Optional[dict] = None, peaks: Optional[dict] = None,
            modes=("f32",)):
    """One run: returns the result object and the path's record.

    ``modes`` adds lower-precision references for the control reading.
    ``overrides`` replaces parts of the resolved cell (``config``,
    ``traffic``, ``limits``) and ``peaks`` the table of peaks; tests use
    them to drive the whole run at a small size on the CPU with
    ``require_chip=False``.
    """
    res = resolve(workload, root)
    for k, v in (overrides or {}).items():
        res[k] = {**res[k], **v} if isinstance(v, dict) else v
    peaks = peaks or _json(os.path.join(BENCH, "peaks.json"))
    devices, peak = devices_for(res["cell"]["chips"], peaks, require_chip)
    os.makedirs(WORK_DIR, exist_ok=True)
    ctx = Ctx(res, seed, seconds, trace, devices, peak, WORK_DIR, modes)
    rec = load_module(res["path"]).run(ctx)

    metrics = {}
    for m in (res["per_layer"] if trace else res["end_to_end"]):
        val = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py")
                          ).read(rec, ctx)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": all(c["value"] <= c["limit"] for c in rec["checks"]),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    if trace and ctx.trace_events is not None:
        from chipbench import trace as tr
        device["busy_s"] = tr.busy_s_mean(ctx.trace_events)
        device["window_s"] = ctx.trace_window_s
        out["breakdown"] = tr.breakdown(ctx.trace_events)
    out["readings"] = {**rec.get("readings", {}),
                       "compiles_in_window": ctx.compiles_in_window,
                       "jax_events_in_window": ctx.jax_events,
                       "memory_stats_change": ctx.memory_change,
                       **ctx.gc_readings()}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in rec["checks"]}
    return out, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    # the cache lives in the checkout at a fixed path, whatever the
    # environment says: only the checkout survives between a cell's runs
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out, _ = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
