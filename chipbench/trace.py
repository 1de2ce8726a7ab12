"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to a list of plain events,
``{"plane", "line", "name", "start_ns", "dur_ns"}``, so the reductions
below run the same on a fresh ``.xplane.pb`` and on the small recorded
trace the tests keep.  Device planes are those named ``/device:TPU:<n>``;
on them only the line of XLA operations counts (module and step lines
repeat the same time).  Host spans are the benchmark's own
``TraceAnnotation`` names, which start with ``cb:``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "cb:"
# control flow that holds other operations: counted in busy time, left out
# of the list of operations that took most time
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]*$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather|reducescatter|alltoall", re.I)


def load_xplane(trace_dir: str) -> List[dict]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: device
    operations and the benchmark's host spans."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(HOST_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def device_planes(events: Iterable[dict]) -> List[str]:
    return sorted({e["plane"] for e in events
                   if DEVICE_PLANE.match(e["plane"])})


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _total(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in iv)


def _ops(events, plane):
    return [e for e in events if e["plane"] == plane]


def busy_ns(events: Sequence[dict], plane: str) -> float:
    """Length of the union of operation intervals on one device."""
    return _total(union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                         for e in _ops(events, plane)]))


def busy_s_mean(events: Sequence[dict]) -> float:
    """Busy seconds averaged over the device planes in the trace."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(busy_ns(events, p) for p in planes) / len(planes) / 1e9


def op_name(name: str) -> str:
    """An XLA operation's short name: ``%fusion.12 = (...) fusion(...)``
    gives ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def outputs(name: str) -> List[str]:
    """The result shapes of an XLA operation's text, e.g.
    ``['bf16[12,5,3,4096,64]{...}', 'f32[12,5,3,4096,1]{...}']``."""
    if " = " not in name:
        return []
    rest = name.split(" = ", 1)[1]
    if not rest.startswith("("):
        return [rest.split(" ", 1)[0]]
    out, depth, cur = [], 0, ""
    for ch in rest[1:]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                break
            depth -= 1
        if ch == "," and depth == 0:
            out.append(cur.strip())
            cur = ""
        else:
            cur += ch
    out.append(cur.strip())
    return [o for o in out if not o.startswith("/*")] if out else []


def kernel_ns(events: Sequence[dict], pattern: str) -> Tuple[float, int]:
    """(summed device time, number of calls) of operations whose short
    name matches ``pattern``, over every device plane."""
    rx = re.compile(pattern)
    hits = [e for e in events if DEVICE_PLANE.match(e["plane"])
            and rx.search(op_name(e["name"]))]
    return sum(e["dur_ns"] for e in hits), len(hits)


def exposed_collective_ns(events: Sequence[dict], plane: str) -> float:
    """Collective time on one device during which no other operation runs
    there."""
    ops = _ops(events, plane)
    coll = union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in ops if COLLECTIVE.search(e["name"])])
    comp = union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in ops if not COLLECTIVE.search(e["name"])])
    exposed, j = 0.0, 0
    for s, e in coll:
        covered = 0.0
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            covered += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
        exposed += (e - s) - covered
    return exposed


def breakdown(events: Sequence[dict], top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (summed by short name,
    over the chips, loops left out), and the longest idle gaps of the first device, each named by
    the benchmark's host span that overlaps it most."""
    planes = device_planes(events)
    by_name: Dict[str, float] = {}
    for e in events:
        short = op_name(e["name"])
        if e["plane"] in planes and not CONTAINER.match(short):
            by_name[short] = by_name.get(short, 0.0) + e["dur_ns"]
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps: List[Tuple[str, float]] = []
    if planes:
        busy = union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                      for e in _ops(events, planes[0])])
        host = [e for e in events if e["name"].startswith(HOST_PREFIX)]
        holes = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        holes.sort(key=lambda h: h[0] - h[1])
        for s, e in holes[:top]:
            best, name = 0.0, "host:other"
            for hs in host:
                ov = (min(e, hs["start_ns"] + hs["dur_ns"])
                      - max(s, hs["start_ns"]))
                if ov > best:
                    best, name = ov, hs["name"]
            gaps.append((name, (e - s) / 1e9))
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}


def idle_percent(events, window_s: float):
    """100 * (1 - busy / window); None without a trace or a device plane."""
    if not events or window_s <= 0 or not device_planes(events):
        return None
    return 100.0 * (1.0 - busy_s_mean(events) / window_s)
