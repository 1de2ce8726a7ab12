"""The program's own spans for the per-layer metrics that read them.

The program records wall-clock spans (``repro.monitoring.spans``) while
the profiler traces, so in a ``--trace 1`` run the window's spans sit in
the same process once the path has run.  Here they are cut to the
run's stretch, reduced to self times, and joined to the profiler's
clock; ``idle_by_span`` splits the device's idle time by them.  A
program that records no spans (or has no such module) gives an empty
list, and every reader then returns None.

Spans are timed on ``time.perf_counter()``, the clock of the path's own
stamps; the trace's events are in nanoseconds on the profiler's clock.
One offset per trace joins them: the median, over the window's calls, of
each ``cb:decode`` / ``cb:prefill`` event's start minus the same call's
first stamp in ``rec`` (both taken within microseconds of each other).
"""
from __future__ import annotations

import bisect
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace


def program_spans() -> list:
    """Every span the program recorded in this process (oldest first by
    end), or [] where it records none."""
    try:
        from repro.monitoring import spans
    except ImportError:
        return []
    return spans.recorded()


def train_window(recs) -> Optional[Tuple[float, float]]:
    """A training window is one traced ``Trainer.run``: the last runtime
    call that ran ``train.step`` spans."""
    runs = {s.parent for s in recs if s.name == "train.step"}
    calls = [s for s in recs if s.name == "ocr.run" and s.sid in runs]
    if not calls:
        return None
    last = max(calls, key=lambda s: s.end)
    return last.start, last.end


def serve_stretch(rec) -> Tuple[float, float]:
    """A serving run's stretch as ``engine_host_ms`` counts it: from the
    window's start to the end of the last backend call that starts at or
    after it, so an arrival window's drain is in it."""
    begin = rec["window"][0]
    return begin, max((b for a, b, *_ in rec["prefills"] + rec["decodes"]
                       if a >= begin), default=begin)


def window_spans(rec, recs=None) -> list:
    """The spans that start inside the run's stretch: a serving run's
    ``serve_stretch``, or a training run's traced ``Trainer.run``."""
    recs = program_spans() if recs is None else recs
    w = serve_stretch(rec) if "decodes" in rec else train_window(recs)
    if w is None:
        return []
    return [s for s in recs if w[0] <= s.start < w[1]]


def named(recs, name: str) -> list:
    return [s for s in recs if s.name == name]


def children(recs) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for s in recs:
        out.setdefault(s.parent, []).append(s)
    return out


def self_times(spans, kids: Dict[int, list]) -> List[float]:
    """Each span's length less the lengths of its direct children."""
    return [(s.end - s.start) - sum(c.end - c.start
                                    for c in kids.get(s.sid, ()))
            for s in spans]


def time_outside(spans, kids: Dict[int, list], excluded: Iterable[str]
                 ) -> List[float]:
    """Each span's length less that of its outermost descendants named in
    ``excluded``."""
    excluded = set(excluded)
    out = []
    for s in spans:
        inner, todo = 0.0, list(kids.get(s.sid, ()))
        while todo:
            c = todo.pop()
            if c.name in excluded:
                inner += c.end - c.start
            else:
                todo.extend(kids.get(c.sid, ()))
        out.append((s.end - s.start) - inner)
    return out


def clock_offset_ns(events: Sequence[dict], rec) -> Optional[float]:
    """Nanoseconds to add to ``perf_counter() * 1e9`` to land on the
    trace's clock: the median over the window's calls of each ``cb:``
    call event's start minus the call's first stamp in ``rec``.  Calls
    are paired in order, and only where the trace holds as many events
    of a kind as ``rec`` holds calls."""
    w = rec.get("window")
    diffs = []
    for kind in ("decode", "prefill"):
        marks = sorted(e["start_ns"] for e in events
                       if e["name"] == "cb:" + kind)
        stamps = sorted(c[0] for c in rec.get(kind + "s", ()))
        if not marks or len(marks) != len(stamps):
            continue
        diffs += [m - 1e9 * t for m, t in zip(marks, stamps)
                  if w is None or w[0] <= t < w[1]]
    return statistics.median(diffs) if diffs else None


def train_offset_ns(events: Sequence[dict], recs) -> Optional[float]:
    """The same join for a training run, which stamps no calls: each
    ``cb:input`` event opens within microseconds of the program's
    ``train.input`` span around the same ``get`` in the traced
    ``Trainer.run``; paired in order where the counts agree."""
    w = train_window(recs)
    if w is None:
        return None
    marks = sorted(e["start_ns"] for e in events if e["name"] == "cb:input")
    starts = sorted(s.start for s in recs if s.name == "train.input"
                    and w[0] <= s.start < w[1])
    if not marks or len(marks) != len(starts):
        return None
    return statistics.median(m - 1e9 * t for m, t in zip(marks, starts))


def device_ops(events: Sequence[dict]) -> List[Tuple[float, float]]:
    """Busy intervals of the first device, in order (profiler ns)."""
    planes = trace.device_planes(events)
    if not planes:
        return []
    return trace.union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                        for e in events if e["plane"] == planes[0]])


def launch_ns(starts_ns: Iterable[float], busy: List[Tuple[float, float]]
              ) -> List[float]:
    """For each host moment, the time to the first device operation that
    starts at or after it (moments with none after them are left out)."""
    begins = [b[0] for b in busy]
    out = []
    for t in starts_ns:
        i = bisect.bisect_left(begins, t)
        if i < len(begins):
            out.append(begins[i] - t)
    return out


def _depths(recs) -> Dict[int, int]:
    parent = {s.sid: s.parent for s in recs}
    depth: Dict[int, int] = {}

    def d(sid):
        if sid not in depth:
            p = parent[sid]
            depth[sid] = d(p) + 1 if p in parent else 0
        return depth[sid]

    for s in recs:
        d(s.sid)
    return depth


def labels(recs, offset_ns: float) -> List[Tuple[float, float, str]]:
    """The trace's timeline cut where any span begins or ends, each piece
    named by the deepest span over it (pieces under no span left out)."""
    depth = _depths(recs)
    name = {s.sid: s.name for s in recs}
    marks = sorted([(1e9 * s.start + offset_ns, 1, s.sid) for s in recs]
                   + [(1e9 * s.end + offset_ns, 0, s.sid) for s in recs])
    active: Dict[int, int] = {}
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, opens, sid in marks:
        if active and prev is not None and t > prev:
            top = max(active, key=lambda k: (active[k], k))
            out.append((prev, t, name[top]))
        if opens:
            active[sid] = depth[sid]
        else:
            active.pop(sid, None)
        prev = t
    return out


def idle_by_span(events: Sequence[dict], recs, offset_ns: float,
                 window_ns: Optional[Tuple[float, float]] = None) -> dict:
    """The first device's idle time between its first and last operation
    (inside ``window_ns`` when given), the share of it that some program
    span covers, the idle split by the deepest span over it, and the
    longest idle gap with its own split."""
    busy = device_ops(events)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    if window_ns is not None:
        gaps = [(max(s, window_ns[0]), min(e, window_ns[1]))
                for s, e in gaps if e > window_ns[0] and s < window_ns[1]]
    segs = labels(recs, offset_ns)
    starts = [s[0] for s in segs]
    total = 0.0
    by: Dict[str, float] = {}
    longest: Tuple[float, Dict[str, float]] = (0.0, {})
    for s, e in gaps:
        own: Dict[str, float] = {}
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(segs) and segs[i][0] < e:
            ov = min(e, segs[i][1]) - max(s, segs[i][0])
            if ov > 0:
                own[segs[i][2]] = own.get(segs[i][2], 0.0) + ov
            i += 1
        for k, v in own.items():
            by[k] = by.get(k, 0.0) + v
        total += e - s
        if e - s > longest[0]:
            longest = (e - s, own)
    covered = sum(by.values())
    return {"idle_s": total / 1e9,
            "covered_share": covered / total if total else None,
            "by_span_s": {k: v / 1e9 for k, v in
                          sorted(by.items(), key=lambda kv: -kv[1])},
            "longest_gap_s": longest[0] / 1e9,
            "longest_gap_by_span_s": {k: v / 1e9
                                      for k, v in longest[1].items()}}
