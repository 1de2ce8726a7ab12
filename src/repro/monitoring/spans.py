"""Wall-clock spans and counters at the program's layer boundaries.

The registry (``repro.monitoring.registry``) runs on the runtime's virtual
clock and never sees a device.  This recorder is its wall-clock side: a
span marks one pass through a layer boundary (``ocr.run``,
``serve.iteration``, ``serve.dispatch``, ``train.step``, ...) and carries
the counters taken there as attributes.

It records exactly while a JAX profiler trace is running
(``jax.profiler.TraceAnnotation.is_enabled()``); otherwise a span site
costs that one check.  Each recorded span is written twice:

* as a ``jax.profiler.TraceAnnotation`` of the same name with its
  counters as metadata, so it sits in the trace on the device's clock;
* as a :class:`Span` in a bounded in-memory buffer, timed on
  ``time.perf_counter()``; a full buffer counts the spans it drops.

``parent`` is the ``sid`` of the enclosing recorded span on the same
thread (-1 at the top).  Nothing here touches the virtual clock, the
runtime's ``Stats`` or the registry, so every virtual-time number is the
same with recording on or off.

    with spans.span("serve.decode", rows=11):
        ...
    with spans.span("ocr.run") as sp:
        ...
        sp.set(tasks=n)       # a counter known only at the end
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple

__all__ = ["Span", "span", "enabled", "recorded", "dropped", "clear"]


class Span(NamedTuple):
    name: str
    start: float                 # time.perf_counter() seconds
    end: float
    parent: int                  # sid of the enclosing span, -1 at the top
    attrs: Dict[str, Any]        # counters taken at this boundary
    sid: int


_buffer: List[Span] = []
_capacity = 1 << 18              # spans the buffer keeps
_dropped = 0
_lock = threading.Lock()         # the cap's check, the append and the count
_ids = itertools.count()
_local = threading.local()
# jax.profiler.TraceAnnotation, bound once jax has been imported: until
# then no profiler can be running, and the runtime never imports jax
_annotation: Any = None


def enabled() -> bool:
    """True while a JAX profiler trace is being taken."""
    ann = _annotation
    if ann is None:
        ann = _bind()
        if ann is None:
            return False
    return ann.is_enabled()


def _bind():
    global _annotation
    prof = sys.modules.get("jax.profiler")
    if prof is not None:
        _annotation = prof.TraceAnnotation
    return _annotation


class _Off:
    """What a span site gets while nothing records."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


OFF = _Off()


class _Recording:
    __slots__ = ("name", "attrs", "sid", "parent", "start", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else -1
        self.sid = next(_ids)
        stack.append(self.sid)
        self._ann = _annotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Add counters taken later in the span."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> bool:
        global _dropped
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        done = Span(self.name, self.start, end, self.parent, self.attrs,
                    self.sid)
        with _lock:
            if len(_buffer) < _capacity:
                _buffer.append(done)
            else:
                _dropped += 1
        return False


def span(name: str, **attrs):
    """A context manager that records ``name`` with ``attrs`` while the
    profiler traces, and does nothing otherwise."""
    if not enabled():
        return OFF
    return _Recording(name, attrs)


def recorded() -> List[Span]:
    """The spans recorded so far, in the order they ended (a copy; the
    buffer is not cleared)."""
    with _lock:
        return list(_buffer)


def dropped() -> int:
    """Spans lost to a full buffer since the last :func:`clear`."""
    return _dropped


def clear() -> None:
    """Empty the buffer and the dropped count."""
    global _dropped
    with _lock:
        _buffer.clear()
        _dropped = 0
