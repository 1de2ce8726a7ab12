"""Mean time from the start of a backend call's ``serve.dispatch`` span
(the program's host arrays, argument transfers and enqueue) to the first
device operation after it, over the calls that start in the stretch
``engine_host_ms`` covers, on the profiler's clock: what the device waits for the host and for a
late-starting program at each call."""
from chipbench import spans


def read(rec, ctx):
    if not ctx.trace_events:
        return None
    inside = spans.named(spans.window_spans(rec), "serve.dispatch")
    offset = spans.clock_offset_ns(ctx.trace_events, rec)
    if not inside or offset is None:
        return None
    waits = spans.launch_ns((1e9 * s.start + offset for s in inside),
                            spans.device_ops(ctx.trace_events))
    return sum(waits) / len(waits) / 1e6 if waits else None
