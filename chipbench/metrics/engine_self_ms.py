"""The engine loop's host time per decode iteration, from the program's
own spans: every ``serve.iteration`` span that starts in the stretch
``engine_host_ms`` covers (the window's start to the last call's end, an
arrival window's drain included), less its ``serve.prefill`` and
``serve.decode`` spans (the backend calls and any wait for an arrival
inside them), summed and over the stretch's ``serve.decode`` spans.  The
in-program counterpart of ``engine_host_ms``; the runtime's ``ocr.run``
spans count in it."""
from chipbench import spans


def read(rec, ctx):
    recs = spans.program_spans()
    inside = spans.window_spans(rec, recs)
    n = len(spans.named(inside, "serve.decode"))
    if not n:
        return None
    own = spans.time_outside(spans.named(inside, "serve.iteration"),
                             spans.children(recs),
                             ("serve.prefill", "serve.decode"))
    return 1e3 * sum(own) / n
