"""The OCR runtime's own host time per decode iteration: the self time of
the program's ``ocr.run`` spans (each ``Runtime.run`` call less the spans
of the task bodies inside it) that start in the stretch
``engine_host_ms`` covers, over the ``serve.decode`` spans that start in
it.  In the engine that is the runtime flushes: the §4 slot tasks, the §6
page partitions and destroys."""
from chipbench import spans


def read(rec, ctx):
    recs = spans.program_spans()
    inside = spans.window_spans(rec, recs)
    n = len(spans.named(inside, "serve.decode"))
    if not n:
        return None
    own = spans.self_times(spans.named(inside, "ocr.run"),
                           spans.children(recs))
    return 1e3 * sum(own) / n
