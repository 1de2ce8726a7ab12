"""Mean host time per window step in the trainer's ``train.input`` span,
its data object's ``get`` (the §5 chunk read through its own runtime):
the in-program counterpart of ``input_wait_ms.train``."""
from chipbench import spans


def read(rec, ctx):
    inside = spans.window_spans(rec)
    n = len(spans.named(inside, "train.step"))
    if not n:
        return None
    return 1e3 * sum(s.end - s.start
                     for s in spans.named(inside, "train.input")) / n
