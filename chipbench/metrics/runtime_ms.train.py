"""The OCR runtime's own host time per window step: the self time of the
program's ``ocr.run`` spans in the traced ``Trainer.run`` (the trainer's
runtime scheduling step tasks between step bodies, and each
``FileTokens.get``'s own runtime), over the window's ``train.step``
spans."""
from chipbench import spans


def read(rec, ctx):
    recs = spans.program_spans()
    inside = spans.window_spans(rec, recs)
    n = len(spans.named(inside, "train.step"))
    if not n:
        return None
    own = spans.self_times(spans.named(inside, "ocr.run"),
                           spans.children(recs))
    return 1e3 * sum(own) / n
