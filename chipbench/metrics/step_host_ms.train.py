"""Mean host time per window step outside the wait for the device: each
``train.step`` span less its ``train.wait`` child, i.e. the input, the
batch's transfer, the step's dispatch and the trainer's bookkeeping."""
from chipbench import spans


def read(rec, ctx):
    recs = spans.program_spans()
    steps = spans.named(spans.window_spans(rec, recs), "train.step")
    if not steps:
        return None
    own = spans.time_outside(steps, spans.children(recs), ("train.wait",))
    return 1e3 * sum(own) / len(steps)
