"""Operations of each prefill at its prompt's true length over the
host-clock time of the prefill call, against the bf16 peak, in percent:
padding to the prompt bucket counts as time, not as work."""
from chipbench import flops


def read(rec, ctx):
    begin = rec["window"][0]
    calls = [(a, b, n) for a, b, n in rec["prefills"] if a >= begin]
    if not calls:
        return None
    work = sum(flops.prefill_flops(rec["dims"], n) for _, _, n in calls)
    t = sum(b - a for a, b, _ in calls)
    return 100.0 * work / t / ctx.peak["flops_bf16"]
