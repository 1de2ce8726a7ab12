"""The decode step's roofline share: the least time the chip needs for the
step's work (every weight read once, each active row's live keys and
values read once, and the step's operations), over the host-clock step
time, summed over the window's steps, in percent."""
from chipbench import flops


def read(rec, ctx):
    begin, end = rec["window"]
    calls = [(a, b, c) for a, b, c in rec["decodes"] if a >= begin and b <= end]
    if not calls:
        return None
    least = 0.0
    for _, _, ctx_lens in calls:
        ops, nbytes = flops.decode_step(rec["dims"], ctx_lens,
                                        rec["weight_itemsize"],
                                        rec["kv_itemsize"])
        least += flops.least_time(ops, nbytes, ctx.peak["flops_bf16"],
                                  ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / sum(b - a for a, b, _ in calls)
