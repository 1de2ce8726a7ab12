"""Model operations of every prompt token prefilled and every token decoded
inside the window, over the window's length times the bf16 peak, in
percent."""
from chipbench import flops
from chipbench.stats import in_window


def read(rec, ctx):
    w, dm = rec["window"], rec["dims"]
    work = sum(flops.prefill_flops(dm, n) for _, b, n in rec["prefills"]
               if in_window(b, w))
    work += sum(flops.decode_token_flops(dm, c) for _, b, cs in rec["decodes"]
                if in_window(b, w) for c in cs)
    return 100.0 * work / ((w[1] - w[0]) * ctx.peak["flops_bf16"])
