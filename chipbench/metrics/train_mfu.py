"""Model FLOPs per token (6 N plus causal attention forward and backward,
no remat recomputation) times the window's tokens per second, over the
chips' bf16 peak, in percent."""


def read(rec, ctx):
    rate = rec["tokens"] / rec["window_s"]
    return 100.0 * rec["flops_per_token"] * rate / (
        ctx.chips * ctx.peak["flops_bf16"])
