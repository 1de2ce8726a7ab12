"""Share of the traced window in which no operation ran on the device
(averaged over the cell's chips), in percent."""
from chipbench.trace import idle_percent


def read(rec, ctx):
    return idle_percent(ctx.trace_events, ctx.trace_window_s)
