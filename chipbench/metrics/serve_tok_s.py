"""Output tokens whose host-clock stamp falls inside the window, over the
window's length."""
from chipbench.stats import in_window


def read(rec, ctx):
    w = rec["window"]
    n = sum(1 for q in rec["requests"] for t in q["times"] if in_window(t, w))
    return n / (w[1] - w[0])
