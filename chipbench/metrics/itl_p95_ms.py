"""95th percentile of every gap between consecutive output tokens of every
request.  Under a backlog window only gaps that lie wholly inside it."""
from chipbench.stats import p95


def read(rec, ctx):
    w = rec["window"]
    whole = ctx.traffic["window"] == "arrivals"
    gaps = []
    for q in rec["requests"]:
        t = q["times"]
        gaps += [b - a for a, b in zip(t, t[1:])
                 if whole or (a >= w[0] and b <= w[1])]
    v = p95(gaps)
    return None if v is None else v * 1e3
