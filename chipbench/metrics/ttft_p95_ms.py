"""95th percentile over every request due in the window of the time from
its due moment to its first token back on the host: the queue before the
request's prefill and the prefill itself.  A request that never got a
first token counts as waiting until the run ended."""
from chipbench.stats import first_token_waits, p95


def read(rec, ctx):
    v = p95(first_token_waits(rec))
    return None if v is None else v * 1e3
