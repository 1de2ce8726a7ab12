"""``engine_self_ms`` in the cells whose end-to-end rate is
``serve_tok_s``: the same reading, moving another metric."""
from chipbench.metrics.engine_self_ms import read  # noqa: F401
