"""``decode_mfu`` in the cells whose end-to-end rate is ``serve_tok_s``:
the same reading, moving another metric."""
from chipbench.metrics.decode_mfu import read  # noqa: F401
