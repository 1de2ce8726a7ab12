"""Mean host time per window step spent in the data object's ``get``: the
token file read through §5 file-mapped chunks."""


def read(rec, ctx):
    w = rec["input_waits"]
    return 1e3 * sum(w) / len(w) if w else None
