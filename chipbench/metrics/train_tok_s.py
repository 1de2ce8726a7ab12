"""Tokens trained in the window over the window's host-clock seconds."""


def read(rec, ctx):
    return rec["tokens"] / rec["window_s"]
