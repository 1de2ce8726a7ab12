"""Seconds from the start of the process to the opening of the window:
imports, weights, data, compilation or its cache, warm-up steps."""


def read(rec, ctx):
    return rec["setup_s"]
