"""Lanes -> staging -> h2d: valid rows per flush over the window."""


def read(ctx):
    w = ctx["window"]
    flushes = w.count("tpu_inference.flushes")
    return w.count("tpu_inference.flush_rows") / flushes if flushes else None
