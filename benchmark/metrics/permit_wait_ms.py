"""Lanes -> staging -> h2d: mean ms a flush waited for one of the
slice's in-flight permits (`tpu_inference.acquire_wait`, per flush)."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.acquire_wait")
    return 1000.0 * total / n if n else None
