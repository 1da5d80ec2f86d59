"""Gather + d2h + reap: mean ms a flush's reaper waited for its scores."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.d2h_wait")
    return 1000.0 * total / n if n else None
