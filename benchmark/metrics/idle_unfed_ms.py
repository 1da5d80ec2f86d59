"""Lanes -> staging -> h2d: mean ms a serve flush's device sat idle with
no work due — from the slice's previous landing to the moment a flush
fell due: a full lane, or the collect deadline of the oldest row
(`tpu_inference.idle_unfed`; 0 where a serve flush was still in flight)."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.idle_unfed")
    return 1000.0 * total / n if n else None
