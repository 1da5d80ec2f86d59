"""Lanes -> staging -> h2d: mean ms a batch's rows sat in their lane
ring, from lane enqueue until the flush that carried them asked for its
in-flight permit (`tpu_inference.lane_wait`, per carried batch)."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.lane_wait")
    return 1000.0 * total / n if n else None
