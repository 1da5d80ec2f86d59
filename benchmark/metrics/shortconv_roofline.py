"""Gated short convolutions, as a kernel: the least time the chip could
take for the traced steps' conv layers — their projections' weights and
taps once a step, the tails the real rows read and wrote (the step's own
counter ``stream_conv_tail_bytes``) and the rows' activations — over the
device time of the conv layers' operations."""
from benchmark.metrics import _conv_trace as ct


def read(ctx):
    n = ct.steps(ctx)
    rows = ct.counter(ctx, "rows_one_step", "traced")
    tails = ct.counter(ctx, "conv_tail_bytes", "traced")
    if not n or not rows or not tails:
        return None
    model, costs = ct.costs(ctx)
    return ct.roofline_pct(ctx, ("shortconv",), *costs.shortconv_cost(
        model, rows, n, tails))
