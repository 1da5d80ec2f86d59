"""Gated expert layers' grouped products, as a kernel: the least time the
chip could take for them over the traced steps — the three matrices of
the DISTINCT held experts each layer hit (the step's own counter) and the
pairs' activations, or the FLOPs of the held pairs — over the device time
of the ``gmm`` kernels."""
from benchmark.metrics import _paged_trace as pt


def read(ctx):
    hit = pt.counter(ctx, "experts_hit", "traced")
    if not hit:
        return None
    model, costs = pt.costs(ctx)
    return pt.roofline_pct(ctx, ("gated_moe",), *costs.moe_cost(
        model, pt.counter(ctx, "pairs_held", "traced"), hit))
