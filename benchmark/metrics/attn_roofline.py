"""Attention kernels, both kinds: the least time the chip could take for
the traced steps' attention — the positions attended (the step's own
counters, over window and full layers) x K and V, plus each query and
its output; 4 x q x positions FLOPs — over the device time of the window
and full layers' operations."""
from benchmark.metrics import _paged_trace as pt


def read(ctx):
    positions = (pt.counter(ctx, "positions_swa", "traced")
                 + pt.counter(ctx, "positions_full", "traced"))
    if not positions:
        return None
    model, costs = pt.costs(ctx)
    rows = (pt.counter(ctx, "rows_one_step", "traced")
            + pt.counter(ctx, "rows_chunked", "traced"))
    return pt.roofline_pct(ctx, ("swa", "full_attn"), *costs.attn_cost(
        model, positions, rows * len(model["attention"])))
