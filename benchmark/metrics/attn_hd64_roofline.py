"""Attention at head_dim 64, as a kernel: the least time the chip could
take for the traced steps' attention — the positions attended (the
step's own counter ``stream_positions_full``) x K and V (2,048 bytes a
position a layer), plus each query and its output; 4 x q x positions
FLOPs — over the device time of the pool's operations."""
from benchmark.metrics import _conv_trace as ct


def read(ctx):
    positions = ct.counter(ctx, "positions_full", "traced")
    if not positions:
        return None
    model, costs = ct.costs(ctx)
    rows = (ct.counter(ctx, "rows_one_step", "traced")
            + ct.counter(ctx, "rows_chunked", "traced"))
    return ct.roofline_pct(ctx, ("attn_hd64",), *costs.attn_cost(
        model, positions, rows * model["mixers"].count("A")))
