"""Attention: mean device ms a one-step program spends on the rows' key /
value rings (gather, unpack, scores, weighted sum, the ring write)."""
from benchmark.metrics import _stream_trace


def read(ctx):
    return _stream_trace.layer_ms(ctx, "attn")
