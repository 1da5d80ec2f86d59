"""State-space mixers: mean device ms a one-step program spends on the
mixers' input projection, convolution and state (gather, update,
read-out, scatter; all M layers)."""
from benchmark.metrics import _stream_trace


def read(ctx):
    return _stream_trace.layer_ms(ctx, "ssm")
