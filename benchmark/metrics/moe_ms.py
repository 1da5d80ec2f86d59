"""Expert layers: mean device ms a one-step program spends in the grouped
products over the held experts (the ``gmm`` kernels; all E
layers)."""
from benchmark.metrics import _stream_trace


def read(ctx):
    return _stream_trace.layer_ms(ctx, "moe")
