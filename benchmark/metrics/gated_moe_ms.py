"""Gated expert layers: mean device ms a one-step program spends in the
grouped products over the held SwiGLU experts (the ``gmm`` kernels; all
E layers)."""
from benchmark.metrics import _paged_trace


def read(ctx):
    return _paged_trace.layer_ms(ctx, "gated_moe")
