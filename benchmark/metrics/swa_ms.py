"""Window attention: mean device ms a one-step program spends on the
window layers' pages (the paged-attention kernel over each row's ring,
the ring's writes)."""
from benchmark.metrics import _paged_trace


def read(ctx):
    return _paged_trace.layer_ms(ctx, "swa")
