"""Full attention: mean device ms a one-step program spends on the full
layers' pool (the paged-attention kernel over each row's history, the
pool's writes)."""
from benchmark.metrics import _paged_trace


def read(ctx):
    return _paged_trace.layer_ms(ctx, "full_attn")
