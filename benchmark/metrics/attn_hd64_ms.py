"""Attention at head_dim 64: mean device ms a one-step program spends on
the attention layers' pool in the paired layout (the paged-attention
kernel over each row's history, the pool's writes)."""
from benchmark.metrics import _conv_trace


def read(ctx):
    return _conv_trace.layer_ms(ctx, "attn_hd64")
