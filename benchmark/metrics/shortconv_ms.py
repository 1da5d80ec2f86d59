"""Gated short convolutions: mean device ms a one-step program spends on
the conv layers' projections, gates, taps and tails (all C layers)."""
from benchmark.metrics import _conv_trace


def read(ctx):
    return _conv_trace.layer_ms(ctx, "shortconv")
