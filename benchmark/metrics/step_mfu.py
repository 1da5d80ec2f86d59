"""Whole step: FLOPs the valid rows of the traced window needed, over
what the chips could have done in that window at their bf16 peak."""
from benchmark import metrics, peaks


def read(ctx):
    if ctx["trace"] is None:
        return None
    traced = ctx["traced"]
    rows = traced.count("tpu_inference.flush_rows")
    window_s = ctx["trace"]["window_s"]
    if not rows or not window_s:
        return None
    flops, _ = metrics.step_cost(ctx, rows, 0, 0)
    peak = peaks.peaks_for(ctx["device"]["kind"])["flops_bf16"]
    return 100.0 * flops / (window_s * peak * ctx["chips"])
