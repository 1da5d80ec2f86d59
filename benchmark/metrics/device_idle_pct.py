"""Device: 100 - busy share of the traced window, on the busiest chip."""
from benchmark import trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    dev, window_s = trace_reduce.fullest(ctx["trace"]), ctx["trace"]["window_s"]
    if dev is None or not window_s or not dev["busy_s"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / window_s)
