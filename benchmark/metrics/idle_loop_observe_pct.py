"""Host event loop: the share of the fullest chip's idle time in the traced
stretch during which the loop thread was inside the tracing's own work,
in whatever step (`sw/observe`)."""
from benchmark.metrics import _loop_trace


def read(ctx):
    return _loop_trace.share(ctx, "observe")
