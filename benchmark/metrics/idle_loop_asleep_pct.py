"""Host event loop: the share of the fullest chip's idle time in the traced
stretch during which the loop thread slept in its selector, waiting for
I/O or a timer (`sw/loop/asleep`)."""
from benchmark.metrics import _loop_trace


def read(ctx):
    return _loop_trace.share(ctx, "asleep")
