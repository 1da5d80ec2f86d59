"""Host event loop: the share of the fullest chip's idle time in the traced
stretch during which the loop thread ran a step of an intake task:
receivers, decode, inbound (`sw/loop/intake`)."""
from benchmark.metrics import _loop_trace


def read(ctx):
    return _loop_trace.share(ctx, "intake")
