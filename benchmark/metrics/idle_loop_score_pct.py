"""Host event loop: the share of the fullest chip's idle time in the traced
stretch during which the loop thread ran a step of a scoring task:
lanes, flush, reaper, resolve (`sw/loop/score`)."""
from benchmark.metrics import _loop_trace


def read(ctx):
    return _loop_trace.share(ctx, "score")
