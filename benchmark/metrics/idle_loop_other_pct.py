"""Host event loop: the share of the fullest chip's idle time in the traced
stretch during which the loop thread ran a step of an unlabeled task, a
collection (`sw/gc`), or no step at all: plain callbacks, the loop's
machinery (`sw/loop/other`, and time in no `sw/loop/*` span)."""
from benchmark.metrics import _loop_trace


def read(ctx):
    return _loop_trace.share(ctx, "other")
