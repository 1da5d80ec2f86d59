"""Expert layers: share of the routed (row, expert) pairs that fell on
held experts. The chip holds half of each layer's experts, so 50 by
construction: a drift is a routing fault."""
from benchmark.metrics import _stream_trace


def read(ctx):
    routed = _stream_trace.counter(ctx, "pairs_routed")
    if not routed:
        return None
    return 100.0 * _stream_trace.counter(ctx, "pairs_held") / routed
