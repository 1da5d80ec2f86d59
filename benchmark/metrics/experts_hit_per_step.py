"""Expert layers: distinct held experts a layer hits in a one-step
call (the step's counter over the window: summed over the E layers and
the calls, so divided by both)."""
from benchmark.metrics import _stream_trace


def read(ctx):
    calls = _stream_trace.counter(ctx, "calls_one_step")
    hit = _stream_trace.counter(ctx, "experts_hit")
    layers = ctx["config"].get("model", {}).get("pattern", "").count("E")
    if not calls or not hit or not layers:
        return None
    return hit / (calls * layers)
