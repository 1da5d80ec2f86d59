"""End to end: the 99th percentile, over EVERY event of the window, of the
time its batch was seen on the scored topic minus its due time at the
generator; an event that never came out counts as still waiting when the
run gave up."""
from benchmark.metrics import latency_percentile


def read(ctx):
    return latency_percentile(ctx, 99)
