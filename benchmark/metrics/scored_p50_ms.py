"""End to end: the median, over EVERY event of the window, of the time its
batch was seen on the scored topic minus its due time at the generator."""
from benchmark.metrics import latency_percentile


def read(ctx):
    return latency_percentile(ctx, 50)
