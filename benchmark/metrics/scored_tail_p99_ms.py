"""Whole path: the 99th percentile, over EVERY event of the window, of the
time its batch was seen on the scored topic minus its due time at the
generator; an event that never came out counts as still waiting when the
run gave up. The deployment's stated SLO. It was the end-to-end metric
``scored_p99_ms`` until its runs spread by more than half of the widest
bound there is (PERF.md, section 2)."""
from benchmark.metrics import latency_percentile


def read(ctx):
    return latency_percentile(ctx, 99)
