"""Host event loop: the share of the window the loop's thread spent in
task steps of stage `score` (lanes, flush, reaper, resolve, publish) —
`loop_busy_seconds_total{stage="score"}` over the window's seconds."""
from benchmark.metrics.loop_intake_pct import share


def read(ctx):
    return share(ctx, "score")
