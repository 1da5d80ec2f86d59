"""Host event loop: the share of the window the loop's thread spent in
task steps of stage `observe` (the tracing itself: stage timers, spans, tail decisions, the
latency ledger, flight recorder, metrics history) —
`loop_busy_seconds_total{stage="observe"}` over the window's seconds."""
from benchmark.metrics.loop_intake_pct import share


def read(ctx):
    return share(ctx, "observe")
