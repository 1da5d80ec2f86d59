"""Host event loop: the share of the window the loop's thread was busy
outside every labeled stage — the rest of its CPU seconds: tasks with no
label (the load generator and the harness's recorder among them), the
loop's own machinery between steps, the collector's pauses —
`loop_busy_seconds_total{stage="other"}` over the window's seconds."""
from benchmark.metrics.loop_intake_pct import share


def read(ctx):
    return share(ctx, "other")
