"""State store: MB of stream state a flush's programs read and wrote
(the step's counters: every leaf of each advanced stream read, every
leaf but the rings written whole, the rings one position a token)."""
from benchmark.metrics import _stream_trace


def read(ctx):
    flushes = ctx["window"].count("tpu_inference.flushes")
    moved = (_stream_trace.counter(ctx, "state_read_bytes")
             + _stream_trace.counter(ctx, "state_written_bytes"))
    return moved / flushes / 1e6 if flushes and moved else None
