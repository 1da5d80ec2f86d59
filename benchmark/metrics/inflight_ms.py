"""Device queue: mean ms from a flush's dispatch call returning to its
scores' transfer landing (`tpu_inference.inflight`, per flush). With
several flushes queued on the device these intervals overlap: it is what an
event waits behind the flushes dispatched before its own."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.inflight")
    return 1000.0 * total / n if n else None
