"""Resolve -> persist -> rules -> outbound: mean ms per flush to write
scores back into their batches and publish the completed ones."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.resolve")
    return 1000.0 * total / n if n else None
