"""Resolve -> persist -> rules -> outbound: mean ms from a batch's
publish on scored-events to the end of the outbound handler for it —
persist and the slowest connector (`pipeline.egress`, per batch)."""


def read(ctx):
    n, total = ctx["window"].hist("pipeline.egress")
    return 1000.0 * total / n if n else None
