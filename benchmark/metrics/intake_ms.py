"""Intake + inbound: mean ms from a message's delivery to its event
source (the receiver's queue) to its batch's lane enqueue — receiver queue,
decode, inbound and their bus waits (`pipeline.intake`, per batch)."""


def read(ctx):
    n, total = ctx["window"].hist("pipeline.intake")
    return 1000.0 * total / n if n else None
