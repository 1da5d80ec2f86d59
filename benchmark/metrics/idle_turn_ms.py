"""Lanes -> staging -> h2d: mean ms a serve flush's device sat idle on
the loop's turn — from the later of the slice's previous landing and the
moment a flush fell due, to the permit asked (`tpu_inference.idle_turn`;
0 where a serve flush was still in flight)."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.idle_turn")
    return 1000.0 * total / n if n else None
