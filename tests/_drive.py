"""A paced drive for the tests that assert a training cadence."""

import asyncio
import time


async def drive_rounds(inst, sim, cond, rounds=0, each=None, timeout_s=90.0):
    """Publish at least ``rounds`` simulator rounds, and on until
    ``cond()``, one at a time: each round is scored and resolved before
    the next is sent, and ``each()`` runs after it. Training rides the
    idle gaps of the in-flight window (a train-lane step only ever
    enters an EMPTY one) and a slot matures by flushes, so rounds sent
    back to back — a fixed count at a fixed pace — train only as often as
    the host outruns the traffic: under six test workers it does not,
    and the lane reads ``saturated`` all the way."""
    scored = inst.metrics.counter("tpu_inference.scored_total")
    t_end = time.monotonic() + timeout_s
    r = 0
    while (r < rounds or not cond()) and time.monotonic() < t_end:
        await sim.publish_round(float(r) * 0.5)
        r += 1
        while scored.value < sim.sent and time.monotonic() < t_end:
            await asyncio.sleep(0.002)
        await asyncio.sleep(0.005)   # a few idle passes for the trainer
        if each is not None:
            each()
    return bool(cond())
