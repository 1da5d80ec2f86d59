"""Open loop: messages leave on a schedule made from the seed, whether or
not earlier ones have come out.

Parameters (traffic file): ``rate_ev_s`` offered events/s,
``samples_per_message``. The schedule is a Poisson process conditioned on
its count: exactly round(rate x seconds / samples) messages, their due
times uniform over the window and sorted — so every seed offers the same
amount of work. Streams take the arrivals in turn, in a seeded order that
changes every round of the fleet: tenants and devices are equally likely,
and a device reports once a round (a sensor does not send five reports in
a tenth of a second, which a uniform draw would have some device do in
one run in a few hundred). Due times are whole milliseconds (the wire's
resolution).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np


def plan(params: dict, n_streams: int, seed: int, seconds: float):
    n = params["samples_per_message"]
    count = int(round(params["rate_ev_s"] * seconds / n))
    rng = np.random.default_rng([seed, 0x09E7])
    due_ms = np.sort(rng.integers(0, int(seconds * 1000), count))
    rounds = -(-count // n_streams)
    stream = np.concatenate([
        np.random.default_rng([seed, 0x09E7, r]).permutation(n_streams)
        for r in range(rounds)])[:count]
    return stream, due_ms


async def drive(run) -> None:
    """Send each message when it is due; a sender that falls behind sends
    everything overdue at once (lateness is recorded per message and the
    latency clock runs from the DUE time either way)."""
    msgs, publish, sent_at = run.timed, run.broker.publish, run.sent_at
    due_s = msgs.due_ms / 1000.0
    payloads, topics = msgs.payloads, msgs.topics
    clock = time.perf_counter
    t0, m, count = run.t0, 0, msgs.count
    while m < count:
        now = clock() - t0
        if due_s[m] > now:
            await asyncio.sleep(min(due_s[m] - now, 0.002))
            continue
        stop = min(int(np.searchsorted(due_s, now, "right")), m + 512)
        for i in range(m, stop):
            await publish(topics[i], payloads[i])
        sent_at[m:stop] = clock() - t0
        m = stop
        await asyncio.sleep(0)
