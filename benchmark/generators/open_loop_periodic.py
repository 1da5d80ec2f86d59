"""Open loop, periodic: every registered device reports one message every
``report_interval_s``, whether or not earlier ones have come out.

Parameters (traffic file): ``report_interval_s``, ``jitter_ms``,
``samples_per_message``. A device's phase is drawn from the seed
uniformly over the interval and each of its reports is jittered by up to
+-``jitter_ms`` (a sensor's clock is its own), so the offered rate is
devices / interval with no knob of its own, and a window of ``seconds``
offers exactly devices x floor(seconds / interval) messages: a report
whose jitter would put it outside the window is clipped to its edge. Due
times are whole milliseconds (the wire's resolution); the latency clock
runs from the due time. The sender is ``open_loop_poisson``'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators.open_loop_poisson import drive  # noqa: F401


def plan(params: dict, n_streams: int, seed: int, seconds: float):
    interval_ms = int(round(1000 * params["report_interval_s"]))
    reports = int(seconds * 1000) // interval_ms
    rng = np.random.default_rng([seed, 0x9E71])
    phase = rng.integers(0, interval_ms, n_streams)
    jitter = rng.integers(-params["jitter_ms"], params["jitter_ms"] + 1,
                          (n_streams, reports))
    due = phase[:, None] + interval_ms * np.arange(reports)[None, :] + jitter
    due = np.clip(due, 0, int(seconds * 1000) - 1).reshape(-1)
    stream = np.repeat(np.arange(n_streams), reports)
    # a stream's reports stay in its own order (jitter < interval / 2)
    order = np.argsort(due, kind="stable")
    return stream[order], due[order]
