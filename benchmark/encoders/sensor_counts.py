"""Traffic for configurations whose readings are raw 16-bit sensor
counts: the fleet, the wire bytes and the pre-fill are
``bulk_binary``'s (same message, same ``Traffic``), the values are here.

Values: each stream is a bounded random walk with a daily-shaped drift
over the 16-bit range — start uniform over the middle three quarters, a
sinusoid of one day's period sampled once an interval, steps of a few
hundred counts, reflected at 0 and 65,535 — rounded to whole counts, so
the f32 wire carries every one exactly and ids spread over the whole
range. Sample k of stream s is a pure function of (seed, s, k).
"""

from __future__ import annotations

import numpy as np

from benchmark.encoders.bulk_binary import (
    EPOCH_MS,
    PREFILL_LEAD_MS,
    Messages,
    Traffic,
    _occurrence,
    encode,
    tenant_token,
)

TOP = 65535


def series(seed: int, n_streams: int, n_samples: int,
           interval_s: float) -> np.ndarray:
    """f32[n_streams, n_samples]: whole counts in [0, 65535]."""
    rng = np.random.default_rng([seed, 0xC0DE])
    start = rng.uniform(0.125 * TOP, 0.875 * TOP, (n_streams, 1))
    phase = rng.uniform(0, 2 * np.pi, (n_streams, 1))
    k = np.arange(n_samples)[None, :]
    drift = 6000.0 * np.sin(2 * np.pi * k * interval_s / 86400.0 + phase)
    walk = np.cumsum(rng.normal(0.0, 300.0, (n_streams, n_samples)), axis=1)
    v = np.abs(start + drift - drift[:, :1] + walk)
    v = TOP - np.abs(TOP - np.mod(v, 2 * TOP))
    return np.rint(v).astype(np.float32)


def build(params: dict, config: dict, seed: int, seconds: float,
          plan) -> Traffic:
    n_tenants, devices = config["tenants"], config["devices_per_tenant"]
    tenants = [tenant_token(i) for i in range(n_tenants)]
    n_streams = n_tenants * devices
    name = params.get("measurement", "count")
    pre = params["prefill"]
    stream, due_ms = plan(params, n_streams, seed, seconds)
    due = np.unique(stream)
    occ = _occurrence(stream)
    n = params["samples_per_message"]
    n_pre = pre["messages"] * pre["samples"]
    values = series(seed, n_streams, n_pre + (int(occ.max()) + 1) * n,
                    params["report_interval_s"])
    filled = due if pre["streams"] == "due" else np.arange(
        n_streams, dtype=np.int64)
    rounds = []
    for r in range(pre["messages"]):
        order = np.random.default_rng([seed, 0xF111, r]).permutation(filled)
        msgs = Messages(
            tenant=(order // devices).astype(np.int32),
            device=(order % devices).astype(np.int32),
            due_ms=np.full(len(order), r, np.int64),
            values=values[order, r * pre["samples"]:(r + 1) * pre["samples"]],
        )
        encode(msgs, tenants, name, EPOCH_MS - PREFILL_LEAD_MS)
        rounds.append(msgs)
    at = n_pre + occ[:, None] * n + np.arange(n)[None, :]
    timed = Messages(
        tenant=(stream // devices).astype(np.int32),
        device=(stream % devices).astype(np.int32),
        due_ms=due_ms.astype(np.int64),
        values=values[stream[:, None], at],
    )
    encode(timed, tenants, name, EPOCH_MS)
    return Traffic(tenants, devices, name, rounds, timed, params, due)
