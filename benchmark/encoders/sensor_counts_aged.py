"""Traffic for a fleet of raw 16-bit sensors whose histories differ in
age: ``sensor_counts``' values, fleet and wire, with each stream's OWN
pre-fill length.

A stream's history is drawn log-uniform over ``prefill.min_samples`` ..
``prefill.max_samples`` readings, stratified: stream i of the seed's
permutation takes the log-uniform's quantile (i + 1/2) / streams, rounded
to whole ``prefill.samples`` messages, so every seed's fleet holds the
same total and only which device is how old moves with the seed (devices
commissioned, replaced or reset at different times). The history goes in
as bulk messages of ``prefill.samples`` readings — round r carries
message r of every stream still that long, in a seeded order — and the
timed readings continue each stream's own series from its own length.
"""

from __future__ import annotations

from dataclasses import dataclass

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
from benchmark.encoders.sensor_counts import series


@dataclass
class AgedTraffic(Traffic):
    lengths: np.ndarray = None    # int64 [streams] readings of pre-fill


def lengths(seed: int, n_streams: int, lo: int, hi: int,
            step: int) -> np.ndarray:
    """int64 [n_streams]: each stream's pre-fill readings (see above)."""
    q = (np.arange(n_streams) + 0.5) / n_streams
    whole = np.exp(np.log(lo) + q * np.log(hi / lo))
    whole = np.maximum(np.rint(whole / step) * step, step).astype(np.int64)
    return whole[np.random.default_rng([seed, 0xA6ED]).permutation(n_streams)]


def build(params: dict, config: dict, seed: int, seconds: float,
          plan) -> AgedTraffic:
    n_tenants, devices = config["tenants"], config["devices_per_tenant"]
    tenants = [tenant_token(i) for i in range(n_tenants)]
    n_streams = n_tenants * devices
    name = params.get("measurement", "count")
    pre = params["prefill"]
    size = pre["samples"]
    stream, due_ms = plan(params, n_streams, seed, seconds)
    due = np.unique(stream)
    occ = _occurrence(stream)
    n = params["samples_per_message"]
    have = lengths(seed, n_streams, pre["min_samples"], pre["max_samples"],
                   size)
    values = series(seed, n_streams, int(have.max()) + (int(occ.max()) + 1) * n,
                    params["report_interval_s"])
    rounds = []
    for r in range(int(have.max()) // size):
        filled = np.flatnonzero(have > r * size)
        order = np.random.default_rng([seed, 0xF111, r]).permutation(filled)
        msgs = Messages(
            tenant=(order // devices).astype(np.int32),
            device=(order % devices).astype(np.int32),
            due_ms=np.full(len(order), r, np.int64),
            values=values[order, r * size:(r + 1) * size],
        )
        encode(msgs, tenants, name, EPOCH_MS - PREFILL_LEAD_MS)
        rounds.append(msgs)
    at = have[stream][:, None] + occ[:, None] * n + np.arange(n)[None, :]
    timed = Messages(
        tenant=(stream // devices).astype(np.int32),
        device=(stream % devices).astype(np.int32),
        due_ms=due_ms.astype(np.int64),
        values=values[stream[:, None], at],
    )
    encode(timed, tenants, name, EPOCH_MS)
    return AgedTraffic(tenants, devices, name, rounds, timed, params, due,
                       lengths=have)
