"""Traffic for configurations that take the program's bulk binary
measurement message: a traffic file's parameters + a configuration's
fleet + ``--seed`` -> wire payloads, made before the window opens.

A traffic file (``benchmark/traffic/<mix>.json``) names this module under
``"encoder"`` and a generator ``kind`` (``benchmark/generators/<kind>.py``)
with its parameters. The kind decides WHEN messages leave (``plan`` gives
each its stream and due time, ``drive`` sends them during the window);
the fleet, the values, the bytes and the pre-fill are here, the same for
every kind. A configuration with another wire brings an encoder of its
own (``build`` with the same signature, a ``Traffic`` with the same
fields).

Values: each stream is a seeded sinusoid with noise, the temperature
profile of the program's own simulator (``sim/devices.py``: base 21,
amplitude 4, noise 0.15), sample k of stream s a pure function of
(seed, s, k) and the message it rides in.

Wire: the program's bulk binary message (``pipeline/decoders.py``
"binary format"), encoded here from its documented layout. ``event_ts``
carries the message's DUE TIME: ``EPOCH_MS + due_ms`` for a timed
message, so a scored batch tells the subscriber when each row was due;
pre-fill messages carry times before ``EPOCH_MS``.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field

import numpy as np

# a fixed epoch (2023-11-14T22:13:20Z) so that the same seed gives the
# same bytes; due time of a row = event_ts - EPOCH_MS, in ms from the
# window's start
EPOCH_MS = 1_700_000_000_000
PREFILL_LEAD_MS = 3_600_000  # pre-fill rows are stamped an hour "earlier"

_MAGIC, _VERSION, _MSG_BULK = 0x5754, 1, 5


def device_token(i: int) -> str:
    """Token of fleet device ``i`` (DeviceManagement.bootstrap_fleet)."""
    return f"dev-{i:05d}"


def tenant_token(i: int) -> str:
    return f"t{i:03d}"


@dataclass
class Messages:
    """Wire messages in send order. ``values[m]`` are the f32 samples of
    message m, for stream (tenant[m], device[m])."""

    tenant: np.ndarray            # int32 [M] tenant index
    device: np.ndarray            # int32 [M] device index within tenant
    due_ms: np.ndarray            # int64 [M] due time from window start
    values: np.ndarray            # f32 [M, n]
    payloads: list = field(default_factory=list)   # bytes per message
    topics: list = field(default_factory=list)     # broker topic per message

    @property
    def count(self) -> int:
        return int(self.tenant.shape[0])

    @property
    def samples(self) -> int:
        return int(self.values.shape[1])


def _stream_values(seed: int, tag: int, stream: np.ndarray,
                   first_sample: np.ndarray, n: int,
                   n_streams: int) -> np.ndarray:
    """f32[M, n]: samples first_sample[m] .. +n-1 of stream[m]."""
    phase = np.random.default_rng([seed, 0x5EED, 1]).uniform(
        0, 2 * np.pi, n_streams)
    noise = np.random.default_rng([seed, 0x5EED, 2, tag]).standard_normal(
        (stream.shape[0], n))
    k = first_sample[:, None] + np.arange(n)[None, :]
    v = 21.0 + 4.0 * np.sin(2 * np.pi * k / 48.0 + phase[stream][:, None])
    return (v + 0.15 * noise).astype(np.float32)


def _occurrence(stream: np.ndarray) -> np.ndarray:
    """k-th message of its stream, for each message in send order."""
    order = np.argsort(stream, kind="stable")
    s = stream[order]
    start = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    run = np.repeat(start, np.diff(np.r_[start, len(s)]))
    occ = np.empty(len(s), np.int64)
    occ[order] = np.arange(len(s)) - run
    return occ


def encode(msgs: Messages, tenants: list, name: str, base_ms: int) -> None:
    """Fill ``msgs.payloads`` / ``msgs.topics`` (bulk binary, stride 0:
    every sample of a message shares its due time)."""
    nm = name.encode()
    heads: dict = {}
    pack = struct.pack
    n = msgs.samples
    raw = msgs.values.astype("<f4").tobytes()
    stride = 4 * n
    payloads, topics = [], []
    for m in range(msgs.count):
        key = (int(msgs.tenant[m]), int(msgs.device[m]))
        head = heads.get(key)
        if head is None:
            tok = device_token(key[1]).encode()
            head = heads[key] = (
                pack("<HBB", _MAGIC, _VERSION, _MSG_BULK)
                + pack("<B", len(tok)) + tok + pack("<B", len(nm)) + nm,
                f"sitewhere/{tenants[key[0]]}/input/{device_token(key[1])}",
            )
        payloads.append(
            head[0] + pack("<IQI", n, base_ms + int(msgs.due_ms[m]), 0)
            + raw[m * stride:(m + 1) * stride])
        topics.append(head[1])
    msgs.payloads, msgs.topics = payloads, topics


@dataclass
class Traffic:
    tenants: list                 # tenant tokens
    devices: int                  # registered devices per tenant
    name: str                     # measurement name
    prefill: list                 # [Messages] one per lockstep round
    timed: Messages
    params: dict                  # the traffic file
    due: np.ndarray = None        # streams that send in the window, sorted

    @property
    def prefill_samples(self) -> int:
        return sum(r.samples for r in self.prefill)


def build(params: dict, config: dict, seed: int, seconds: float,
          plan) -> Traffic:
    """``plan(params, n_streams, seed, seconds) -> (stream, due_ms)`` is
    the generator kind's; streams are numbered tenant * devices + device."""
    n_tenants, devices = config["tenants"], config["devices_per_tenant"]
    tenants = [tenant_token(i) for i in range(n_tenants)]
    n_streams = n_tenants * devices
    name = params.get("measurement", "temperature")
    pre = params["prefill"]
    stream, due_ms = plan(params, n_streams, seed, seconds)
    due = np.unique(stream)
    # whose windows are filled before the clock starts: the streams that
    # send in the window ("due": only their history serves a request), or
    # the whole registered fleet ("all")
    filled = due if pre["streams"] == "due" else np.arange(
        n_streams, dtype=np.int64)
    rounds = []
    for r in range(pre["messages"]):
        # another stream order each round, so that a lane never sees its
        # streams in id order
        order = np.random.default_rng([seed, 0xF111, r]).permutation(filled)
        first = np.full(len(order), r * pre["samples"], np.int64)
        msgs = Messages(
            tenant=(order // devices).astype(np.int32),
            device=(order % devices).astype(np.int32),
            due_ms=np.full(len(order), r, np.int64),
            values=_stream_values(seed, r, order, first, pre["samples"],
                                  n_streams),
        )
        encode(msgs, tenants, name, EPOCH_MS - PREFILL_LEAD_MS)
        rounds.append(msgs)
    n = params["samples_per_message"]
    first = pre["messages"] * pre["samples"] + _occurrence(stream) * n
    timed = Messages(
        tenant=(stream // devices).astype(np.int32),
        device=(stream % devices).astype(np.int32),
        due_ms=due_ms.astype(np.int64),
        values=_stream_values(seed, 1000, stream, first, n, n_streams),
    )
    encode(timed, tenants, name, EPOCH_MS)
    return Traffic(tenants, devices, name, rounds, timed, params, due)
