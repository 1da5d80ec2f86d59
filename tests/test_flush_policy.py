"""The flush policy (ISSUE 35): a due flush waits for the one in flight
unless a lane has filled its smallest bucket.

``_scoring_loop`` flushes a (family, slice) when a lane is full or the
collect deadline is reached — and then ``SliceRuntime.held`` decides whether
the flush leaves now. These tests drive the rule through a live
instance with a gated scorer (a score plane whose materialization, and
so its landing, waits on an event): small batches ride out together
when the flush in flight lands; a lane at the smallest bucket pipelines
``max_inflight`` deep as before; the hold lifts on the landing (not the
publish), on a force-resolved flush, on degraded pass-through and at
teardown; slices hold independently; per-tenant order survives; the
train lane neither holds a serve flush nor jumps a held one.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.pipeline.slices import _PendingFlush, _empty_taken
from sitewhere_tpu.runtime.config import (
    FaultTolerancePolicy,
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
    TrainingConfig,
)
from sitewhere_tpu.runtime.faultplan import DeviceFault, DeviceFaultPlan

MB = MicroBatchConfig(max_batch=64, deadline_ms=1.0, buckets=(32, 64), window=8)
SMALL = 8                   # rows: far under the smallest bucket
BUCKET = MB.buckets[0]      # rows: a lane at the smallest bucket
KEY = ("lstm_ad", 0)


class GatedScores:
    """A score plane whose materialization blocks on a gate (no
    ``is_ready``/``copy_to_host_async``: the service's fallback path, so
    the flush has landed exactly when the gate has opened)."""

    def __init__(self, inner, gate: threading.Event) -> None:
        self.inner, self.gate = inner, gate

    def __getitem__(self, idx):
        return GatedScores(self.inner[idx], self.gate)

    def __array__(self, dtype=None):
        if not self.gate.wait(timeout=60.0):
            raise RuntimeError("gate never opened")
        a = np.asarray(self.inner)
        return a.astype(dtype) if dtype is not None else a


def _gate_each(scorer) -> list:
    """Every dispatch of ``scorer`` from now on lands when its own gate
    (appended to the returned list at dispatch) opens."""
    gates: list = []
    orig = scorer.step_counts

    def step(i, v, c):
        gate = threading.Event()
        gates.append(gate)
        return GatedScores(orig(i, v, c), gate)

    scorer.step_counts = step
    return gates


async def _wait_for(cond, timeout_s=20.0, interval=0.01) -> bool:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() >= deadline:
            return False
        await asyncio.sleep(interval)
    return True


async def _instance(tenants=("acme",), tenant_axis=1, slots_per_shard=4,
                    max_inflight=None, mb=MB, **tenant_kw):
    kw = {} if max_inflight is None else {"inference_max_inflight": max_inflight}
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="fp",
        mesh=MeshConfig(tenant_axis=tenant_axis, data_axis=1,
                        slots_per_shard=slots_per_shard),
        **kw,
    ))
    await inst.start()
    for tok in tenants:
        await inst.tenant_management.create_tenant(
            tok, template="iot-temperature", microbatch=mb,
            model_config={"hidden": 8}, max_streams=64, **tenant_kw,
        )
    await inst.drain_tenant_updates()
    assert await _wait_for(lambda: all(t in inst.tenants for t in tenants))
    fleets = {
        tok: [d.token for d in
              inst.tenants[tok].device_management.bootstrap_fleet(4)]
        for tok in tenants
    }
    await asyncio.get_running_loop().run_in_executor(
        None, inst.inference.prewarm)
    return inst, fleets


def _batch(tenant: str, toks, n: int, base: float) -> MeasurementBatch:
    return MeasurementBatch.from_columns(
        tenant, [toks[i % len(toks)] for i in range(n)],
        ["temperature"] * n, [base + float(i) for i in range(n)], [0.0] * n,
    )


async def _publish(inst, tenant: str, toks, n: int, base: float) -> None:
    await inst.bus.publish(
        inst.bus.naming.inbound_events(tenant), _batch(tenant, toks, n, base))


def _scored_consumer(inst, tenant: str):
    topic = inst.bus.naming.scored_events(tenant)
    inst.bus.subscribe(topic, "flush-policy-test")
    got: list = []

    async def drained(n: int, timeout_s=20.0) -> list:
        deadline = time.monotonic() + timeout_s
        while len(got) < n and time.monotonic() < deadline:
            got.extend(await inst.bus.consume(
                topic, "flush-policy-test", 64, timeout_s=0))
            await asyncio.sleep(0.01)
        return got

    return drained


def _count(inst, name: str) -> float:
    return inst.metrics.counter(f"tpu_inference.{name}").value


def _reap_len(svc, key=KEY) -> int:
    s = svc._slices.get(key)   # None before its birth and after the stop
    return len(s.reap) if s is not None else 0


def _lane_rows(svc, key=KEY) -> int:
    s = svc._slices.get(key)
    return sum(l.count for l in s.lanes.values()) if s is not None else 0


def _open(gates) -> None:
    for g in gates:
        g.set()


# ------------------------------------------------ the hold, and its exit
async def test_small_batches_wait_for_the_flush_in_flight_and_leave_together():
    inst, fleets = await _instance()
    svc, toks = inst.inference, fleets["acme"]
    gates = _gate_each(svc.scorers["lstm_ad"])
    try:
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc) == 1)
        await asyncio.sleep(0.25)
        await _publish(inst, "acme", toks, SMALL, 200.0)
        await asyncio.sleep(0.05)
        await _publish(inst, "acme", toks, SMALL, 300.0)
        # both are due (1 ms) and stay on the lanes: one flush in flight
        assert await _wait_for(lambda: _count(inst, "flush_held") > 0)
        await asyncio.sleep(0.15)
        assert _count(inst, "flushes") == 1 and _reap_len(svc) == 1
        assert _lane_rows(svc) == 2 * SMALL
        gates[0].set()      # flush 1 lands: the hold lifts
        assert await _wait_for(lambda: _count(inst, "flushes") == 2)
        # ... and the second flush carries everything that arrived meanwhile
        rec = list(svc.flush_records.values())[-1]
        assert rec["rows"] == 2 * SMALL and len(rec["seqs"]) == 2
        assert _lane_rows(svc) == 0
        gates[1].set()
        assert await _wait_for(lambda: _count(inst, "scored_total") >= 3 * SMALL)
        # never two in flight: nothing ahead of either flush at its dispatch
        assert _count(inst, "inflight_depth_sum") == 0
        assert _count(inst, "flush_pipelined") == 0
        assert _count(inst, "deliver_backpressure") == 0
        # the time held is lane wait: enqueue -> permit asked
        assert rec["t_asked"] - rec["t_oldest"] >= 0.15
        assert inst.metrics.histogram("tpu_inference.lane_wait").count == 3
        assert inst.metrics.histogram("tpu_inference.acquire_wait").mean < 0.02
    finally:
        _open(gates)
        await inst.terminate()


async def test_lane_at_smallest_bucket_pipelines_up_to_max_inflight():
    """From the smallest bucket up the dispatch sequence is the old one:
    each due flush joins the ones in flight, ``max_inflight`` deep, the
    next waits for a permit, and resolution stays FIFO."""
    inst, fleets = await _instance(max_inflight=3)
    svc, toks = inst.inference, fleets["acme"]
    assert svc.max_inflight == 3
    drained = _scored_consumer(inst, "acme")
    gates = _gate_each(svc.scorers["lstm_ad"])
    try:
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc) == 1)
        for depth, base in ((2, 200.0), (3, 300.0)):
            await _publish(inst, "acme", toks, BUCKET, base)
            assert await _wait_for(lambda: _reap_len(svc) == depth), (
                "a lane at the smallest bucket waited for the flush in flight")
        assert _count(inst, "flush_pipelined") == 2
        assert _count(inst, "inflight_depth_sum") == 1 + 2
        assert _count(inst, "flush_held") == 0
        # the fourth asks for a permit that is not there
        await _publish(inst, "acme", toks, BUCKET, 400.0)
        assert await _wait_for(
            lambda: _count(inst, "deliver_backpressure") >= 1)
        await asyncio.sleep(0.1)
        assert _reap_len(svc) == 3 and len(gates) == 3
        # landing out of order resolves nothing past the head
        gates[2].set()
        gates[1].set()
        await asyncio.sleep(0.2)
        assert not await drained(1, timeout_s=0.1)
        gates[0].set()
        assert await _wait_for(lambda: len(gates) == 4)
        gates[3].set()
        got = await drained(4)
        assert [float(b.values[0]) for b in got] == [100.0, 200.0, 300.0, 400.0]
        assert all(np.isfinite(np.asarray(b.scores)).all() for b in got)
    finally:
        _open(gates)
        await inst.terminate()


async def test_hold_lifts_on_the_landing_not_on_the_publish():
    """The device is free from the landing on: a flush whose resolve is
    stuck publishing holds a queue slot, not the next dispatch."""
    inst, fleets = await _instance()
    svc, toks = inst.inference, fleets["acme"]
    svc.deliver_drain_timeout_s = 0.5
    topic = inst.bus.naming.scored_events("acme")
    try:
        # wedge the scored topic: a pinned group + retention 1 makes the
        # resolve task's publish backpressure until the group leaves
        inst.bus.subscribe(topic, "stall")
        tp = inst.bus.topic(topic)
        tp.retention = 1
        await inst.bus.publish(topic, _batch("acme", toks, 1, 0.0))
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(
            lambda: svc._slices[KEY].resolving is not None
            and _reap_len(svc) == 1)
        head = svc._slices[KEY].reap[0]
        assert head.landed() and not head.resolved
        await _publish(inst, "acme", toks, SMALL, 200.0)
        assert await _wait_for(lambda: _count(inst, "flushes") == 2), (
            "a landed flush still held the next one")
        assert svc._slices[KEY].reap[0] is head and not head.resolved
        assert _count(inst, "inflight_depth_sum") == 0
        assert _count(inst, "flush_pipelined") == 0
        tp.retention = 65536
        inst.bus.unsubscribe(topic, "stall")
        assert await _wait_for(
            lambda: svc._slices[KEY].resolving is None
            and not _reap_len(svc))
        assert _count(inst, "scored_total") >= 2 * SMALL
    finally:
        inst.bus.unsubscribe(topic, "stall")
        await inst.terminate()


async def test_hold_lifts_when_the_flush_in_flight_is_force_resolved():
    """A flush that never lands blows its deadline and leaves the reap
    queue force-resolved: the rows it held back are not stranded."""
    ft = FaultTolerancePolicy(
        flush_deadline_ms=300.0, flush_deadline_x=8.0, poison_retry=False,
        probe_interval_s=30.0,
    )
    inst, fleets = await _instance(fault_tolerance=ft)
    svc, toks = inst.inference, fleets["acme"]
    drained = _scored_consumer(inst, "acme")
    plan = svc.faultplan = DeviceFaultPlan(DeviceFault(
        "hang_dispatch", families=("lstm_ad",), lanes=("serve",), first_n=1))
    try:
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc) == 1)
        await _publish(inst, "acme", toks, SMALL, 200.0)
        assert await _wait_for(lambda: _count(inst, "flush_held") > 0)
        assert _lane_rows(svc) == SMALL and _count(inst, "flushes") == 1
        # deadline -> force-resolve (unscored) + quarantine: the tenant
        # fails over, the rows held back move behind the slice-move
        # fence and are scored on the new slice, after the flush they
        # waited for
        got = await drained(2)
        assert [float(b.values[0]) for b in got] == [100.0, 200.0]
        assert np.isnan(np.asarray(got[0].scores)).all()
        assert np.isfinite(np.asarray(got[1].scores)).all()
        assert inst.metrics.counter(
            "tpu_flush_timeout_total", family="lstm_ad", slice="0"
        ).value == 1
        assert svc.engines["acme"].placement.shard != 0
        assert "acme" not in svc._fences
        assert not any(_lane_rows(svc, k) for k in svc._slices)
        assert not any(_reap_len(svc, k) for k in svc._slices)
        assert not svc._batches
    finally:
        plan.clear()
        await inst.terminate()


async def _park(svc, key) -> None:
    svc._parked.add(key[0])


async def _open_breaker(svc, key) -> None:
    svc.breakers[key].trip()


async def _quarantine(svc, key) -> None:
    # the fleet fills every slot, so the tenant cannot fail over and
    # degrades to pass-through on the quarantined slice
    await svc._quarantine_slice(svc._slices[key], reason="test")
    assert svc._slices[key].quarantine is not None
    assert key[0] not in svc._parked


@pytest.mark.parametrize("degrade", [_park, _open_breaker, _quarantine])
async def test_hold_does_not_delay_degraded_passthrough(degrade):
    """Where ``_flush_slice`` would not dispatch (family parked, breaker
    open, slice quarantined) there is nothing to wait for: due rows pass
    through unscored although a flush is still in flight."""
    inst, fleets = await _instance(
        tenants=("acme", "bravo"), tenant_axis=2, slots_per_shard=1)
    svc, toks = inst.inference, fleets["acme"]
    key = ("lstm_ad", svc.engines["acme"].placement.shard)
    drained = _scored_consumer(inst, "acme")
    gates = _gate_each(svc.scorers[key])
    try:
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc, key) == 1)
        await degrade(svc, key)
        await _publish(inst, "acme", toks, SMALL, 200.0)
        got = await drained(1)
        assert len(got) == 1 and float(got[0].values[0]) == 200.0
        assert np.isnan(np.asarray(got[0].scores)).all()
        assert not gates[0].is_set() and _lane_rows(svc, key) == 0
        _open(gates)
        got = await drained(2)
        assert float(got[1].values[0]) == 100.0
        assert np.isfinite(np.asarray(got[1].scores)).all()
        assert not svc._batches
    finally:
        _open(gates)
        await inst.terminate()


async def test_teardown_strands_neither_the_flush_in_flight_nor_the_held_rows():
    inst, fleets = await _instance()
    svc, toks = inst.inference, fleets["acme"]
    svc.deliver_drain_timeout_s = 0.3
    gates = _gate_each(svc.scorers["lstm_ad"])
    try:
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc) == 1)
        await _publish(inst, "acme", toks, SMALL, 200.0)
        assert await _wait_for(lambda: _count(inst, "flush_held") > 0)
        assert _lane_rows(svc) == SMALL and _count(inst, "scored_total") == 0
    finally:
        await inst.terminate()
        _open(gates)   # free the executor thread
    assert _count(inst, "scored_total") >= 2 * SMALL
    # the stop resolved every batch, and no slice (its reap queue, its
    # lanes) outlives the service
    assert not svc._batches and not svc._slices


async def test_two_slices_hold_independently():
    inst, fleets = await _instance(
        tenants=("alfa", "bravo"), tenant_axis=2, slots_per_shard=1)
    svc = inst.inference
    sa = svc.engines["alfa"].placement.shard
    sb = svc.engines["bravo"].placement.shard
    assert sa != sb
    drained_b = _scored_consumer(inst, "bravo")
    gates = _gate_each(svc.scorers[("lstm_ad", sa)])
    try:
        await _publish(inst, "alfa", fleets["alfa"], SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc, ("lstm_ad", sa)) == 1)
        await _publish(inst, "alfa", fleets["alfa"], SMALL, 200.0)
        assert await _wait_for(lambda: _count(inst, "flush_held") > 0)
        # the other slice flushes and delivers as if alone
        for base in (300.0, 400.0):
            await _publish(inst, "bravo", fleets["bravo"], SMALL, base)
            await asyncio.sleep(0.1)
        got = await drained_b(2)
        assert [float(b.values[0]) for b in got] == [300.0, 400.0]
        assert all(np.isfinite(np.asarray(b.scores)).all() for b in got)
        assert _lane_rows(svc, ("lstm_ad", sa)) == SMALL
        assert _reap_len(svc, ("lstm_ad", sa)) == 1
        assert _count(inst, "flushes") == 3
        _open(gates)
        assert await _wait_for(lambda: len(gates) == 2)
        _open(gates)
        assert await _wait_for(
            lambda: _count(inst, "scored_total") >= 4 * SMALL)
        assert _count(inst, "flush_pipelined") == 0
    finally:
        _open(gates)
        await inst.terminate()


async def test_per_tenant_order_is_preserved_across_a_held_flush():
    inst, fleets = await _instance()
    svc, toks = inst.inference, fleets["acme"]
    drained = _scored_consumer(inst, "acme")
    gates = _gate_each(svc.scorers["lstm_ad"])
    try:
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc) == 1)
        for base in (200.0, 300.0, 400.0):
            await _publish(inst, "acme", toks, SMALL // 2, base)
            await asyncio.sleep(0.03)
        assert not await drained(1, timeout_s=0.1)
        gates[0].set()
        assert await _wait_for(lambda: len(gates) == 2)
        gates[1].set()
        got = await drained(4)
        assert [float(b.values[0]) for b in got] == [100.0, 200.0, 300.0, 400.0]
        assert all(np.isfinite(np.asarray(b.scores)).all() for b in got)
        assert _count(inst, "flushes") == 2
    finally:
        _open(gates)
        await inst.terminate()


# ------------------------------------------- the plane a flush takes
# a smallest bucket above the scorer's narrow plane of 128 lanes
WIDE = MicroBatchConfig(max_batch=1024, deadline_ms=1.0, buckets=(1024,),
                        window=8)
NARROW = 128


async def test_a_flush_takes_the_narrow_plane_while_every_lane_fits_it():
    """The plane follows the fullest lane: up to 128 rows the narrow
    plane, from 129 the smallest bucket's — every plane compiled by the
    prewarm, so no flush compiles."""
    inst, fleets = await _instance(mb=WIDE)
    svc, toks = inst.inference, fleets["acme"]
    t = svc.scorers["lstm_ad"].n_slots
    try:
        scored = 0
        for n, lanes in ((SMALL, NARROW), (NARROW, NARROW), (NARROW + 1, 1024),
                         (200, 1024)):
            flushes = _count(inst, "flushes")
            await _publish(inst, "acme", toks, n, 100.0)
            scored += n
            assert await _wait_for(
                lambda: _count(inst, "scored_total") >= scored)
            assert _count(inst, "flushes") == flushes + 1
            rec = list(svc.flush_records.values())[-1]
            assert (rec["rows"], rec["bucket"], rec["plane"]) == (
                n, lanes, t * lanes)
        assert _count(inst, "flush_narrow") == 2
        assert _count(inst, "plane_lanes") == t * (2 * NARROW + 2 * 1024)
        assert _count(inst, "flush_rows") == scored
        assert _count(inst, "compiles") == 0
    finally:
        await inst.terminate()


async def test_a_lane_over_the_narrow_plane_is_still_held_under_the_bucket():
    """The hold reads the smallest bucket, not the plane: 200 rows on a
    lane under a 1,024 bucket wait for the flush in flight, then leave
    together at the bucket's plane."""
    inst, fleets = await _instance(mb=WIDE)
    svc, toks = inst.inference, fleets["acme"]
    gates = _gate_each(svc.scorers["lstm_ad"])
    try:
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc) == 1)
        await _publish(inst, "acme", toks, 200, 200.0)
        assert await _wait_for(lambda: _count(inst, "flush_held") > 0)
        await asyncio.sleep(0.1)
        assert _count(inst, "flushes") == 1 and _lane_rows(svc) == 200
        gates[0].set()
        assert await _wait_for(lambda: _count(inst, "flushes") == 2)
        rec = list(svc.flush_records.values())[-1]
        assert (rec["rows"], rec["bucket"]) == (200, 1024)
        gates[1].set()
        assert await _wait_for(
            lambda: _count(inst, "scored_total") >= SMALL + 200)
        assert _count(inst, "flush_narrow") == 1
        assert _count(inst, "flush_pipelined") == 0
    finally:
        _open(gates)
        await inst.terminate()


async def test_a_narrow_flush_after_the_prewarm_compiles_nothing():
    """``prewarm()`` compiled the narrow plane's step, gather rungs and
    single-slot slice: a live narrow flush adds no compile."""
    inst, fleets = await _instance(mb=WIDE)
    svc, toks = inst.inference, fleets["acme"]
    s = svc._slices[KEY]
    assert {NARROW, 1024} <= s.seen_shapes
    try:
        for r in range(3):
            await _publish(inst, "acme", toks, SMALL, 10.0 * r)
            assert await _wait_for(
                lambda: _count(inst, "scored_total") >= SMALL * (r + 1))
        assert _count(inst, "flush_narrow") == _count(inst, "flushes") > 0
        assert _count(inst, "compiles") == 0
        assert inst.metrics.counter(
            "tpu_inference_compiles", family="lstm_ad",
            bucket=str(NARROW)).value == 0
    finally:
        await inst.terminate()


# ------------------------------------------------------- the train lane
async def test_train_step_in_flight_does_not_hold_a_serve_flush():
    inst, fleets = await _instance()
    svc, toks = inst.inference, fleets["acme"]
    gate = threading.Event()
    try:
        # a train-lane step in flight on the slice, as _dispatch_train
        # leaves one: its own permit, the reap FIFO, lane="train"
        await svc._slices[KEY].permits.acquire()
        pf = _PendingFlush(
            "lstm_ad", GatedScores(np.zeros((4,), np.float32), gate),
            _empty_taken(), 0, False, 0, 0, lane="train",
        )
        pf.ensure_host_future(asyncio.get_running_loop(), svc._deliver_pool)
        svc._reap_enqueue(pf)
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _count(inst, "flushes") == 1), (
            "a train step in flight held a serve flush")
        assert not pf.resolved
        assert _count(inst, "flush_held") == 0
        # it does stand ahead on the device, and it is no serve flush
        assert _count(inst, "inflight_depth_sum") == 1
        assert _count(inst, "flush_pipelined") == 0
        gate.set()
        assert await _wait_for(lambda: _count(inst, "scored_total") >= SMALL)
        assert await _wait_for(lambda: not _reap_len(svc))
    finally:
        gate.set()
        await inst.terminate()


async def test_train_lane_does_not_jump_a_held_serve_flush():
    inst, fleets = await _instance(training=TrainingConfig(
        enabled=True, every_n_flushes=1, lr=5e-3))
    svc, toks = inst.inference, fleets["acme"]
    scorer = svc.scorers["lstm_ad"]
    assert scorer.train_lane
    steps = inst.metrics.counter("tpu_inference.train_steps")
    saturated = inst.metrics.counter(
        "tpu_train_skipped_total", family="lstm_ad", reason="saturated")
    gates: list = []
    try:
        # warm: serve flushes mature the cadence, the lane takes steps
        for r in range(4):
            await _publish(inst, "acme", toks, SMALL, 10.0 * r)
            await asyncio.sleep(0.05)
        assert await _wait_for(lambda: steps.value >= 1, timeout_s=60.0)
        assert await _wait_for(lambda: not _reap_len(svc))
        gates = _gate_each(scorer)
        await _publish(inst, "acme", toks, SMALL, 100.0)
        assert await _wait_for(lambda: _reap_len(svc) == 1)
        steps0, sat0 = steps.value, saturated.value
        await _publish(inst, "acme", toks, SMALL, 200.0)
        assert await _wait_for(lambda: _count(inst, "flush_held") > 0)
        # the cadence is mature (the gated flush ticked it), yet the lane
        # yields to the flush in flight — and so to the rows it holds back
        assert await _wait_for(lambda: saturated.value > sat0)
        assert steps.value == steps0
        flushes0 = _count(inst, "flushes")
        gates[0].set()
        assert await _wait_for(lambda: _count(inst, "flushes") == flushes0 + 1)
        # dispatch order is the flight recorder's ring order: the held
        # serve flush left before any train step did
        ring = inst.flightrec.describe()["rings"]["flush"]["lstm_ad"]["records"]
        held = [r for r in ring if r.get("lane") == "serve"][-1]
        before = ring[:ring.index(held)]
        assert before[-1].get("lane") == "serve", (
            "a train step was dispatched between the flush in flight and "
            "the one it held back")
        _open(gates)
        assert await _wait_for(
            lambda: _count(inst, "scored_total") >= 6 * SMALL)
        assert await _wait_for(lambda: steps.value > steps0)
    finally:
        _open(gates)
        await inst.terminate()
