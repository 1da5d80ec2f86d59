"""Auto-failover chaos tests: a fault-injected scorer recovers scoring
on a DIFFERENT mesh shard without losing events (SURVEY.md §5
"tenant-engine failover to a different mesh shard")."""

import asyncio

import numpy as np

from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
)
from sitewhere_tpu.services.event_store import EventQuery
from sitewhere_tpu.sim import DeviceSimulator, SimProfile


async def _instance():
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="fo",
        mesh=MeshConfig(tenant_axis=2, data_axis=1, slots_per_shard=2),
    ))
    await inst.start()
    await inst.tenant_management.create_tenant(
        "acme", template="iot-temperature",
        microbatch=MicroBatchConfig(
            max_batch=256, deadline_ms=1.0, buckets=(64, 256), window=16
        ),
        model_config={"hidden": 16},
        max_streams=256,
    )
    await inst.drain_tenant_updates()
    for _ in range(100):
        if "acme" in inst.tenants:
            break
        await asyncio.sleep(0.02)
    inst.tenants["acme"].device_management.bootstrap_fleet(6)
    return inst


async def test_scorer_faults_trigger_failover_without_losing_events():
    inst = await _instance()
    try:
        engine = inst.inference.engines["acme"]
        scorer = inst.inference.scorers["lstm_ad"]
        old_shard = engine.placement.shard
        sim = DeviceSimulator(
            inst.broker, SimProfile(n_devices=6, seed=4, samples_per_message=5),
            topic_pattern="sitewhere/input/{device}",
        )
        # healthy warm-up traffic
        for r in range(5):
            await sim.publish_round(float(r))
        scored = inst.metrics.counter("tpu_inference.scored_total")
        for _ in range(200):
            if scored.value >= sim.sent:
                break
            await asyncio.sleep(0.02)
        # chaos: the next flushes fail at the scorer
        scorer.fault_steps = inst.inference.failover_threshold
        for r in range(10):
            await sim.publish_round(10.0 + r)
            await asyncio.sleep(0.01)
        failovers = inst.metrics.counter("tpu_inference.failovers")
        for _ in range(300):
            if failovers.value >= 1:
                break
            await asyncio.sleep(0.02)
        assert failovers.value >= 1, "failover never triggered"
        assert engine.placement.shard != old_shard, "tenant stayed on shard"
        # scoring RESUMES on the new shard
        before = scored.value
        for r in range(5):
            await sim.publish_round(30.0 + r)
        for _ in range(300):
            if scored.value - before >= 5 * 6 * 5:
                break
            await asyncio.sleep(0.02)
        assert scored.value - before >= 5 * 6 * 5, "scoring did not resume"
        # NO event lost: everything sent is persisted exactly once (rows
        # caught in the faulted flushes persist unscored)
        persisted = inst.metrics.counter("event_management.persisted")
        for _ in range(300):
            if persisted.value >= sim.sent:
                break
            await asyncio.sleep(0.02)
        assert persisted.value >= sim.sent, (persisted.value, sim.sent)
        store = inst.tenants["acme"].event_store
        evs, total = store.list_measurements(EventQuery(page_size=100000))
        assert total == sim.sent
        assert len({e.id for e in evs}) == total
    finally:
        await inst.terminate()


async def test_failover_carries_trained_params():
    """A failover move carries the tenant's live params onto the NEW
    mesh slice's scorer and wipes the vacated slot — params follow the
    tenant across chips."""
    inst = await _instance()
    try:
        import jax

        engine = inst.inference.engines["acme"]
        old_p = engine.placement
        old_scorer = inst.inference.scorers[("lstm_ad", old_p.shard)]
        # perturb the tenant's params so the carry-over is observable
        marked = jax.tree_util.tree_map(
            lambda x: x + 0.75, old_scorer.slot_params(old_p.slot)
        )
        old_scorer.activate(old_p.slot, params=marked)
        ok = await inst.inference._failover_tenant(engine)
        assert ok
        new_p = engine.placement
        assert new_p.shard != old_p.shard
        new_scorer = inst.inference.scorers[("lstm_ad", new_p.shard)]
        assert new_scorer is not old_scorer
        got = new_scorer.slot_params(new_p.slot)
        for a, b in zip(
            jax.tree_util.tree_leaves(marked), jax.tree_util.tree_leaves(got)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5
            )
        # the vacated slot is wiped back to pristine
        base = old_scorer._base_params
        for a, b in zip(
            jax.tree_util.tree_leaves(old_scorer.slot_params(old_p.slot)),
            jax.tree_util.tree_leaves(base),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
    finally:
        await inst.terminate()


async def test_supervised_scoring_loop_restarts_after_crash():
    inst = await _instance()
    try:
        svc = inst.inference
        # poison one consume call → the loop crashes once, the supervisor
        # restarts it, scoring continues
        orig = svc.bus.consume
        calls = {"n": 0}

        async def flaky(topic, group, *a, **kw):
            if calls["n"] == 0 and group == svc.group:
                calls["n"] += 1
                raise RuntimeError("injected loop crash")
            return await orig(topic, group, *a, **kw)

        svc.bus.consume = flaky
        sim = DeviceSimulator(
            inst.broker, SimProfile(n_devices=6, seed=5, samples_per_message=5),
            topic_pattern="sitewhere/input/{device}",
        )
        for r in range(5):
            await sim.publish_round(float(r))
        scored = inst.metrics.counter("tpu_inference.scored_total")
        for _ in range(300):
            if scored.value >= sim.sent:
                break
            await asyncio.sleep(0.02)
        assert scored.value >= sim.sent
        assert svc._loop_super.restarts >= 1
    finally:
        inst.inference.bus.consume = orig
        await inst.terminate()


def _poison_dlq_rows(inst, tenant: str) -> int:
    """Rows parked in the tenant's scorer-poison DLQ topic. Under a
    fleet-wide persistent fault the poison-ejection heuristic (two
    DIFFERENT slices failing the same staged rows) can fire for the
    flush whose retry crossed the failover boundary — those rows are
    accounted (inspectable, requeue-able), not lost, so the zero-loss
    invariant is store ∪ DLQ, exactly the chaos suites' definition."""
    topic = inst.bus.naming.dead_letter(tenant, "scorer-poison")
    if topic not in inst.bus.topics():
        return 0
    n = 0
    for _off, entry in inst.bus.peek(topic, 100000)["entries"]:
        payload = entry.get("payload") if isinstance(entry, dict) else None
        rows = getattr(payload, "n", None)
        if rows:
            n += int(rows)
    return n


async def test_persistent_faults_park_family_but_events_still_flow():
    """When failover can't heal (fault persists), the family parks and
    events pass through UNSCORED — degraded, never lost."""
    inst = await _instance()
    try:
        svc = inst.inference
        # the fault is chip-independent here: pre-build BOTH slices'
        # scorers and poison them, so failover moves land on an equally
        # broken slice and the park escalation engages
        engine = svc.engines["acme"]
        for sl in range(svc.mm.n_slices):
            svc.scorer_for_slice("lstm_ad", sl, engine.config)
        for _sl, sc in svc.scorers.family_items("lstm_ad"):
            sc.fault_steps = 10**9  # permanent fault
        sim = DeviceSimulator(
            inst.broker, SimProfile(n_devices=6, seed=6, samples_per_message=5),
            topic_pattern="sitewhere/input/{device}",
        )
        for r in range(40):
            await sim.publish_round(float(r))
            await asyncio.sleep(0.01)
        parked = inst.metrics.counter("tpu_inference.parked")
        for _ in range(400):
            if parked.value >= 1:
                break
            await asyncio.sleep(0.02)
        assert parked.value >= 1, "family never parked"
        # events still flow end-to-end (unscored); the flush whose retry
        # crossed the failover boundary may sit in the scorer-poison DLQ
        # instead of the store (both chips failed its rows) — accounted
        # either way, never lost
        before = inst.metrics.counter("event_management.persisted").value
        for r in range(5):
            await sim.publish_round(100.0 + r)
        persisted = inst.metrics.counter("event_management.persisted")
        for _ in range(300):
            if persisted.value + _poison_dlq_rows(inst, "acme") >= sim.sent:
                break
            await asyncio.sleep(0.02)
        accounted = persisted.value + _poison_dlq_rows(inst, "acme")
        assert accounted >= sim.sent, (accounted, sim.sent)
        # tenant restart clears the fault (rebuild) and unparks
        for _sl, sc in svc.scorers.family_items("lstm_ad"):
            sc.fault_steps = 0
        await inst.restart_tenant("acme")
        assert "lstm_ad" not in svc._parked
        before = inst.metrics.counter("tpu_inference.scored_total").value
        for r in range(5):
            await sim.publish_round(200.0 + r)
        scored = inst.metrics.counter("tpu_inference.scored_total")
        for _ in range(300):
            if scored.value - before >= 5 * 6 * 5:
                break
            await asyncio.sleep(0.02)
        assert scored.value - before >= 5 * 6 * 5, "scoring did not resume"
    finally:
        await inst.terminate()
