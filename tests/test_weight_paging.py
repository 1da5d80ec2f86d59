"""Weight paging (ISSUE 19): virtualized slots with async page-in /
LRU page-out over the per-(family, slice) ``SlotPager``.

Covers the tentpole's contract edges: demand page-in past physical
capacity with zero loss, bitwise param/score fidelity across a
page-out → page-in cycle, the ``WEIGHT_PAGING_ENABLED`` kill switch
restoring physical-slot semantics, page-out racing rows already in
serve lanes (FIFO via the paging fence), eviction dropping pending
train-lane rows (counted, PR 12 round-4 rule), and quarantine of a
slice hosting paged-out tenants (ghosts re-point without touching the
dead devices)."""

import asyncio

import numpy as np

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.runtime.bus import EventBus
from sitewhere_tpu.runtime.config import (
    MicroBatchConfig,
    TrainingConfig,
    tenant_config_from_template,
)


def _mb():
    return MicroBatchConfig(
        max_batch=64, deadline_ms=1.0, buckets=(64,), window=8
    )


async def _service(tenant_axis=2, data_axis=4, slots_per_shard=1):
    from sitewhere_tpu.pipeline.inference import TpuInferenceService

    bus = EventBus()
    svc = TpuInferenceService(
        bus,
        mm=MeshManager(tenant=tenant_axis, data=data_axis),
        slots_per_shard=slots_per_shard,
    )
    await svc.start()
    return svc, bus


async def _add(svc, bus, tok, **overrides):
    cfg = tenant_config_from_template(
        tok, "iot-temperature", microbatch=_mb(), max_streams=8,
        wire_dtype="f32", model_config={"hidden": 8}, **overrides
    )
    bus.subscribe(bus.naming.scored_events(tok), "t")
    await svc.add_tenant(cfg)


def _batch(tok, rows=8, value=1.0):
    return MeasurementBatch.from_columns(
        tok,
        [f"d{i % 2}" for i in range(rows)],
        ["temperature"] * rows,
        [value + 0.01 * i for i in range(rows)],
        [0.0] * rows,
    )


async def _score(svc, bus, tok, batch, timeout_s=30.0):
    """Publish one batch and collect its rows back off the scored topic
    (scored or unscored — zero-loss is the caller's assert)."""
    topic = bus.naming.scored_events(tok)
    await bus.publish(bus.naming.inbound_events(tok), batch)
    out = []
    for _ in range(int(timeout_s / 0.02)):
        out += await bus.consume(topic, "t", 64, timeout_s=0)
        if sum(b.n for b in out) >= batch.n:
            return out
        await asyncio.sleep(0.02)
    raise AssertionError(
        f"{tok}: {sum(b.n for b in out)}/{batch.n} rows returned"
    )


# ------------------------------------------------------- demand page-in
async def test_overflow_tenant_pages_in_on_demand_zero_loss():
    """A tenant past physical capacity starts VIRTUAL (ghost placement,
    no device slot) and its first traffic demand-pages it in — evicting
    the LRU resident — with every row scored."""
    svc, bus = await _service()  # capacity: 2 tenants (2 shards x 1 slot)
    try:
        assert svc.pager is not None
        for tok in ("pa", "pb", "pc"):
            await _add(svc, bus, tok)
        ghost = svc.engines["pc"]
        assert ghost.placement.slot < 0, "overflow tenant must start ghost"
        assert svc.metrics.counter(
            "tpu_paging.virtual_starts", family="lstm_ad"
        ).value == 1
        out = await _score(svc, bus, "pc", _batch("pc"))
        assert ghost.placement.slot >= 0, "demand page-in never landed"
        assert all(not np.isnan(b.scores).any() for b in out)
        assert svc.metrics.counter(
            "tpu_paging.page_ins", family="lstm_ad", origin="demand"
        ).value >= 1
        assert svc.metrics.counter(
            "tpu_paging.page_outs", family="lstm_ad"
        ).value >= 1
        # exactly capacity tenants resident; the victim is now a ghost
        ghosts = [
            t for t, e in svc.engines.items() if e.placement.slot < 0
        ]
        assert len(ghosts) == 1 and ghosts[0] in ("pa", "pb")
        # the victim's state lives host-side as encoded segment bytes
        assert svc.pager.cache.get(ghosts[0]) is not None
    finally:
        await svc.terminate()


# -------------------------------------------------- bitwise round trip
async def test_page_out_page_in_scores_bitwise_identical():
    """Twin tenants with identical perturbed params score an identical
    batch bitwise-equal AFTER one of them takes a page-out → page-in
    round trip — paging moves weights, never numerics. (Window HISTORY
    restarts across a page-out, the failover contract — so the round
    trip happens before any traffic advances either twin's window.)"""
    import jax

    svc, bus = await _service()
    try:
        for tok in ("ta", "tb"):
            await _add(svc, bus, tok)
        for tok in ("ta", "tb"):
            eng = svc.engines[tok]
            scorer = svc.scorers[("lstm_ad", eng.placement.shard)]
            marked = jax.tree_util.tree_map(
                lambda x: x + 0.75, scorer.slot_params(eng.placement.slot)
            )
            scorer.activate(eng.placement.slot, params=marked)
        # page ta out BEFORE any traffic: the perturbed params round-trip
        # through encode → host cache → decode → page-in
        svc._page_out(svc.engines["ta"])
        assert svc.engines["ta"].placement.slot < 0
        assert svc.pager.cache.get("ta") is not None
        a1 = (await _score(svc, bus, "ta", _batch("ta")))
        b1 = (await _score(svc, bus, "tb", _batch("tb")))
        assert svc.engines["ta"].placement.slot >= 0
        assert a1[0].scores.tobytes() == b1[0].scores.tobytes(), (
            "paged-in tenant diverged from its never-paged twin"
        )
        # a second identical batch advances both windows in lockstep —
        # still bitwise equal (the page-in left no hidden slot skew)
        a2 = (await _score(svc, bus, "ta", _batch("ta", value=3.0)))
        b2 = (await _score(svc, bus, "tb", _batch("tb", value=3.0)))
        assert a2[0].scores.tobytes() == b2[0].scores.tobytes()
    finally:
        await svc.terminate()


# ------------------------------------------------------- kill switch
async def test_kill_switch_restores_physical_slot_semantics(monkeypatch):
    """``WEIGHT_PAGING_ENABLED=False`` (captured at service build, the
    FUSED_STEP_ENABLED pattern): no pager, no ghosts — a tenant past
    capacity fails placement exactly like the pre-paging build."""
    from sitewhere_tpu.runtime import paging
    from sitewhere_tpu.runtime.lifecycle import LifecycleState

    monkeypatch.setattr(paging, "WEIGHT_PAGING_ENABLED", False)
    svc, bus = await _service()
    try:
        assert svc.pager is None and not svc.paging_enabled
        for tok in ("ka", "kb"):
            await _add(svc, bus, tok)
        # the overflow engine parks in START_ERROR on PlacementError —
        # the lifecycle tree's pre-paging behavior, no ghost placement
        await _add(svc, bus, "kc")
        eng = svc.engines["kc"]
        assert eng.state is LifecycleState.START_ERROR
        assert any("PlacementError" in e for e in eng.errors)
        # physical tenants still score normally
        out = await _score(svc, bus, "ka", _batch("ka"))
        assert all(not np.isnan(b.scores).any() for b in out)
    finally:
        await svc.terminate()


# ------------------------------------- page-out racing in-flight rows
async def test_page_out_with_rows_in_lanes_keeps_fifo_zero_loss():
    """Eviction while the tenant still has rows packed in serve lanes:
    the rows park behind the paging fence and drain FIFO into the new
    slot after re-activation — nothing lost, nothing reordered."""
    svc, bus = await _service()
    try:
        for tok in ("fa", "fb"):
            await _add(svc, bus, tok)
        eng = svc.engines["fa"]
        topic = bus.naming.scored_events("fa")
        # first wave enters the service, then the tenant is evicted
        # before (or while) its rows flush
        await bus.publish(bus.naming.inbound_events("fa"), _batch("fa", value=1.0))
        await asyncio.sleep(0)
        svc._page_out(eng)
        assert eng.placement.slot < 0
        # second wave arrives for the now-ghost tenant (parks FIFO)
        await bus.publish(bus.naming.inbound_events("fa"), _batch("fa", value=2.0))
        out = []
        for _ in range(1500):
            out += await bus.consume(topic, "t", 64, timeout_s=0)
            if sum(b.n for b in out) >= 16:
                break
            await asyncio.sleep(0.02)
        assert sum(b.n for b in out) == 16, "rows lost across page-out"
        assert eng.placement.slot >= 0
        # FIFO: wave-1 values (1.x) resolve before wave-2 values (2.x)
        vals = np.concatenate([b.values for b in out])
        assert (vals[:8] < 2.0).all() and (vals[8:] >= 2.0).all()
    finally:
        await svc.terminate()


async def test_page_out_strands_no_parked_rows_without_new_traffic():
    """Rows parked at EVICTION time must drive their own page-in (the
    ``_paging_tick`` fence re-demand): no new arrival is ever required
    for parked work to finish."""
    svc, bus = await _service()
    try:
        for tok in ("sa", "sb"):
            await _add(svc, bus, tok)
        eng = svc.engines["sa"]
        topic = bus.naming.scored_events("sa")
        await bus.publish(bus.naming.inbound_events("sa"), _batch("sa"))
        await asyncio.sleep(0)
        svc._page_out(eng)
        # NO further traffic for sa — the parked rows alone must bring
        # the tenant back
        out = []
        for _ in range(1500):
            out += await bus.consume(topic, "t", 64, timeout_s=0)
            if sum(b.n for b in out) >= 8:
                break
            await asyncio.sleep(0.02)
        assert sum(b.n for b in out) == 8, "parked rows stranded"
        assert eng.placement.slot >= 0
    finally:
        await svc.terminate()


# ------------------------------------------------ train-lane eviction
async def test_eviction_drops_pending_train_rows_counted():
    """Evicting a train-lane tenant drops its pending (not-yet-stepped)
    replay rows — counted, per the PR 12 round-4 rule: training rows are
    best-effort history, never worth blocking an eviction on — while the
    page-out blob stays DIRTY (optimizer progress must persist)."""
    svc, bus = await _service()
    try:
        await _add(svc, bus, "tr", training=TrainingConfig(
            enabled=True, every_n_flushes=1000
        ))
        await _add(svc, bus, "ts")
        eng = svc.engines["tr"]
        p = eng.placement
        n = 12
        home = svc._slices[("lstm_ad", p.shard)]
        home.train_lane(p.slot, 0).push(
            np.zeros((n,), np.int32), np.ones((n,), np.float32),
            np.int64(-1), np.full((n,), -1, np.int32),
        )
        svc._page_out(eng)
        assert svc.metrics.counter(
            "tpu_paging.train_rows_dropped", family="lstm_ad"
        ).value == n
        assert not home.train_lanes
        blob = svc.pager.cache.get("tr")
        assert blob is not None and blob[1] is True, (
            "train-lane page-out must write back dirty"
        )
    finally:
        await svc.terminate()


# --------------------------------------------------------- quarantine
async def test_quarantine_slice_with_paged_out_tenants():
    """Quarantining a slice that hosts ghost placements: the ghosts
    re-point at a healthy slice as encoded bytes — no device touch, no
    failover flush — and the next demand page-in lands them healthy."""
    svc, bus = await _service()
    try:
        for tok in ("qa", "qb", "qc"):
            await _add(svc, bus, tok)
        ghost = svc.engines["qc"]
        assert ghost.placement.slot < 0
        sick = ghost.placement.shard
        await svc._quarantine_slice(
            svc._slices[("lstm_ad", sick)], "test-kill"
        )
        assert ghost.placement.slot < 0, "ghost must stay virtual"
        assert ghost.placement.shard != sick, "ghost still on dead slice"
        assert svc.metrics.counter(
            "tpu_paging.quarantine_ghosts", family="lstm_ad"
        ).value >= 1
        out = await _score(svc, bus, "qc", _batch("qc"))
        assert sum(b.n for b in out) == 8
        assert ghost.placement.slot >= 0
        assert ghost.placement.shard != sick
    finally:
        await svc.terminate()


# ------------------------------------------------- observability hooks
async def test_paging_stats_and_metrics_surface():
    """``describe()`` carries the pager roll-up and the activation wait
    lands in the ``tenant_activation_ms`` histogram with the ``paged``
    flightrec mark (satellite 1: cold-start activation SLO)."""
    svc, bus = await _service()
    try:
        for tok in ("ma", "mb", "mc"):
            await _add(svc, bus, tok)
        await _score(svc, bus, "mc", _batch("mc"))
        stats = svc.describe()["paging"]
        assert stats["page_ins"] >= 1
        assert stats["pagein_p99_ms"] is not None
        assert stats["hit_rate"] is not None
        h = svc.metrics.histogram(
            "tenant_activation_ms", unit="ms", family="lstm_ad"
        )
        assert h._n >= 1
    finally:
        await svc.terminate()
