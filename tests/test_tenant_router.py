"""Tenant→mesh-slice routing tests (docs/PERFORMANCE.md "Multi-chip
serving"): deterministic slice assignment, rebalance-on-remove remap,
and — service-level — a failover slice MOVE that preserves per-tenant
FIFO delivery through the ``_SliceFence``."""

import asyncio
import threading
import time

import numpy as np

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.parallel.tenant_router import PlacementError, TenantRouter
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
)


# ------------------------------------------------------- router determinism
def test_deterministic_slice_assignment():
    """Identical placement sequences produce identical (shard, slot)
    maps: least-loaded shard first, ties to the lowest index, lowest
    free slot — no randomness anywhere."""
    def run():
        r = TenantRouter(n_shards=4, slots_per_shard=2)
        return [r.place(f"t{i}", family="lstm_ad") for i in range(8)]

    a, b = run(), run()
    assert [(p.shard, p.slot) for p in a] == [(p.shard, p.slot) for p in b]
    # round-robin spread across slices before any slot doubles up
    assert [(p.shard, p.slot) for p in a[:4]] == [
        (0, 0), (1, 0), (2, 0), (3, 0)
    ]
    assert [(p.shard, p.slot) for p in a[4:]] == [
        (0, 1), (1, 1), (2, 1), (3, 1)
    ]
    r = TenantRouter(n_shards=2, slots_per_shard=1)
    r.place("x")
    r.place("y")
    try:
        r.place("z")
        raise AssertionError("capacity exceeded without PlacementError")
    except PlacementError:
        pass


def test_rebalance_on_remove_remaps_deterministically():
    """Removing tenants skews per-slice load; rebalance() moves the
    lexicographically-first tenant off the most-loaded slice until the
    gap is ≤ 1 — and reports every move for the serving layer to apply
    through its FIFO fence."""
    r = TenantRouter(n_shards=3, slots_per_shard=2)
    for t in ("a", "b", "c", "d", "e", "f"):
        r.place(t, family="lstm_ad")
    # a,d → shard 0; b,e → shard 1; c,f → shard 2
    r.remove("b")
    r.remove("e")  # shard 1 now empty, shards 0/2 hold 2 each
    moves = r.rebalance("lstm_ad")
    assert len(moves) == 1
    old, new = moves[0]
    # donor = highest load, ties to the HIGHEST index → shard 2; its
    # lexicographically-first tenant is "c"
    assert (old.tenant, old.shard) == ("c", 2)
    assert new.shard == 1 and new.slot == 0
    assert new.generation == old.generation + 1
    assert r.placement("c").shard == 1
    assert sorted(len(s) for s in r._used["lstm_ad"]) == [1, 1, 2]
    # balanced within 1 → idempotent
    assert r.rebalance("lstm_ad") == []


def test_failover_prefers_least_loaded_other_shard():
    r = TenantRouter(n_shards=3, slots_per_shard=2)
    p0 = r.place("t0")
    r.place("t1")  # shard 1
    p2 = r.failover("t0")
    assert p2.shard == 2  # least-loaded shard that isn't 0
    assert p2.generation == p0.generation + 1
    assert r.shard_load("lstm_ad") == [0, 1, 1]


# ---------------------------------------------- service-level FIFO fence
class GatedScores:
    """Score double whose materialization blocks on a gate (no
    ``is_ready``/``copy_to_host_async`` → executor fallback path)."""

    def __init__(self, inner, gate: threading.Event) -> None:
        self.inner = inner
        self.gate = gate

    def __getitem__(self, idx):
        return GatedScores(self.inner[idx], self.gate)

    def __array__(self, dtype=None):
        if not self.gate.wait(timeout=60.0):
            raise RuntimeError("gate never opened")
        a = np.asarray(self.inner)
        return a.astype(dtype) if dtype is not None else a


def _batch(tenant, toks, n, base=0.0):
    return MeasurementBatch.from_columns(
        tenant, [toks[i % len(toks)] for i in range(n)],
        ["temperature"] * n, [base + float(i) for i in range(n)], [0.0] * n,
    )


async def _wait_for(cond, timeout_s=20.0, interval=0.01):
    deadline = time.monotonic() + timeout_s
    while True:
        if cond():
            return True
        if time.monotonic() >= deadline:
            return False
        await asyncio.sleep(interval)


async def test_failover_slice_move_keeps_per_tenant_fifo():
    """A tenant moves slices while a flush is STILL IN FLIGHT on the
    old slice: later rows park behind the slice fence, nothing delivers
    out of order, and once the old flush resolves the fence lifts and
    the new slice serves the parked rows — batches arrive strictly in
    enqueue order with finite scores on both sides of the move."""
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="fence",
        mesh=MeshConfig(tenant_axis=2, data_axis=1, slots_per_shard=2),
    ))
    await inst.start()
    gate = threading.Event()
    try:
        await inst.tenant_management.create_tenant(
            "acme", template="iot-temperature",
            microbatch=MicroBatchConfig(
                max_batch=64, deadline_ms=1.0, buckets=(32, 64), window=8
            ),
            model_config={"hidden": 8}, max_streams=64,
        )
        await inst.drain_tenant_updates()
        assert await _wait_for(lambda: "acme" in inst.tenants)
        toks = [
            d.token
            for d in inst.tenants["acme"].device_management.bootstrap_fleet(4)
        ]
        svc = inst.inference
        topic = inst.bus.naming.scored_events("acme")
        inst.bus.subscribe(topic, "fence-test")

        async def drain():
            return await inst.bus.consume(topic, "fence-test", 64, timeout_s=0)

        engine = svc.engines["acme"]
        assert engine.placement.shard == 0
        scorer0 = svc.scorers[("lstm_ad", 0)]
        orig = scorer0.step_counts
        scorer0.step_counts = lambda i, v, c: GatedScores(orig(i, v, c), gate)
        # batch 1 flushes on slice 0 and WEDGES in flight (gated d2h)
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"),
            _batch("acme", toks, 8, base=100.0),
        )
        assert await _wait_for(
            lambda: len(svc._slices[("lstm_ad", 0)].reap) == 1
        )
        # the move: slice 0 → slice 1 with batch 1 still unresolved
        assert await svc._failover_tenant(engine)
        assert engine.placement.shard == 1
        assert "acme" in svc._fences
        # batch 2 arrives during the move → parks behind the fence
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"),
            _batch("acme", toks, 8, base=200.0),
        )
        assert await _wait_for(lambda: svc._fences["acme"].depth() >= 8)
        await asyncio.sleep(0.3)
        assert not await drain(), "fenced rows delivered ahead of in-flight"
        assert svc.metrics.counter("tpu_inference.fenced_rows").value >= 8
        # old flush lands → fence lifts → new slice scores the backlog
        gate.set()
        got: list = []
        deadline = time.monotonic() + 30.0
        while len(got) < 2 and time.monotonic() < deadline:
            got.extend(await drain())
            await asyncio.sleep(0.02)
        assert len(got) >= 2, "slice move lost a batch"
        assert float(got[0].values[0]) == 100.0, "batch order broke"
        assert float(got[1].values[0]) == 200.0
        assert np.isfinite(np.asarray(got[0].scores)).all()
        assert np.isfinite(np.asarray(got[1].scores)).all(), (
            "post-move rows were not scored on the new slice"
        )
        assert "acme" not in svc._fences
        assert not svc._slices[("lstm_ad", 0)].reap
    finally:
        gate.set()
        await inst.terminate()


async def test_apply_rebalance_moves_live_tenant_and_scoring_continues():
    """Service-level rebalance: after a remove skews load, the router's
    plan is applied through the fenced migration and the moved tenant
    keeps scoring on its new slice."""
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="rb",
        mesh=MeshConfig(tenant_axis=2, data_axis=1, slots_per_shard=2),
    ))
    await inst.start()
    try:
        mb = MicroBatchConfig(
            max_batch=64, deadline_ms=1.0, buckets=(32, 64), window=8
        )
        for t in ("a1", "b1", "c1"):
            await inst.tenant_management.create_tenant(
                t, template="iot-temperature", microbatch=mb,
                model_config={"hidden": 8}, max_streams=64,
            )
        await inst.drain_tenant_updates()
        assert await _wait_for(
            lambda: {"a1", "b1", "c1"} <= set(inst.tenants)
        )
        svc = inst.inference
        # a1→(0,0) b1→(1,0) c1→(0,1); removing b1 empties shard 1
        assert svc.engines["b1"].placement.shard == 1
        await inst.remove_tenant("b1")
        moved = await svc.apply_rebalance("lstm_ad")
        assert moved == 1
        mover = svc.engines["a1"]
        assert mover.placement.shard == 1
        toks = [
            d.token
            for d in inst.tenants["a1"].device_management.bootstrap_fleet(4)
        ]
        scored = inst.metrics.counter("tpu_inference.scored_total")
        before = scored.value
        await inst.bus.publish(
            inst.bus.naming.inbound_events("a1"), _batch("a1", toks, 16)
        )
        assert await _wait_for(lambda: scored.value - before >= 16)
        assert svc.metrics.counter("tpu_inference.rebalanced").value == 1
    finally:
        await inst.terminate()
