"""Checkpoint/resume: param round trips, bus snapshots, and the headline
guarantee — an instance killed mid-stream restarts with NO event lost and
NO event persisted twice (SURVEY.md §5 checkpoint)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.runtime.bus import EventBus
from sitewhere_tpu.runtime.checkpoint import CheckpointManager
from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig
from sitewhere_tpu.services.event_store import EventQuery
from sitewhere_tpu.sim import DeviceSimulator, SimProfile


def test_params_round_trip(tmp_path):
    ck = CheckpointManager(tmp_path)
    # pytree with nested dicts AND a list (the ViT blocks shape)
    params = {
        "patch": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
        "blocks": [
            {"w": np.ones((2, 2), np.float32)},
            {"w": np.full((2, 2), 7.0, np.float32)},
        ],
    }
    ck.save_params("acme", "vit_b16", params)
    loaded = ck.load_params("acme", "vit_b16")
    flat_a = jax.tree_util.tree_leaves(params)
    flat_b = jax.tree_util.tree_leaves(loaded)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(a, b)
    assert ck.load_params("acme", "nope") is None
    ck.delete_params("acme")
    assert ck.load_params("acme", "vit_b16") is None


async def test_bus_snapshot_round_trip(tmp_path):
    ck = CheckpointManager(tmp_path)
    bus = EventBus()
    bus.subscribe("t.a", "g1")
    for i in range(10):
        await bus.publish("t.a", {"i": i})
    got = await bus.consume("t.a", "g1", 4, timeout_s=0)
    assert len(got) == 4
    ck.save_bus(bus)

    bus2 = EventBus()
    assert ck.load_bus(bus2)
    rest = await bus2.consume("t.a", "g1", 100, timeout_s=0)
    assert [r["i"] for r in rest] == list(range(4, 10))  # cursor preserved
    # offsets continue monotonically after restore
    off = await bus2.publish("t.a", {"i": 10})
    assert off == 10


async def test_crash_resume_exactly_once(tmp_path):
    """Kill an instance mid-stream, restart from the checkpoint, and prove
    every sent event is persisted exactly once."""
    def make_cfg():
        return InstanceConfig(
            instance_id="ck",
            data_dir=str(tmp_path),
            checkpointing=True,
            mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
        )

    inst = SiteWhereInstance(make_cfg())
    await inst.start()
    await inst.bootstrap(default_tenant="acme", dataset_devices=8)
    for _ in range(100):
        if "acme" in inst.tenants:
            break
        await asyncio.sleep(0.02)
    sim = DeviceSimulator(
        inst.broker, SimProfile(n_devices=8, seed=11),
        topic_pattern="sitewhere/input/{device}",
    )
    for step in range(25):
        await sim.publish_round(float(step))
        await asyncio.sleep(0.002)
    sent = sim.sent
    # wait until at least SOME events persisted, but don't drain fully —
    # the crash must catch events still in flight on the bus
    persisted = inst.metrics.counter("event_management.persisted")
    for _ in range(200):
        if persisted.value >= sent * 0.3:
            break
        await asyncio.sleep(0.02)
    await inst.stop()          # "crash": engines drain lanes unscored
    await inst.checkpoint()
    await inst.terminate()

    # fresh process analog: new instance, same data_dir
    inst2 = SiteWhereInstance(make_cfg())
    await inst2.start()
    restored = await inst2.restore()
    assert restored == 1 and "acme" in inst2.tenants
    store = inst2.tenant("acme").event_store
    # the backlog left on the bus drains into the store exactly once
    for _ in range(400):
        evs, total = store.list_measurements(EventQuery(page_size=100000))
        if total >= sent:
            break
        await asyncio.sleep(0.05)
    evs, total = store.list_measurements(EventQuery(page_size=100000))
    assert total == sent, f"persisted {total} != sent {sent}"
    ids = [e.id for e in evs]
    assert len(set(ids)) == total, "event persisted twice after resume"
    # device model survived too
    assert inst2.tenant("acme").device_management.get_device("dev-00000") is not None
    await inst2.terminate()


async def test_tenant_params_persist_across_restart(tmp_path):
    """Engine stop saves slot params; engine start restores them (even
    onto a different slot)."""
    cfg = InstanceConfig(
        instance_id="ckp",
        data_dir=str(tmp_path),
        checkpointing=True,
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
    )
    inst = SiteWhereInstance(cfg)
    await inst.start()
    await inst.bootstrap(default_tenant="acme")
    for _ in range(100):
        if "acme" in inst.tenants:
            break
        await asyncio.sleep(0.02)
    engine = inst.inference.engines["acme"]
    scorer = inst.inference.scorers[
        (engine.config.model, engine.placement.shard)
    ]
    slot = engine.placement.slot
    # perturb the tenant's params so restore is observable
    marked = jax.tree_util.tree_map(
        lambda x: x + 1.25, scorer.slot_params(slot)
    )
    scorer.activate(slot, params=marked)
    await inst.stop()
    await inst.checkpoint()
    await inst.terminate()

    inst2 = SiteWhereInstance(cfg)
    await inst2.start()
    await inst2.restore()
    engine2 = inst2.inference.engines["acme"]
    scorer2 = inst2.inference.scorers[
        (engine2.config.model, engine2.placement.shard)
    ]
    slot2 = engine2.placement.slot
    got = scorer2.slot_params(slot2)
    for a, b in zip(
        jax.tree_util.tree_leaves(marked), jax.tree_util.tree_leaves(got)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
    await inst2.terminate()


async def test_restore_preserves_override_config(tmp_path):
    """A tenant added with config overrides (different model than its
    template) must resume with the SAME config — not a re-derivation from
    the template (ADVICE r2: manifest carried only {token, template})."""
    def make_cfg():
        return InstanceConfig(
            instance_id="ov", data_dir=str(tmp_path), checkpointing=True,
        )

    inst = SiteWhereInstance(make_cfg())
    await inst.start()
    await inst.tenant_management.create_tenant(
        "acme", template="default", model="deepar", decoder="json",
    )
    await inst.drain_tenant_updates()
    assert inst.tenants["acme"].config.model == "deepar"
    await inst.stop()
    await inst.checkpoint()
    await inst.terminate()

    inst2 = SiteWhereInstance(make_cfg())
    restored = await inst2.restore()
    assert restored == 1
    assert inst2.tenants["acme"].config.model == "deepar"
    assert inst2.tenants["acme"].config.template == "default"
    await inst2.terminate()


def test_tenant_config_dict_round_trip():
    from sitewhere_tpu.runtime.config import (
        MicroBatchConfig,
        TenantEngineConfig,
        tenant_config_from_dict,
        tenant_config_to_dict,
    )

    cfg = TenantEngineConfig(
        tenant="t1", template="forecasting", model="deepar",
        model_config={"context": 64},
        microbatch=MicroBatchConfig(max_batch=512, deadline_ms=2.0, buckets=(64, 512)),
        max_streams=123, decoder="binary", shared_input=True,
    )
    assert tenant_config_from_dict(tenant_config_to_dict(cfg)) == cfg


def test_segmented_event_checkpoint_incremental_and_torn_write(tmp_path):
    """Sealed chunks encode once (incremental segments); a torn write
    (crash before the meta commit) must load the PREVIOUS consistent set —
    no duplicated, no missing rows."""
    import json as _json

    from sitewhere_tpu.core.batch import MeasurementBatch
    from sitewhere_tpu.services.device_management import DeviceManagement
    from sitewhere_tpu.services.event_store import EventQuery, EventStore

    ck = CheckpointManager(tmp_path)
    dm = DeviceManagement("seg")
    store = EventStore("seg")

    def add_rows(n, base):
        store.add_measurement_batch(MeasurementBatch.from_column_chunks(
            "seg",
            [("d1", "t", np.arange(base, base + n).astype(np.float32),
              np.arange(base, base + n).astype(np.float64) + 1)],
        ))

    add_rows(100, 0)
    store.measurements._seal()      # chunk 0
    add_rows(50, 100)               # tail
    snap1 = ck.snapshot_tenant_stores(dm, store)
    assert len(snap1["segments"]) == 1  # chunk 0 encoded
    ck.write_tenant_stores("seg", snap1)

    add_rows(30, 150)
    snap2 = ck.snapshot_tenant_stores(dm, store)
    assert snap2["segments"] == []  # chunk 0 NOT re-encoded
    ck.write_tenant_stores("seg", snap2)

    got = ck.load_event_store("seg")
    assert len(got.measurements) == 180
    _, total = got.list_measurements(EventQuery(page_size=1))
    assert total == 180

    # torn write: new snapshot whose files land but whose meta does NOT
    add_rows(999, 180)
    store.measurements._seal()      # chunk 1 (tail rows sealed into it)
    snap3 = ck.snapshot_tenant_stores(dm, store)
    assert len(snap3["segments"]) == 1
    # simulate crash: write the segment + tail files but skip the meta
    name, data = snap3["segments"][0]
    (tmp_path / "events" / name).write_bytes(data)
    (tmp_path / "events" / snap3["tail_name"]).write_bytes(snap3["tail"])
    got = ck.load_event_store("seg")
    # previous committed set: exactly 180 rows, no dup/missing
    assert len(got.measurements) == 180
    ids = got.measurements.columns()["event_id"]
    assert len(set(ids)) == 180

    # completing the commit makes the new set visible
    ck.write_tenant_stores("seg", snap3)
    got = ck.load_event_store("seg")
    assert len(got.measurements) == 180 + 999


def test_segment_lineage_mismatch_forces_full_rewrite(tmp_path):
    """A DIFFERENT store (new lineage) over the same data_dir must not
    reuse the previous lineage's segments even when row counts line up."""
    from sitewhere_tpu.core.batch import MeasurementBatch
    from sitewhere_tpu.services.device_management import DeviceManagement
    from sitewhere_tpu.services.event_store import EventStore

    ck = CheckpointManager(tmp_path)
    dm = DeviceManagement("seg")

    def store_with(vals):
        s = EventStore("seg")
        s.add_measurement_batch(MeasurementBatch.from_column_chunks(
            "seg",
            [("d1", "t", np.asarray(vals, np.float32),
              np.ones(len(vals), np.float64))],
        ))
        s.measurements._seal()
        return s

    s1 = store_with([1.0, 2.0, 3.0])
    ck.write_tenant_stores("seg", ck.snapshot_tenant_stores(dm, s1))
    # new lineage, identical chunk counts, different data
    s2 = store_with([7.0, 8.0, 9.0])
    snap = ck.snapshot_tenant_stores(dm, s2)
    assert len(snap["segments"]) == 1  # re-encoded despite matching counts
    ck.write_tenant_stores("seg", snap)
    got = ck.load_event_store("seg")
    assert sorted(got.measurements.columns()["value"].tolist()) == [7.0, 8.0, 9.0]
    # and the restored store continues the lineage (incremental reuse works)
    got.add_measurement_batch(MeasurementBatch.from_column_chunks(
        "seg", [("d1", "t", np.asarray([10.0], np.float32),
                 np.asarray([2.0]))],
    ))
    snap2 = ck.snapshot_tenant_stores(dm, got)
    assert snap2["segments"] == []  # sealed segment reused across restore


def test_dirty_segment_rewrite_recheckpoints_despite_matching_counts(
    tmp_path,
):
    """maintain() re-encoding a score-written segment in place keeps the
    row count — the next checkpoint must still rewrite it (reuse is
    keyed on segment identity, not counts), or the rescore silently
    reverts to NaN on restore and the dedupe re-replays it."""
    from sitewhere_tpu.core.batch import MeasurementBatch
    from sitewhere_tpu.services.device_management import DeviceManagement
    from sitewhere_tpu.services.event_store import EventStore

    ck = CheckpointManager(tmp_path)
    dm = DeviceManagement("seg")
    store = EventStore("seg")
    store.add_measurement_batch(MeasurementBatch.from_column_chunks(
        "seg",
        [("d1", "t", np.arange(100).astype(np.float32),
          np.arange(100).astype(np.float64) + 1)],
    ))
    store.measurements._seal()
    ck.write_tenant_stores("seg", ck.snapshot_tenant_stores(dm, store))

    ids = store.measurements.segments[0].event_ids()
    fresh = np.linspace(0.0, 1.0, 100).astype(np.float32)
    assert store.measurements.write_back_scores(ids, fresh) == 100
    acts = store.measurements.maintain()
    assert acts["rewritten"] == 1  # same count, new bytes
    snap = ck.snapshot_tenant_stores(dm, store)
    assert len(snap["segments"]) == 1  # re-encoded, NOT count-reused
    ck.write_tenant_stores("seg", snap)

    got = ck.load_event_store("seg")
    np.testing.assert_allclose(
        got.measurements.columns()["score"], fresh, rtol=1e-6
    )
    assert sum(
        sl.n for sl in got.measurements.scan(only_unscored=True)
    ) == 0  # nothing re-replays after restore
    # steady state: the rewritten file reuses again on the next cycle
    snap2 = ck.snapshot_tenant_stores(dm, got)
    assert snap2["segments"] == []


def test_cleanup_never_touches_prefix_sibling_tenant(tmp_path):
    """ADVICE r4 (medium): checkpointing tenant 'prod' must NOT delete
    tenant 'prod-eu's committed segment files — cleanup is anchored to
    the exact per-tenant file grammar, not a bare prefix glob."""
    from sitewhere_tpu.core.batch import MeasurementBatch
    from sitewhere_tpu.services.device_management import DeviceManagement
    from sitewhere_tpu.services.event_store import EventStore

    ck = CheckpointManager(tmp_path)

    def make(tenant, n):
        dm = DeviceManagement(tenant)
        store = EventStore(tenant)
        store.add_measurement_batch(MeasurementBatch.from_column_chunks(
            tenant,
            [("d1", "t", np.arange(n).astype(np.float32),
              np.arange(n).astype(np.float64) + 1)],
        ))
        return dm, store

    dm_eu, store_eu = make("prod-eu", 40)
    ck.save_tenant_stores("prod-eu", dm_eu, store_eu)
    eu_files = {
        p.name for p in (tmp_path / "events").iterdir() if "prod-eu" in p.name
    }
    assert eu_files  # the victim tenant has on-disk state

    # checkpoint 'prod' twice (second write triggers cleanup of stale
    # 'prod' files — which under the old glob also matched 'prod-eu-*')
    dm_p, store_p = make("prod", 10)
    ck.save_tenant_stores("prod", dm_p, store_p)
    ck.save_tenant_stores("prod", dm_p, store_p)

    survivors = {p.name for p in (tmp_path / "events").iterdir()}
    assert eu_files <= survivors
    got = ck.load_event_store("prod-eu")
    assert len(got.measurements) == 40
