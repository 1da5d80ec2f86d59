"""Live training in the running pipeline: per-tenant models adapt on
their resident window state, and the CEP UDF evaluates with the tenant's
LIVE params (train_resident must not be dead code and ModelUdf must not
score with a fresh init forever)."""

import asyncio
import math

import numpy as np

from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.pipeline.rules import ModelUdf
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
    TrainingConfig,
)
from sitewhere_tpu.sim import DeviceSimulator, SimProfile
from tests._drive import drive_rounds


async def _training_instance(every_n=2):
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="tr",
        mesh=MeshConfig(tenant_axis=1, data_axis=1, slots_per_shard=2),
    ))
    await inst.start()
    await inst.tenant_management.create_tenant(
        "acme", template="iot-temperature",
        model_config={"hidden": 16},
        microbatch=MicroBatchConfig(
            max_batch=256, deadline_ms=1.0, buckets=(64, 256), window=16
        ),
        training=TrainingConfig(enabled=True, every_n_flushes=every_n, lr=5e-3),
        max_streams=256,
    )
    await inst.drain_tenant_updates()
    for _ in range(100):
        if "acme" in inst.tenants:
            break
        await asyncio.sleep(0.02)
    inst.tenants["acme"].device_management.bootstrap_fleet(8)
    return inst


async def test_pipeline_trains_and_model_adapts():
    inst = await _training_instance()
    try:
        sim = DeviceSimulator(
            inst.broker,
            SimProfile(n_devices=8, seed=1, samples_per_message=8,
                       noise=0.01, period_s=4.0),
            topic_pattern="sitewhere/input/{device}",
        )
        trains = inst.metrics.counter("tpu_inference.train_steps")
        first_loss = None

        def note_first_loss():
            nonlocal first_loss
            if first_loss is None and "lstm_ad" in inst.inference.last_train_losses:
                first_loss = float(np.asarray(
                    inst.inference.last_train_losses["lstm_ad"]
                ).max())

        assert await drive_rounds(
            inst, sim, lambda: trains.value > 3, rounds=120,
            each=note_first_loss,
        ), f"training cadence never fired: {trains.value} train steps"
        # params measurably diverged from the pristine base
        engine = inst.inference.engines["acme"]
        scorer = inst.inference.scorers[
            ("lstm_ad", engine.placement.shard)
        ]
        slot = engine.placement.slot
        import jax

        diffs = [
            float(np.abs(np.asarray(a) - np.asarray(b)).max())
            for a, b in zip(
                jax.tree_util.tree_leaves(scorer.slot_params(slot)),
                jax.tree_util.tree_leaves(scorer._base_params),
            )
        ]
        assert max(diffs) > 1e-4, "slot params never moved"
        # the model ADAPTED: training loss on the resident windows dropped
        last_loss = float(np.asarray(
            inst.inference.last_train_losses["lstm_ad"]
        ).max())
        assert first_loss is not None
        assert last_loss < first_loss, (first_loss, last_loss)
    finally:
        await inst.terminate()


async def test_udf_uses_live_tenant_params():
    inst = await _training_instance()
    try:
        sim = DeviceSimulator(
            inst.broker,
            SimProfile(n_devices=8, seed=2, samples_per_message=8,
                       noise=0.01, period_s=4.0),
            topic_pattern="sitewhere/input/{device}",
        )
        trains = inst.metrics.counter("tpu_inference.train_steps")
        assert await drive_rounds(
            inst, sim, lambda: trains.value >= 3, rounds=80
        ), f"training cadence never fired: {trains.value} train steps"
        cfg = {"hidden": 16, "window": 16}
        live = ModelUdf("lstm_ad", cfg).bind_params_source(
            inst.inference.params_source("acme")
        )
        fresh = ModelUdf("lstm_ad", cfg)
        values = np.asarray(
            [21.0 + 4.0 * math.sin(i / 4.0) for i in range(16)], np.float32
        )
        s_live = live.score(values)
        s_fresh = fresh.score(values)
        # same window, different verdicts — the UDF tracks the tenant's
        # trained model, not a fresh init
        assert abs(s_live - s_fresh) > 1e-6, (s_live, s_fresh)
        # source degrades gracefully when the tenant goes away
        await inst.remove_tenant("acme")
        assert live.params_source() is None
        live.score(values)  # falls back to local params, no crash
    finally:
        await inst.terminate()


async def test_disabled_training_tenant_is_masked_in_shared_stack():
    """Two tenants in one family stack: only the training-enabled one's
    params move."""
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="tm",
        mesh=MeshConfig(tenant_axis=1, data_axis=1, slots_per_shard=2),
    ))
    await inst.start()
    try:
        common = dict(
            model_config={"hidden": 16},
            microbatch=MicroBatchConfig(
                max_batch=256, deadline_ms=1.0, buckets=(64, 256), window=16
            ),
            max_streams=256,
            shared_input=False,
        )
        await inst.tenant_management.create_tenant(
            "learner", template="iot-temperature",
            training=TrainingConfig(enabled=True, every_n_flushes=2, lr=5e-3),
            **common,
        )
        await inst.tenant_management.create_tenant(
            "frozen", template="iot-temperature", **common,
        )
        await inst.drain_tenant_updates()
        for _ in range(100):
            if {"learner", "frozen"} <= set(inst.tenants):
                break
            await asyncio.sleep(0.02)
        for rt in inst.tenants.values():
            rt.device_management.bootstrap_fleet(4)
        sims = [
            DeviceSimulator(
                inst.broker,
                SimProfile(n_devices=4, seed=3, samples_per_message=8,
                           noise=0.01),
                topic_pattern=f"sitewhere/{t}/input/{{device}}",
            )
            for t in ("learner", "frozen")
        ]
        for r in range(100):
            for sim in sims:
                await sim.publish_round(float(r) * 0.5)
            await asyncio.sleep(0.005)
        trains = inst.metrics.counter("tpu_inference.train_steps")
        for _ in range(100):
            if trains.value >= 2:
                break
            await asyncio.sleep(0.05)
        assert trains.value >= 2
        import jax

        def diverged(tenant):
            engine = inst.inference.engines[tenant]
            place = engine.placement
            scorer = inst.inference.scorers[
                (engine.config.model, place.shard)
            ]
            return max(
                float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(
                    jax.tree_util.tree_leaves(scorer.slot_params(place.slot)),
                    jax.tree_util.tree_leaves(scorer._base_params),
                )
            )

        assert diverged("learner") > 1e-4
        assert diverged("frozen") == 0.0, "frozen tenant's params moved"
    finally:
        await inst.terminate()


async def test_wire_dtype_conflict_surfaces():
    """A second tenant asking a DIFFERENT wire dtype on an existing
    family stack is surfaced (metric + recorded error), not silent."""
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig

    inst = SiteWhereInstance(InstanceConfig(
        instance_id="wd",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
    ))
    await inst.start()
    try:
        await inst.tenant_management.create_tenant(
            "w1", template="iot-temperature", wire_dtype="bf16")
        await inst.tenant_management.create_tenant(
            "w2", template="iot-temperature", wire_dtype="f32")
        await inst.drain_tenant_updates()  # applies both adds synchronously
        assert "w2" in inst.tenants
        conflicts = inst.metrics.counter(
            "tpu_inference.wire_dtype_conflicts")
        assert conflicts.value == 1
        # the family runs at the FIRST tenant's wire (documented
        # first-wins) — on EVERY slice it is served from
        slices = inst.inference.scorers.family_items("lstm_ad")
        assert slices and all(
            sc.wire_dtype == "bf16" for _sl, sc in slices
        )
    finally:
        await inst.terminate()
