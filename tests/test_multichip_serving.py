"""Multi-chip serving acceptance (ISSUE 11, the MULTICHIP dryrun
pattern made production): real bus traffic through a 4×2 mesh instance
— four tenant-axis slices, each with its own scorer, staging pool, and
per-device reap queue — must score every tenant bitwise-identically to
a single-device reference instance, with zero collective primitives in
the per-slice hot-path jaxpr and per-device metric attribution live.

Runs on the forced-host 8-device CPU rig (tests/conftest.py sets
``--xla_force_host_platform_device_count=8`` before jax imports)."""

import asyncio
import importlib.util
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
)

_spec = importlib.util.spec_from_file_location(
    "check_fusion",
    Path(__file__).resolve().parent.parent / "tools" / "check_fusion.py",
)
check_fusion = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_fusion)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the forced 8-device rig"
)

TENANTS = ("m0", "m1", "m2", "m3")
MB = MicroBatchConfig(max_batch=256, deadline_ms=1.0, buckets=(64, 256),
                      window=8)
ROUNDS = 3
ROWS = 16


async def _wait_for(cond, timeout_s=30.0, interval=0.01):
    deadline = time.monotonic() + timeout_s
    while True:
        if cond():
            return True
        if time.monotonic() >= deadline:
            return False
        await asyncio.sleep(interval)


async def _build(inst: SiteWhereInstance) -> dict:
    """Create the four tenants and return per-tenant device tokens."""
    for t in TENANTS:
        await inst.tenant_management.create_tenant(
            t, template="iot-temperature", microbatch=MB,
            model_config={"hidden": 8}, max_streams=64, wire_dtype="f32",
        )
    await inst.drain_tenant_updates()
    assert await _wait_for(lambda: set(TENANTS) <= set(inst.tenants))
    return {
        t: [d.token
            for d in inst.tenants[t].device_management.bootstrap_fleet(4)]
        for t in TENANTS
    }


def _round_batch(tenant, toks, r):
    # deterministic values, 4 rows per stream per round
    return MeasurementBatch.from_columns(
        tenant, [toks[i % 4] for i in range(ROWS)],
        ["temperature"] * ROWS,
        [100.0 * r + float(i) for i in range(ROWS)],
        [0.0] * ROWS,
    )


async def _drive(inst, fleets) -> dict:
    """Publish ROUNDS rounds per tenant (serialized per round so flush
    grouping is identical across instances) and collect the scored
    batches per tenant, in delivery order."""
    group = "multichip-test"
    for t in TENANTS:
        inst.bus.subscribe(inst.bus.naming.scored_events(t), group)
    scored = inst.metrics.counter("tpu_inference.scored_total")
    expect = 0
    for r in range(ROUNDS):
        for t in TENANTS:
            await inst.bus.publish(
                inst.bus.naming.inbound_events(t),
                _round_batch(t, fleets[t], r),
            )
            expect += ROWS
        assert await _wait_for(
            lambda: scored.value >= expect
        ), f"round {r} never fully scored ({scored.value}/{expect})"
    out = {}
    for t in TENANTS:
        got = await inst.bus.consume(
            inst.bus.naming.scored_events(t), group, 64, timeout_s=0
        )
        out[t] = [b for b in got if isinstance(b, MeasurementBatch)]
    return out


async def test_mesh_serving_matches_single_device_bitwise():
    mesh_inst = SiteWhereInstance(InstanceConfig(
        instance_id="mesh8",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=1),
    ))
    # single-device reference: same tenants stacked on ONE chip
    ref_inst = SiteWhereInstance(
        InstanceConfig(instance_id="ref1",
                       mesh=MeshConfig(slots_per_shard=4)),
        mesh=MeshManager(tenant=1, data=1, devices=jax.devices()[:1]),
    )
    await mesh_inst.start()
    await ref_inst.start()
    try:
        mesh_fleets = await _build(mesh_inst)
        ref_fleets = await _build(ref_inst)
        svc = mesh_inst.inference
        # every tenant landed on its own slice (deterministic router)
        assert sorted(
            e.placement.shard for e in svc.engines.values()
        ) == [0, 1, 2, 3]
        assert sorted(k for k in svc.scorers) == [
            ("lstm_ad", sl) for sl in range(4)
        ]
        mesh_scored = await _drive(mesh_inst, mesh_fleets)
        ref_scored = await _drive(ref_inst, ref_fleets)
        total = 0
        for t in TENANTS:
            assert len(mesh_scored[t]) == len(ref_scored[t]) == ROUNDS, (
                t, len(mesh_scored[t]), len(ref_scored[t])
            )
            for mb_, rb_ in zip(mesh_scored[t], ref_scored[t]):
                a = np.asarray(mb_.scores)
                b = np.asarray(rb_.scores)
                # BITWISE per-tenant parity with the single-device stack
                assert a.tobytes() == b.tobytes(), (
                    f"tenant {t}: mesh scores diverge from single-device "
                    f"reference (max |d|="
                    f"{np.nanmax(np.abs(a - b))})"
                )
                assert np.isfinite(a).all()
                total += len(a)
        assert total == ROUNDS * ROWS * len(TENANTS)

        # --- per-device attribution: every slice's chip shows up -----
        m = mesh_inst.metrics
        dev_rows = {
            sl: m.counter(
                "tpu_inference_device_rows_total",
                device=svc.mm.slice_device_label(sl),
            ).value
            for sl in range(4)
        }
        assert all(v >= ROUNDS * ROWS for v in dev_rows.values()), dev_rows
        # device-labeled MFU accounts exist per slice (separate names —
        # never mixed into the per-family aggregate)
        for sl in range(4):
            assert m.counter(
                "tpu_device_flops_total", family="lstm_ad",
                device=svc.mm.slice_device_label(sl),
            ).value > 0
        # flight-recorder records name the slice AND the chip
        recs = mesh_inst.flightrec._rings[("flush", "lstm_ad")].records()
        assert recs
        seen_slices = {r.get("mesh_slice") for r in recs}
        assert seen_slices == {0, 1, 2, 3}
        assert all(r.get("device_label") for r in recs)

        # --- zero collectives in the per-slice hot-path jaxpr --------
        scorer = svc.scorers[("lstm_ad", 0)]
        t, d = scorer.n_slots, scorer.mm.n_data_shards
        b = 64
        ids = np.zeros((t, d * b), scorer.ids_np_dtype)
        vals = np.zeros((t, d * b), scorer.vals_np_dtype)
        counts = np.zeros((t, d), np.int32)
        staged = scorer.stage_inputs(ids, vals, counts)
        jaxpr = jax.make_jaxpr(scorer._step_counts)(
            scorer.kernel_params(), scorer.state, scorer.active, *staged
        )
        assert check_fusion.collective_eqns(jaxpr.jaxpr) == [], (
            "collective primitive on the serving hot path"
        )
        # ...and in the per-slice gather (the d2h compaction)
        plane = scorer.step_counts(*staged)
        gathered = scorer.gather_rows(plane, staged[2], 8)
        gj = jax.make_jaxpr(
            lambda s, c: scorer._gather_fn()(s, c, 64)
        )(plane, staged[2])
        assert check_fusion.collective_eqns(gj.jaxpr) == []
        del gathered
    finally:
        await mesh_inst.terminate()
        await ref_inst.terminate()


async def test_mesh_slices_flush_concurrently_with_own_staging():
    """Structural concurrency: each slice owns its staging pool and reap
    queue — four tenants' flushes populate four distinct (family, slice)
    queues and staging rotations, never one shared funnel."""
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="mesh8c",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=1),
    ))
    await inst.start()
    try:
        fleets = await _build(inst)
        await _drive(inst, fleets)
        svc = inst.inference
        staged_slices = {s.sl for s in svc._slices.values() if s.staging}
        assert staged_slices == {0, 1, 2, 3}, staged_slices
        assert set(svc._slices) == {("lstm_ad", sl) for sl in range(4)}
        # per-device deliver gauges exported (zero when drained)
        for sl in range(4):
            g = inst.metrics.gauge(
                "tpu_inference_deliver_inflight_device",
                device=svc.mm.slice_device_label(sl),
            )
            assert g.value == 0
    finally:
        await inst.terminate()
