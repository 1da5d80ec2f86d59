"""Continual-learning train lane tests (docs/PERFORMANCE.md "Continual
learning lane"): fused-vs-legacy grad parity on identical stacked
params, the TRAIN_LANE_ENABLED kill-switch restore of the inline path,
zero-stall hot-swap → canary arming with lane-tagged flightrec records,
overload arbitration (a throttled tenant trains exactly 0 steps while an
idle one trains at full rate), per-slice isolation (a saturated slice's
in-flight window defers training without stalling siblings), the
replay-fed microbatch loop end to end, and the check_fusion stacked-grad
lint (tier-1 import, like check_hotpath)."""

import asyncio
import importlib.util
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import sitewhere_tpu.parallel.sharded as sharded
from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
    OverloadPolicy,
    TrainingConfig,
)
from sitewhere_tpu.sim import DeviceSimulator, SimProfile
from tests._drive import drive_rounds

_spec = importlib.util.spec_from_file_location(
    "check_fusion_tl",
    Path(__file__).resolve().parent.parent / "tools" / "check_fusion.py",
)
check_fusion = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_fusion)

W, HID = 8, 8


async def _wait_for(cond, secs=20.0, tick=0.02):
    deadline = time.monotonic() + secs
    while time.monotonic() < deadline:
        if cond():
            return True
        await asyncio.sleep(tick)
    return bool(cond())


# ----------------------------------------------------------- scorer twins
def _build_scorer(family="lstm_ad", lane=True, wire_dtype="f32",
                  param_dtype="f32", seed=0):
    """Same seed everywhere ⇒ identical stacked params across twins."""
    prev = sharded.TRAIN_LANE_ENABLED
    sharded.TRAIN_LANE_ENABLED = lane
    try:
        mm = MeshManager(tenant=4, data=2)
        spec = get_model(family)
        over = {"hidden": HID, "dtype": "float32"}
        if family == "lstm_ad":
            over["window"] = W
        if family == "transformer":
            over = {"context": W, "dim": 16, "depth": 1, "heads": 2,
                    "dtype": "float32"}
        cfg = make_config(family, over)
        return sharded.ShardedScorer(
            mm, spec, cfg, slots_per_shard=2, max_streams=16, window=W,
            seed=seed, wire_dtype=wire_dtype, param_dtype=param_dtype,
        )
    finally:
        sharded.TRAIN_LANE_ENABLED = prev


def _warm(scorer, rounds=14, seed=7):
    """Identical window state on every twin: same streams, same values."""
    for i in range(rounds):
        rng = np.random.default_rng(seed + i)
        t, d = scorer.n_slots, scorer.mm.n_data_shards
        ids = np.zeros((t, d * 4), scorer.ids_np_dtype)
        vals = np.zeros((t, d * 4), scorer.vals_np_dtype)
        counts = np.zeros((t, d), np.int32)
        for ti in range(t):
            ids[ti, :4] = [0, 1, 0, 1]
            vals[ti, :4] = rng.normal(size=4)
            counts[ti, 0] = 4
        scorer.step_counts(*scorer.stage_inputs(ids, vals, counts))


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("wire_dtype", ["f32", "bf16", "f16"])
def test_fused_vs_legacy_grad_parity_lstm(wire_dtype):
    """One fused stacked train step must move the params (through the
    loss_stacked backward pass) to the same place the legacy per-slot
    vmap step does, on identical stacked params and window state — for
    every wire dtype the serving stack runs."""
    a = _build_scorer(lane=True, wire_dtype=wire_dtype)
    b = _build_scorer(lane=False, wire_dtype=wire_dtype)
    assert a.train_lane and not b.train_lane
    for s in (a, b):
        s.activate(0, trainable=True)
        s.activate(1, trainable=True)
        _warm(s)
        s.init_optimizer()
    la = np.asarray(a.train_lane_step())
    lb = np.asarray(b.train_resident())
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for x, y in zip(_leaves(a.params), _leaves(b.params)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)
    # optimizer state marched in lockstep too (Adam moments + count)
    for x, y in zip(_leaves(a._opt_state), _leaves(b._opt_state)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("family", ["deepar", "transformer"])
def test_fused_vs_legacy_grad_parity_other_families(family):
    a = _build_scorer(family=family, lane=True)
    b = _build_scorer(family=family, lane=False)
    assert a.train_lane and not b.train_lane
    for s in (a, b):
        s.activate(0, trainable=True)
        _warm(s)
        s.init_optimizer()
    paths = [
        jax.tree_util.keystr(k)
        for k, _ in jax.tree_util.tree_flatten_with_path(a.params)[0]
    ]
    before = [x.copy() for x in _leaves(a.params)]
    la = np.asarray(a.train_lane_step())
    lb = np.asarray(b.train_resident())
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-5)
    for path, p0, x, y in zip(
        paths, before, _leaves(a.params), _leaves(b.params)
    ):
        if path.endswith("['wk']['b']"):
            # attention's KEY bias has no gradient: one bias on every key
            # shifts all of a query's logits alike, and softmax cancels
            # it. What either program computes there is rounding noise
            # of a few 1e-8 around zero, and Adam's first step — g /
            # (|g| + 1e-8) at lr 1 — turns noise of that size into an
            # update anywhere inside (-1, 1): two correct programs differ
            # there by up to 0.08. A real gradient moves a parameter by
            # the whole lr; hold both twins to "no real gradient here"
            # (|g| < 1e-6) instead of to each other.
            assert np.abs(x - p0).max() < 0.99, path
            assert np.abs(y - p0).max() < 0.99, path
            continue
        np.testing.assert_allclose(x, y, rtol=1e-3, atol=1e-4)


def test_kill_switch_restores_legacy_train_program_bitwise():
    """TRAIN_LANE_ENABLED=False must dispatch training through EXACTLY
    the legacy step program: a kill-switch scorer's train_resident
    output equals a lane-ON twin's legacy ``_train`` (both flags build
    it from the same _build_train_step) invoked directly on identical
    state — bitwise, not approximately."""
    off = _build_scorer(lane=False)
    on = _build_scorer(lane=True)
    assert off._train_fused is None and not off.train_lane
    for s in (off, on):
        s.activate(0, trainable=True)
        _warm(s)
        s.init_optimizer()
    mask = np.ones((on.n_slots,), bool)
    l_off = np.asarray(off.train_resident())
    # drive the lane-ON scorer's LEGACY step directly (the program the
    # kill switch restores) on its identical params/opt/state
    p2, o2, l_ref = on._train(
        on.params, on._opt_state,
        on.state, on.active & on.train_mask & mask, on.slot_lr,
    )
    assert (l_off == np.asarray(l_ref)).all()
    for x, y in zip(_leaves(off.params), _leaves(p2)):
        assert (x == y).all(), "kill-switch params diverged from legacy"
    for x, y in zip(_leaves(off._opt_state), _leaves(o2)):
        assert (x == y).all(), "kill-switch opt state diverged from legacy"


# ------------------------------------------------------- instance harness
async def _instance(mesh=None, **tenants):
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="tlane",
        mesh=mesh or MeshConfig(tenant_axis=1, data_axis=1,
                                slots_per_shard=4),
    ))
    await inst.start()
    for name, overrides in tenants.items():
        await inst.tenant_management.create_tenant(
            name, template="iot-temperature",
            model_config={"hidden": 16},
            microbatch=MicroBatchConfig(
                max_batch=256, deadline_ms=1.0, buckets=(64, 256),
                window=16,
            ),
            max_streams=256,
            **overrides,
        )
    await inst.drain_tenant_updates()
    assert await _wait_for(
        lambda: all(t in inst.tenants for t in tenants)
    )
    for t in tenants:
        inst.tenants[t].device_management.bootstrap_fleet(6)
    return inst


async def test_kill_switch_service_path_stays_inline(monkeypatch):
    """With the kill switch off, the service must run the pre-lane
    inline cadence: train steps fire from the flush path at
    every_n_flushes, the async lane never engages, and no lane-only
    metric moves."""
    monkeypatch.setattr(sharded, "TRAIN_LANE_ENABLED", False)
    inst = await _instance(acme={"training": TrainingConfig(
        enabled=True, every_n_flushes=2, lr=5e-3)})
    try:
        sim = DeviceSimulator(
            inst.broker,
            SimProfile(n_devices=6, seed=1, samples_per_message=8,
                       noise=0.01, period_s=4.0),
            topic_pattern="sitewhere/input/{device}",
        )
        for r in range(50):
            await sim.publish_round(float(r) * 0.5)
            await asyncio.sleep(0.005)
        m = inst.metrics
        trains = m.counter("tpu_inference.train_steps")
        assert await _wait_for(lambda: trains.value >= 3)
        eng = inst.inference.engines["acme"]
        scorer = inst.inference.scorers[("lstm_ad", eng.placement.shard)]
        assert scorer.train_lane is False
        assert scorer._train_fused is None
        # lane-only signals stayed dark
        assert m.counter("tpu_train_steps_total", tenant="acme").value == 0
        assert m.counter(
            "tpu_train_swaps_total", family="lstm_ad"
        ).value == 0
        assert not any(
            s.train_lanes for s in inst.inference._slices.values()
        )
        # losses land via the inline path (device array, not reaper np)
        assert ("lstm_ad", eng.placement.shard) in (
            inst.inference.last_train_losses
        )
    finally:
        await inst.terminate()


async def test_hot_swap_arms_canary_and_flightrec_lane():
    """Every swap_every lane steps the trained weights commit: the
    kernel sidecar re-derives, the PR 9 canary arms, and the swap's
    flightrec record carries lane="train"."""
    inst = await _instance(acme={
        "training": TrainingConfig(
            enabled=True, every_n_flushes=2, lr=5e-3, swap_every=2,
        ),
        "param_dtype": "bf16",
        "canary_frac": 1.0,
    })
    try:
        sim = DeviceSimulator(
            inst.broker,
            SimProfile(n_devices=6, seed=2, samples_per_message=8,
                       noise=0.01, period_s=4.0),
            topic_pattern="sitewhere/input/{device}",
        )
        m = inst.metrics
        swaps = m.counter("tpu_train_swaps_total", family="lstm_ad")
        assert await drive_rounds(inst, sim, lambda: swaps.value >= 1), (
            m.counter("tpu_inference.train_steps").value,
            m.counter(
                "tpu_train_skipped_total", family="lstm_ad",
                reason="saturated",
            ).value,
        )
        eng = inst.inference.engines["acme"]
        scorer = inst.inference.scorers[("lstm_ad", eng.placement.shard)]
        assert scorer.train_lane
        # the commit armed the canary (post-swap shadow coverage)
        assert scorer._canary_countdown > 0
        rings = inst.flightrec.describe()["rings"]
        swap_recs = [
            r for v in rings.get("swap", {}).values()
            for r in v["records"]
        ]
        assert swap_recs, "swap must leave a flightrec record"
        assert all(r["lane"] == "train" for r in swap_recs)
        assert all(r["canary_armed"] for r in swap_recs)
        # train-step flush records ride the same rings, lane-tagged
        flush_recs = [
            r for v in rings.get("flush", {}).values()
            for r in v["records"]
        ]
        lanes = {r.get("lane") for r in flush_recs}
        assert "train" in lanes and "serve" in lanes
        ok_train = [r for r in flush_recs if r.get("lane") == "train"
                    and r.get("status") == "ok"]
        assert ok_train and all("device_s" in r for r in ok_train)
    finally:
        await inst.terminate()


def _json_payload(dev_i: int, values) -> bytes:
    import json

    return json.dumps({
        "device": f"dev-{dev_i:05d}",
        "events": [
            {"name": "temperature", "value": float(v)} for v in values
        ],
    }).encode()


async def _send_rounds(inst, tenant, rounds, base=0.0):
    rt = inst.tenants[tenant]
    for r in range(rounds):
        for dev in range(4):
            await rt.source.receiver.submit(
                _json_payload(dev, [base + r + 0.1 * i for i in range(8)]),
                topic=f"tl/{tenant}/input",
            )
        await asyncio.sleep(0.005)


async def test_overload_arbitration_hostile_trains_exactly_zero():
    """Serve/train arbitration, per tenant: a tenant whose overload
    credit never reaches 1 trains EXACTLY 0 steps while its idle
    neighbor in the same family stack trains at full rate."""
    # the hostile tenant's policy pins credit at 0 from the first
    # controller refresh (lag 0 already sits past the credit band)
    hostile_pol = OverloadPolicy(
        enabled=True, credit_lag_lo=-100, credit_lag_hi=-50,
    )
    inst = await _instance(
        good={"training": TrainingConfig(
            enabled=True, every_n_flushes=2, lr=5e-3)},
        hostile={
            "training": TrainingConfig(
                enabled=True, every_n_flushes=2, lr=5e-3),
            "overload": hostile_pol,
        },
    )
    try:
        assert await _wait_for(
            lambda: inst.overload.credit("hostile") < 1.0
        )
        m = inst.metrics
        good_steps = m.counter("tpu_train_steps_total", tenant="good")
        bad_steps = m.counter("tpu_train_steps_total", tenant="hostile")
        for burst in range(10):
            await _send_rounds(inst, "good", 5, base=burst * 10.0)
            await _send_rounds(inst, "hostile", 5, base=burst * 10.0)
            if good_steps.value >= 3:
                break
        assert await _wait_for(lambda: good_steps.value >= 3)
        assert bad_steps.value == 0, (
            "a throttled tenant must train exactly 0 steps"
        )
        assert m.counter(
            "tpu_train_skipped_total", family="lstm_ad",
            reason="throttled",
        ).value > 0
        # both tenants' SERVE traffic flowed throughout — arbitration
        # touched training only
        assert m.counter("tpu_inference.scored_total").value > 0
    finally:
        await inst.terminate()


async def test_saturated_slice_defers_training_without_stalling_siblings():
    """The lane only dispatches into a FREE in-flight permit: with one
    (family, slice)'s window exhausted its training parks (counted as
    reason="saturated") while another slice's serve + train lanes keep
    flowing — then resumes once permits free up."""
    inst = await _instance(acme={"training": TrainingConfig(
        enabled=True, every_n_flushes=1, lr=5e-3)})
    try:
        # second family (deepar via the forecasting template): its own
        # (family, slice) key ⇒ its own in-flight window on the same chip
        await inst.tenant_management.create_tenant(
            "fcst", template="forecasting",
            model_config={"hidden": 16, "context": 16},
            microbatch=MicroBatchConfig(
                max_batch=256, deadline_ms=1.0, buckets=(64, 256),
                window=16,
            ),
            max_streams=256,
            training=TrainingConfig(enabled=True, every_n_flushes=1,
                                    lr=5e-3),
        )
        await inst.drain_tenant_updates()
        assert await _wait_for(lambda: "fcst" in inst.tenants)
        inst.tenants["fcst"].device_management.bootstrap_fleet(6)
        svc = inst.inference
        m = inst.metrics
        # warm both tenants' serve paths through their own receivers
        await _send_rounds(inst, "acme", 10)
        await _send_rounds(inst, "fcst", 10)
        a_eng = svc.engines["acme"]
        key_a = ("lstm_ad", a_eng.placement.shard)
        assert await _wait_for(lambda: key_a in svc.scorers)
        # quiesce acme's serve lanes, then saturate its in-flight window
        # (as if that slice's serve dispatches owned every permit) and
        # force its cadence mature — the lane must PARK, not wait
        scored = m.counter("tpu_inference.scored_total")
        await _wait_for(lambda: scored.value > 0)
        await asyncio.sleep(0.2)
        sem = svc._slices[key_a].permits
        for _ in range(svc.max_inflight):
            await sem.acquire()
        svc._slices[key_a].train_ticks[a_eng.placement.slot] = 10_000
        a_steps0 = m.counter("tpu_train_steps_total", tenant="acme").value
        sat = m.counter(
            "tpu_train_skipped_total", family="lstm_ad",
            reason="saturated",
        )
        f_steps = m.counter("tpu_train_steps_total", tenant="fcst")
        f0 = f_steps.value
        # only the SIBLING family gets traffic: its serve flushes and
        # train steps must keep flowing while acme's lane parks
        await _send_rounds(inst, "fcst", 30, base=100.0)
        assert await _wait_for(lambda: sat.value > 0)
        assert m.counter(
            "tpu_train_steps_total", tenant="acme"
        ).value == a_steps0, "saturated slice must train exactly 0 steps"
        assert await _wait_for(lambda: f_steps.value > f0)
        # release: acme's still-mature tick trains on the next pass
        for _ in range(svc.max_inflight):
            sem.release()
        a_after = m.counter("tpu_train_steps_total", tenant="acme")
        await _send_rounds(inst, "fcst", 10, base=200.0)
        assert await _wait_for(lambda: a_after.value > a_steps0)
    finally:
        await inst.terminate()


def _history_batch(n, t0, tenant, n_devices=6):
    rng = np.random.default_rng(int(t0) % 2**31)
    toks = np.asarray(
        [f"dev-{i % n_devices}" for i in range(n)], object
    )
    return MeasurementBatch(
        tenant=tenant,
        stream_ids=np.zeros((n,), np.int32),
        values=rng.normal(21.0, 1.0, n).astype(np.float32),
        event_ts=np.arange(n, dtype=np.float64) + t0,
        received_ts=np.arange(n, dtype=np.float64) + t0,
        valid=np.ones((n,), bool),
        device_tokens=toks,
        names=np.full((n,), "temp", object),
    )


async def test_replay_fed_microbatches_end_to_end():
    """The loop the lane closes: scored history replays through the
    ``train`` target onto replay-train-feed, the scoring loop's intake
    routes it into train lane rings, microbatches pack through the
    staging → h2d wire into the train feed windows, and fused train
    steps run on history the resident state never saw."""
    inst = await _instance(acme={"training": TrainingConfig(
        enabled=True, every_n_flushes=10_000,  # cadence can't fire —
        # every step this test sees is replay-fed
        lr=5e-3, replay_microbatch=128,
    )})
    try:
        store = inst.tenants["acme"].event_store
        now = time.time() * 1000.0
        n = 1024
        for off in range(0, n, 256):
            b = _history_batch(256, now - 10_000 + off, "acme")
            b.scores = np.abs(
                np.random.default_rng(off).normal(size=256)
            ).astype(np.float32)  # already-scored history
            store.add_measurement_batch(b)
        store.measurements._seal()
        m = inst.metrics
        rows = m.counter("tpu_train_rows_total", family="lstm_ad")
        steps = m.counter("tpu_train_steps_total", tenant="acme")
        job = inst.replay.start_job("acme", store, target="train")
        assert await _wait_for(lambda: job.status == "done", secs=30)
        assert job.replayed == n
        assert await _wait_for(lambda: rows.value >= n, secs=30)
        assert await _wait_for(lambda: steps.value >= 1)
        eng = inst.inference.engines["acme"]
        scorer = inst.inference.scorers[("lstm_ad", eng.placement.shard)]
        # history landed in the TRAIN feed windows, not the serve state
        feed = scorer._train_feed_state
        assert feed is not None
        assert int(np.asarray(feed.count).sum()) >= n
        assert int(np.asarray(scorer.state.count).sum()) == 0
        # flightrec train records name the replay source
        rings = inst.flightrec.describe()["rings"]
        train_recs = [
            r for v in rings.get("flush", {}).values()
            for r in v["records"] if r.get("lane") == "train"
        ]
        assert any(r.get("source") == "replay" for r in train_recs)
        assert sum(
            r.get("rows", 0) for r in train_recs
            if r.get("source") == "replay"
        ) == n
        # rings drained; depth gauge reads 0
        assert m.gauge(
            "tpu_inference_train_rows", family="lstm_ad"
        ).value == 0
        # lane self-pacing (its own step in the reap FIFO) must not
        # read as serve saturation — no serve traffic ran here at all
        assert m.counter(
            "tpu_train_skipped_total", family="lstm_ad",
            reason="saturated",
        ).value == 0
    finally:
        await inst.terminate()


async def test_prewarmed_lane_first_dispatch_reports_no_compile():
    """Review regression: prewarm compiles the lane's executables, so
    the first real train dispatch must not report a compile — a false
    `compiled: true` would fire the steady_state_recompile watchdog the
    moment a routine replay train job starts."""
    inst = await _instance(acme={"training": TrainingConfig(
        enabled=True, every_n_flushes=10_000, lr=5e-3,
        replay_microbatch=64,
    )})
    try:
        await asyncio.get_running_loop().run_in_executor(
            None, inst.inference.prewarm
        )
        m = inst.metrics
        compiles0 = m.counter("tpu_inference.compiles").value
        topic = inst.bus.naming.train_feed("acme")
        now = time.time() * 1000.0
        await inst.bus.publish(topic, _history_batch(256, now, "acme"))
        steps = m.counter("tpu_train_steps_total", tenant="acme")
        assert await _wait_for(lambda: steps.value >= 1)
        assert m.counter("tpu_inference.compiles").value == compiles0, (
            "prewarmed train lane must not count a steady-state compile"
        )
        rings = inst.flightrec.describe()["rings"]
        train_recs = [
            r for v in rings.get("flush", {}).values()
            for r in v["records"] if r.get("lane") == "train"
        ]
        assert train_recs and not any(
            r.get("compiled") for r in train_recs
        )
    finally:
        await inst.terminate()


async def test_replay_backfill_does_not_starve_resident_cadence():
    """Review regression: a long replay backfill holding feed_rows ≥
    microbatch must not starve a co-tenant's mature resident cadence —
    the lane alternates sources when both are pending."""
    inst = await _instance(
        mesh=MeshConfig(tenant_axis=1, data_axis=8, slots_per_shard=4),
        feda={"training": TrainingConfig(
            enabled=True, every_n_flushes=10_000, lr=5e-3,
            replay_microbatch=64,
        )},
        live={"training": TrainingConfig(
            enabled=True, every_n_flushes=1, lr=5e-3,
            replay_microbatch=64,
        )},
    )
    try:
        m = inst.metrics
        topic = inst.bus.naming.train_feed("feda")
        now = time.time() * 1000.0
        live_steps = m.counter("tpu_train_steps_total", tenant="live")
        rows = m.counter("tpu_train_rows_total", family="lstm_ad")
        # keep feda's feed saturated while live serve traffic matures
        # the co-tenant's cadence ticks
        for burst in range(12):
            await inst.bus.publish(
                topic, _history_batch(128, now + burst, "feda")
            )
            await _send_rounds(inst, "live", 3, base=burst * 10.0)
        assert await _wait_for(lambda: rows.value >= 128), (
            "replay lane never consumed the backfill"
        )
        assert await _wait_for(lambda: live_steps.value >= 1), (
            "resident cadence starved behind the replay backfill"
        )
    finally:
        await inst.terminate()


async def test_inline_step_on_mixed_stack_commits_pending_lane_steps():
    """Review regression: on a stack mixing lane and inline tenants, an
    inline train_resident invalidates the shared kernel sidecar — which
    publishes the lane tenants' in-flight weights to serving — so it
    must COUNT as a commit (canary armed, swap counted and recorded),
    not silently bypass the swap contract."""
    inst = await _instance(
        mesh=MeshConfig(tenant_axis=1, data_axis=8, slots_per_shard=4),
        lane={"training": TrainingConfig(
            enabled=True, every_n_flushes=10_000, lr=5e-3,
            replay_microbatch=64, swap_every=1_000,  # cadence commit
            # can't fire — only the inline step may commit here
        )},
        inline={"training": TrainingConfig(
            enabled=True, every_n_flushes=2, lr=5e-3, train_lane=False,
        )},
    )
    try:
        svc = inst.inference
        m = inst.metrics
        eng = svc.engines["lane"]
        key = (eng.config.model, eng.placement.shard)
        topic = inst.bus.naming.train_feed("lane")
        now = time.time() * 1000.0
        await inst.bus.publish(topic, _history_batch(128, now, "lane"))
        lane_steps = m.counter("tpu_train_steps_total", tenant="lane")
        assert await _wait_for(lambda: lane_steps.value >= 1)
        assert await _wait_for(lambda: svc._slices[key].lane_swap > 0)
        swaps = m.counter("tpu_train_swaps_total", family="lstm_ad")
        s0 = swaps.value
        # the inline tenant's cadence fires off serve flushes
        await _send_rounds(inst, "inline", 10)
        assert await _wait_for(lambda: swaps.value > s0), (
            "inline sidecar invalidation bypassed the swap contract"
        )
        assert svc._slices[key].lane_swap == 0
        rings = inst.flightrec.describe()["rings"]
        srecs = [
            r for v in rings.get("swap", {}).values()
            for r in v["records"]
        ]
        assert any(r.get("inline") for r in srecs)
    finally:
        await inst.terminate()


async def test_slice_move_drops_stale_train_rows():
    """Review regression: a failover/rebalance move must drop the
    tenant's pending train rows keyed to the OLD (slot, data-shard) —
    the next tenant placed on that slot must never train on another
    tenant's replayed data — and clear the old slot's cadence tick."""
    inst = await _instance(acme={"training": TrainingConfig(
        enabled=True, every_n_flushes=10_000, lr=5e-3,
        replay_microbatch=100_000,  # rows buffer, never dispatch
    )})
    try:
        svc = inst.inference
        eng = svc.engines["acme"]
        old_p = eng.placement
        key_old = (eng.config.model, old_p.shard)
        topic = inst.bus.naming.train_feed("acme")
        now = time.time() * 1000.0
        await inst.bus.publish(topic, _history_batch(256, now, "acme"))
        gauge = inst.metrics.gauge(
            "tpu_inference_train_rows", family=eng.config.model
        )
        assert await _wait_for(lambda: gauge.value >= 256)
        old = svc._slices[key_old]
        old.train_ticks[old_p.slot] = 9_999
        assert await svc._failover_tenant(eng)
        assert eng.placement.shard != old_p.shard or (
            eng.placement.slot != old_p.slot
        )
        stale = [k for k in old.train_lanes if k[0] == old_p.slot]
        assert not stale, "train rows survived the slice move"
        assert gauge.value == 0
        assert old_p.slot not in old.train_ticks, (
            "stale cadence tick survived the move"
        )
    finally:
        await inst.terminate()


async def test_engine_stop_clears_train_cursor_and_gauge():
    """Review regression: an engine stop must deregister its train-feed
    group cursor (a stale registered group never advances and would
    backpressure the topic forever — wedging any later replay train
    job) and must not leave a phantom ring-depth gauge reading."""
    inst = await _instance(acme={"training": TrainingConfig(
        enabled=True, every_n_flushes=10_000, lr=5e-3,
        replay_microbatch=100_000,  # rings hold rows, never dispatch
    )})
    try:
        topic = inst.bus.naming.train_feed("acme")
        assert inst.bus.topic(topic).group_offsets, (
            "lane-on tenant must subscribe its feed"
        )
        now = time.time() * 1000.0
        await inst.bus.publish(topic, _history_batch(256, now, "acme"))
        m = inst.metrics
        gauge = m.gauge("tpu_inference_train_rows", family="lstm_ad")
        assert await _wait_for(lambda: gauge.value >= 256)
        await inst.inference.remove_tenant("acme")
        assert not inst.bus.topic(topic).group_offsets, (
            "stopped engine left a stale train-feed cursor — later "
            "replay train jobs would wedge on its backpressure"
        )
        assert gauge.value == 0, "phantom train-ring depth after stop"
    finally:
        await inst.terminate()


async def test_skip_counter_no_trainer():
    """A tenant that opts into training on a family without a loss
    contract must not be dark: the skip counter names the reason."""
    inst = await _instance(acme={"training": TrainingConfig(
        enabled=True, every_n_flushes=1, lr=5e-3)})
    try:
        eng = inst.inference.engines["acme"]
        scorer = inst.inference.scorers[("lstm_ad", eng.placement.shard)]
        # simulate a loss-less family (e.g. a scorer-only model)
        import dataclasses

        scorer.spec = dataclasses.replace(scorer.spec, loss=None)
        sim = DeviceSimulator(
            inst.broker,
            SimProfile(n_devices=6, seed=5, samples_per_message=8),
            topic_pattern="sitewhere/input/{device}",
        )
        for r in range(10):
            await sim.publish_round(float(r))
            await asyncio.sleep(0.005)
        skip = inst.metrics.counter(
            "tpu_train_skipped_total", family="lstm_ad",
            reason="no_trainer",
        )
        assert await _wait_for(lambda: skip.value > 0)
    finally:
        await inst.terminate()


async def test_lane_off_replay_train_job_completes(monkeypatch):
    """Review regression: with the lane OFF (tenant opt-out or kill
    switch) the train-feed topic must stay UNSUBSCRIBED — a registered
    group with no consumer engages the bus's publish backpressure and a
    replay train job would wedge forever once the topic fills. Off-lane,
    the topic keeps its lossy retention tail and the job completes."""
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="tlane-off",
        mesh=MeshConfig(tenant_axis=1, data_axis=1, slots_per_shard=4),
        bus_retention=256,  # tiny: the job MUST outrun retention
    ))
    await inst.start()
    try:
        await inst.tenant_management.create_tenant(
            "acme", template="iot-temperature",
            model_config={"hidden": 16},
            microbatch=MicroBatchConfig(
                max_batch=256, deadline_ms=1.0, buckets=(64, 256),
                window=16,
            ),
            max_streams=256,
            training=TrainingConfig(
                enabled=True, every_n_flushes=2, train_lane=False,
            ),
        )
        await inst.drain_tenant_updates()
        assert await _wait_for(lambda: "acme" in inst.tenants)
        store = inst.tenants["acme"].event_store
        now = time.time() * 1000.0
        n = 4096  # rows >> retention × batch size
        for off in range(0, n, 512):
            b = _history_batch(512, now - 10_000 + off, "acme")
            b.scores = np.ones((512,), np.float32)
            store.add_measurement_batch(b)
        store.measurements._seal()
        topic = inst.bus.naming.train_feed("acme")
        assert not inst.bus.topic(topic).group_offsets, (
            "train feed must not be subscribed while the lane is off"
        )
        job = inst.replay.start_job("acme", store, target="train")
        assert await _wait_for(lambda: job.status == "done", secs=30), (
            f"train replay wedged with the lane off: {job.report()}"
        )
        assert job.replayed == n
    finally:
        await inst.terminate()


async def test_replay_step_trains_only_fed_slots():
    """Review regression: an admitted co-tenant whose feed holds ZERO
    replayed rows must not take a zero-gradient optimizer step when its
    neighbor's microbatch dispatches — stale Adam momentum would move
    its weights with no data, and its bias-correction count would
    inflate."""
    inst = await _instance(
        # data_axis=8 pins the tenant axis to ONE shard on the 8-device
        # test rig, so both tenants share a single (family, slice) stack
        mesh=MeshConfig(tenant_axis=1, data_axis=8, slots_per_shard=4),
        feda={"training": TrainingConfig(
            enabled=True, every_n_flushes=10_000, lr=5e-3,
            replay_microbatch=64,
        )},
        idle={"training": TrainingConfig(
            enabled=True, every_n_flushes=10_000, lr=5e-3,
            replay_microbatch=64,
        )},
    )
    try:
        m = inst.metrics
        eng_a = inst.inference.engines["feda"]
        eng_b = inst.inference.engines["idle"]
        assert eng_a.config.model == eng_b.config.model
        assert eng_a.placement.shard == eng_b.placement.shard, (
            "test precondition: both tenants must share one slice stack"
        )
        assert eng_a.placement.slot != eng_b.placement.slot
        sc = inst.inference.scorers[
            (eng_a.config.model, eng_a.placement.shard)
        ]
        base = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            sc.slot_params(eng_b.placement.slot)
        )]
        # feed ONLY tenant feda through its train-feed topic
        topic = inst.bus.naming.train_feed("feda")
        now = time.time() * 1000.0
        for off in range(0, 512, 128):
            b = _history_batch(128, now + off, "feda")
            await inst.bus.publish(topic, b)
        a_steps = m.counter("tpu_train_steps_total", tenant="feda")
        assert await _wait_for(lambda: a_steps.value >= 1)
        await asyncio.sleep(0.2)
        assert m.counter(
            "tpu_train_steps_total", tenant="idle"
        ).value == 0, "unfed co-tenant must not be credited train steps"
        after = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            sc.slot_params(eng_b.placement.slot)
        )]
        for x, y in zip(base, after):
            assert (x == y).all(), (
                "unfed co-tenant's weights moved on a zero-grad step"
            )
        # the fed tenant's weights DID move
        a_after = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            sc.slot_params(eng_a.placement.slot)
        )]
        a_base = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            sc._base_params
        )]
        assert any(
            np.abs(x - y).max() > 0 for x, y in zip(a_after, a_base)
        )
    finally:
        await inst.terminate()


# ------------------------------------------------------------------ lints
def test_train_fusion_lint_clean():
    assert check_fusion.lint_train_fusion() == []


def test_train_fusion_lint_catches_stale_registry():
    findings = check_fusion.lint_train_fusion({"vit_b16": {}})
    assert findings and "loss_stacked" in findings[0]
    findings = check_fusion.lint_train_fusion({"no_such_family": {}})
    assert findings and "not in MODEL_REGISTRY" in findings[0]
