"""Fault-domain supervision (ISSUE 14): flush deadlines, hung-device
quarantine + probation re-adoption, poison-batch ejection, and the
injectable device-fault layer (docs/ROBUSTNESS.md "Device fault
domains").

Unit coverage for the new pieces (DeviceFaultPlan, RollingQuantile,
router quarantine, CircuitBreaker.trip, the check_supervised lint, the
flush_timeout watchdog rule, replay recover_unscored) plus tier-1
service-level drives: a hung transfer force-resolves within its
deadline and the slice heals through probation; a fleet sized exactly
to capacity degrades to unscored pass-through with zero loss and
RECOVERS scored delivery once probation re-admits (the PR 10
verify-drive finding, now tested); a poison batch ejects to the
scorer-poison DLQ after two chips agree and the tenant keeps serving.
The full 4×2-mesh live-traffic matrix lives in tests/test_device_chaos.py
(chaos marker, tools/run_chaos.sh MESH_ONLY=1)."""

import asyncio
import importlib.util
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.parallel.tenant_router import PlacementError, TenantRouter
from sitewhere_tpu.runtime.bus import CircuitBreaker
from sitewhere_tpu.runtime.config import (
    FaultTolerancePolicy,
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
)
from sitewhere_tpu.runtime.faultplan import (
    DeviceFault,
    DeviceFaultPlan,
    FaultyResult,
    InjectedDeviceFault,
)
from sitewhere_tpu.runtime.metrics import MetricsRegistry, RollingQuantile

_spec = importlib.util.spec_from_file_location(
    "check_supervised",
    Path(__file__).resolve().parent.parent / "tools" / "check_supervised.py",
)
check_supervised = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_supervised)


async def _wait_for(cond, timeout_s=30.0, interval=0.02):
    deadline = time.monotonic() + timeout_s
    while True:
        if cond():
            return True
        if time.monotonic() >= deadline:
            return False
        await asyncio.sleep(interval)


# ------------------------------------------------------------- faultplan
def test_fault_plan_selectors_nth_and_budget():
    plan = DeviceFaultPlan(
        DeviceFault("slow_chip", families=("lstm_ad",), slices=(1,),
                    lanes=("serve",), nth=2, first_n=2),
    )
    # wrong family / slice / lane: no draw
    assert plan.match("deepar", 1, "serve") is None
    assert plan.match("lstm_ad", 0, "serve") is None
    assert plan.match("lstm_ad", 1, "train") is None
    # nth=2: 1st matching flush passes, 2nd fires
    assert plan.match("lstm_ad", 1, "serve") is None
    assert plan.match("lstm_ad", 1, "serve") is not None
    # budget first_n=2: one more firing, then exhausted forever
    assert plan.match("lstm_ad", 1, "serve") is None
    assert plan.match("lstm_ad", 1, "serve") is not None
    for _ in range(6):
        assert plan.match("lstm_ad", 1, "serve") is None
    assert plan.injected == 2
    # clear() drops everything
    plan2 = DeviceFaultPlan(DeviceFault("corrupt_result"))
    plan2.clear()
    assert plan2.match("lstm_ad", 0, "serve") is None


def test_faulty_result_fault_behaviors():
    arr = np.ones((4,), np.float32)

    # corrupt_result: the transfer "lands" full of NaN
    plan = DeviceFaultPlan(DeviceFault("corrupt_result"))
    out = plan.wrap(arr, "lstm_ad", 0, "serve")
    assert isinstance(out, FaultyResult)
    got = np.asarray(out)
    assert got.shape == (4,) and np.all(np.isnan(got))

    # fail_after_delay: looks in-flight, then raises
    plan = DeviceFaultPlan(DeviceFault("fail_after_delay", delay_s=0.01))
    out = plan.wrap(arr, "lstm_ad", 0, "serve")
    with pytest.raises(InjectedDeviceFault):
        np.asarray(out)

    # fail_dispatch raises at the dispatch site, not on wrap — and a
    # wrap() draw must NOT consume its budget on an inert proxy (every
    # dispatch site wraps right after maybe_raise)
    plan = DeviceFaultPlan(DeviceFault("fail_dispatch", first_n=1))
    assert plan.wrap(arr, "lstm_ad", 0, "serve") is arr
    with pytest.raises(InjectedDeviceFault):
        plan.maybe_raise("lstm_ad", 0, "serve")
    plan.maybe_raise("lstm_ad", 0, "serve")  # budget spent: no raise

    # hang_dispatch: never ready, materialization parks until clear()
    plan = DeviceFaultPlan(DeviceFault("hang_dispatch"))
    out = plan.wrap(arr, "lstm_ad", 0, "serve")
    assert out.is_ready() is False
    landed = []
    th = threading.Thread(target=lambda: landed.append(np.asarray(out)))
    th.start()
    th.join(timeout=0.1)
    assert th.is_alive(), "hung materialization returned early"
    plan.clear()
    th.join(timeout=5.0)
    assert not th.is_alive() and len(landed) == 1


def test_rolling_quantile_window_and_cache():
    rq = RollingQuantile(window=32, refresh_every=1)
    for v in range(RollingQuantile.MIN_SAMPLES - 1):
        rq.add(float(v))
    assert rq.quantile() is None  # under MIN_SAMPLES the floor rules
    for v in range(100):
        rq.add(float(v))
    # window keeps only the last 32 samples: p99 ~ the recent max
    assert rq.quantile() >= 97.0


# ------------------------------------------------- router quarantine
def test_router_quarantine_placement_failover_rebalance():
    r = TenantRouter(n_shards=2, slots_per_shard=2)
    r.quarantine("lstm_ad", 0)
    # placement routes around the SUSPECT shard
    assert r.place("a", "lstm_ad").shard == 1
    assert r.place("b", "lstm_ad").shard == 1
    # ...but a full fleet still places (degraded beats unplaceable)
    assert r.place("c", "lstm_ad").shard == 0
    # failover never LANDS on a quarantined shard: b can only go to 0,
    # which is quarantined -> PlacementError (stays in place, degraded)
    r.remove("c")
    with pytest.raises(PlacementError):
        r.failover("b")
    # rebalance neither drains nor feeds quarantined shards
    r.rebalance("lstm_ad")
    assert r.placement("a").shard == 1
    assert r.describe()["quarantined"] == {"lstm_ad": [0]}
    # readmit: shard serves again and failover can land there
    r.readmit("lstm_ad", 0)
    assert r.quarantined("lstm_ad") == set()
    assert r.failover("b").shard == 0


def test_breaker_trip_forces_open():
    b = CircuitBreaker("t", metrics=MetricsRegistry())
    assert b.allow()
    b.trip()  # no outcomes recorded: a hung device never raises
    assert not b.allow()


# ----------------------------------------------- check_supervised lint
def test_check_supervised_lint_is_clean():
    assert check_supervised.lint_supervised() == []


def test_check_supervised_catches_unsupervised_awaits():
    src = (
        "class S:\n"
        "    async def bad(self):\n"
        "        await loop.run_in_executor(pool, fn)\n"
        "    async def empty_optout(self):\n"
        "        await pf.ensure_host_future(loop, pool)  "
        "# supervised: ok()\n"
        "    async def named_optout(self):\n"
        "        await asyncio.wait(futs)  "
        "# supervised: ok(flush-deadline timer)\n"
        "    async def wrapped(self):\n"
        "        await asyncio.wait_for(loop.run_in_executor(p, f), 1.0)\n"
    )
    fns = ["S.bad", "S.empty_optout", "S.named_optout", "S.wrapped",
           "S.gone"]
    findings = check_supervised.lint_source(src, fns, "x.py")
    assert len(findings) == 3
    assert any("bad" in f and "without a deadline" in f for f in findings)
    assert any("empty_optout" in f and "names no" in f for f in findings)
    assert any("'S.gone' not found" in f for f in findings)
    # wait_for-wrapped and watchdog-named awaits are clean
    assert not any("named_optout" in f or "wrapped" in f for f in findings)


# --------------------------------------------- watchdog flush_timeout
def test_watchdog_flush_timeout_rule():
    from sitewhere_tpu.runtime.flightrec import FlightRecorder
    from sitewhere_tpu.runtime.history import MetricsHistory, Watchdog
    from sitewhere_tpu.runtime.tracing import Tracer, TracingConfig

    reg = MetricsRegistry()
    t = {"now": 0.0}
    hist = MetricsHistory(reg, capacity=600, clock=lambda: t["now"])
    fr = FlightRecorder(min_snapshot_interval_s=0.0, clock=lambda: t["now"])
    tracer = Tracer(reg, default=TracingConfig(sample_rate=0.0))
    wd = Watchdog(
        reg, hist, flightrec=fr, tracer=tracer, clock=lambda: t["now"],
        warmup=5, window=3, cooldown_s=10.0, flush_timeout_min=3,
    )
    c = reg.counter("tpu_flush_timeout_total", family="lstm_ad", slice="2")
    for i in range(8):
        t["now"] = float(i)
        hist.sample()
        assert all(a["rule"] != "flush_timeout" for a in wd.evaluate())
    c.inc(2)  # below flush_timeout_min: quiet
    t["now"] = 8.0
    hist.sample()
    assert all(a["rule"] != "flush_timeout" for a in wd.evaluate())
    c.inc(3)  # sustained timeouts inside the window
    t["now"] = 9.0
    hist.sample()
    fired = [a for a in wd.evaluate() if a["rule"] == "flush_timeout"]
    assert len(fired) == 1
    assert "lstm_ad@s2" in fired[0]["detail"]
    assert fired[0]["family"] == "lstm_ad"
    assert fired[0]["slice"] == "2"
    # snapshot names the slice
    assert any(
        s["reason"] == "watchdog:flush_timeout" for s in fr.snapshots()
    )
    # cooldown: the persisting condition does not re-alert
    c.inc(3)
    t["now"] = 10.0
    hist.sample()
    assert all(a["rule"] != "flush_timeout" for a in wd.evaluate())


# ------------------------------------------- replay recover_unscored
async def test_replay_recover_unscored_rewinds_hard_killed_rescore(tmp_path):
    from sitewhere_tpu.pipeline.replay import ReplayEngine
    from sitewhere_tpu.runtime.bus import EventBus, TopicNaming
    from sitewhere_tpu.services.event_store import EventStore

    def batch(n, t0):
        rng = np.random.RandomState(int(t0) % 65536)
        return MeasurementBatch(
            tenant="t1",
            stream_ids=np.zeros((n,), np.int32),
            values=rng.rand(n).astype(np.float32),
            event_ts=t0 + np.arange(n, dtype=np.float64),
            received_ts=t0 + np.arange(n, dtype=np.float64) + 5.0,
            valid=np.ones((n,), bool),
            device_tokens=np.array([f"dev-{i % 4}" for i in range(n)],
                                   object),
            names=np.full((n,), "temp", object),
        )

    bus = EventBus(TopicNaming("rp"))
    store = EventStore("t1", rows_per_segment=256)
    for k in range(3):
        store.add_measurement_batch(batch(256, 1000 + 256 * k))
    store.measurements._seal()
    topic = bus.naming.inbound_events("t1")
    bus.subscribe(topic, "replay-test")
    eng1 = ReplayEngine(bus, MetricsRegistry(), state_dir=tmp_path,
                        batch_rows=64)
    job1 = eng1.start_job("t1", store)
    assert await _wait_for(lambda: job1.replayed >= 128, 30.0, 0.0)
    await eng1.stop()
    # graceful stop persisted "paused"; fake the HARD kill: the process
    # died mid-run, so the file still says "running"
    path = tmp_path / f"{job1.job_id}.json"
    state = json.loads(path.read_text())
    assert state["status"] == "paused" and state["cursor"] > 0
    state["status"] = "running"
    path.write_text(json.dumps(state))

    m2 = MetricsRegistry()
    eng2 = ReplayEngine(bus, m2, state_dir=tmp_path, batch_rows=64)
    assert eng2.resume_jobs({"t1": store}, recover_unscored=True) == 1
    job2 = eng2.jobs[job1.job_id]
    # the cursor REWOUND to the window start: the resumed job IS the
    # only_unscored rescore of the whole window, so the NaN window a
    # hard kill left (published, never written back) re-publishes
    assert m2.counter("replay_recovered_windows_total",
                      tenant="t1").value == 1
    assert await _wait_for(lambda: job2.status == "done")
    # the rewound life re-published the FULL window on top of the
    # pre-crash count (the accounting trade documented on resume_jobs)
    assert job2.replayed == state["replayed"] + 3 * 256
    await eng2.stop()

    # a PAUSED file (graceful stop) is never rewound even with the
    # knob on: the guarantee boundary only leaks on non-graceful death
    path2 = tmp_path / f"{job1.job_id}.json"
    if not path2.exists():  # terminal jobs retire their files
        state["status"] = "paused"
        state["cursor"] = 128
        path2.write_text(json.dumps(state))
        m3 = MetricsRegistry()
        eng3 = ReplayEngine(bus, m3, state_dir=tmp_path, batch_rows=64)
        assert eng3.resume_jobs({"t1": store}, recover_unscored=True) == 1
        assert m3.counter("replay_recovered_windows_total",
                          tenant="t1").value == 0
        assert eng3.jobs[job1.job_id].cursor >= 128
        await eng3.stop()


# ------------------------------------------ service-level supervision
_FT = FaultTolerancePolicy(
    flush_deadline_ms=500.0,
    flush_deadline_x=8.0,
    probation_probes=2,
    probe_interval_s=0.05,
    backoff_base_s=0.002,
    backoff_max_s=0.02,
)
_MB = MicroBatchConfig(max_batch=64, deadline_ms=1.0, buckets=(32, 64),
                       window=8)
_ROWS = 16


async def _instance(instance_id, tenants, slots_per_shard=2):
    inst = SiteWhereInstance(InstanceConfig(
        instance_id=instance_id,
        mesh=MeshConfig(tenant_axis=2, data_axis=1,
                        slots_per_shard=slots_per_shard),
    ))
    await inst.start()
    for t in tenants:
        await inst.tenant_management.create_tenant(
            t, template="iot-temperature", microbatch=_MB,
            model_config={"hidden": 8}, max_streams=64,
            fault_tolerance=_FT,
        )
    await inst.drain_tenant_updates()
    assert await _wait_for(lambda: set(tenants) <= set(inst.tenants))
    fleets = {
        t: [d.token
            for d in inst.tenants[t].device_management.bootstrap_fleet(4)]
        for t in tenants
    }
    return inst, fleets


def _round_batch(tenant, toks, r):
    return MeasurementBatch.from_columns(
        tenant, [toks[i % len(toks)] for i in range(_ROWS)],
        ["temperature"] * _ROWS,
        [100.0 * r + float(i) for i in range(_ROWS)],
        [0.0] * _ROWS,
    )


async def _publish(inst, tenant, toks, r):
    await inst.bus.publish(
        inst.bus.naming.inbound_events(tenant),
        _round_batch(tenant, toks, r),
    )


def _dlq_rows(inst, tenant, stage="scorer-poison"):
    topic = inst.bus.naming.dead_letter(tenant, stage)
    if topic not in inst.bus.topics():
        return 0
    n = 0
    for _off, entry in inst.bus.peek(topic, 100000)["entries"]:
        payload = entry.get("payload") if isinstance(entry, dict) else None
        rows = getattr(payload, "n", None)
        if rows:
            n += int(rows)
    return n


def _timeouts(svc):
    return sum(
        v for v in svc.metrics.snapshot_families(
            ("tpu_flush_timeout_total",)
        ).values()
        if isinstance(v, (int, float))
    )


async def test_hung_transfer_force_resolves_and_probation_readmits():
    """The tentpole, end to end on a 2-slice mesh: a transfer that
    never lands blows its flush deadline -> the rows force-resolve in
    their FIFO slot (zero loss), the slice quarantines (breaker trip +
    flightrec snapshot + timeout counter), the tenant fails over, and
    once the fault clears probation probes re-admit the slice."""
    inst, fleets = await _instance("dfh", ["acme"])
    try:
        svc = inst.inference
        engine = svc.engines["acme"]
        sl0 = engine.placement.shard
        scored = inst.metrics.counter("tpu_inference.scored_total")
        persisted = inst.metrics.counter("event_management.persisted")
        sent = 0
        for r in range(3):  # healthy warm-up: shapes compiled, p99 fed
            await _publish(inst, "acme", fleets["acme"], r)
            sent += _ROWS
        assert await _wait_for(lambda: scored.value >= sent)

        plan = DeviceFaultPlan(DeviceFault(
            "hang_transfer", families=("lstm_ad",), slices=(sl0,),
            lanes=("serve",), first_n=1,
        ))
        svc.faultplan = plan
        deadline_s = svc._slices[("lstm_ad", sl0)].flush_deadline_s(
            svc._family_ft("lstm_ad")
        )
        assert deadline_s is not None
        t0 = time.monotonic()
        await _publish(inst, "acme", fleets["acme"], 10)
        sent += _ROWS
        # the wedged flush force-resolves within its deadline + one
        # reap tick (generous slack for the 2-core CI rig)
        assert await _wait_for(lambda: _timeouts(svc) >= 1, 30.0)
        assert time.monotonic() - t0 <= deadline_s + 10.0
        assert inst.metrics.counter(
            "tpu_flush_timeout_total", family="lstm_ad", slice=str(sl0)
        ).value >= 1
        # SUSPECT: quarantined + snapshot; tenant failed over
        assert inst.metrics.counter("tpu_inference.quarantined").value >= 1
        assert any(
            s["reason"] == "flush-timeout:lstm_ad"
            for s in svc.flightrec.snapshots()
        )
        assert await _wait_for(
            lambda: engine.placement.shard != sl0, 15.0
        ), "tenant never failed over off the wedged slice"
        # zero loss: every row accounted (the timed-out flush's rows
        # retried onto the failover slice or resolved unscored)
        assert await _wait_for(lambda: persisted.value >= sent)
        # scoring RESUMES on the new slice
        before = scored.value
        for r in range(3):
            await _publish(inst, "acme", fleets["acme"], 20 + r)
            sent += _ROWS
        assert await _wait_for(lambda: scored.value - before >= 3 * _ROWS)
        # fault clears -> probation probes land -> slice re-admitted
        plan.clear()
        assert await _wait_for(
            lambda: not svc.quarantined_slices()
            and inst.metrics.counter("tpu_inference.readmitted").value >= 1,
            30.0,
        ), "probation never re-admitted the healed slice"
        assert svc.router.quarantined("lstm_ad") == set()
        assert inst.metrics.gauge(
            "tpu_inference_quarantined_slices"
        ).value == 0
        assert inst.metrics.counter("tpu_inference.probe_flushes").value >= 2
    finally:
        await inst.terminate()


async def test_capacity_fleet_degrades_unscored_and_recovers():
    """The PR 10 capacity rule, now tested (satellite): a fleet sized
    EXACTLY to capacity (no free slot anywhere else) cannot fail a
    quarantined slice's tenant over -> its events pass through
    UNSCORED with zero loss; once probation re-admits the healed
    slice, scored delivery resumes."""
    inst, fleets = await _instance("dfc", ["capa", "capb"],
                                   slots_per_shard=1)
    try:
        svc = inst.inference
        ea, eb = svc.engines["capa"], svc.engines["capb"]
        assert ea.placement.shard != eb.placement.shard  # both slices full
        sa = ea.placement.shard
        scored = inst.metrics.counter("tpu_inference.scored_total")
        persisted = inst.metrics.counter("event_management.persisted")
        sent = 0
        for r in range(2):
            for t in ("capa", "capb"):
                await _publish(inst, t, fleets[t], r)
                sent += _ROWS
        assert await _wait_for(lambda: scored.value >= sent)

        await svc._quarantine_slice(svc._slices[("lstm_ad", sa)], reason="test")
        # stranded: nowhere to go (capb's slice is full), NOT parked
        # (the other slice is healthy), placement unchanged
        assert ea.placement.shard == sa
        assert "lstm_ad" not in svc._parked
        assert svc.router.quarantined("lstm_ad") == {sa}
        # capa degrades to unscored pass-through; capb keeps scoring
        before_scored = scored.value
        for r in range(3):
            await _publish(inst, "capa", fleets["capa"], 10 + r)
            sent += _ROWS
        assert await _wait_for(lambda: persisted.value >= sent)
        assert inst.metrics.counter(
            "tpu_inference.quarantine_passthrough"
        ).value >= 1
        b_scored = scored.value
        await _publish(inst, "capb", fleets["capb"], 20)
        sent += _ROWS
        assert await _wait_for(lambda: scored.value - b_scored >= _ROWS)
        assert await _wait_for(lambda: persisted.value >= sent)
        # the slice is healthy (no faultplan): probation re-admits it
        # and capa's SCORED delivery resumes in place
        assert await _wait_for(
            lambda: not svc.quarantined_slices(), 30.0
        ), "probation never re-admitted"
        before = scored.value
        for r in range(3):
            await _publish(inst, "capa", fleets["capa"], 30 + r)
            sent += _ROWS
        assert await _wait_for(lambda: scored.value - before >= 3 * _ROWS)
        assert await _wait_for(lambda: persisted.value >= sent)
        assert scored.value >= sent - 3 * _ROWS  # only the passthrough
        # window went unscored — everything else scored
    finally:
        await inst.terminate()


async def test_poison_batch_ejects_to_dlq_and_tenant_keeps_serving():
    """Poison-batch ejection end to end: a batch whose dispatch faults
    is retried once with the SAME staged rows on the failover slice; a
    second failure there means two chips agreed -> the batch ships to
    the per-tenant scorer-poison DLQ, the tenant keeps serving, and
    after probation + rebalance-back its batches score normally on the
    ORIGINAL slice."""
    inst, fleets = await _instance("dfp", ["pa", "pb"])
    try:
        svc = inst.inference
        svc.failover_threshold = 1  # first strike fails the tenant over
        ea, eb = svc.engines["pa"], svc.engines["pb"]
        sa = ea.placement.shard
        assert eb.placement.shard != sa
        scored = inst.metrics.counter("tpu_inference.scored_total")
        persisted = inst.metrics.counter("event_management.persisted")
        sent = 0
        for r in range(2):
            for t in ("pa", "pb"):
                await _publish(inst, t, fleets[t], r)
                sent += _ROWS
        assert await _wait_for(lambda: scored.value >= sent)

        svc.faultplan = DeviceFaultPlan(
            # strike 1: pa's serve flush on its home slice
            DeviceFault("fail_dispatch", families=("lstm_ad",),
                        slices=(sa,), lanes=("serve",), first_n=1),
            # strike 2: the one-shot retry (its own lane — landing on
            # the failover slice), confirming the DATA owns the fault
            DeviceFault("fail_dispatch", families=("lstm_ad",),
                        lanes=("retry",), first_n=1),
        )
        await _publish(inst, "pa", fleets["pa"], 10)  # the poison batch
        poisoned_rows = _ROWS
        assert await _wait_for(
            lambda: inst.metrics.counter(
                "tpu_inference.poison_ejected"
            ).value >= 1,
            30.0,
        ), "poison batch never ejected"
        # exactly ONE batch in the scorer-poison DLQ, trace-linked
        assert await _wait_for(
            lambda: _dlq_rows(inst, "pa") == poisoned_rows
        )
        assert inst.metrics.counter("tpu_inference.poison_ejected").value == 1
        assert inst.metrics.counter("tpu_inference.poison_retries").value == 1
        # accounting: everything NOT poisoned persisted; the poison rows
        # are in the DLQ (inspectable/requeue-able), not lost
        assert await _wait_for(
            lambda: persisted.value + _dlq_rows(inst, "pa") >= sent
            + poisoned_rows
        )
        # the tenant keeps serving (no park, no breaker penalty loop)
        assert "lstm_ad" not in svc._parked
        before = scored.value
        for r in range(3):
            await _publish(inst, "pa", fleets["pa"], 20 + r)
            sent += _ROWS
        assert await _wait_for(lambda: scored.value - before >= 3 * _ROWS)
        # probation heals the original slice (fault budget exhausted)
        # and rebalance-back brings pa home; subsequent batches score
        # normally on the ORIGINAL slice
        assert await _wait_for(
            lambda: not svc.quarantined_slices(), 30.0
        ), "probation never re-admitted the original slice"
        assert await _wait_for(
            lambda: ea.placement.shard == sa, 30.0
        ), "tenant never rebalanced back to its original slice"
        before = scored.value
        for r in range(2):
            await _publish(inst, "pa", fleets["pa"], 30 + r)
        assert await _wait_for(lambda: scored.value - before >= 2 * _ROWS)
    finally:
        await inst.terminate()


async def test_media_classify_timeout_drops_batch_and_recovers():
    """The media lane is a supervised fault domain too: a classify
    readback that hangs blows its deadline -> the batch's frames drop
    (media is lossy by design), tpu_flush_timeout_total counts it
    against the tenant's classify lane, and the pipeline keeps
    classifying afterwards."""
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="dfm", mesh=MeshConfig(slots_per_shard=2),
    ))
    await inst.start()
    plan = None
    try:
        await inst.tenant_management.create_tenant(
            "cam", template="media", media_tiny=True,
        )
        await inst.drain_tenant_updates()
        assert await _wait_for(lambda: "cam" in inst.tenants)
        rt = inst.tenants["cam"]
        pipe = rt.media_pipeline
        pipe.flush_deadline_ms = 300.0
        plan = DeviceFaultPlan(DeviceFault(
            "hang_transfer", lanes=("media",), first_n=1,
        ))
        pipe.faultplan = plan
        stream = rt.media.create_stream("asn-1", content_type="video/raw")
        size = pipe.image_size
        rng = np.random.RandomState(0)

        def chunk(seed):
            return rng.randint(0, 255, (size, size, 3), np.uint8).tobytes()

        classified = inst.metrics.counter("media.frames_classified")
        timeouts = inst.metrics.counter("media.classify_timeouts")
        for seq in range(8):
            await pipe.submit_chunk(stream.stream_id, seq, chunk(seq))
        assert await _wait_for(lambda: timeouts.value >= 1, 30.0), (
            "classify timeout never fired"
        )
        assert inst.metrics.counter(
            "tpu_flush_timeout_total", family="vit_b16[cam]", slice="media"
        ).value >= 1
        plan.clear()  # release the parked worker thread
        before = classified.value
        for seq in range(8, 16):
            await pipe.submit_chunk(stream.stream_id, seq, chunk(seq))
        assert await _wait_for(lambda: classified.value - before >= 8), (
            "pipeline did not keep classifying after the timeout"
        )
    finally:
        if plan is not None:
            plan.clear()
        await inst.terminate()
