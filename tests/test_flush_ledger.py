"""The flush record and the event-loop ledger (ISSUE 28).

(a) Every flush of a short run leaves ONE record whose perf_counter
stamps are contiguous — oldest row enqueued → permit asked → permit got
→ assembled → h2d staged → dispatch returned → landed → resolved — and
span exactly enqueue → published of the batches it carried; (b) two
flushes of different length in flight give each batch ITS OWN numbers in
``stage_vector`` (the last-resolved-flush profile gave both the same);
(c) ``service`` never overlaps — its sum over a run stays inside the
wall clock — while ``inflight`` does; (d) the loop ledger's children sum
to the loop thread's CPU seconds, an awaiting handler is not charged for
its busy neighbour, and ``observe`` is moved out of the stage it ran
under; (e) ``runtime_gc_*`` count a forced full collection; (f) with
``tracing.enabled=False`` no ``Span`` is allocated and the flush
histograms still record; (g) every serve flush records the host's side
of the device's idle before it once — ``idle_unfed`` (no work due) and
``idle_turn`` (the loop's turn after it fell due), both 0 behind a flush
in flight — and a lane that waits out its collect deadline shows in the
first, not in the second.
"""

import asyncio
import gc
import threading
import time

import numpy as np
import pytest

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.runtime import tracing
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
    TracingConfig,
)
from sitewhere_tpu.runtime.latency import stage_vector
from sitewhere_tpu.runtime.loopledger import (
    STAGES,
    GcAccount,
    stage_of_task_name,
)
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.tracing import StageTimer, Tracer

MB = MicroBatchConfig(max_batch=64, deadline_ms=1.0, buckets=(32, 64), window=8)
STAMPS = ("t_oldest", "t_asked", "t_got", "t_assembled", "t_staged",
          "t_dispatched", "t_landed", "t_resolved")
FLUSH_STAGES = ("lane_wait", "permit_wait", "flush_assembly", "h2d_stage",
                "dispatch", "inflight", "resolve")


async def _instance(tracing_cfg=None, mb=MB):
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="fl",
        mesh=MeshConfig(tenant_axis=1, data_axis=1, slots_per_shard=4),
    ))
    await inst.start()
    await inst.tenant_management.create_tenant(
        "acme", template="iot-temperature", microbatch=mb,
        model_config={"hidden": 8}, max_streams=64,
        tracing=tracing_cfg or TracingConfig(sample_rate=1.0,
                                             slo_ms=60_000.0),
    )
    await inst.drain_tenant_updates()
    for _ in range(300):
        if "acme" in inst.tenants:
            break
        await asyncio.sleep(0.02)
    toks = [d.token for d in
            inst.tenants["acme"].device_management.bootstrap_fleet(4)]
    await asyncio.get_running_loop().run_in_executor(
        None, inst.inference.prewarm)
    return inst, toks


def _batch(inst, toks, n: int, base: float = 0.0) -> MeasurementBatch:
    b = MeasurementBatch.from_columns(
        "acme", [toks[i % len(toks)] for i in range(n)],
        ["temperature"] * n, [base + float(i) for i in range(n)], [0.0] * n,
    )
    b.trace_ctx = inst.tracer.mint("acme")
    return b


async def _publish(inst, batch) -> None:
    await inst.bus.publish(inst.bus.naming.inbound_events("acme"), batch)


async def _wait_for(cond, timeout_s=20.0, interval=0.01) -> bool:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() >= deadline:
            return False
        await asyncio.sleep(interval)
    return True


def _inference_span(inst, batch):
    tr = inst.tracer.store.peek(batch.trace_ctx.trace_id)
    return tr, next(s for s in tr.spans if s.stage == "inference")


# ------------------------------------------------- (a) the flush record
async def test_flush_record_is_contiguous_from_enqueue_to_published():
    inst, toks = await _instance()
    svc = inst.inference
    try:
        topic = inst.bus.naming.scored_events("acme")
        inst.bus.subscribe(topic, "flush-ledger-test")
        batches = [_batch(inst, toks, 8, base=10.0 * i) for i in range(6)]
        for b in batches:
            await _publish(inst, b)
            await asyncio.sleep(0.01)   # several flushes, not one
        scored = svc.metrics.counter("tpu_inference.scored_total")
        assert await _wait_for(lambda: scored.value >= 48)
        recs = list(svc.flush_records.values())
        assert recs, "no flush left a record"
        by_flush: dict = {}
        for b in batches:
            _tr, span = _inference_span(inst, b)
            by_flush.setdefault(span.annotations["flush_id"], []).append(b)
        assert set(by_flush) <= set(svc.flush_records)
        for rec in recs:
            assert rec["status"] == "ok"
            stamps = [rec[k] for k in STAMPS]
            # contiguous: each interval starts where the last one ended,
            # so they are ordered and sum to the whole
            assert stamps == sorted(stamps), rec
            pieces = [b - a for a, b in zip(stamps, stamps[1:])]
            assert sum(pieces) == pytest.approx(
                rec["t_resolved"] - rec["t_oldest"], abs=1e-9)
            mine = by_flush.get(rec["flush_id"], [])
            assert mine, "a flush record none of the batches points to"
            # ... and the whole IS enqueue → published of what it carried
            assert rec["t_oldest"] == min(b.t_lane for b in mine)
            last_pub = max(b.t_scored for b in mine)
            assert rec["t_dispatched"] <= last_pub <= rec["t_resolved"]
            assert rec["t_resolved"] - last_pub < 0.05
            # the record names its children, the spans name the record
            assert len(rec["seqs"]) == len(mine)
        m = svc.metrics
        assert m.histogram("tpu_inference.lane_wait").count == len(batches)
        for name in ("inflight", "service", "publish", "acquire_wait",
                     "flush_assembly", "h2d_stage", "dispatch", "resolve"):
            assert m.histogram(f"tpu_inference.{name}").count == len(recs)
        assert m.counter("tpu_inference.flushes").value == len(recs)
        # the flight recorder's ring holds the same dicts, not copies
        ring = inst.flightrec.describe()["rings"]["flush"]["lstm_ad"]
        assert ring["records"][-1] is recs[-1]
    finally:
        await inst.terminate()


# ------------------- (b), (c) two flushes of different length in flight
class _GatedScores:
    """A score plane whose materialization blocks on a gate (no
    ``is_ready``/``copy_to_host_async``: the service's fallback path)."""

    def __init__(self, inner, gate: threading.Event) -> None:
        self.inner, self.gate = inner, gate

    def __getitem__(self, idx):
        return _GatedScores(self.inner[idx], self.gate)

    def __array__(self, dtype=None):
        if not self.gate.wait(timeout=60.0):
            raise RuntimeError("gate never opened")
        a = np.asarray(self.inner)
        return a.astype(dtype) if dtype is not None else a


async def _two_flushes_in_flight():
    """Flush 1 dispatched at 0, flush 2 at ~0.25 s, both landing at
    ~0.4 s: in flight ~0.4 s and ~0.15 s. Returns the instance's pieces
    and the wall seconds from the first dispatch to the last resolve."""
    inst, toks = await _instance()
    svc = inst.inference
    gates: list = []
    scorer = svc.scorers["lstm_ad"]
    orig = scorer.step_counts

    def gated_step(i, v, c):
        gate = threading.Event()
        gates.append(gate)
        return _GatedScores(orig(i, v, c), gate)

    scorer.step_counts = gated_step
    try:
        # the second batch fills the smallest bucket, so it does not wait
        # for the first flush to land (the flush policy's pipelining exit)
        b1 = _batch(inst, toks, 8, 100.0)
        b2 = _batch(inst, toks, MB.buckets[0], 200.0)
        t0 = time.perf_counter()
        await _publish(inst, b1)
        assert await _wait_for(
            lambda: len(svc._slices[("lstm_ad", 0)].reap) == 1)
        await asyncio.sleep(0.25)
        await _publish(inst, b2)
        assert await _wait_for(
            lambda: len(svc._slices[("lstm_ad", 0)].reap) == 2)
        await asyncio.sleep(0.15)
        for g in gates:
            g.set()
        scored = svc.metrics.counter("tpu_inference.scored_total")
        assert await _wait_for(lambda: scored.value >= 8 + MB.buckets[0])
        wall = time.perf_counter() - t0
        return inst, (b1, b2), wall
    except BaseException:
        for g in gates:
            g.set()
        await inst.terminate()
        raise


async def test_two_flushes_in_flight_each_batch_gets_its_own_numbers():
    inst, (b1, b2), _wall = await _two_flushes_in_flight()
    try:
        flushes = inst.inference.flush_records
        vecs = []
        for b in (b1, b2):
            tr, span = _inference_span(inst, b)
            rec = flushes[span.annotations["flush_id"]]
            vec, _total = stage_vector(tr, flushes)
            # the batch's inflight is ITS flush's dispatch → landed
            assert vec["inflight"][1] == pytest.approx(
                rec["device_s"] * 1e3, abs=2.0)
            # cut, not scaled: the seven stages are the span
            assert sum(vec[s][1] for s in FLUSH_STAGES) == pytest.approx(
                span.end_ms - span.start_ms, abs=1e-6)
            vecs.append(vec)
        assert vecs[0]["inflight"][1] > 350.0
        assert 100.0 < vecs[1]["inflight"][1] < 300.0
        # the latency engine the instance wired reads the same records
        assert inst.latency.flushes is flushes
    finally:
        await inst.terminate()


async def test_service_never_overlaps_while_inflight_does():
    inst, _batches, wall = await _two_flushes_in_flight()
    try:
        recs = list(inst.inference.flush_records.values())
        assert len(recs) == 2
        inflight = sum(r["device_s"] for r in recs)
        service = sum(r["service_s"] for r in recs)
        assert service <= wall
        assert inflight > wall            # ~0.4 + ~0.15 over ~0.4 s
        # the second flush was served only after the first had landed
        assert recs[1]["service_s"] < recs[1]["device_s"] - 0.1
        assert recs[1]["t_landed"] - recs[0]["t_landed"] == pytest.approx(
            recs[1]["service_s"], abs=1e-6)
        m = inst.inference.metrics
        # one flush in flight ahead of the second, none ahead of the first
        assert m.counter("tpu_inference.inflight_depth_sum").value == 1
        assert m.counter("tpu_inference.flush_pipelined").value == 1
        # device seconds and MFU are fed the service time
        secs = m.counter("tpu_device_seconds_total", family="lstm_ad").value
        assert secs == pytest.approx(service, abs=1e-6)
    finally:
        await inst.terminate()


# ------------------------------------------------- (d) the loop ledger
def _by_stage(snap: dict) -> dict:
    """``loop_busy_seconds_total`` summed over ``task`` within a stage."""
    out = dict.fromkeys(STAGES, 0.0)
    for key, v in snap.items():
        if key.startswith("loop_busy_seconds_total{"):
            stage = key.split('stage="')[1].split('"')[0]
            out[stage] += v
    return out


def _burn(cpu_s: float) -> float:
    """Burn ``cpu_s`` of this thread's CPU; returns the wall seconds it
    took (more, when the OS took the thread off its core meanwhile)."""
    w, t = time.perf_counter(), time.thread_time()
    while time.thread_time() - t < cpu_s:
        pass
    return time.perf_counter() - w


def _ledger(reg, cycle: int = 1):
    """The registry's ledger; ``cycle=1`` times every step."""
    ledger = reg.loop_ledger
    ledger.CYCLE = cycle
    return ledger


async def test_loop_ledger_children_sum_to_the_threads_cpu_time():
    reg = MetricsRegistry()
    ledger = _ledger(reg)
    tracer = Tracer(reg, default=TracingConfig(sample_rate=1.0,
                                               slo_ms=60_000.0))
    timer = StageTimer(tracer, reg, "t1", "persistence")
    real = tracer.record_span
    wall = {"egress": 0.0, "score": 0.0, "observe": 0.0, "unlabeled": 0.0}

    def slow_span(*a, **kw):    # the tracing's own cost, made visible
        wall["observe"] += _burn(0.002)
        return real(*a, **kw)

    tracer.record_span = slow_span
    rounds = 40

    async def handler():        # awaits while the neighbour is busy
        for _ in range(rounds):
            wall["egress"] += _burn(0.002)
            ctx = tracer.mint("t1")
            item = type("Item", (), {"trace_ctx": ctx, "trace": {}})()
            timer.observe(item, 0.0, 1.0)
            await asyncio.sleep(0.004)

    async def neighbour():
        for _ in range(rounds):
            wall["score"] += _burn(0.004)
            await asyncio.sleep(0)

    async def unlabeled():
        for _ in range(rounds):
            wall["unlabeled"] += _burn(0.001)
            await asyncio.sleep(0.002)

    ledger.install(asyncio.get_running_loop())
    try:
        cpu0 = time.thread_time()
        tasks = [
            asyncio.create_task(handler(), name="event-persistence[t1]"),
            asyncio.create_task(neighbour(), name="tpu-inference-loop"),
            asyncio.create_task(unlabeled()),
        ]
        await asyncio.gather(*tasks)
        snap = reg.snapshot()
        cpu = time.thread_time() - cpu0
    finally:
        ledger.uninstall()
    child = _by_stage(snap)
    # within a stage the children are the kinds of task
    assert snap['loop_busy_seconds_total{stage="egress",'
                'task="event-persistence"}'] == child["egress"]
    # the children sum to the thread's CPU seconds. The labeled stages
    # are WALL self time: where the OS took the thread off its core
    # inside labeled steps for longer than everything unlabeled ran (six
    # test workers share these cores), they alone exceed the CPU clock
    # and ``other`` stands at zero
    labeled = wall["egress"] + wall["score"] + wall["observe"]
    assert sum(child.values()) == pytest.approx(max(cpu, labeled), rel=0.05)
    # self time: the handler awaited through 40 x 4 ms of its neighbour
    assert child["egress"] == pytest.approx(wall["egress"], rel=0.1)
    assert child["score"] == pytest.approx(wall["score"], rel=0.1)
    # observe is moved, not copied: the 2 ms a span costs here is in
    # ``observe`` and NOT in the stage that called it
    assert child["observe"] == pytest.approx(wall["observe"], rel=0.1)
    assert child["intake"] == 0.0
    # the unlabeled task's steps are inside ``other`` and published apart
    steps = snap["loop_unlabeled_task_seconds_total"]
    assert steps == pytest.approx(wall["unlabeled"], rel=0.1)
    assert reg.loop_ledger.current is None


def test_task_names_map_to_stages():
    assert stage_of_task_name("pump:event-source[mqtt]") == "intake"
    assert stage_of_task_name("inbound-processing[t1]") == "intake"
    assert stage_of_task_name("supervise:tpu-inference-reaper") == "score"
    assert stage_of_task_name("tpu-inference-loop") == "score"
    assert stage_of_task_name("tpu-inference-resolve[lstm_ad/0]") == "score"
    assert stage_of_task_name("event-persistence[t1]") == "egress"
    assert stage_of_task_name("rule-processing[t1]") == "egress"
    assert stage_of_task_name("outbound-connectors[t1]") == "egress"
    assert stage_of_task_name("Task-17") is None


async def test_unnamed_child_task_inherits_its_creators_stage():
    reg = MetricsRegistry()
    ledger = _ledger(reg)
    ledger.install(asyncio.get_running_loop())
    try:
        async def child():
            _burn(0.01)

        async def parent():
            await asyncio.gather(child(), child())   # unnamed tasks

        await asyncio.create_task(parent(), name="outbound-connectors[t1]")
        snap = reg.snapshot()
    finally:
        ledger.uninstall()
    assert snap['loop_busy_seconds_total{stage="egress",'
                'task="outbound-connectors"}'] >= 0.018
    assert snap["loop_unlabeled_task_seconds_total"] < 0.005
    # uninstalled: the factory is gone and ``other`` stands still
    assert asyncio.get_running_loop().get_task_factory() is None
    frozen = _by_stage(reg.snapshot())["other"]
    _burn(0.01)
    assert _by_stage(reg.snapshot())["other"] == frozen


async def test_a_collection_inside_a_step_is_nobodys_stage():
    """The clock steps and ``observe`` stretches are timed on stops for
    the collector: a pause inside either is in ``other`` alone."""
    reg = MetricsRegistry()
    ledger = _ledger(reg)
    acct = GcAccount(reg)
    junk = [[i] for i in range(100_000)]   # a full collection worth timing
    burned: list = []

    async def handler():
        t0 = ledger.clock()
        gc.collect(2)                       # inside an observe stretch
        ledger.observe_from(t0)
        gc.collect(2)                       # inside the step proper
        burned.append(_burn(0.01))

    ledger.install(asyncio.get_running_loop())
    acct.install()
    try:
        await asyncio.create_task(handler(), name="rule-processing[t1]")
        snap = reg.snapshot()
    finally:
        acct.uninstall()
        ledger.uninstall()
    del junk
    child = _by_stage(snap)
    paused = reg.histogram("runtime_gc_pause_seconds", generation="2")
    pause_s = paused.summary()["mean"] * paused.count
    assert paused.count == 2 and pause_s > 0.004
    assert ledger.paused == pytest.approx(pause_s, rel=0.01)
    # neither pause is in the step's stage nor in ``observe`` (the
    # slack is for a busy machine taking the thread off its core) ...
    assert child["egress"] == pytest.approx(burned[0], abs=0.1 * pause_s)
    assert child["observe"] < 0.1 * pause_s
    # ... they are CPU seconds of the thread that no stage owns
    assert child["other"] >= 0.5 * pause_s


async def test_duty_cycle_scales_a_slice_to_its_cycle_and_steps_are_free_between():
    """One slice in four is timed: the counters read as if every step
    had been, the children still sum to the thread's CPU time, and
    between slices a task's ``send`` is the coroutine's own."""
    reg = MetricsRegistry()
    ledger = _ledger(reg, cycle=4)
    ledger.SLICE_S = 0.02
    wall = {"score": 0.0, "egress": 0.0}
    sends: set = set()

    async def busy(stage, cpu_s, stop):
        me = asyncio.current_task().get_coro()
        while time.perf_counter() < stop:
            wall[stage] += _burn(cpu_s)
            sends.add((ledger.sampling, type(me.send).__name__))
            await asyncio.sleep(0)

    ledger.install(asyncio.get_running_loop())
    try:
        cpu0 = time.thread_time()
        stop = time.perf_counter() + 1.0
        await asyncio.gather(
            asyncio.create_task(busy("score", 0.0006, stop),
                                name="tpu-inference-loop"),
            asyncio.create_task(busy("egress", 0.0003, stop),
                                name="rule-processing[t1]"),
        )
        snap = reg.snapshot()
        cpu = time.thread_time() - cpu0
    finally:
        ledger.uninstall()
    child = _by_stage(snap)
    # in a slice the step is a timed closure; between slices it is the
    # coroutine's own bound method — nothing of the ledger runs in it
    assert (True, "function") in sends
    assert (False, "builtin_function_or_method") in sends
    assert (False, "function") not in sends
    # a dozen slices stand for the whole second
    assert child["score"] == pytest.approx(wall["score"], rel=0.2)
    assert child["egress"] == pytest.approx(wall["egress"], rel=0.2)
    assert child["score"] > 1.5 * child["egress"]
    stolen = max(0.0, wall["score"] + wall["egress"] - cpu)
    assert sum(child.values()) >= cpu * 0.95
    assert sum(child.values()) <= (cpu + stolen) * 1.25


# --------------------------------------------------- (e) the collector
def test_runtime_gc_counts_a_forced_full_collection():
    reg = MetricsRegistry()
    acct = GcAccount(reg)
    full = reg.counter("runtime_gc_collections_total", generation="2")
    pause = reg.histogram("runtime_gc_pause_seconds", generation="2")
    acct.install()
    try:
        before = full.value
        gc.collect(2)
        assert full.value == before + 1
        assert pause.count == full.value
        assert 0.0 < pause.summary()["max"] < 5.0
    finally:
        acct.uninstall()
    after = full.value
    gc.collect(2)
    assert full.value == after, "still counting after uninstall"


# ---------------------------------------------- (f) tracing switched off
async def test_tracing_disabled_allocates_no_span_and_flushes_still_record(
    monkeypatch,
):
    made: list = []
    real_span = tracing.Span

    def counting_span(*a, **kw):
        made.append(kw.get("stage"))
        return real_span(*a, **kw)

    monkeypatch.setattr(tracing, "Span", counting_span)
    inst, toks = await _instance(TracingConfig(enabled=False))
    try:
        assert inst.tracer.mint("acme") is None
        batches = [
            MeasurementBatch.from_columns(
                "acme", [toks[i % 4] for i in range(8)], ["temperature"] * 8,
                [float(i) for i in range(8)], [0.0] * 8,
            )
            for _ in range(3)
        ]
        for b in batches:
            # decoded-events: inbound (mint-if-absent), scoring, persist,
            # rules and outbound all see them
            await inst.bus.publish(inst.bus.naming.decoded_events("acme"), b)
            await asyncio.sleep(0.01)
        m = inst.metrics
        egress = m.histogram("pipeline.egress")
        assert await _wait_for(lambda: egress.count >= 3)
        assert made == [], f"spans allocated with tracing off: {made}"
        assert inst.tracer.store.active_count() == 0
        flushes = m.counter("tpu_inference.flushes").value
        assert flushes >= 1
        assert m.histogram("tpu_inference.lane_wait").count == 3
        for name in ("inflight", "service", "publish"):
            assert m.histogram(f"tpu_inference.{name}").count == flushes
        assert len(inst.inference.flush_records) == flushes
    finally:
        await inst.terminate()


# --------------------------- (g) the host's gap between landing and ask
MB_SLOW = MicroBatchConfig(max_batch=64, deadline_ms=40.0, buckets=(32, 64),
                           window=8)


def _split(rec) -> tuple:
    return rec["idle_unfed_s"], rec["idle_turn_s"]


async def test_idle_split_is_the_loops_turn_when_held_and_unfed_on_a_deadline():
    inst, toks = await _instance(mb=MB_SLOW)
    svc = inst.inference
    m = svc.metrics
    gates: list = []
    scorer = svc.scorers["lstm_ad"]
    orig = scorer.step_counts

    def gated_step(i, v, c):
        gate = threading.Event()
        gates.append(gate)
        return _GatedScores(orig(i, v, c), gate)

    scorer.step_counts = gated_step
    scored = m.counter("tpu_inference.scored_total")
    held = m.counter("tpu_inference.flush_held")
    try:
        # flush 1: nothing has landed before it
        await _publish(inst, _batch(inst, toks, 8, 0.0))
        assert await _wait_for(lambda: len(gates) == 1)
        # flush 2's rows fall due while flush 1 is in flight: held
        await _publish(inst, _batch(inst, toks, 8, 100.0))
        assert await _wait_for(lambda: held.value > 0)
        await asyncio.sleep(0.1)           # well past their 40 ms deadline
        gates[0].set()
        assert await _wait_for(lambda: len(gates) == 2)
        gates[1].set()
        assert await _wait_for(lambda: scored.value >= 16)
        # an idle slice, then a lane that waits out its collect deadline
        await asyncio.sleep(0.1)
        await _publish(inst, _batch(inst, toks, 8, 200.0))
        assert await _wait_for(lambda: len(gates) == 3)
        gates[2].set()
        assert await _wait_for(lambda: scored.value >= 24)
        recs = sorted(svc.flush_records.values(),
                      key=lambda r: r["flush_id"])
    finally:
        for g in gates:
            g.set()
        await inst.terminate()
    assert len(recs) == 3 == m.counter("tpu_inference.flushes").value
    # once a serve flush, never negative
    for name in ("idle_unfed", "idle_turn"):
        assert m.histogram(f"tpu_inference.{name}").count == 3
    assert all(min(_split(r)) >= 0.0 for r in recs)
    assert _split(recs[0]) == (0.0, 0.0)
    # flush 2 was due long before flush 1 landed: its idle is all the
    # loop's turn, from that landing to the permit asked
    unfed, turn = _split(recs[1])
    assert unfed == 0.0
    assert recs[1]["t_asked"] - recs[0]["t_landed"] - 1e-6 <= turn
    assert turn <= recs[1]["t_asked"] - recs[0]["t_dispatched"]
    # flush 3: the idle slice and the 40 ms collect deadline are unfed;
    # the turn runs from the deadline's own moment to the ask
    unfed, turn = _split(recs[2])
    due = recs[2]["t_oldest"] + MB_SLOW.deadline_ms / 1000.0
    assert unfed >= due - recs[1]["t_landed"] - 1e-6
    assert unfed >= 0.1 + MB_SLOW.deadline_ms / 1000.0 - 0.01
    assert turn <= recs[2]["t_asked"] - due + 1e-6
    assert turn >= recs[2]["t_asked"] - due - 0.005


async def test_idle_split_is_zero_behind_a_flush_in_flight():
    inst, toks = await _instance()
    svc = inst.inference
    m = svc.metrics
    s = svc._slices[("lstm_ad", 0)]
    gates: list = []
    scorer = svc.scorers["lstm_ad"]
    orig = scorer.step_counts

    def gated_step(i, v, c):
        gate = threading.Event()
        gates.append(gate)
        return _GatedScores(orig(i, v, c), gate)

    scorer.step_counts = gated_step
    scored = m.counter("tpu_inference.scored_total")
    try:
        # flush 0 lands and resolves: the slice has a landing behind it
        await _publish(inst, _batch(inst, toks, 8, 0.0))
        assert await _wait_for(lambda: len(gates) == 1)
        gates[0].set()
        assert await _wait_for(lambda: scored.value >= 8)
        # flush 1 in flight; flush 2 fills the smallest bucket and joins
        # the busy device (the flush policy's pipelining exit)
        await _publish(inst, _batch(inst, toks, 8, 100.0))
        assert await _wait_for(lambda: len(s.reap) == 1)
        await _publish(inst, _batch(inst, toks, MB.buckets[0], 200.0))
        assert await _wait_for(lambda: len(s.reap) == 2)
        for g in gates:
            g.set()
        assert await _wait_for(lambda: scored.value >= 16 + MB.buckets[0])
        recs = sorted(svc.flush_records.values(),
                      key=lambda r: r["flush_id"])
    finally:
        for g in gates:
            g.set()
        await inst.terminate()
    assert len(recs) == 3
    assert m.histogram("tpu_inference.idle_turn").count == 3
    assert m.histogram("tpu_inference.idle_unfed").count == 3
    # flush 1 followed flush 0's landing on an idle device ...
    idle = sum(_split(recs[1]))
    assert recs[1]["t_asked"] - recs[0]["t_landed"] - 1e-6 <= idle
    assert idle <= recs[1]["t_asked"] - recs[0]["t_dispatched"]
    # ... flush 2 asked while flush 1 was still in flight: nothing idle
    assert recs[2]["t_asked"] < recs[1]["t_landed"]
    assert _split(recs[2]) == (0.0, 0.0)
