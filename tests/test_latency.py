"""End-to-end latency attribution (ISSUE 17 acceptance).

Covers: (a) stage-vector flattening — canonical axis mapping, the
flush-profile split of the inference span, profile scaling, and fork-max
semantics for rules/outbound siblings; (b) additive p99 budget
decomposition (contributions + residual == cohort mean by construction)
and dominant-stage extraction; (c) SLO burn-rate accounting — window
math, replay exclusion, never-raise ingest, ledger LRU bound; (d) the
``slo_burn`` watchdog rule naming tenant + dominant stage in the alert
and its flight-recorder snapshot; (e) forced tail stage records beating
the flight-recorder stride without resetting it; (f) the
``tpu_flush_latency_p99_ms`` live gauge + history allowlist wiring;
(g) trace/priority stamp propagation through replay-published batches,
DLQ entries and requeue, and retry continuity; (h) the check_metrics
queue-wait-twin lint; and (j) the live REST
acceptance — ``/api/latency`` decomposition reconciling with the
measured e2e p99 within 15% on a driven instance."""

import asyncio
import importlib.util
import json
import types
from contextlib import asynccontextmanager
from pathlib import Path

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from sitewhere_tpu.api.rest import make_app
from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.pipeline.replay import ReplayEngine
from sitewhere_tpu.runtime.bus import EventBus, RetryingConsumer, TopicNaming
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    TracingConfig,
    tenant_config_from_template,
)
from sitewhere_tpu.runtime.flightrec import FlightRecorder
from sitewhere_tpu.runtime.history import (
    DEFAULT_ALLOWLIST,
    WATCHDOG_REQUIRED,
    MetricsHistory,
    Watchdog,
)
from sitewhere_tpu.runtime.latency import (
    PATH_STAGES,
    STAGES,
    LatencyEngine,
    StageLedger,
    _BurnAccount,
    dominant_stage_of,
    stage_vector,
)
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.overload import clear_deadline
from sitewhere_tpu.runtime.tracing import StageTimer, Tracer, now_ms
from sitewhere_tpu.services.event_store import EventStore

_TOOLS = Path(__file__).resolve().parent.parent / "tools"


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(name, _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_metrics = _load_tool("check_metrics")


# ------------------------------------------------------------- helpers
def _feed_trace(
    tracer: Tracer,
    tenant: str = "t1",
    priority: str = "measurement",
    persistence_svc: float = 1.5,
):
    """One full-pipeline trace with controlled timings: decode qw 2 /
    svc 3, inbound 1/2, inference 4/20 carrying ``flush_id`` 7 (cut by
    ``_flush_record`` into lane_wait 4, permit_wait 1, assembly 1, h2d
    0.5, dispatch 0.5, inflight 10, resolve 3), persistence 0.5/<svc>, a
    rules fork span 0.2/1, and TWO concurrent outbound siblings (0.3/2
    and 0.1/5)."""
    ctx = tracer.mint(tenant, priority=priority)
    b = now_ms()
    tracer.record_span(ctx, "decode", b + 2, b + 5, queue_wait_ms=2.0,
                       n_events=4)
    tracer.record_span(ctx, "inbound", b + 6, b + 8, queue_wait_ms=1.0)
    tracer.record_span(
        ctx, "inference", b + 12, b + 32, queue_wait_ms=4.0,
        family="lstm_ad", flush_id=7,
    )
    end_p = b + 33.5 + persistence_svc
    tracer.record_span(ctx, "persistence", b + 33.5, end_p,
                       queue_wait_ms=0.5)
    tracer.record_span(ctx, "rules", end_p + 0.2, end_p + 1.2,
                       queue_wait_ms=0.2, advance=False)
    for qw, svc in ((0.3, 2.0), (0.1, 5.0)):
        tracer.record_span(ctx, "outbound", end_p + qw, end_p + qw + svc,
                           queue_wait_ms=qw, advance=False)
    return ctx


def _flush_record(span_start_ms: float, offsets_ms, flush_id: int = 7):
    """A flush record as ``TpuInferenceService._flush_record`` leaves it:
    perf_counter stamps (here: seconds on an arbitrary origin) and
    ``ts_ms``, the wall clock at ``t_dispatched``. ``offsets_ms`` are the
    six stamps' offsets from the span's start."""
    names = ("t_asked", "t_got", "t_assembled", "t_staged", "t_dispatched",
             "t_landed")
    origin = 5000.0  # perf_counter seconds; only differences matter
    rec = {"flush_id": flush_id}
    for name, off in zip(names, offsets_ms):
        rec[name] = origin + off / 1e3
    rec["ts_ms"] = span_start_ms + offsets_ms[4]
    return rec


_FLUSH_STAGES = ("lane_wait", "permit_wait", "flush_assembly", "h2d_stage",
                 "dispatch", "inflight", "resolve")


# ------------------------------------------- (a) stage-vector flattening
def test_stage_vector_axis_mapping_and_fork_max():
    reg = MetricsRegistry()
    tracer = Tracer(reg, default=TracingConfig(sample_rate=1.0,
                                               slo_ms=60_000))
    ctx = _feed_trace(tracer)
    tr = tracer.store.peek(ctx.trace_id)
    inf = next(s for s in tr.spans if s.stage == "inference")
    flushes = {7: _flush_record(inf.start_ms, (4, 5, 6, 6.5, 7, 17))}
    vec, total = stage_vector(tr, flushes)
    # decode queue wait IS the ingest stage (receive → decode start)
    assert vec["ingest"] == [0.0, pytest.approx(2.0)]
    assert vec["decode"] == [0.0, pytest.approx(3.0)]
    assert vec["inbound"] == [pytest.approx(1.0), pytest.approx(2.0)]
    # inference span cut at the boundaries of ITS OWN flush's record
    assert vec["lane_wait"] == [pytest.approx(4.0), pytest.approx(4.0)]
    assert vec["permit_wait"][1] == pytest.approx(1.0)
    assert vec["flush_assembly"][1] == pytest.approx(1.0)
    assert vec["h2d_stage"][1] == pytest.approx(0.5)
    assert vec["dispatch"][1] == pytest.approx(0.5)
    assert vec["inflight"][1] == pytest.approx(10.0)
    assert vec["resolve"][1] == pytest.approx(3.0)
    assert "d2h_wait" not in vec  # a histogram, no longer on the axis
    # contiguous cuts: the seven pieces ARE the span, nothing scaled
    assert sum(vec[s][1] for s in _FLUSH_STAGES) == pytest.approx(20.0)
    assert vec["persistence"] == [pytest.approx(0.5), pytest.approx(1.5)]
    # fork stages keep the SLOWEST sibling, never the overlapped sum
    assert vec["outbound"] == [pytest.approx(0.1), pytest.approx(5.0)]
    assert vec["rules"] == [pytest.approx(0.2), pytest.approx(1.0)]
    assert total == pytest.approx(40.1, abs=1.0)
    # additivity: the on-path stages never claim more than the trace total
    on_path = sum(sum(vec[s]) for s in PATH_STAGES if s in vec)
    assert on_path <= total + 0.01
    assert dominant_stage_of(tr, flushes) == "inflight"
    # without its flush's record the span stays whole under lane_wait
    vec0, _ = stage_vector(tr)
    assert vec0["lane_wait"] == [pytest.approx(4.0), pytest.approx(20.0)]


def test_stage_vector_never_scales_and_sums_to_the_span():
    """The inference span is cut, not apportioned: whatever the flush
    record claims — stamps before the span began (a batch that waited
    out an earlier flush), or after it ended — every cut is clamped
    into the span, so the stages sum to it exactly and nothing is ever
    scaled (the old ledger scaled a neighbour flush's 12 ms profile
    down into a 5 ms span)."""
    reg = MetricsRegistry()
    tracer = Tracer(reg, default=TracingConfig(sample_rate=1.0,
                                               slo_ms=60_000))
    ctx = tracer.mint("t1")
    b = now_ms()
    tracer.record_span(ctx, "inference", b, b + 5, queue_wait_ms=1.0,
                       flush_id=3)
    tr = tracer.store.peek(ctx.trace_id)
    # a 12 ms record around a 5 ms span: asked 2 ms BEFORE the span's
    # start, landed 4 ms AFTER its end
    rec = _flush_record(tr.spans[0].start_ms, (-2, 1, 2, 2.5, 3, 9),
                        flush_id=3)
    vec, _total = stage_vector(tr, {3: rec})
    assert sum(vec[s][1] for s in _FLUSH_STAGES) == pytest.approx(5.0,
                                                                  abs=1e-6)
    assert vec["lane_wait"] == [pytest.approx(1.0), pytest.approx(0.0)]
    assert vec["permit_wait"][1] == pytest.approx(1.0)   # 0 → 1, clamped
    assert vec["dispatch"][1] == pytest.approx(0.5)      # unscaled
    assert vec["inflight"][1] == pytest.approx(2.0)      # 3 → 5, clamped
    assert vec["resolve"][1] == pytest.approx(0.0)
    # an unresolved flush (no landing stamp yet) claims nothing
    del rec["t_landed"]
    vec, _total = stage_vector(tr, {3: rec})
    assert vec["lane_wait"] == [pytest.approx(1.0), pytest.approx(5.0)]


# ----------------------------------------- (b) additive p99 decomposition
def test_ledger_decompose_is_additive_and_names_dominant_stage():
    led = StageLedger("t1", "measurement")
    for i in range(1, 33):
        total = float(i)
        led.add({
            "lane_wait": [0.0, total * 0.6],
            "persistence": [0.0, total * 0.25],
            "rules": [0.0, total * 5.0],  # fork: huge but off-path
        }, total)
    d = led.decompose()
    assert d is not None and d["n"] == 32
    by = {s["stage"]: s for s in d["stages"]}
    assert list(by) == list(STAGES)
    assert by["rules"]["on_path"] is False
    assert by["lane_wait"]["on_path"] is True
    # contributions + residual equal the cohort mean EXACTLY (modulo
    # the 3-dp rounding the report applies per stage)
    attributed = sum(
        s["total_ms"] for s in d["stages"] if s["on_path"]
    )
    assert attributed + d["residual_ms"] == pytest.approx(
        d["cohort_mean_ms"], abs=0.05
    )
    # the cohort mean tracks the p99 by construction
    assert abs(d["cohort_mean_ms"] - d["e2e_p99_ms"]) <= (
        0.15 * d["e2e_p99_ms"]
    )
    # the residual is the 15% of each total no stage claimed
    assert d["residual_ms"] == pytest.approx(
        d["cohort_mean_ms"] * 0.15, abs=0.05
    )
    assert led.dominant_stage() == "lane_wait"
    # below the floor there is no decomposition, and no blame
    thin = StageLedger("t1", "measurement")
    for i in range(StageLedger.MIN_DECOMPOSE - 1):
        thin.add({"decode": [0.0, 1.0]}, 1.0)
    assert thin.decompose() is None
    assert thin.dominant_stage() == ""


def test_ledger_window_costs_the_collector_one_object_a_trace():
    """A ledger keeps WINDOW vectors a (tenant, priority), every tenant
    its own: kept as the dicts of lists ``stage_vector`` returns they
    were some fifteen containers a trace in every full collection. The
    window keeps one flat array a trace, and the decomposition reads the
    same numbers back from it."""
    import gc

    vec = {s: [float(i), float(2 * i)] for i, s in enumerate(STAGES)}
    led = StageLedger("t1", "measurement")
    gc.collect()
    before = len(gc.get_objects())
    for _ in range(64):
        led.add({s: list(cell) for s, cell in vec.items()}, 1000.0)
    gc.collect()
    # the stage quantile windows are made at the first add; after it an
    # entry is one object, so 64 entries stay far below one dict and 13
    # lists each
    assert len(gc.get_objects()) - before < 64 * 2 + 8 * len(STAGES)
    by = {s["stage"]: s for s in led.decompose()["stages"]}
    for i, s in enumerate(STAGES):
        assert by[s]["queue_wait_ms"] == pytest.approx(float(i))
        assert by[s]["service_ms"] == pytest.approx(float(2 * i))


# --------------------------------------------- (c) burn-rate accounting
def test_burn_account_windows_and_none_when_empty():
    acct = _BurnAccount()
    # no traffic ≠ zero breach rate: the empty window reads None
    assert acct.fraction(300, 1000.0) is None
    for i in range(10):
        acct.note(i < 5, now_s=1000.0 + i)
    assert acct.fraction(300, 1009.0) == pytest.approx(0.5)
    # an hour later: the 5 min window sees only the new bucket, the 1 h
    # window still merges both
    acct.note(True, now_s=2000.0)
    assert acct.fraction(300, 2000.0) == pytest.approx(1.0)
    assert acct.fraction(3600, 2000.0) == pytest.approx(6 / 11)


def test_engine_replay_exclusion_never_raise_and_lru_bound():
    reg = MetricsRegistry()
    eng = LatencyEngine(reg)
    tracer = Tracer(reg, default=TracingConfig(sample_rate=1.0, slo_ms=5.0))
    tracer.latency = eng
    # a replay cohort gets attribution but never burns the SLO budget
    _feed_trace(tracer, tenant="t1", priority="replay")
    tracer.gc(force=True)
    assert ("t1", "replay") in eng._ledgers
    assert "t1" not in eng._burn
    assert eng.burn_rates("t1") == {"burn_5m": None, "burn_1h": None}
    # live traffic past the 5 ms SLO burns: fraction 1.0 / budget 0.01
    _feed_trace(tracer, tenant="t1")
    tracer.gc(force=True)
    assert ("t1", "measurement") in eng._ledgers
    assert eng.burn_rates("t1")["burn_5m"] == pytest.approx(100.0)
    # a malformed trace is counted, never raised into the tail decision
    eng.ingest_trace(object(), 5.0)
    assert reg.counter("latency_ledger_errors").value == 1
    # (tenant, priority) cardinality is LRU-bounded
    eng.MAX_LEDGERS = 4
    for i in range(8):
        _feed_trace(tracer, tenant=f"lru-{i}")
    tracer.gc(force=True)
    assert len(eng._ledgers) == 4
    assert ("lru-7", "measurement") in eng._ledgers
    assert ("t1", "replay") not in eng._ledgers  # oldest evicted
    # remove_tenant drops ledgers, burn state and labeled gauges
    eng.refresh_gauges()
    eng.remove_tenant("lru-7")
    assert all(t != "lru-7" for (t, _p) in eng._ledgers)


# ------------------------------------------ (d) the slo_burn watchdog rule
def test_slo_burn_watchdog_names_tenant_stage_and_snapshots():
    reg = MetricsRegistry()
    t = {"now": 0.0}
    hist = MetricsHistory(reg, capacity=600, clock=lambda: t["now"])
    fr = FlightRecorder(min_snapshot_interval_s=0.0,
                        clock=lambda: t["now"])
    tracer = Tracer(reg, default=TracingConfig(sample_rate=0.0, slo_ms=5.0))
    eng = LatencyEngine(reg)
    eng.tracer = tracer
    tracer.latency = eng
    fr.add_context("latency", eng.snapshot_context)
    wd = Watchdog(
        reg, hist, flightrec=fr, tracer=tracer, latency=eng,
        clock=lambda: t["now"], warmup=5, window=3, cooldown_s=10.0,
        min_flushes=4,
    )
    # quiet engine → the rule holds its fire
    assert [a for a in wd.evaluate() if a["rule"] == "slo_burn"] == []
    # a tenant with a 60 ms persistence stall breaching its 5 ms SLO on
    # every trace: 100x burn on BOTH windows → page
    for _ in range(10):
        _feed_trace(tracer, tenant="t7", persistence_svc=60.0)
    tracer.gc(force=True)
    fired = [a for a in wd.evaluate() if a["rule"] == "slo_burn"]
    assert len(fired) == 1
    alert = fired[0]
    assert alert["tenant"] == "t7"
    assert alert["stage"] == "persistence"
    assert alert["burn_5m"] >= 14.4
    assert alert["burn_1h"] is not None and alert["burn_1h"] >= 1.0
    assert "t7" in alert["detail"] and "persistence" in alert["detail"]
    # the incident snapshot carries the same naming plus the engine's
    # own cohort context
    snaps = [s for s in fr.snapshots()
             if s["reason"] == "watchdog:slo_burn"]
    assert len(snaps) == 1
    assert snaps[0]["meta"]["tenant"] == "t7"
    assert snaps[0]["meta"]["stage"] == "persistence"
    cohorts = snaps[0]["context"]["latency"]["cohorts"]
    assert cohorts and cohorts[0]["tenant"] == "t7"
    assert cohorts[0]["dominant_stage"] == "persistence"
    # cooldown: the persistent condition does not re-page this tick
    assert [a for a in wd.evaluate() if a["rule"] == "slo_burn"] == []


# --------------------------------- (e) forced tail stage records (stride)
def _stage_records(fr: FlightRecorder, key: str):
    rings = fr.describe()["rings"].get("stage", {})
    return rings.get(key, {"records": []})["records"]


def test_forced_tail_stage_records_beat_the_stride_without_resetting_it():
    reg = MetricsRegistry()
    fr = FlightRecorder()
    tracer = Tracer(reg, default=TracingConfig(sample_rate=0.0,
                                               slo_ms=60_000))
    tracer.flightrec = fr
    st = StageTimer(tracer, reg, "t1", "decode")
    b = now_ms()

    def observe(ctx):
        st.observe(types.SimpleNamespace(trace_ctx=ctx), b, b + 1.0,
                   queue_wait_ms=0.5)

    key = "t1/decode"
    observe(tracer.mint("t1"))  # primed: the FIRST batch records
    assert len(_stage_records(fr, key)) == 1
    for _ in range(3):
        observe(tracer.mint("t1"))
    assert len(_stage_records(fr, key)) == 1  # strided off
    # a retry-forced trace records UNCONDITIONALLY, mid-stride — the
    # incident snapshot needs the slow event's OWN timings
    hot = tracer.mint("t1")
    tracer.mark_hit(hot, "retry")
    observe(hot)
    recs = _stage_records(fr, key)
    assert len(recs) == 2
    assert recs[-1]["forced"] == "tail"
    # the forced record did not reset the stride: the steady cadence
    # lands exactly on the 8th cold batch since the last strided record
    for _ in range(3):
        observe(tracer.mint("t1"))
    assert len(_stage_records(fr, key)) == 2
    observe(tracer.mint("t1"))
    recs = _stage_records(fr, key)
    assert len(recs) == 3 and "forced" not in recs[-1]


# ------------------------- (f) flush-latency gauge + history allowlist
def test_flush_latency_gauge_and_history_wiring():
    from sitewhere_tpu.pipeline.slices import SliceRuntime

    reg = MetricsRegistry()
    home = SliceRuntime("lstm_ad", 0, None, None, reg, 2, 2)
    for _ in range(10):
        home.note_device_s(0.005)
    g = reg.gauge("tpu_flush_latency_p99_ms", family="lstm_ad", slice="0")
    assert g.value == pytest.approx(5.0, rel=0.02)
    # the history sampler keeps the attribution families by default, and
    # a trimmed allowlist cannot starve the slo_burn rule's evidence
    for fam in ("latency_e2e_p99_ms", "latency_stage_p99_ms",
                "latency_slo_burn", "tpu_flush_latency_p99_ms"):
        assert fam in DEFAULT_ALLOWLIST, fam
    for fam in ("latency_e2e_p99_ms", "latency_slo_burn"):
        assert fam in WATCHDOG_REQUIRED, fam


# --------------------- (g) trace-stamp propagation: replay / DLQ / retry
def _mk_batch(n, t0=1000.0, tenant="t1"):
    rng = np.random.RandomState(7)
    return MeasurementBatch(
        tenant=tenant,
        stream_ids=np.zeros((n,), np.int32),
        values=rng.rand(n).astype(np.float32),
        event_ts=t0 + np.arange(n, dtype=np.float64),
        received_ts=t0 + np.arange(n, dtype=np.float64) + 5.0,
        valid=np.ones((n,), bool),
        device_tokens=np.array([f"dev-{i % 4}" for i in range(n)], object),
        names=np.full((n,), "temp", object),
    )


async def _wait_for(cond, secs=20.0):
    for _ in range(int(secs / 0.02)):
        if cond():
            return True
        await asyncio.sleep(0.02)
    return cond()


async def test_replay_batches_mint_replay_priority_and_skip_burn():
    bus = EventBus(TopicNaming("rp"))
    store = EventStore("t1", rows_per_segment=256)
    store.add_measurement_batch(_mk_batch(256))
    store.measurements._seal()
    topic = bus.naming.inbound_events("t1")
    bus.subscribe(topic, "lat-test")
    reg = MetricsRegistry()
    tracer = Tracer(reg, default=TracingConfig(sample_rate=1.0,
                                               slo_ms=60_000))
    eng = LatencyEngine(reg)
    eng.tracer = tracer
    tracer.latency = eng
    repl = ReplayEngine(bus, MetricsRegistry(), batch_rows=100,
                        tracer=tracer)
    job = repl.start_job("t1", store, target="rescore")
    assert await _wait_for(lambda: job.status == "done")
    got = []
    while True:
        items = await bus.consume(topic, "lat-test", 256, timeout_s=0.05)
        if not items:
            break
        got.extend(items)
    assert got
    # every republished batch carries a freshly minted replay-priority
    # context (the ledger key that keeps backfill out of the live SLO)
    for b in got:
        assert b.trace_ctx is not None
        assert b.trace_ctx.priority == "replay"
        assert b.trace_ctx.source_topic == "replay:rescore"
    base = now_ms()
    for b in got:
        tracer.record_span(b.trace_ctx, "inbound", base, base + 1.0,
                           queue_wait_ms=0.2)
    tracer.gc(force=True)
    led = eng._ledgers.get(("t1", "replay"))
    assert led is not None and len(led.entries) == len(got)
    assert eng._burn == {}  # replay NEVER burns the budget


async def test_dlq_entry_and_requeue_preserve_the_trace_context():
    reg = MetricsRegistry()
    bus = EventBus(TopicNaming("dl"))
    tracer = Tracer(reg, default=TracingConfig(sample_rate=0.0,
                                               slo_ms=60_000))
    cons = RetryingConsumer(bus, "t1", "inference", "g", metrics=reg,
                            tracer=tracer)
    ctx = tracer.mint("t1")
    item = types.SimpleNamespace(trace_ctx=ctx, deadline_ms=123.0)
    bus.subscribe(cons.dlq_topic, "dlq-reader")
    await cons.dead_letter(item, "src-topic", attempts=3,
                           error=RuntimeError("boom"))
    entries = await bus.consume(cons.dlq_topic, "dlq-reader", 16,
                                timeout_s=1.0)
    assert len(entries) == 1
    entry = entries[0]
    # the DLQ entry cross-references the trace and wraps the original
    # payload — the stamp survives the round trip
    assert entry["trace_id"] == ctx.trace_id
    assert entry["payload"].trace_ctx is ctx
    # requeue re-admission strips the deadline but not the trace context
    clear_deadline(entry)
    assert entry["payload"].deadline_ms is None
    assert entry["payload"].trace_ctx is ctx
    # the touched trace is tail-retained under the dlq reason, and a
    # post-requeue span lands on the SAME trace (continuity)
    b = now_ms()
    tracer.record_span(ctx, "inference", b, b + 2.0, queue_wait_ms=0.5)
    tracer.gc(force=True)
    tr = tracer.store.peek(ctx.trace_id)
    assert tr is not None and tr.decision == "dlq"
    assert [s.stage for s in tr.spans] == ["inference"]


def test_retry_spans_accumulate_on_one_trace():
    """A cross-slice poison retry re-runs the inference stage: both
    attempts record as spans of ONE retained trace, and the linear-stage
    vector sums them (retries are exactly the p99 story)."""
    reg = MetricsRegistry()
    tracer = Tracer(reg, default=TracingConfig(sample_rate=0.0,
                                               slo_ms=60_000))
    ctx = tracer.mint("t1")
    b = now_ms()
    tracer.record_span(ctx, "inference", b, b + 5, queue_wait_ms=1.0)
    tracer.mark_hit(ctx, "retry")
    tracer.record_span(ctx, "inference", b + 6, b + 9, queue_wait_ms=0.5)
    tracer.gc(force=True)
    tr = tracer.store.peek(ctx.trace_id)
    assert tr is not None and tr.decision == "retry"
    assert [s.stage for s in tr.spans].count("inference") == 2
    vec, _total = stage_vector(tr)
    assert vec["lane_wait"] == [pytest.approx(1.5), pytest.approx(8.0)]


# ------------------------------- (h) check_metrics queue-wait-twin lint
def test_check_metrics_queue_wait_twin_rule():
    reg = MetricsRegistry()
    reg.histogram("pipeline_stage_seconds", tenant="t1",
                  stage="decode").record(0.01)
    errs = check_metrics.lint_exposition(reg.prometheus_text())
    assert any(
        "pipeline_stage_queue_wait_seconds twin" in e for e in errs
    ), errs
    # pairing the wait histogram clears the finding
    reg.histogram("pipeline_stage_queue_wait_seconds", tenant="t1",
                  stage="decode").record(0.001)
    assert check_metrics.lint_exposition(reg.prometheus_text()) == []
    # the twin must match per-CHILD: a wait series for another label set
    # does not cover a new service series
    reg.histogram("pipeline_stage_seconds", tenant="t2",
                  stage="outbound").record(0.01)
    errs = check_metrics.lint_exposition(reg.prometheus_text())
    assert len(errs) == 1 and 't2' in errs[0] and "outbound" in errs[0]


# ------------------------------------------ (j) live REST reconciliation
@asynccontextmanager
async def _instance(tenant: str, tracing: TracingConfig):
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="lat",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
    ))
    await inst.start()
    try:
        await inst.add_tenant(tenant_config_from_template(
            tenant, "iot-temperature", tracing=tracing,
        ))
        rt = inst.tenants[tenant]
        rt.device_management.bootstrap_fleet(5)
        yield inst, rt
    finally:
        await inst.terminate()


@asynccontextmanager
async def _client(inst):
    client = TestClient(TestServer(make_app(inst)))
    await client.start_server()
    try:
        inst.users.create_user("admin", "password", ["ROLE_ADMIN"])
        resp = await client.post(
            "/api/authapi/jwt",
            json={"username": "admin", "password": "password"},
        )
        token = (await resp.json())["token"]
        client._session.headers["Authorization"] = f"Bearer {token}"
        yield client
    finally:
        await client.close()


async def _ingest(inst, tenant: str, n: int, pace_every: int = 0) -> None:
    """Publish n measurements; ``pace_every`` > 0 inserts short gaps so
    the receiver drains MULTIPLE decode batches (one trace each) instead
    of coalescing the burst into a single giant batch."""
    for i in range(n):
        await inst.broker.publish(
            f"sitewhere/{tenant}/input/dev-0000{i % 5}",
            json.dumps({
                "type": "measurement",
                "device_token": f"dev-0000{i % 5}",
                "name": "temperature",
                "value": 20.0 + (i % 7),
            }).encode(),
        )
        if pace_every and i % pace_every == pace_every - 1:
            await asyncio.sleep(0.04)


async def test_rest_latency_reports_reconcile_with_measured_p99():
    """Acceptance: on a driven instance the live decomposition is
    additive, reconciles with the measured e2e p99 within 15%, the
    breach cohorts name a dominant stage with openable trace links, the
    burn surfaces page-worthy rates under a sub-ms SLO, and the scrape
    (with the latency gauges live) passes the exposition lint including
    the queue-wait-twin rule."""
    cfg = TracingConfig(enabled=True, sample_rate=1.0, slo_ms=0.5)
    async with _instance("t1", cfg) as (inst, rt):
        # warmup: the first flush pays JAX compile, a 100x outlier that
        # no cohort mean should be asked to reconcile — drive it, then
        # reset the ledgers so the report covers steady state only
        await _ingest(inst, "t1", 24)
        await _wait_for(lambda: len(rt.event_store) >= 24)
        await asyncio.sleep(0.5)
        inst.tracer.gc(force=True)
        inst.latency._ledgers.clear()
        # steady state: paced so each drain cycle mints its own trace
        await _ingest(inst, "t1", 120, pace_every=6)
        await _wait_for(lambda: len(rt.event_store) >= 144)
        await asyncio.sleep(0.4)  # let outbound/rules spans land
        async with _client(inst) as client:
            resp = await client.get("/api/latency?flush=1")
            assert resp.status == 200
            body = await resp.json()
            assert body["stages"] == list(STAGES)
            fleet = body["fleet"]
            assert fleet is not None and fleet["n"] >= 8
            on_path = sum(
                s["total_ms"] for s in fleet["stages"] if s["on_path"]
            )
            assert on_path + fleet["residual_ms"] == pytest.approx(
                fleet["cohort_mean_ms"], abs=0.05
            )
            # the contiguous axis: the inference span is cut on the
            # batch's own flush record, so the device queue shows as a
            # stage of its own and next to nothing is left unnamed
            by = {s["stage"]: s for s in fleet["stages"]}
            assert by["inflight"]["total_ms"] > 0.0
            assert by["resolve"]["total_ms"] > 0.0
            assert "d2h_wait" not in by
            assert fleet["residual_ms"] <= 0.10 * fleet["cohort_mean_ms"]
            # the headline acceptance: decomposition ↔ measured p99
            assert abs(fleet["cohort_mean_ms"] - fleet["e2e_p99_ms"]) <= (
                0.15 * fleet["e2e_p99_ms"] + 0.05
            )
            assert body["cohorts"]
            assert body["cohorts"][0]["tenant"] == "t1"
            assert body["cohorts"][0]["dominant_stage"] in PATH_STAGES
            assert body["overhead"]["ingest_calls"] >= 8
            assert body["burn"]["t1"]["burn_5m"] is not None
            assert body["burn"]["t1"]["burn_5m"] >= 14.4  # sub-ms SLO

            resp = await client.get(
                "/api/tenants/t1/latency?worst=3&flush=1"
            )
            assert resp.status == 200
            rep = await resp.json()
            assert rep["slo_ms"] == pytest.approx(0.5)
            meas = rep["priorities"]["measurement"]
            assert meas["dominant_stage"] in PATH_STAGES
            assert rep["breach_cohorts"]
            top = rep["breach_cohorts"][0]
            assert top["tenant"] == "t1" and top["count"] >= 1
            assert top["stage"] in (*PATH_STAGES, "unattributed")
            assert 1 <= len(top["worst"]) <= 3
            link = top["worst"][0]["chrome"]
            assert link.startswith("/api/traces/")
            resp = await client.get(link)
            assert resp.status == 200
            trace = await resp.json()
            assert trace["traceEvents"]

            resp = await client.get("/api/tenants/nope/latency")
            assert resp.status == 404
            resp = await client.get("/api/tenants/t1/latency?worst=bogus")
            assert resp.status == 400

            # live gauges + conformant exposition (twin rule included)
            inst.latency.refresh_gauges()
            resp = await client.get("/metrics")
            text = await resp.text()
            assert 'latency_e2e_p99_ms{priority="measurement",tenant="t1"}' \
                in text
            assert "latency_slo_burn" in text
            assert check_metrics.lint_exposition(text) == []
