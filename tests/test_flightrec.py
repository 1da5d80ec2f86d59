"""Flight recorder & device-time attribution (ISSUE 6 acceptance).

Covers: (a) flight-recorder ring bounds + wrap + snapshot immutability /
rate limiting; (b) an injected scorer fault that trips the breaker
produces a snapshot retrievable over REST containing the faulting
flush's timing record with its trace_id linked; (c) live MFU accounting
matches a hand-computed FLOP count for a known LSTM config within 5%;
(d) watchdog rules fire the alert counter, force trace retention, and
snapshot the recorder; (e) metrics-history ring wrap + downsampling;
(g) OpenMetrics EOF + label-cardinality lint additions."""

import asyncio
import importlib.util
import json
import time
from contextlib import asynccontextmanager
from pathlib import Path

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from sitewhere_tpu.api.rest import make_app
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.runtime.config import (
    FaultTolerancePolicy,
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
    TracingConfig,
    tenant_config_from_template,
)
from sitewhere_tpu.runtime.flightrec import FlightRecorder, chrome_flush_events
from sitewhere_tpu.runtime.history import MetricsHistory, Watchdog
from sitewhere_tpu.runtime.metrics import (
    MetricsRegistry,
    MfuAccount,
    PEAK_FLOPS_BF16,
)
from sitewhere_tpu.runtime.tracing import Tracer

_TOOLS = Path(__file__).resolve().parent.parent / "tools"


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(name, _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_metrics = _load_tool("check_metrics")


# -- (a) flight-recorder rings ------------------------------------------


def test_ring_bounds_wrap_and_eviction():
    fr = FlightRecorder(capacity=4, max_rings=2)
    for i in range(7):
        fr.record("flush", "lstm_ad", rows=i)
    ring = fr.describe()["rings"]["flush"]["lstm_ad"]
    assert ring["capacity"] == 4
    assert ring["total"] == 7
    rows = [r["rows"] for r in ring["records"]]
    assert rows == [3, 4, 5, 6]  # oldest→newest, oldest wrapped out
    # ring count is bounded: a third key evicts the least-recently-used
    fr.record("flush", "deepar", rows=0)
    fr.record("flush", "lstm_ad", rows=99)   # touch → deepar is now LRU
    fr.record("flush", "transformer", rows=0)
    kinds = fr.describe()["rings"]["flush"]
    assert set(kinds) == {"lstm_ad", "transformer"}


def test_snapshot_immutable_rate_limited_and_bounded():
    t = [0.0]
    fr = FlightRecorder(
        capacity=8, max_snapshots=2, min_snapshot_interval_s=5.0,
        clock=lambda: t[0],
    )
    rec = fr.record("flush", "lstm_ad", rows=1, status="inflight")
    snap = fr.snapshot("breaker:lstm_ad", family="lstm_ad")
    assert snap is not None and snap["n_records"] == 1
    # completing the live record must NOT rewrite the frozen evidence
    rec["status"] = "ok"
    assert snap["rings"]["flush"]["lstm_ad"][0]["status"] == "inflight"
    # rate limit per reason; a different reason still snapshots
    assert fr.snapshot("breaker:lstm_ad") is None
    assert fr.snapshots_suppressed == 1
    t[0] = 6.0
    assert fr.snapshot("breaker:lstm_ad") is not None
    t[0] = 20.0
    fr.snapshot("slo:t1")
    assert len(fr.snapshots()) == 2  # bounded deque: oldest dropped
    assert fr.get_snapshot(snap["id"]) is None


def test_chrome_export_joins_host_and_device_windows():
    fr = FlightRecorder()
    fr.record(
        "flush", "lstm_ad", rows=64, bucket=64, assembly_s=0.001,
        h2d_stage_s=0.0005, dispatch_s=0.002, device_s=0.010,
        d2h_wait_s=0.003, resolve_s=0.001, status="ok", trace_id="abc",
    )
    events = chrome_flush_events(fr.describe()["rings"])
    by_name = {e["name"]: e for e in events}
    assert {"assembly", "h2d_stage", "dispatch", "device", "d2h_wait",
            "resolve"} <= set(by_name)
    # host phases are contiguous and end where the device window starts
    assert by_name["assembly"]["ts"] < by_name["h2d_stage"]["ts"]
    assert by_name["h2d_stage"]["ts"] < by_name["dispatch"]["ts"]
    dispatch_end = by_name["dispatch"]["ts"] + by_name["dispatch"]["dur"]
    assert abs(dispatch_end - by_name["device"]["ts"]) < 1.0  # µs
    # readback follows the device window
    dev_end = by_name["device"]["ts"] + by_name["device"]["dur"]
    assert abs(by_name["d2h_wait"]["ts"] - dev_end) < 1.0
    assert by_name["device"]["tid"] == "device"
    assert by_name["device"]["args"]["trace_id"] == "abc"


# -- (c) hand-computed FLOPs vs the declared accounting ------------------


def test_lstm_flops_per_row_matches_hand_count():
    """Independent hand count for lstm_ad (W=32, H=64): W-1 scan steps,
    each a fused [1→4H] + [H→4H] gate matmul, plus the per-step [H→1]
    head — 2 FLOPs per MAC. Must agree with the family's declared
    flops_per_row within 5% (the live-gauge acceptance bar)."""
    W, H = 32, 64
    steps = W - 1
    hand = steps * (2 * (1 * 4 * H) + 2 * (H * 4 * H) + 2 * (H * 1))
    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {"window": W, "hidden": H})
    declared = spec.flops_per_row(cfg, W)
    assert abs(declared - hand) / hand < 0.05
    # and transformer/deepar/vit declare the contract too
    for fam in ("deepar", "transformer", "vit_b16"):
        s = get_model(fam)
        assert s.flops_per_row is not None
        assert s.flops_per_row(s.config_cls(), W) > 0


def test_mfu_account_counters_and_gauge():
    reg = MetricsRegistry()
    acc = MfuAccount(reg, "lstm_ad")
    acc.record(flops=2.0e9, device_s=0.25)
    acc.record(flops=1.0e9, device_s=0.05)
    assert reg.counter("tpu_flops_total", family="lstm_ad").value == 3.0e9
    assert reg.counter(
        "tpu_device_seconds_total", family="lstm_ad"
    ).value == 0.3
    assert reg.gauge("tpu_mfu_pct", family="lstm_ad").value > 0.0


# -- instance-level: live attribution end to end -------------------------


@asynccontextmanager
async def booted(tenant="t1", **tenant_overrides):
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="fr",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
        history_resolution_s=0.05,  # fast ticks so history fills in-test
    ))
    await inst.start()
    try:
        await inst.add_tenant(tenant_config_from_template(
            tenant, "iot-temperature", **tenant_overrides,
        ))
        rt = inst.tenants[tenant]
        rt.device_management.bootstrap_fleet(5)
        yield inst, rt
    finally:
        await inst.terminate()


async def ingest(inst, tenant: str, n: int, base: float = 20.0) -> None:
    for i in range(n):
        await inst.broker.publish(
            f"sitewhere/{tenant}/input/dev-0000{i % 5}",
            json.dumps({
                "type": "measurement",
                "device_token": f"dev-0000{i % 5}",
                "name": "temperature",
                "value": base + (i % 7),
            }).encode(),
        )


async def wait_persisted(rt, n: int, timeout_s: float = 30.0) -> None:
    for _ in range(int(timeout_s / 0.05)):
        if len(rt.event_store) >= n:
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"only {len(rt.event_store)}/{n} persisted")


@asynccontextmanager
async def rest_client(inst):
    client = TestClient(TestServer(make_app(inst)))
    await client.start_server()
    try:
        inst.users.create_user("fradmin", "password", ["ROLE_ADMIN"])
        resp = await client.post(
            "/api/authapi/jwt",
            json={"username": "fradmin", "password": "password"},
        )
        token = (await resp.json())["token"]
        client._session.headers["Authorization"] = f"Bearer {token}"
        yield client
    finally:
        await client.close()


async def test_live_attribution_end_to_end():
    """Real scoring traffic: tpu_flops_total equals flushes × padded
    plane × hand-computed per-row FLOPs (within 5%), the live gauge
    moves, the flush blackbox fills with completed timing records, the
    per-family deliver gauge + device-stamped dispatch family exist, the
    history ring fills, and the scrape passes the extended lint."""
    mb = MicroBatchConfig(max_batch=64, deadline_ms=5.0, buckets=(64,),
                          window=32)
    async with booted("t1", microbatch=mb) as (inst, rt):
        await ingest(inst, "t1", 200)
        await wait_persisted(rt, 200)
        m = inst.metrics
        flushes = m.counter("tpu_inference.flushes").value
        assert flushes >= 1
        # executed plane per flush: n_slots × data shards × bucket
        scorer = inst.inference.scorers["lstm_ad"]
        plane_rows = scorer.n_slots * inst.mesh.n_data_shards * 64
        W, H = 32, 64
        hand_per_row = (W - 1) * (
            2 * (1 * 4 * H) + 2 * (H * 4 * H) + 2 * H
        )
        expected = flushes * plane_rows * hand_per_row
        got = m.counter("tpu_flops_total", family="lstm_ad").value
        assert abs(got - expected) / expected < 0.05, (got, expected)
        assert m.counter(
            "tpu_device_seconds_total", family="lstm_ad"
        ).value > 0
        assert m.gauge("tpu_mfu_pct", family="lstm_ad").value > 0
        # flush blackbox records completed in place by the reaper
        rings = inst.flightrec.describe()["rings"]
        recs = rings["flush"]["lstm_ad"]["records"]
        done = [r for r in recs if r.get("status") == "ok"]
        assert done, recs
        for field in ("rows", "bucket", "assembly_s", "h2d_stage_s",
                      "dispatch_s", "d2h_wait_s", "resolve_s", "device_s"):
            assert done[-1].get(field) is not None, (field, done[-1])
        assert "stage" in rings  # strided per-stage records ride along
        # let the 50 ms history tick sample a few times
        await asyncio.sleep(0.3)
        assert inst.history.count >= 2
        assert inst.history.latest("tpu_inference.flushes") >= flushes - 1
        text = m.prometheus_text()
        assert 'tpu_inference_deliver_inflight_family{family="lstm_ad"}' in text
        assert "tpu_mfu_pct{" in text
        # 8-virtual-device mesh = the multichip path: dispatch carries a
        # device label (per-device attribution for the mesh promotion)
        disp = [
            l for l in text.splitlines()
            if l.startswith("tpu_inference_dispatch_seconds{")
        ]
        assert disp and all('device="' in l for l in disp), disp[:3]
        assert not check_metrics.lint_exposition(text)
        async with rest_client(inst) as client:
            resp = await client.get("/api/flightrec?chrome=1")
            body = await resp.json()
            assert resp.status == 200
            assert body["rings"]["flush"]["lstm_ad"]["records"]
            assert body["traceEvents"]
            resp = await client.get(
                "/api/metrics/history?name=tpu_inference.flushes&step=2"
            )
            hist = await resp.json()
            assert resp.status == 200
            assert hist["series"]["tpu_inference.flushes"]
            assert len(hist["age_s"]) == hist["samples"]


async def test_shadow_canary_never_inflates_mfu_accounting():
    """ISSUE-9 MfuAccount audit: with the canary shadow-scoring EVERY
    flush (canary_frac=1.0, standing bf16 variant), tpu_flops_total must
    equal flushes × plane × the SERVING variant's per-row flops exactly
    — zero shadow contamination — while the shadow work lands in its own
    tpu_shadow_flops_total counter. The MFU meter (the idle-decay tick)
    must carry only the primary marks: a shadow flush marking it would
    both inflate the live gauge and keep an idle family's decay alive."""
    mb = MicroBatchConfig(max_batch=64, deadline_ms=5.0, buckets=(64,),
                          window=32)
    async with booted(
        "t1", microbatch=mb, param_dtype="bf16", canary_frac=1.0,
    ) as (inst, rt):
        await ingest(inst, "t1", 200)
        await wait_persisted(rt, 200)
        m = inst.metrics
        scorer = inst.inference.scorers["lstm_ad"]
        assert scorer.param_dtype == "bf16" and scorer.canary_frac == 1.0
        flushes = m.counter("tpu_inference.flushes").value
        canary = m.counter("tpu_inference.canary_flushes").value
        assert flushes >= 1 and canary == flushes  # frac 1.0, standing
        primary = m.counter("tpu_flops_total", family="lstm_ad").value
        shadow = m.counter("tpu_shadow_flops_total", family="lstm_ad").value
        # exact expected totals from the same per-flush functions the
        # service uses — equality IS the no-inflation proof
        assert primary == pytest.approx(
            flushes * scorer.flops_per_flush(64), rel=1e-6
        )
        assert shadow == pytest.approx(
            canary * scorer.shadow_flops_per_flush(64), rel=1e-6
        )
        # the shadow count is the LEGACY (per-step head, full width)
        # count — genuinely different work than the fused k=1 variant
        assert scorer.shadow_flops_per_flush(64) > scorer.flops_per_flush(64)
        # idle-decay meter carries only primary marks: its windowed mass
        # equals the primary counter, not primary+shadow
        acc = inst.inference._mfu["lstm_ad"]
        marked = sum(n for _ts, n in acc._meter._events)
        assert marked == pytest.approx(primary, rel=1e-6)
        # divergence verdicts reached the canary surface
        assert m.counter(
            "score_canary_flushes_total", family="lstm_ad"
        ).value == canary
        delta = m.gauge(
            "score_canary_mean_abs_delta", family="lstm_ad"
        ).value
        assert 0.0 <= delta < 0.05  # bf16 vs f32 master: cast noise only
        rep = inst.tenant_health_report("t1")
        assert rep["canary"]["flushes"] == canary
        assert rep["variant"]["param_dtype"] == "bf16"


# -- (b) breaker trip → snapshot over REST -------------------------------


async def test_breaker_trip_snapshot_over_rest():
    """An injected scorer fault trips the family breaker; the snapshot
    taken at the trip is retrievable over REST and contains the faulting
    flush's timing record with its trace_id, which resolves at
    /api/traces/{id}."""
    ft = FaultTolerancePolicy(
        breaker_defer_to_failover=False, breaker_min_samples=2,
        breaker_window=4, breaker_failure_rate=0.5, breaker_open_s=60.0,
    )
    tr = TracingConfig(enabled=True, sample_rate=1.0, slo_ms=60_000)
    async with booted(
        "t1", fault_tolerance=ft, tracing=tr,
    ) as (inst, rt):
        await ingest(inst, "t1", 40)
        await wait_persisted(rt, 40)
        inst.inference.scorers["lstm_ad"].fault_steps = 3
        await ingest(inst, "t1", 40, base=30.0)
        # events still persist (resolved unscored through the reap FIFO)
        await wait_persisted(rt, 80)
        for _ in range(200):
            if inst.flightrec.snapshots_taken:
                break
            await asyncio.sleep(0.05)
        snaps = inst.flightrec.snapshots()
        assert snaps, "breaker trip took no flight-recorder snapshot"
        snap = next(s for s in snaps if s["reason"].startswith("breaker:"))
        faulting = [
            r for r in snap["rings"]["flush"]["lstm_ad"]
            if r.get("status") == "error"
        ]
        assert faulting, snap["rings"]["flush"]["lstm_ad"]
        rec = faulting[0]
        assert "injected scorer fault" in rec["error"]
        assert rec["assembly_s"] is not None  # the timing record
        assert rec["trace_id"], rec
        # the snapshot's meta links the trip-causing flush's trace
        assert snap["meta"].get("trace_id") in {
            r["trace_id"] for r in faulting
        }
        async with rest_client(inst) as client:
            resp = await client.get("/api/flightrec/snapshots")
            listing = await resp.json()
            assert resp.status == 200
            assert any(
                s["reason"] == snap["reason"] for s in listing["snapshots"]
            )
            # the listing is summaries only — full rings (potentially
            # tens of MB across retained snapshots) are per-id fetches
            assert all("rings" not in s for s in listing["snapshots"])
            resp = await client.get(
                f"/api/flightrec/snapshots?id={snap['id']}"
            )
            body = await resp.json()
            assert resp.status == 200
            got = [
                r for r in body["rings"]["flush"]["lstm_ad"]
                if r.get("status") == "error"
            ]
            assert got and got[0]["trace_id"] == rec["trace_id"]
            assert body["traceEvents"] is not None
            # the linked trace resolves (flush pending tail decisions)
            await client.get("/api/traces?flush=1")
            resp = await client.get(f"/api/traces/{rec['trace_id']}")
            assert resp.status == 200


# -- (d) watchdog ---------------------------------------------------------


def _mk_watchdog(reg, **kw):
    t = {"now": 0.0}

    def clock():
        return t["now"]

    hist = MetricsHistory(reg, capacity=600, clock=clock)
    fr = FlightRecorder(min_snapshot_interval_s=0.0, clock=clock)
    tracer = Tracer(reg, default=TracingConfig(sample_rate=0.0))
    wd = Watchdog(
        reg, hist, flightrec=fr, tracer=tracer, clock=clock,
        warmup=5, window=3, cooldown_s=10.0, credit_window=4,
        min_flushes=4, **kw,
    )
    return t, hist, fr, tracer, wd


def test_watchdog_recompile_alert_retention_and_snapshot():
    reg = MetricsRegistry()
    compiles = reg.counter("tpu_inference.compiles")
    compiles.inc(3)  # prewarm compiles, before warmup — never alert
    t, hist, fr, tracer, wd = _mk_watchdog(reg)
    for i in range(8):
        t["now"] = float(i)
        hist.sample()
        assert wd.evaluate() == []
    compiles.inc()  # steady-state recompile
    t["now"] = 8.0
    hist.sample()
    fired = wd.evaluate()
    assert [a["rule"] for a in fired] == ["steady_state_recompile"]
    assert reg.counter(
        "watchdog_alerts_total", rule="steady_state_recompile"
    ).value == 1
    # cooldown: the same persistent condition does not re-alert
    t["now"] = 9.0
    hist.sample()
    assert wd.evaluate() == []
    # flight recorder snapshotted under the rule's reason
    assert any(
        s["reason"] == "watchdog:steady_state_recompile"
        for s in fr.snapshots()
    )
    # forced retention: a clean trace deciding inside the window is KEPT
    # (sample_rate 0.0 would have dropped it)
    from sitewhere_tpu.runtime.tracing import now_ms

    ctx = tracer.mint("t1")
    wall = now_ms()
    tracer.record_span(ctx, "outbound", wall, wall + 1.0)  # fast & clean
    tracer.gc(force=True)
    tr = tracer.store.peek(ctx.trace_id)
    assert tr is not None and tr.decision == "watchdog"


def test_watchdog_credit_and_d2h_spike_rules():
    reg = MetricsRegistry()
    t, hist, fr, _tracer, wd = _mk_watchdog(reg)
    credit = reg.gauge("overload_credit", tenant="t9")
    d2h = reg.histogram("tpu_inference.d2h_wait", unit="s")
    credit.set(1.0)
    # steady fast-wait traffic: the windowed-mean rule deltas the
    # cumulative count/sum series, so both windows need real samples
    for i in range(6):
        t["now"] = float(i)
        for _ in range(5):
            d2h.record(0.001)
        hist.sample()
        wd.evaluate()
    credit.set(0.4)  # sustained sub-1 credit
    for i in range(6, 11):
        t["now"] = float(i)
        for _ in range(5):
            d2h.record(0.001)
        hist.sample()
    fired = wd.evaluate(now=t["now"])
    assert "overload_credit" in [a["rule"] for a in fired]
    detail = next(a for a in fired if a["rule"] == "overload_credit")
    assert "t9" in detail["detail"]
    # wait spike: flood with slow waits → the WINDOW mean jumps (the
    # lifetime p99 alone would go inert after hours of uptime — the
    # rule must delta, not read cumulative state)
    for _ in range(200):
        d2h.record(0.4)
    t["now"] = 12.0
    hist.sample()
    fired = wd.evaluate(now=t["now"])
    assert "d2h_wait_spike" in [a["rule"] for a in fired]
    assert reg.counter(
        "watchdog_alerts_total", rule="d2h_wait_spike"
    ).value == 1


def test_watchdog_overlap_collapse_rule():
    reg = MetricsRegistry()
    t, hist, fr, _tracer, wd = _mk_watchdog(reg)
    staged = reg.counter("tpu_inference.h2d_staged")
    ovl = reg.counter("tpu_inference.h2d_overlapped")
    # healthy window: ~60% overlap
    for i in range(4):
        staged.inc(5)
        ovl.inc(3)
        t["now"] = float(i)
        hist.sample()
    # collapse: flushes keep coming, overlap stops
    for i in range(4, 8):
        staged.inc(5)
        t["now"] = float(i)
        hist.sample()
    fired = wd.evaluate(now=t["now"])
    assert "h2d_overlap_collapse" in [a["rule"] for a in fired]


def test_watchdog_rule_error_is_counted_not_silent():
    """A rule that raises must not kill the tick NOR go dark: the
    failure is visible as watchdog_rule_errors_total{rule}."""
    reg = MetricsRegistry()
    t, hist, fr, _tracer, wd = _mk_watchdog(reg)
    wd._rule_steady_state_recompile = None  # not callable → raises
    hist.sample()
    assert wd.evaluate(now=0.0) == []  # other rules still evaluated
    assert reg.counter(
        "watchdog_rule_errors_total", rule="steady_state_recompile"
    ).value == 1


def test_custom_allowlist_unions_watchdog_required():
    """A trimmed metrics_history_allowlist must not starve the enabled
    watchdog's rules of the families they read; with the watchdog off
    the configured list stands as-is."""
    from sitewhere_tpu.runtime.history import WATCHDOG_REQUIRED

    on = SiteWhereInstance(InstanceConfig(
        instance_id="fr-al",
        mesh=MeshConfig(tenant_axis=4, data_axis=2),
        metrics_history_allowlist=["tpu_mfu_pct"],
    ))
    assert "tpu_mfu_pct" in on.history.allowlist
    assert set(WATCHDOG_REQUIRED) <= set(on.history.allowlist)
    off = SiteWhereInstance(InstanceConfig(
        instance_id="fr-al2",
        mesh=MeshConfig(tenant_axis=4, data_axis=2),
        metrics_history_allowlist=["tpu_mfu_pct"],
        watchdog_enabled=False,
    ))
    assert off.history.allowlist == ("tpu_mfu_pct",)


# -- (e) history ring -----------------------------------------------------


def test_history_wrap_and_downsampling():
    reg = MetricsRegistry()
    g = reg.gauge("overload_credit", tenant="a")
    t = {"now": 0.0}
    hist = MetricsHistory(reg, capacity=10, clock=lambda: t["now"])
    for i in range(25):
        t["now"] = float(i)
        g.set(float(i))
        hist.sample()
    assert hist.count == 10 and hist.total == 25
    v = hist.values('overload_credit{tenant="a"}')
    assert list(v) == [float(x) for x in range(15, 25)]  # oldest-first
    # max-pool downsampling preserves the spike in each bucket
    assert hist.downsample(v, 3) == [17.0, 20.0, 23.0, 24.0]
    # all-NaN buckets render as None (series absent during those ticks)
    nanv = np.array([np.nan, np.nan, 1.0, np.nan])
    assert hist.downsample(nanv, 2) == [None, 1.0]
    body = hist.series(names=['overload_credit{tenant="a"}'], step=5)
    assert body["series"]['overload_credit{tenant="a"}'] == [19.0, 24.0]
    assert body["samples"] == 2 and len(body["age_s"]) == 2
    # a series that appears mid-flight backfills NaN → None on render
    reg.gauge("overload_credit", tenant="b").set(7.0)
    t["now"] = 25.0
    hist.sample()
    vb = hist.values('overload_credit{tenant="b"}')
    assert np.isnan(vb[:-1]).all() and vb[-1] == 7.0


# -- (g) exposition lint additions ---------------------------------------


def test_lint_eof_and_cardinality():
    reg = MetricsRegistry()
    reg.counter("good_total", tenant="a").inc()
    text = reg.prometheus_text()
    assert text.rstrip().endswith("# EOF")
    assert not check_metrics.lint_exposition(text)
    # truncated exposition (no EOF) is a finding
    truncated = text.rsplit("# EOF", 1)[0]
    errs = check_metrics.lint_exposition(truncated)
    assert any("EOF" in e for e in errs)
    # per-event identity labels are findings
    reg2 = MetricsRegistry()
    reg2.counter("evil_total", trace_id="abc123").inc()
    errs = check_metrics.lint_exposition(reg2.prometheus_text())
    assert any("trace_id" in e for e in errs)
    # unbounded child sets are findings (tiny cap to keep the test fast)
    reg3 = MetricsRegistry()
    for i in range(8):
        reg3.gauge("fanout", shard=str(i)).set(1.0)
    errs = check_metrics.lint_exposition(
        reg3.prometheus_text(), max_children=5
    )
    assert any("unbounded label set" in e for e in errs)
    # gauges must not wear the counter suffix
    reg4 = MetricsRegistry()
    reg4.gauge("depth_total", tenant="a").set(1.0)
    errs = check_metrics.lint_exposition(reg4.prometheus_text())
    assert any("_total suffix" in e for e in errs)
