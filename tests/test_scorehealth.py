"""Score-quality & model-health suite (docs/OBSERVABILITY.md "Score
health & canaries"): device-side sketch == np.histogram on the identical
masked rows for every wire dtype and for fused K>1, ScoreHealth drift
windows (reference freeze / PSI / KS / quantiles / re-baseline), the
shadow-scoring canary (int8 divergence vs the bf16 control, state
non-commitment), the watchdog score rules with variant-stamped snapshot
meta, the check_metrics bin-cardinality / score_quality naming rules,
and the end-to-end drift drive: one tenant's regime change fires
score_drift while the healthy tenants stay quiet."""

import asyncio
import importlib.util
import json
import time
from contextlib import asynccontextmanager
from pathlib import Path

import numpy as np
import pytest

import sitewhere_tpu.parallel.sharded as sharded
from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.models.common import SKETCH_NBINS, sketch_edges
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.runtime.bus import EventBus
from sitewhere_tpu.runtime.history import MetricsHistory, Watchdog
from sitewhere_tpu.runtime.flightrec import FlightRecorder
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.scorehealth import (
    ScoreHealth,
    hist_quantile,
    ks_stat,
    psi,
)

_spec = importlib.util.spec_from_file_location(
    "check_metrics",
    Path(__file__).resolve().parent.parent / "tools" / "check_metrics.py",
)
check_metrics = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_metrics)

W, HID = 16, 8


def _build(wire_dtype="f32", fuse_k=1, param_dtype="f32", fused=True,
           sketch=True):
    prev_f, prev_s = sharded.FUSED_STEP_ENABLED, sharded.SCORE_SKETCH_ENABLED
    sharded.FUSED_STEP_ENABLED = fused
    sharded.SCORE_SKETCH_ENABLED = sketch
    try:
        mm = MeshManager(tenant=4, data=2)
        cfg = make_config("lstm_ad", {"window": W, "hidden": HID})
        return sharded.ShardedScorer(
            mm, get_model("lstm_ad"), cfg, slots_per_shard=2,
            max_streams=16, window=W, wire_dtype=wire_dtype,
            fuse_k=fuse_k, param_dtype=param_dtype,
        )
    finally:
        sharded.FUSED_STEP_ENABLED = prev_f
        sharded.SCORE_SKETCH_ENABLED = prev_s


def _flush(rng, scorer, b_lane=6, burst=False, slots=(1, 5)):
    """Counts-mode wire flush for the ACTIVE slots (the service never
    packs rows for inactive ones); ``burst`` packs several rows per
    stream (exercises the fused K>1 per-position resolution)."""
    t, d = scorer.n_slots, scorer.mm.n_data_shards
    ids = np.zeros((t, d * b_lane), scorer.ids_np_dtype)
    vals = np.zeros((t, d * b_lane), scorer.vals_np_dtype)
    counts = np.zeros((t, d), np.int32)
    for ti in slots:
        for di in range(d):
            k = int(rng.integers(1, b_lane + 1))
            base = di * b_lane
            n_streams = 2 if burst else 8
            ids[ti, base:base + k] = np.sort(rng.integers(0, n_streams, k))
            vals[ti, base:base + k] = rng.normal(size=k)
            counts[ti, di] = k
    return ids, vals, counts


def _expected_hist(scorer, s_np, counts, b_lane):
    """np.histogram per slot over exactly the masked (valid) rows."""
    bins = np.r_[-np.inf, scorer.sketch_edges, np.inf]
    t, d = scorer.n_slots, scorer.mm.n_data_shards
    out = np.zeros((t, SKETCH_NBINS), np.int64)
    for ti in range(t):
        rows = np.concatenate([
            s_np[ti, di * b_lane: di * b_lane + counts[ti, di]]
            for di in range(d)
        ])
        out[ti], _ = np.histogram(rows, bins=bins)
    return out


# ------------------------------------------------- device-side sketches
@pytest.mark.parametrize("wire_dtype", ["f32", "bf16", "f16"])
def test_sketch_matches_np_histogram_every_wire_dtype(wire_dtype):
    """The step's device histogram equals np.histogram over the identical
    masked rows, every step of a stateful drive."""
    scorer = _build(wire_dtype=wire_dtype)
    for s in (1, 5):
        scorer.activate(s)
    rng = np.random.default_rng(11)
    b_lane = 6
    for _ in range(4):
        ids, vals, counts = _flush(rng, scorer, b_lane)
        s_dev = scorer.step_counts(*scorer.stage_inputs(ids, vals, counts))
        s_np = np.asarray(s_dev).astype(np.float32)
        sk = np.asarray(scorer.last_sketch)
        assert sk.shape == (scorer.n_slots, scorer.mm.n_data_shards,
                            SKETCH_NBINS)
        got = sk.sum(axis=1)
        exp = _expected_hist(scorer, s_np, counts, b_lane)
        np.testing.assert_array_equal(got, exp)
    assert got.sum() == counts.sum()  # every valid row binned, none extra


def test_sketch_matches_histogram_fused_k_gt1():
    """fuse_k=3: burst rows resolve at their OWN position's score and the
    sketch bins those per-position scores — still equal to np.histogram
    over the returned (per-row) plane."""
    scorer = _build(fuse_k=3)
    assert scorer.k_steps == 3
    scorer.activate(0)
    scorer.activate(3)
    rng = np.random.default_rng(13)
    b_lane = 6
    distinct = 0
    for _ in range(5):
        ids, vals, counts = _flush(rng, scorer, b_lane, burst=True,
                                   slots=(0, 3))
        s_np = np.asarray(
            scorer.step_counts(*scorer.stage_inputs(ids, vals, counts))
        ).astype(np.float32)
        got = np.asarray(scorer.last_sketch).sum(axis=1)
        exp = _expected_hist(scorer, s_np, counts, b_lane)
        np.testing.assert_array_equal(got, exp)
        # burst rows of one stream produced distinct per-position scores
        row0 = s_np[0, :counts[0, 0]]
        distinct = max(distinct, len(np.unique(row0[row0 != 0.0])))
    assert distinct > 1


def test_sketch_kill_switch_and_legacy_branch():
    """SCORE_SKETCH_ENABLED=False builds steps with no histogram output;
    the legacy (unfused) branch emits the sketch too."""
    off = _build(sketch=False)
    off.activate(0)
    rng = np.random.default_rng(5)
    ids, vals, counts = _flush(rng, off, slots=(0,))
    np.asarray(off.step_counts(*off.stage_inputs(ids, vals, counts)))
    assert off.last_sketch is None and not off.sketch
    legacy = _build(fused=False)
    assert not legacy.fused and legacy.sketch
    legacy.activate(0)
    s_np = np.asarray(
        legacy.step_counts(*legacy.stage_inputs(ids, vals, counts))
    ).astype(np.float32)
    got = np.asarray(legacy.last_sketch).sum(axis=1)
    np.testing.assert_array_equal(
        got, _expected_hist(legacy, s_np, counts, 6)
    )


# ------------------------------------------------- ScoreHealth statistics
def test_psi_ks_and_quantile_math():
    rng = np.random.default_rng(0)
    # concentrated score bulk (a realistic anomaly-score distribution
    # occupies a band of the log axis, not all 64 bins)
    base = np.zeros(SKETCH_NBINS, np.int64)
    base[12:24] = rng.integers(50, 100, 12)
    # same distribution, resampled: debiased PSI ~ 0, KS small
    noisy = base.copy()
    noisy[12:24] += rng.integers(-5, 6, 12)
    assert psi(base, noisy) < 0.05
    assert ks_stat(base, noisy) < 0.05
    # mass shifted decades up the log axis: both explode
    shifted = np.roll(base, 30)
    assert psi(base, shifted) > 1.0
    assert ks_stat(base, shifted) > 0.3
    assert psi(np.zeros(SKETCH_NBINS), base) == 0.0
    # quantile interpolation: all mass in one bin → inside that bin
    edges = sketch_edges()
    h = np.zeros(SKETCH_NBINS, np.int64)
    h[10] = 100
    q = hist_quantile(h, edges, 0.5)
    assert edges[9] <= q <= edges[10]
    assert hist_quantile(h, edges, 0.0) <= q <= hist_quantile(h, edges, 0.99)


def test_reference_freeze_drift_verdict_and_rebaseline():
    reg = MetricsRegistry()
    sh = ScoreHealth(reg, window_rows=100, warmup_windows=2, skip_windows=1,
                     min_eval_interval_s=0.0)
    edges = sketch_edges()
    sh.register("t1", "lstm_ad", 0, edges, variant={"param_dtype": "int8"})
    rng = np.random.default_rng(1)

    def ingest(hist):
        full = np.zeros((4, SKETCH_NBINS), np.int64)
        full[0] = hist
        sh.ingest_sketch("lstm_ad", full)

    base = np.zeros(SKETCH_NBINS, np.int64)
    base[20:30] = 10  # 100 rows/window
    ingest(base.copy())                      # skip window (cold start)
    assert sh.health_report("t1")["verdict"] == "warming"
    for _ in range(2):                       # warmup → reference freezes
        ingest(base.copy())
    rep = sh.health_report("t1")
    assert rep["reference_rows"] == 200
    ingest(base.copy())                      # healthy window
    rep = sh.health_report("t1")
    assert rep["verdict"] == "ok" and rep["psi"] < 0.25
    assert rep["quantiles"]["p50"] > 0
    assert rep["variant"] == {"param_dtype": "int8"}
    drifted = np.roll(base, 25)
    for _ in range(8):                       # rolling window fully drifted
        ingest(drifted.copy())
    rep = sh.health_report("t1")
    assert rep["verdict"] == "drifting" and rep["psi"] > 1.0
    assert reg.gauge(
        "score_quality_psi", family="lstm_ad", tenant="t1"
    ).value > 1.0
    d = sh.dist_report("t1")
    assert d["reference"] is not None and len(d["edges"]) == SKETCH_NBINS - 1
    # explicit re-baseline: reference drops, warmup restarts
    assert sh.rebaseline("t1")
    rep = sh.health_report("t1")
    assert rep["verdict"] == "warming" and rep["reference_rows"] == 0
    ingest(drifted.copy())                   # skip again
    for _ in range(2):
        ingest(drifted.copy())               # new reference = new regime
    ingest(drifted.copy())
    assert sh.health_report("t1")["verdict"] == "ok"


def test_rates_unscored_nan_and_remove():
    reg = MetricsRegistry()
    sh = ScoreHealth(reg, window_rows=64, warmup_windows=1, skip_windows=0,
                     min_eval_interval_s=0.0)
    sh.register("t1", "lstm_ad", 1, sketch_edges())
    hist = np.zeros((2, SKETCH_NBINS), np.int64)
    hist[1, 30] = 48
    nan_by_slot = np.array([0, 8])
    sh.note_unscored("t1", 8)
    sh.ingest_sketch("lstm_ad", hist, nan_by_slot)  # 48+8+8 = 64 → rotate
    rep = sh.health_report("t1")
    assert rep["rates"]["nan"] == pytest.approx(8 / 64)
    assert rep["rates"]["unscored"] == pytest.approx(8 / 64)
    assert rep["nan_total"] == 8 and rep["unscored_total"] == 8
    assert reg.gauge(
        "score_quality_nan_rate", family="lstm_ad", tenant="t1"
    ).value == pytest.approx(8 / 64)
    # failover slot re-map keeps history; remove drops ONLY this
    # module's tenant children — an engine stop also runs on hot
    # reconfigure, so other subsystems' cumulative tenant counters must
    # survive it
    sh.register("t1", "lstm_ad", 3, sketch_edges())
    assert sh.health_report("t1")["rows_total"] == 64
    reg.counter("pipeline_expired_total", tenant="t1",
                stage="inference").inc(3)
    sh.remove("t1")
    assert sh.health_report("t1") is None
    assert "score_quality_nan_rate" not in {
        n for n, fam in reg._labeled.items() if fam
    }
    assert reg.counter(
        "pipeline_expired_total", tenant="t1", stage="inference"
    ).value == 3


# ------------------------------------------------- shadow-scoring canary
def test_canary_gating_and_hot_swap_arming():
    scorer = _build()  # fused, f32, k=1
    scorer.canary_frac = 1.0
    assert not scorer.canary_active()       # nothing to compare
    assert not scorer.canary_take()
    import jax

    params = get_model("lstm_ad").init(
        jax.random.PRNGKey(9), scorer.cfg
    )
    scorer.activate(0, params=params)       # hot-swap arms the canary
    assert scorer.canary_active()
    took = [scorer.canary_take() for _ in range(sharded.CANARY_SWAP_FLUSHES)]
    assert all(took)                        # frac 1.0 → every flush
    assert not scorer.canary_take()         # countdown burned down
    scorer.canary_frac = 0.5
    int8 = _build(param_dtype="int8")
    int8.canary_frac = 0.5
    int8.activate(0)
    takes = [int8.canary_take() for _ in range(10)]
    assert sum(takes) == 5                  # variant condition is standing
    int8.canary_frac = 0.0
    assert not int8.canary_active()


def test_shadow_divergence_int8_vs_bf16_control():
    """The int8 canary reports real divergence the bf16 control does not,
    and a deliberately mis-scaled int8 sidecar reports much more —
    exactly the regression the canary exists to catch. Comparison runs
    on gathered row vectors, the same way the service compares."""

    def divergence(param_dtype, corrupt=False):
        scorer = _build(param_dtype=param_dtype)
        scorer.canary_frac = 1.0
        scorer.activate(0)
        scorer.activate(5)
        r = np.random.default_rng(21)
        worst = 0.0
        for i in range(6):
            ids, vals, counts = _flush(r, scorer, slots=(0, 5))
            n = int(counts.sum())
            staged = scorer.stage_inputs(
                ids.astype(scorer.ids_np_dtype),
                vals.astype(scorer.vals_np_dtype), counts,
            )
            if corrupt and i >= 3:
                import jax.tree_util as jtu

                kp = scorer.kernel_params()
                scorer._kernel_params = jtu.tree_map(
                    lambda x: x * 4.0
                    if hasattr(x, "dtype")
                    and x.dtype == np.float32 and x.ndim >= 2 else x,
                    kp,
                )
                scorer._kernel_dirty = False
            shadow_g = np.asarray(scorer.gather_rows(
                scorer.shadow_step_counts(*staged), staged[2], n
            )).astype(np.float32)[:n]
            prim_g = np.asarray(scorer.gather_rows(
                scorer.step_counts(*staged), staged[2], n
            )).astype(np.float32)[:n]
            ok = np.isfinite(shadow_g) & np.isfinite(prim_g)
            if i >= 3 and ok.any():
                worst = max(
                    worst, float(np.abs(prim_g[ok] - shadow_g[ok]).mean())
                )
        return worst

    d_bf16 = divergence("bf16")
    d_int8 = divergence("int8")
    d_bad = divergence("int8", corrupt=True)
    assert d_bf16 < 2e-2                    # control: cast noise only
    assert d_int8 > 0.0                     # real quantization divergence
    assert d_bad > max(d_int8, 5e-2) and d_bad > 4 * d_bf16


def test_shadow_never_commits_window_state():
    """Interleaving shadow steps must not change the primary sequence:
    the shadow reads state without donating or committing it."""
    a = _build(param_dtype="int8")
    b = _build(param_dtype="int8")
    for s in (a, b):
        s.canary_frac = 1.0
        s.activate(0)
    rng = np.random.default_rng(3)
    flushes = [_flush(rng, a, slots=(0,)) for _ in range(4)]
    outs_a, outs_b = [], []
    for ids, vals, counts in flushes:
        sa = a.stage_inputs(ids, vals, counts)
        a.shadow_step_counts(*sa)           # shadow runs...
        outs_a.append(np.asarray(a.step_counts(*sa)).astype(np.float32))
        sb = b.stage_inputs(ids, vals, counts)
        outs_b.append(np.asarray(b.step_counts(*sb)).astype(np.float32))
    for x, y in zip(outs_a, outs_b):
        np.testing.assert_array_equal(x, y)  # ...and left no trace


# ------------------------------------------------- watchdog score rules
class _VariantStub:
    def variant(self, tenant):
        return {"param_dtype": "int8", "k_steps": 2}


def _hist_with(reg, n, setter):
    hist = MetricsHistory(reg, resolution_s=1.0, capacity=64)
    for i in range(n):
        setter(i)
        hist.sample(now=float(i))
    return hist


def test_watchdog_score_drift_rule_meta_and_cooldown():
    reg = MetricsRegistry()
    fr = FlightRecorder()
    g = reg.gauge("score_quality_psi", family="lstm_ad", tenant="drifty")
    calm = reg.gauge("score_quality_psi", family="lstm_ad", tenant="calm")
    calm.set(0.01)

    def setter(i):
        g.set(0.9 if i >= 4 else 0.0)

    hist = _hist_with(reg, 12, setter)
    wd = Watchdog(
        reg, hist, flightrec=fr, scorehealth=_VariantStub(),
        drift_window=4, cooldown_s=60.0,
    )
    fired = wd.evaluate(now=100.0)
    drift = [a for a in fired if a["rule"] == "score_drift"]
    assert len(drift) == 1
    assert drift[0]["tenant"] == "drifty"
    assert "calm" not in drift[0]["detail"]
    assert drift[0]["variant"]["param_dtype"] == "int8"
    assert reg.counter(
        "watchdog_alerts_total", rule="score_drift"
    ).value == 1
    snaps = fr.snapshot_summaries()
    assert any(
        s["reason"] == "watchdog:score_drift"
        and s["meta"].get("tenant") == "drifty"
        and s["meta"].get("variant", {}).get("param_dtype") == "int8"
        for s in snaps
    )
    assert not [
        a for a in wd.evaluate(now=110.0) if a["rule"] == "score_drift"
    ]  # cooldown


def test_watchdog_nan_rate_spike_rule():
    reg = MetricsRegistry()
    g = reg.gauge("score_quality_nan_rate", family="lstm_ad", tenant="t9")

    def setter(i):
        g.set(0.5 if i >= 6 else 0.0)

    hist = _hist_with(reg, 12, setter)
    wd = Watchdog(reg, hist, drift_window=4)
    fired = wd.evaluate(now=50.0)
    spikes = [a for a in fired if a["rule"] == "nan_rate_spike"]
    assert len(spikes) == 1 and spikes[0]["tenant"] == "t9"
    # below threshold: quiet
    reg2 = MetricsRegistry()
    g2 = reg2.gauge("score_quality_nan_rate", family="lstm_ad", tenant="t9")
    hist2 = _hist_with(reg2, 12, lambda i: g2.set(0.02))
    assert not [
        a for a in Watchdog(reg2, hist2, drift_window=4).evaluate(now=50.0)
        if a["rule"] == "nan_rate_spike"
    ]


# ------------------------------------------------- check_metrics rules
def _expo(samples, types):
    lines = []
    for fam, kind in types:
        lines.append(f"# HELP {fam} x")
        lines.append(f"# TYPE {fam} {kind}")
    lines.extend(samples)
    lines.append("# EOF")
    return "\n".join(lines) + "\n"


def test_lint_bin_cardinality_rule():
    ok = _expo(
        [f'score_bins{{bin="{i}"}} 1' for i in range(64)],
        [("score_bins", "gauge")],
    )
    assert not check_metrics.lint_exposition(ok)
    over = _expo(
        [f'score_bins{{bin="{i}"}} 1' for i in range(65)],
        [("score_bins", "gauge")],
    )
    errs = check_metrics.lint_exposition(over)
    assert any("per-bin exposition" in e for e in errs)
    bucket = _expo(
        [f'lat_bucket{{le="{i}"}} 1' for i in range(70)],
        [("lat", "histogram")],
    )
    assert any(
        "per-bin exposition" in e
        for e in check_metrics.lint_exposition(bucket)
    )


def test_lint_score_quality_gauge_contract():
    # a score_quality_* counter is a finding, whatever its suffix
    bad = _expo(
        ['score_quality_rows_total{tenant="a"} 3'],
        [("score_quality_rows_total", "counter")],
    )
    errs = check_metrics.lint_exposition(bad)
    assert any("gauges by contract" in e for e in errs)
    # a gauge wearing _total is caught by the existing suffix rule
    bad2 = _expo(
        ['score_quality_psi_total{tenant="a"} 0.5'],
        [("score_quality_psi_total", "gauge")],
    )
    assert any(
        "_total suffix" in e for e in check_metrics.lint_exposition(bad2)
    )
    clean = _expo(
        ['score_quality_psi{tenant="a"} 0.5'],
        [("score_quality_psi", "gauge")],
    )
    assert not check_metrics.lint_exposition(clean)


# ------------------------------------------------- resolve-path counters
async def test_unscored_resolve_counts_and_notes():
    svc_bus = EventBus()
    from sitewhere_tpu.pipeline.inference import TpuInferenceService

    svc = TpuInferenceService(svc_bus)
    svc.scorehealth.register("t1", "lstm_ad", 0, sketch_edges())
    n = 10
    batch = MeasurementBatch(
        tenant="t1",
        stream_ids=np.zeros((n,), np.int32),
        values=np.zeros((n,), np.float32),
        event_ts=np.arange(n, dtype=np.float64),
        received_ts=np.arange(n, dtype=np.float64),
        valid=np.ones((n,), bool),
        device_tokens=np.array([f"d{i}" for i in range(n)], object),
        names=np.full((n,), "temp", object),
        scores=np.full((n,), np.nan, np.float32),
    )
    svc._batches[7] = [batch, n]
    published = await svc._resolve_rows(
        np.full((n,), 7, np.int64), np.arange(n, dtype=np.int32), None,
        publish_nowait=True, family="lstm_ad",
    )
    assert published == 1
    assert svc.metrics.counter(
        "tpu_scores_unscored_total", family="lstm_ad"
    ).value == n
    assert svc.scorehealth._tenants["t1"].unscored_total == n


# ------------------------------------------------- end-to-end drift drive
@asynccontextmanager
async def _drift_instance():
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.runtime.config import (
        InstanceConfig,
        MeshConfig,
        MicroBatchConfig,
        tenant_config_from_template,
    )

    inst = SiteWhereInstance(InstanceConfig(
        instance_id="drift",
        mesh=MeshConfig(tenant_axis=4, data_axis=2, slots_per_shard=2),
        history_resolution_s=0.05,
    ))
    # in-test windows: small enough to rotate within seconds of traffic
    # window sizes chosen for PSI estimator margin: ref = 4x64 rows →
    # healthy noise floor ~0.1 after debias, well under the 0.25
    # threshold (production defaults are 10-20x larger again)
    inst.scorehealth.window_rows = 64
    inst.scorehealth.window_s = 0.4
    inst.scorehealth.warmup_windows = 4
    inst.scorehealth.skip_windows = 2
    inst.watchdog.drift_window = 4
    await inst.start()
    try:
        mb = MicroBatchConfig(
            max_batch=64, deadline_ms=5.0, buckets=(64,), window=16
        )
        tenants = ["drifty", "calm1", "calm2", "calm3"]
        for t in tenants:
            await inst.add_tenant(tenant_config_from_template(
                t, "iot-temperature", microbatch=mb,
                model_config={"hidden": 8},
            ))
            inst.tenants[t].device_management.bootstrap_fleet(4)
        yield inst, tenants
    finally:
        await inst.terminate()


async def test_e2e_drift_fires_watchdog_healthy_tenants_quiet():
    """The whole chain on live traffic: an injected regime change in ONE
    tenant's stream → sustained PSI over threshold → score_drift alert →
    flightrec snapshot naming the tenant and its active variant → REST
    health verdict — while every healthy tenant stays 'ok' and unnamed.
    (The 32-tenant variant of this drive runs in the verify pass; the
    tier-1 version keeps the same 1-drifting/N-healthy shape small.)"""
    from aiohttp.test_utils import TestClient, TestServer

    from sitewhere_tpu.api.rest import make_app

    async with _drift_instance() as (inst, tenants):
        rng = np.random.default_rng(3)
        ticks = {t: 0 for t in tenants}
        scored = inst.metrics.counter("tpu_inference.scored_total")
        sent = 0

        async def until(cond, timeout_s=30.0, tick=0.01) -> bool:
            t_end = time.monotonic() + timeout_s
            while not cond() and time.monotonic() < t_end:
                await asyncio.sleep(tick)
            return bool(cond())

        async def burst(drifting=None):
            # one event per device per tenant → every stream contributes
            # one row per flush (paced traffic, not a replay burst): the
            # next burst waits until this one is scored, so that a host
            # shared with five other test workers cannot pile two rows
            # of a stream into one flush (where they would share the
            # newest window's score and PSI would read noise)
            nonlocal sent
            for t in tenants:
                j = ticks[t]
                ticks[t] += 1
                for d in range(4):
                    if t == drifting:
                        # stuck-oscillating sensor: a regime change in
                        # the DYNAMICS (window normalization hides pure
                        # mean/scale shifts by design)
                        v = 100.0 * (j % 2) + float(rng.normal() * 0.01)
                    else:
                        v = 20.0 + float(rng.normal())
                    await inst.broker.publish(
                        f"sitewhere/{t}/input/dev-0000{d}",
                        json.dumps({
                            "type": "measurement",
                            "device_token": f"dev-0000{d}",
                            "name": "temperature", "value": v,
                        }).encode(),
                    )
                    sent += 1
            await until(lambda: scored.value >= sent, 5.0, tick=0.003)
            await asyncio.sleep(0.01)

        for _ in range(160):             # phase 1: references freeze
            await burst()
        assert await until(lambda: all(
            inst.scorehealth.health_report(t)["reference_rows"] > 0
            for t in tenants
        )), "a tenant's drift reference never froze"
        for _ in range(100):             # phase 2: drifty regime-changes
            await burst(drifting="drifty")
        # the alert needs PSI sustained over drift_window watchdog ticks
        # after the verdict turns: wait for the end of the chain
        assert await until(lambda: any(
            s["reason"] == "watchdog:score_drift"
            for s in inst.flightrec.snapshot_summaries()
        )), inst.tenant_health_report("drifty")

        rep = inst.tenant_health_report("drifty")
        assert rep["verdict"] == "drifting" and rep["psi"] > 1.0
        for t in tenants[1:]:
            calm = inst.tenant_health_report(t)
            assert calm["verdict"] == "ok" and calm["psi"] < 0.25, (t, calm)
        drift_alerts = [
            a for a in inst.watchdog.alerts if a["rule"] == "score_drift"
        ]
        assert drift_alerts and drift_alerts[0]["tenant"] == "drifty"
        assert all("calm" not in a["detail"] for a in drift_alerts)
        snaps = [
            s for s in inst.flightrec.snapshot_summaries()
            if s["reason"] == "watchdog:score_drift"
        ]
        assert snaps and snaps[0]["meta"]["tenant"] == "drifty"
        assert snaps[0]["meta"]["variant"]["param_dtype"] == "f32"
        # the per-flush blackbox now carries score-quality fields
        recs = inst.flightrec.describe()["rings"]["flush"]["lstm_ad"][
            "records"
        ]
        done = [r for r in recs if r.get("status") == "ok"]
        assert done and done[-1].get("score_p99") is not None
        assert done[-1].get("nan_rows") == 0
        # REST surface + exposition lint
        client = TestClient(TestServer(make_app(inst)))
        await client.start_server()
        try:
            inst.users.create_user("sh", "password", ["ROLE_ADMIN"])
            resp = await client.post(
                "/api/authapi/jwt",
                json={"username": "sh", "password": "password"},
            )
            token = (await resp.json())["token"]
            client._session.headers["Authorization"] = f"Bearer {token}"
            resp = await client.get("/api/tenants/drifty/health")
            body = await resp.json()
            assert resp.status == 200
            assert body["verdict"] == "drifting"
            assert body["variant"]["fused"] is True
            resp = await client.get("/api/tenants/drifty/scores/dist")
            dist = await resp.json()
            assert resp.status == 200
            assert len(dist["current"]) == SKETCH_NBINS
            assert dist["reference_rows"] > 0
            resp = await client.get("/api/tenants/nope/health")
            assert resp.status == 404
        finally:
            await client.close()
        assert not check_metrics.lint_exposition(
            inst.metrics.prometheus_text()
        )


def test_page_out_rekey_preserves_reference_and_neighbor_binding():
    """Weight paging (ISSUE 19): ``unbind_slot`` at page-out releases the
    (family, slice, slot) join WITHOUT touching the frozen reference or
    PSI window history, and the re-register at page-in re-maps the key
    without severing a NEIGHBOR that took the freed slot in between —
    the guarded-pop rule in ``ScoreHealth.register``."""
    reg = MetricsRegistry()
    sh = ScoreHealth(reg, window_rows=100, warmup_windows=2, skip_windows=1,
                     min_eval_interval_s=0.0)
    edges = sketch_edges()
    sh.register("pa", "lstm_ad", 0, edges)

    def ingest(slot, hist):
        full = np.zeros((4, SKETCH_NBINS), np.int64)
        full[slot] = hist
        sh.ingest_sketch("lstm_ad", full)

    base = np.zeros(SKETCH_NBINS, np.int64)
    base[20:30] = 10  # 100 rows/window
    for _ in range(3):                       # skip + warmup → frozen ref
        ingest(0, base.copy())
    assert sh.health_report("pa")["reference_rows"] == 200

    # page-out: the join is released, history is not
    sh.unbind_slot("pa")
    rep = sh.health_report("pa")
    assert rep["reference_rows"] == 200, "page-out reset the reference"
    # slot 0 is free — the sketch plane's slot-0 row joins to nobody
    ingest(0, base.copy())
    assert sh.health_report("pa")["reference_rows"] == 200

    # a neighbor pages IN to the freed slot
    sh.register("pb", "lstm_ad", 0, edges)
    ingest(0, base.copy())                   # pb's skip window
    # pa pages back in on a DIFFERENT slot: the re-map must not pop
    # pb's (family, 0, 0) binding (pa's remembered key) and must keep
    # pa's frozen reference — no re-warmup after a residency gap
    sh.register("pa", "lstm_ad", 2, edges)
    ingest(0, base.copy())
    ingest(2, base.copy())
    rep_a, rep_b = sh.health_report("pa"), sh.health_report("pb")
    assert rep_a["reference_rows"] == 200, "page-in re-warmed the reference"
    assert rep_a["verdict"] == "ok"
    for _ in range(2):
        ingest(0, base.copy())               # pb finishes warmup intact
    assert sh.health_report("pb")["reference_rows"] == 200, (
        "pa's re-register severed pb's slot binding"
    )
    # double unbind is a no-op; unbind of an unknown tenant too
    sh.unbind_slot("pa")
    sh.unbind_slot("pa")
    sh.unbind_slot("nobody")
