"""Layered configuration: instance → microservice → tenant engine.

Capability parity with the reference's config system (2.x: per-tenant XML in
Zookeeper, hot-reloadable; 3.0: k8s CRDs ``SiteWhereInstance/-Microservice/
-Tenant/-TenantEngine`` — SURVEY.md §5 [U]; reference mount empty, see
provenance banner). Preserved capabilities: per-tenant hot reconfigure and
template-based tenant bootstrap. Redesigned as dataclasses loaded from
JSON/TOML-ish dicts; no external coordination service.
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass, field, replace
from pathlib import Path
from typing import Any, Dict, List, Optional


@dataclass(frozen=True)
class MeshConfig:
    """TPU mesh layout for the tpu-inference path (rebuild-only; BASELINE.json:5)."""

    tenant_axis: int = 1      # shards along the tenant axis
    data_axis: int = 1        # data-parallel shards per tenant shard
    model_axis: int = 1       # tensor-parallel shards (large models)
    slots_per_shard: int = 8  # stacked tenant slots per tenant shard
    dtype: str = "bfloat16"


@dataclass(frozen=True)
class MicroBatchConfig:
    """Micro-batcher knobs — the p99-vs-throughput tradeoff (SURVEY.md §7)."""

    max_batch: int = 4096          # events per pjit call (per tenant shard)
    deadline_ms: float = 5.0       # max collect window before flushing
    buckets: tuple = (256, 1024, 4096)  # static-shape buckets (XLA recompile avoidance)
    window: int = 32               # series window length fed to models


@dataclass(frozen=True)
class FaultTolerancePolicy:
    """At-least-once knobs for every pipeline stage (retry budgets, DLQ,
    circuit breakers) — see docs/ROBUSTNESS.md.

    Retries: a stage handler (or publish) that raises gets re-run up to
    ``max_attempts`` with exponential backoff + jitter; exhausted or
    poison items route to the tenant's per-stage dead-letter topic with
    stage / attempt / error metadata attached.

    Breakers: the scorer (per model family) and each outbound connector
    sit behind a closed/open/half-open breaker driven by the failure
    rate over a rolling window of outcomes. An open breaker stops
    hammering the dependency (events pass through unscored / park on
    the DLQ) and half-opens after ``breaker_open_s`` to probe recovery.
    """

    max_attempts: int = 3
    backoff_base_s: float = 0.02    # first retry delay; doubles per attempt
    backoff_max_s: float = 1.0
    backoff_jitter: float = 0.2     # ± fraction of the computed delay
    breaker_window: int = 32        # rolling outcome-sample window
    breaker_failure_rate: float = 0.5
    breaker_min_samples: int = 10   # no verdict before this many samples
    breaker_open_s: float = 2.0     # open → half-open schedule
    breaker_half_open_max: int = 1  # concurrent trial calls while half-open
    # scorer breakers only: defer to the shard-failover → park escalation
    # (the breaker's verdict window is floored at the park budget so the
    # first-line healing is never starved of failure outcomes). Set False
    # in chaos/testing configs to let the scorer breaker act first.
    breaker_defer_to_failover: bool = True
    # -- flush supervisor (docs/ROBUSTNESS.md "Device fault domains") ----
    # Every dispatched flush (serve/train/shadow lanes; media classify
    # carries its own copy of these knobs) gets a completion deadline:
    # max(flush_deadline_ms, flush_deadline_x × the (family, slice)'s
    # observed dispatch→landed p99). An overdue flush force-resolves
    # UNSCORED in its FIFO slot (zero loss, per-tenant order preserved),
    # the slice goes SUSPECT (breaker trip + quarantine + probation),
    # and tpu_flush_timeout_total{family,slice} counts it. 0 disables
    # supervision for the family (the rollback knob). Family-pinned
    # (first tenant wins), like the breaker policy itself.
    flush_deadline_ms: float = 5000.0
    flush_deadline_x: float = 8.0
    # consecutive synthetic probe flushes that must land before a
    # quarantined slice is re-admitted to the router (and its tenants
    # rebalanced back)
    probation_probes: int = 3
    # seconds between probation probes on a quarantined slice
    probe_interval_s: float = 0.5
    # poison-batch ejection: a flush whose dispatch faults is retried
    # ONCE with the same staged host rows (on the tenant's current —
    # post-failover, if the fault also moved it — slice); a second
    # failure attributes the fault to the DATA and ships the offending
    # batch to the per-tenant DLQ (stage "scorer-poison") so the tenant
    # keeps serving instead of burning breaker/failover capacity on it
    poison_retry: bool = True


@dataclass(frozen=True)
class OverloadPolicy:
    """Overload control & graceful degradation knobs (runtime.overload /
    docs/ROBUSTNESS.md "Overload & degradation").

    Admission: every accepted payload is stamped with a deadline of
    ``deadline_ms`` (0 = 2 × ``TracingConfig.slo_ms``); receiver queues
    shed by priority class (alerts > commands > measurements) at the
    per-class fill watermarks below instead of blind shed-oldest.

    Fairness: the tpu-inference consumption loop rations intake by
    deficit round-robin over ``weight`` — a hostile tenant's backlog
    stays in its own bus topic, which drives its credit signal down and
    throttles its receivers cooperatively (``credit_lag_lo/hi``).

    Degradation: ``ladder`` lists sheddable features in engage order
    (``sample_inference``: score only ``inference_sample_rate`` of
    measurements; ``persist_only``: pause rule evaluation;
    ``pause_fanout``: pause outbound connector fan-out for measurement
    batches). Rungs engage after ``engage_hold_s`` of sustained
    pressure (pipeline lag ≥ ``engage_lag`` or ≥
    ``engage_expired_per_s`` deadline misses/s) and disengage one rung
    per ``hysteresis_s`` of sustained calm (lag ≤ ``disengage_lag``,
    zero recent misses).
    """

    enabled: bool = True
    deadline_ms: float = 0.0        # admission deadline budget; 0 = 2×slo
    weight: float = 1.0             # fair-queue (DRR) weight
    # receiver-queue fill watermarks per priority class (fractions)
    shed_alerts_fill: float = 0.98
    shed_commands_fill: float = 0.90
    shed_measurements_fill: float = 0.75
    # credit signal: 1.0 at lag ≤ lo, linearly down to 0.0 at lag ≥ hi
    credit_lag_lo: int = 512
    credit_lag_hi: int = 8192
    # degradation ladder + thresholds/hysteresis
    ladder: tuple = ("sample_inference", "persist_only", "pause_fanout")
    inference_sample_rate: float = 0.25
    engage_lag: int = 4096
    engage_expired_per_s: int = 50
    disengage_lag: int = 256
    engage_hold_s: float = 0.5
    hysteresis_s: float = 2.0
    # persistence is the system of record: by default it observes
    # lateness (pipeline_deadline_late_total) but never drops — opt in
    # to strict deadline enforcement at the store boundary here
    drop_expired_at_persist: bool = False


@dataclass(frozen=True)
class TracingConfig:
    """End-to-end event tracing knobs (runtime.tracing / docs/OBSERVABILITY.md).

    Tail-based sampling: with tracing enabled, EVERY event's spans are
    recorded while its trace is in flight; the keep/drop decision runs at
    the tail (terminal stage). Traces that breached ``slo_ms``, errored,
    or hit retry/DLQ/breaker machinery are always kept; clean traces keep
    with probability ``sample_rate``. ``enabled = False`` is the hot-path
    guard: no context is minted at ingest, so no stage allocates spans.
    """

    enabled: bool = True
    sample_rate: float = 0.05   # clean-trace keep probability (tail)
    slo_ms: float = 250.0       # end-to-end latency SLO; breaches retained
    max_traces: int = 512       # retained-ring floor contributed by this tenant


@dataclass(frozen=True)
class TrainingConfig:
    """Live on-device training knobs (rebuild-only; docs/PERFORMANCE.md
    "Continual learning lane").

    Resident-state steps train on windows that already live sharded on
    device, so they move zero bytes host<->device; the REPLAY-FED lane
    additionally streams scored history (the replay engine's ``train``
    target) through the staging → h2d feed path into train microbatches
    — windows beyond the resident state, at the same wire cost per row
    as scoring. Training dispatches async at low priority off the flush
    critical path (per-slice in-flight window + overload arbitration);
    the ``parallel.sharded.TRAIN_LANE_ENABLED`` kill switch restores the
    inline every_n_flushes path bitwise."""

    enabled: bool = False
    every_n_flushes: int = 50   # one optimizer step per N scoring flushes
    lr: float = 1e-3
    # ride the async train lane when the family kernel supports it (fused
    # stacked step + loss_stacked contract); False pins this tenant to
    # the inline pre-lane cadence even while the lane is globally on
    train_lane: bool = True
    # zero-stall hot-swap cadence: every N lane steps the trained master
    # weights commit to the serving kernel view (quantized-sidecar
    # re-derive + PR 9 canary arm). Family-pinned (first tenant wins),
    # like the fused-kernel knobs.
    swap_every: int = 8
    # replay-fed microbatch: buffered train-feed rows per ingest+train
    # dispatch (the lane's unit of wire transfer; 2× this is the train
    # ring watermark). Family-pinned.
    replay_microbatch: int = 1024


@dataclass(frozen=True)
class TenantEngineConfig:
    tenant: str = "default"
    template: str = "default"       # template this config was built from
    model: str = "lstm_ad"          # model-zoo key for the scoring model
    model_config: Dict[str, Any] = field(default_factory=dict)
    microbatch: MicroBatchConfig = field(default_factory=MicroBatchConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)
    fault_tolerance: FaultTolerancePolicy = field(
        default_factory=FaultTolerancePolicy
    )
    tracing: TracingConfig = field(default_factory=TracingConfig)
    overload: OverloadPolicy = field(default_factory=OverloadPolicy)
    max_streams: int = 4096         # window-state capacity (series slots)
    decoder: str = "json"
    # host↔device wire dtype for scoring values/scores ("f32" | "bf16" |
    # "f16"): bf16 halves transfer bytes at ~3 significant digits — the
    # right trade for anomaly scoring over a bandwidth-bound link
    wire_dtype: str = "f32"
    # fused megabatch kernel knobs (parallel.sharded; docs/PERFORMANCE.md
    # "Fused tenant kernels"). Like wire_dtype, the FIRST tenant of a
    # model family pins them for the whole stack (conflicts surface via
    # tpu_inference.fused_knob_conflicts). Both are no-ops while the
    # FUSED_STEP_ENABLED kill switch is off.
    #   fuse_k: score the last K window positions per flush in ONE scan —
    #   burst rows of a stream resolve at their own timestep instead of
    #   all taking the newest score (rows deeper than K clamp to the
    #   oldest of the K columns — size K >= expected burst depth), and
    #   each h2d'd plane amortizes K timesteps of output
    fuse_k: int = 1
    #   param_dtype: stacked weight precision "f32" | "bf16" | "int8"
    #   (int8 = per-slot per-channel scales, dequant fused in the scan
    #   step — see docs/PERFORMANCE.md for when int8 is safe)
    param_dtype: str = "f32"
    # shadow-scoring canary fraction (family-pinned like the knobs above;
    # docs/OBSERVABILITY.md "Score health & canaries"): while a canary
    # condition holds — the stack scores through a non-f32 / K>1 variant,
    # or a param hot-swap recently landed — this fraction of flushes is
    # ALSO scored through the legacy f32 step and the divergence reported
    # as score_canary_* metrics. 0 (default) disables shadow scoring.
    canary_frac: float = 0.0
    # threshold of the tenant's anomaly-score rule, in the family's score
    # units: sigmas for the window scorers (3.0), nats of surprisal for a
    # token scorer (ln vocab and up)
    rule_min_score: float = 3.0
    # streaming-media classification leg (chunks → ViT → events); tiny
    # uses the test-sized ViT so CI exercises the full flow cheaply
    media_pipeline: bool = False
    media_tiny: bool = False
    # real-socket MQTT ingest: {"host": ..., "port": ..., "topics": [...]}
    # adds an MqttReceiver-backed event source beside the in-proc one
    mqtt_ingest: Optional[Dict[str, Any]] = None
    # real-wire command delivery destination (default: in-proc sim broker):
    #   {"type": "mqtt", "host": ..., "port": ..., "topic_pattern": ...,
    #    "qos": 1}   — port 0 = the instance's embedded MQTT broker
    #   {"type": "coap", "path": "command"}  — per-device coap_host/
    #    coap_port metadata addresses the device's CoAP server
    command_destination: Optional[Dict[str, Any]] = None
    # opt-in to the instance-shared 'sitewhere/input/+' broker pattern; the
    # tenant-scoped 'sitewhere/{tenant}/input/+' pattern is always active.
    # With >1 tenant and no flag, shared-input routes to NO tenant (isolation)
    shared_input: bool = False
    # opt-in local search indexing (the Solr-connector analog): adds a
    # SearchIndexConnector to the outbound chain and serves term search
    # over recent events at GET /api/events/search?q=...
    search_index: bool = False


@dataclass(frozen=True)
class MicroserviceConfig:
    name: str = "pipeline"
    consumer_group: Optional[str] = None   # default: name
    poll_batch: int = 1024

    @property
    def group(self) -> str:
        return self.consumer_group or self.name


@dataclass(frozen=True)
class InstanceConfig:
    instance_id: str = "sw"
    data_dir: str = "./_data"
    mesh: MeshConfig = field(default_factory=MeshConfig)
    default_tenant_template: str = "default"
    bus_retention: int = 65536
    # concurrent in-flight score materializations: each flush's device→host
    # transfer rides its own executor thread, so throughput over a
    # high-latency link ≈ max_inflight × flush_rows / RTT
    inference_max_inflight: int = 8
    # opt-in durability: per-tenant params on engine stop/start, bus
    # offsets+logs, device model + event stores under data_dir
    checkpointing: bool = False
    # >0: a supervised autosave task checkpoints the live instance every
    # interval (plus once inside stop()) — a hard kill loses at most one
    # interval's worth of un-snapshotted state
    checkpoint_interval_s: float = 0.0
    # instance-level CoAP/UDP ingest endpoint (None = off; 0 = ephemeral
    # port). Devices POST /input?tenant=...&auth=... with a wire payload
    coap_ingest_port: Optional[int] = None
    # instance-level embedded MQTT 3.1.1 broker (None = off; 0 = ephemeral
    # port). CONNECT username/password = tenant token/auth token, checked
    # through the same authenticate_device gate as CoAP/HTTP/WS ingest
    mqtt_broker_port: Optional[int] = None
    # non-empty: capture a jax.profiler trace for the instance's lifetime
    # into this directory (start() → stop()) — the SURVEY §5 tracing
    # plan's second half, beside the per-stage envelope timestamps
    profile_dir: str = ""
    # debug mode: make XLA raise on NaN/Inf in any compiled computation
    # (jax_debug_nans) — the SURVEY §5 sanitizer-analog flag. Costly
    # (disables async dispatch); for debugging sessions, never production
    debug_nans: bool = False
    # metrics history ring + watchdog (runtime.history): a ~15-minute,
    # 1 s-resolution in-process time-series over an allowlist of metric
    # families (None = runtime.history.DEFAULT_ALLOWLIST), served at
    # GET /api/metrics/history; the watchdog evaluates its rules every
    # sample tick (recompile / overlap collapse / credit / d2h-wait
    # spike) and alerts through watchdog_alerts_total{rule}, forced
    # trace retention, and a flight-recorder snapshot
    metrics_history_allowlist: Optional[List[str]] = None
    history_resolution_s: float = 1.0
    watchdog_enabled: bool = True
    # hard-kill replay recovery (pipeline/replay.py): when resuming
    # replay jobs after a NON-graceful restore (job file still says
    # "running" — a graceful stop persists "paused"), rewind a resumed
    # rescore job's cursor to its window start so the only_unscored plan
    # re-covers the published-but-not-written-back NaN window the crash
    # left behind (already-scored rows dedupe away). Opt-in: the rewind
    # re-publishes the recovered window's unscored rows.
    replay_recover_unscored: bool = False


# -- tenant templates (reference: tenant templates + datasets bootstrap
# new tenants, SURVEY.md §5 [U]) -----------------------------------------

TENANT_TEMPLATES: Dict[str, Dict[str, Any]] = {
    "default": {
        "model": "lstm_ad",
        "model_config": {},
        "datasets": ["empty"],
    },
    "iot-temperature": {
        "model": "lstm_ad",
        "model_config": {"hidden": 64},
        "datasets": ["temperature-sensors"],
    },
    "sensor-tokens": {
        # raw sensor counts scored as tokens by a stream-state family
        # (models/nemotron_h.py): the model_config carries the widths
        "model": "nemotron_h",
        "model_config": {},
        "datasets": ["empty"],
    },
    "sensor-tokens-paged": {
        # the same readings scored by a stream-state family whose keys
        # and values live in pages (models/afmoe.py)
        "model": "afmoe",
        "model_config": {},
        "datasets": ["empty"],
    },
    "sensor-tokens-lfm2": {
        # ... and by one that keeps convolution tails beside its pages
        # (models/lfm2_moe.py)
        "model": "lfm2_moe",
        "model_config": {},
        "datasets": ["empty"],
    },
    "forecasting": {
        "model": "deepar",
        "model_config": {"context": 128},
        "datasets": ["empty"],
    },
    "media": {
        "model": "lstm_ad",   # telemetry still scores; frames ride the
        "model_config": {},   # media pipeline (vit) beside it
        "datasets": ["empty"],
        "media_pipeline": True,
    },
}


def tenant_config_from_template(
    tenant: str, template: str = "default", **overrides: Any
) -> TenantEngineConfig:
    resolved = template if template in TENANT_TEMPLATES else "default"
    tpl = TENANT_TEMPLATES[resolved]
    known = TenantEngineConfig.__dataclass_fields__
    extra = {
        k: v for k, v in tpl.items()
        if k in known and k not in ("model", "model_config")
    }
    cfg = TenantEngineConfig(
        tenant=tenant,
        template=resolved,  # record what was APPLIED, not what was asked for
        model=tpl["model"],
        model_config=dict(tpl["model_config"]),
        **extra,
    )
    if overrides:
        cfg = replace(cfg, **overrides)
    return cfg


# -- (de)serialization ----------------------------------------------------

def _to_jsonable(obj: Any) -> Any:
    if hasattr(obj, "__dataclass_fields__"):
        return {k: _to_jsonable(v) for k, v in asdict(obj).items()}
    if isinstance(obj, dict):
        return {k: _to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_to_jsonable(v) for v in obj]
    return obj


def tenant_config_to_dict(cfg: TenantEngineConfig) -> Dict[str, Any]:
    """Full round-trippable dict for manifests/checkpoints — tenants added
    with overrides (model, decoder, …) must resume with the SAME config,
    not a re-derivation from the template."""
    return _to_jsonable(cfg)


def tenant_config_from_dict(d: Dict[str, Any]) -> TenantEngineConfig:
    d = dict(d)
    mb = d.pop("microbatch", None) or {}
    tr = d.pop("training", None) or {}
    ft = d.pop("fault_tolerance", None) or {}
    tc = d.pop("tracing", None) or {}
    ov = d.pop("overload", None) or {}
    if "buckets" in mb:
        mb["buckets"] = tuple(mb["buckets"])
    if "ladder" in ov:
        ov["ladder"] = tuple(ov["ladder"])
    # drop unknown keys at EVERY level: a manifest written by a newer build
    # (extra knobs) must degrade gracefully, not abort the whole restore
    mb_known = MicroBatchConfig.__dataclass_fields__
    tr_known = TrainingConfig.__dataclass_fields__
    ft_known = FaultTolerancePolicy.__dataclass_fields__
    tc_known = TracingConfig.__dataclass_fields__
    ov_known = OverloadPolicy.__dataclass_fields__
    known = TenantEngineConfig.__dataclass_fields__
    return TenantEngineConfig(
        microbatch=MicroBatchConfig(
            **{k: v for k, v in mb.items() if k in mb_known}
        ),
        training=TrainingConfig(
            **{k: v for k, v in tr.items() if k in tr_known}
        ),
        fault_tolerance=FaultTolerancePolicy(
            **{k: v for k, v in ft.items() if k in ft_known}
        ),
        tracing=TracingConfig(
            **{k: v for k, v in tc.items() if k in tc_known}
        ),
        overload=OverloadPolicy(
            **{k: v for k, v in ov.items() if k in ov_known}
        ),
        **{
            k: v
            for k, v in d.items()
            if k in known
            and k not in ("microbatch", "training", "fault_tolerance",
                          "tracing", "overload")
        },
    )


def save_instance_config(cfg: InstanceConfig, path: str | Path) -> None:
    Path(path).write_text(json.dumps(_to_jsonable(cfg), indent=2))


def load_instance_config(path: str | Path) -> InstanceConfig:
    d = json.loads(Path(path).read_text())
    mesh = MeshConfig(**d.pop("mesh", {}))
    return InstanceConfig(mesh=mesh, **d)
