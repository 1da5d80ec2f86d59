"""End-to-end event tracing: spans, tail-based sampling, bounded store.

The SURVEY §5 observability gap this closes: the pipeline already stamps
per-stage timestamps onto payloads (``MeasurementBatch.trace``/
``DeviceEvent.trace``), but nothing correlates them into a queryable
trace, and nothing attributes a slow p99 to a stage, a tenant, or a
retry/DLQ/breaker event. This module adds:

- **spans** per pipeline stage (decode → inbound → inference →
  persistence → rules → outbound), each split into queue-wait vs.
  service time, recorded against the ``TraceContext`` the payload
  carries (``core.trace`` — the propagation half);
- **tail-based sampling**: every span is recorded while the trace is
  in flight; the keep/drop decision happens at the TAIL, when the
  terminal (outbound) span lands. Traces that breached the tenant's
  latency SLO, errored, or were touched by retry/DLQ/breaker machinery
  are ALWAYS kept; clean traces keep with probability ``sample_rate``.
  That is what makes a 0.0 sample rate useful in production: the
  interesting 0.01% still lands in the store;
- a **bounded in-process TraceStore** (retained ring + in-flight map,
  both capped) served by ``GET /api/traces`` and
  ``GET /api/traces/{id}`` (Chrome trace-event export) on the REST API.

Hot-path contract: when tracing is disabled for a tenant
(``TenantEngineConfig.tracing.enabled = False``) ``mint`` returns None,
payloads carry no context, and every stage's recorder early-outs before
allocating a span — guarded, not stripped, so flipping the knob needs no
restart.
"""

from __future__ import annotations

import random
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from sitewhere_tpu.core.trace import TraceContext, new_span_id, trace_ctx_of
from sitewhere_tpu.runtime.config import TracingConfig
from sitewhere_tpu.runtime.loopledger import sw
from sitewhere_tpu.runtime.metrics import MetricsRegistry

# the terminal pipeline stage: its span seals the trace and schedules the
# tail sampling decision (after a short grace so the racing rules span —
# both consume persisted-events — can still land)
TERMINAL_STAGE = "outbound"


def now_ms() -> float:
    return time.time() * 1000.0


@dataclass(slots=True)
class Span:
    trace_id: str
    span_id: str
    parent_id: str
    stage: str
    tenant: str
    start_ms: float          # service start (queue wait precedes it)
    end_ms: float
    queue_wait_ms: float = 0.0
    n_events: int = 0
    error: str = ""
    annotations: Dict[str, Any] = field(default_factory=dict)

    @property
    def service_ms(self) -> float:
        return max(0.0, self.end_ms - self.start_ms)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "stage": self.stage,
            "tenant": self.tenant,
            "start_ms": self.start_ms,
            "end_ms": self.end_ms,
            "queue_wait_ms": self.queue_wait_ms,
            "service_ms": self.service_ms,
            "n_events": self.n_events,
            "error": self.error,
            "annotations": dict(self.annotations),
        }


class TraceRecord:
    """One trace's spans + retention bookkeeping."""

    __slots__ = (
        "trace_id", "tenant", "device", "source_topic", "priority",
        "spans", "forced", "created_ms", "last_ms", "seal_at_ms",
        "decision",
    )

    MAX_SPANS = 128  # derived-event fan-out bound

    def __init__(self, ctx: TraceContext, now: float) -> None:
        self.trace_id = ctx.trace_id
        self.tenant = ctx.tenant
        self.device = ctx.device
        self.source_topic = ctx.source_topic
        self.priority = getattr(ctx, "priority", "") or "measurement"
        self.spans: List[Span] = []
        self.forced: List[str] = []   # retention reasons (dlq/retry/…)
        self.created_ms = now
        self.last_ms = now
        self.seal_at_ms: Optional[float] = None  # decision deadline
        self.decision: str = ""       # "" in flight, else retention reason

    def add_span(self, span: Span) -> None:
        if len(self.spans) < self.MAX_SPANS:
            self.spans.append(span)
        self.last_ms = max(self.last_ms, span.end_ms)

    def force(self, reason: str) -> None:
        if reason not in self.forced:
            self.forced.append(reason)

    @property
    def start_ms(self) -> float:
        return min(
            (s.start_ms - s.queue_wait_ms for s in self.spans),
            default=self.created_ms,
        )

    @property
    def duration_ms(self) -> float:
        return max(0.0, self.last_ms - self.start_ms)

    def stages(self) -> List[str]:
        return [s.stage for s in self.spans]

    def summary(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "tenant": self.tenant,
            "device": self.device,
            "source_topic": self.source_topic,
            "start_ms": self.start_ms,
            "duration_ms": self.duration_ms,
            "n_spans": len(self.spans),
            "stages": self.stages(),
            "retained": self.decision,
            "hits": list(self.forced),
        }

    def to_dict(self) -> Dict[str, Any]:
        d = self.summary()
        d["spans"] = [s.to_dict() for s in self.spans]
        return d


class TraceStore:
    """Bounded in-process trace storage with tail decisions.

    ``_active`` holds in-flight traces (capped — overflow forces the
    oldest through its tail decision early); ``_retained`` is the ring
    the query surface serves (capped — oldest drop off). All access is
    event-loop-threaded; no locks."""

    def __init__(self, max_active: int = 2048, max_retained: int = 512) -> None:
        self.max_active = max_active
        self.max_retained = max_retained
        self._active: "OrderedDict[str, TraceRecord]" = OrderedDict()
        self._retained: "OrderedDict[str, TraceRecord]" = OrderedDict()

    def active_count(self) -> int:
        return len(self._active)

    def retained_count(self) -> int:
        return len(self._retained)

    def get_or_create(self, ctx: TraceContext, now: float) -> Optional[TraceRecord]:
        tr = self._active.get(ctx.trace_id)
        if tr is None:
            tr = self._retained.get(ctx.trace_id)  # late span after keep
        if tr is None:
            tr = TraceRecord(ctx, now)
            self._active[ctx.trace_id] = tr
        return tr

    def peek(self, trace_id: str) -> Optional[TraceRecord]:
        return self._active.get(trace_id) or self._retained.get(trace_id)

    def retain(self, tr: TraceRecord, reason: str) -> None:
        tr.decision = reason
        self._active.pop(tr.trace_id, None)
        self._retained[tr.trace_id] = tr
        while len(self._retained) > self.max_retained:
            self._retained.popitem(last=False)

    def drop(self, tr: TraceRecord) -> None:
        self._active.pop(tr.trace_id, None)

    def pop_due(self, now: float, idle_timeout_ms: float) -> List[TraceRecord]:
        """Traces whose tail decision is due: sealed past grace, idle past
        the timeout (a trace that never reached the terminal stage must
        not pin the active map), or evicted by the active-size cap."""
        due: List[TraceRecord] = []
        due_ids: set = set()
        for tid, tr in list(self._active.items()):
            if (tr.seal_at_ms is not None and now >= tr.seal_at_ms) or (
                now - tr.last_ms >= idle_timeout_ms
            ):
                due.append(tr)
                due_ids.add(tid)
        # capacity eviction: force the oldest non-due traces through their
        # decision until the survivors fit (every due trace leaves _active
        # when decided, so only the non-due count is against the cap)
        non_due_active = len(self._active) - len(due)
        while non_due_active > self.max_active and self._active:
            tid, tr = self._active.popitem(last=False)
            if tid not in due_ids:
                due.append(tr)
                due_ids.add(tid)
                non_due_active -= 1
        return due

    def list(
        self, tenant: str = "", limit: int = 100, include_active: bool = True
    ) -> List[TraceRecord]:
        out: List[TraceRecord] = []
        pools = [reversed(self._retained.values())]
        if include_active:
            pools.append(reversed(self._active.values()))
        for pool in pools:
            for tr in pool:
                if tenant and tr.tenant != tenant:
                    continue
                out.append(tr)
                if len(out) >= limit:
                    return out
        return out


class Tracer:
    """Per-instance tracing facade: minting, span recording, tail
    sampling. One Tracer is shared by every stage of every tenant; the
    per-tenant knobs (enabled / sample_rate / slo_ms) come from
    ``TenantEngineConfig.tracing`` via ``configure_tenant``."""

    SEAL_GRACE_MS = 250.0      # wait for the racing rules span
    IDLE_TIMEOUT_MS = 10_000.0  # unfinished traces decide after this

    def __init__(
        self,
        metrics: Optional[MetricsRegistry] = None,
        default: Optional[TracingConfig] = None,
        rng: Optional[random.Random] = None,
        max_active: int = 2048,
        max_retained: int = 512,
    ) -> None:
        self.metrics = metrics or MetricsRegistry()
        self.default = default or TracingConfig()
        self.rng = rng or random.Random()
        self.store = TraceStore(max_active, max_retained)
        self._policies: Dict[str, TracingConfig] = {}
        self._gc_tick = 0
        # flight-recorder bridge (runtime.flightrec, wired by the
        # instance): SLO-breach tail decisions snapshot the blackbox, and
        # StageTimers feed it strided per-stage records
        self.flightrec = None
        # latency-attribution bridge (runtime.latency.LatencyEngine,
        # wired by the instance): EVERY tail decision — kept or
        # dropped — feeds the stage ledgers before sampling applies,
        # so attribution never suffers sampling bias
        self.latency = None
        # watchdog-forced retention: until this wall-ms, EVERY tail
        # decision keeps its trace (reason "watchdog") — the traffic
        # around an alert is exactly what sampling would discard
        self._force_until_ms = 0.0
        self.metrics.describe(
            "traces_retained", "traces kept by tail-based sampling, by reason"
        )
        self.metrics.describe(
            "traces_dropped", "clean traces dropped by tail-based sampling"
        )

    # -- per-tenant policy ------------------------------------------------
    def configure_tenant(self, tenant: str, cfg: TracingConfig) -> None:
        self._policies[tenant] = cfg
        if cfg.max_traces > self.store.max_retained:
            self.store.max_retained = cfg.max_traces

    def remove_tenant(self, tenant: str) -> None:
        self._policies.pop(tenant, None)

    def policy_for(self, tenant: str) -> TracingConfig:
        return self._policies.get(tenant, self.default)

    def enabled_for(self, tenant: str) -> bool:
        return self.policy_for(tenant).enabled

    # -- minting (ingest edges) -------------------------------------------
    def mint(
        self, tenant: str, device: str = "", source_topic: str = "",
        priority: str = "measurement",
    ) -> Optional[TraceContext]:
        """A fresh context, or None when tracing is off for the tenant —
        the None IS the hot-path guard: no context on the payload means
        no stage allocates a span for it."""
        if not self.enabled_for(tenant):
            return None
        return TraceContext(
            tenant=tenant, device=device, source_topic=source_topic,
            priority=priority,
        )

    # -- span recording ----------------------------------------------------
    def record_span(
        self,
        ctx: Optional[TraceContext],
        stage: str,
        start_ms: float,
        end_ms: float,
        queue_wait_ms: float = 0.0,
        n_events: int = 0,
        error: str = "",
        terminal: Optional[bool] = None,
        advance: bool = True,
        **annotations: Any,
    ) -> Optional[Span]:
        if ctx is None:
            return None
        now = now_ms()
        tr = self.store.get_or_create(ctx, now)
        span = Span(
            trace_id=ctx.trace_id,
            span_id=new_span_id(),
            parent_id=ctx.span_id,
            stage=stage,
            tenant=ctx.tenant or tr.tenant,
            start_ms=start_ms,
            end_ms=end_ms,
            queue_wait_ms=max(0.0, queue_wait_ms),
            n_events=n_events,
            error=error,
            annotations=annotations,
        )
        tr.add_span(span)
        if advance:
            ctx.span_id = span.span_id  # next stage parents here
        if error:
            tr.force("error")
        if terminal if terminal is not None else stage == TERMINAL_STAGE:
            if tr.seal_at_ms is None:
                tr.seal_at_ms = now + self.SEAL_GRACE_MS
        self._gc_tick += 1
        if self._gc_tick >= 32:
            self.gc(now)
        return span

    # -- tail hits (retry / DLQ / breaker) --------------------------------
    def mark_hit(self, item_or_ctx: Any, reason: str) -> None:
        """Force-retain the trace touched by a robustness event. ``item``
        may be a context or any pipeline payload (the DLQ writer passes
        the raw item)."""
        ctx = (
            item_or_ctx
            if isinstance(item_or_ctx, TraceContext)
            else trace_ctx_of(item_or_ctx)
        )
        if ctx is None:
            return
        tr = self.store.get_or_create(ctx, now_ms())
        tr.force(reason)
        self.metrics.counter("trace_hits", reason=reason).inc()

    def force_retain(self, duration_ms: float) -> None:
        """Keep EVERY trace deciding within the next ``duration_ms``
        (reason "watchdog"). Extension-only: overlapping alerts never
        shorten an earlier window."""
        until = now_ms() + max(0.0, duration_ms)
        if until > self._force_until_ms:
            self._force_until_ms = until

    # -- span-time retention probe (forced flightrec stage records) -------
    def trace_is_hot(self, ctx: Optional[TraceContext]) -> bool:
        """True when the payload's trace is already bound for retention
        (forced by retry/DLQ/error, or past the tenant's SLO budget) —
        the stage-record stride must not skip these: the incident
        snapshot needs the SLOW event's own timings, not a neighbor's."""
        if ctx is None:
            return False
        tr = self.store.peek(ctx.trace_id)
        if tr is None:
            return False
        if tr.forced:
            return True
        return tr.duration_ms >= self.policy_for(tr.tenant).slo_ms

    # -- tail decision ----------------------------------------------------
    def _decide(self, tr: TraceRecord) -> None:
        pol = self.policy_for(tr.tenant)
        if self.latency is not None:
            # attribution reads every decision, BEFORE sampling drops
            # the clean majority (ingest_trace never raises)
            self.latency.ingest_trace(tr, pol.slo_ms)
        if tr.forced:
            reason = tr.forced[0]
        elif tr.duration_ms >= pol.slo_ms:
            reason = "slo"
        elif now_ms() < self._force_until_ms:
            reason = "watchdog"
        elif self.rng.random() < pol.sample_rate:
            reason = "sampled"
        else:
            self.store.drop(tr)
            self.metrics.counter("traces_dropped", tenant=tr.tenant).inc()
            return
        self.store.retain(tr, reason)
        self.metrics.counter(
            "traces_retained", tenant=tr.tenant, reason=reason
        ).inc()
        if reason == "slo" and self.flightrec is not None:
            # an SLO breach is an incident: freeze the blackbox. The
            # reason must be the FIXED string "slo" (tenant goes in the
            # meta): a per-tenant reason would let a multi-tenant breach
            # storm mint N unsuppressed reasons at once and churn the
            # first failure's snapshot out of the bounded list — exactly
            # what the per-reason rate limit exists to prevent
            self.flightrec.snapshot(
                "slo", tenant=tr.tenant, trace_id=tr.trace_id,
                duration_ms=round(tr.duration_ms, 3),
            )

    def gc(self, now: Optional[float] = None, force: bool = False) -> int:
        """Run due tail decisions; ``force`` decides every in-flight trace
        now (test/diagnostic surface: ``GET /api/traces?flush=1``)."""
        self._gc_tick = 0
        now = now if now is not None else now_ms()
        if force:
            due = list(self.store._active.values())
        else:
            due = self.store.pop_due(now, self.IDLE_TIMEOUT_MS)
        for tr in due:
            self._decide(tr)
        return len(due)


# rules and outbound BOTH consume persisted-events concurrently (a fork):
# neither may advance the shared context's span chain, or whichever runs
# first would re-parent the other nondeterministically — both record as
# siblings under the persistence span instead
FORK_STAGES = frozenset({"rules", "outbound"})


class StageTimer:
    """One pipeline stage's recorder: labeled latency metrics always,
    spans only when the payload carries a context (tail sampling needs
    every span of a traced event; untraced tenants pay two histogram
    records per batch and nothing else)."""

    __slots__ = (
        "tracer", "tenant", "stage", "service_h", "wait_h", "events_c",
        "_fr_tick", "_ledger",
    )

    # flight-recorder stride: one per-stage blackbox record every Nth
    # batch — recent-history evidence at ~zero steady-state cost (the
    # per-flush records carry the fine-grained story)
    FLIGHTREC_STRIDE = 8

    def __init__(
        self,
        tracer: Optional[Tracer],
        metrics: MetricsRegistry,
        tenant: str,
        stage: str,
    ) -> None:
        self.tracer = tracer
        self.tenant = tenant
        self.stage = stage
        # primed so the FIRST batch records (evidence exists from the
        # start), then every FLIGHTREC_STRIDE-th
        self._fr_tick = self.FLIGHTREC_STRIDE - 1
        self._ledger = metrics.loop_ledger
        metrics.describe(
            "pipeline_stage_seconds",
            "per-stage service time (handler run) per tenant",
        )
        metrics.describe(
            "pipeline_stage_queue_wait_seconds",
            "time between the previous stage's publish and this stage's "
            "handler start",
        )
        metrics.describe(
            "pipeline_stage_events", "events processed per stage per tenant"
        )
        self.service_h = metrics.histogram(
            "pipeline_stage_seconds", tenant=tenant, stage=stage
        )
        self.wait_h = metrics.histogram(
            "pipeline_stage_queue_wait_seconds", tenant=tenant, stage=stage
        )
        self.events_c = metrics.counter(
            "pipeline_stage_events", tenant=tenant, stage=stage
        )

    def observe(
        self,
        item: Any,
        start_ms: float,
        end_ms: float,
        n_events: int = 1,
        error: str = "",
        queue_wait_ms: Optional[float] = None,
        **annotations: Any,
    ) -> None:
        # what this recorder — and the span, tail decision, ledger feed
        # and blackbox record under it — costs the event loop is charged
        # to the loop ledger's ``observe`` stage, not to the stage that
        # called it (nothing is read while no step is being timed)
        ledger = self._ledger
        t_observe = ledger.clock() if ledger.current is not None else 0.0
        # while a profiler capture runs the same stretch is ``sw/observe``
        # on the loop thread's line
        with sw("observe"):
            if queue_wait_ms is None:
                queue_wait_ms = queue_wait_from(item, start_ms)
            self.service_h.record(max(0.0, end_ms - start_ms) / 1000.0)
            self.wait_h.record(max(0.0, queue_wait_ms) / 1000.0)
            self.events_c.inc(n_events)
            if self.tracer is not None:
                ctx = trace_ctx_of(item)
                self.tracer.record_span(
                    ctx, self.stage, start_ms, end_ms,
                    queue_wait_ms=queue_wait_ms, n_events=n_events,
                    error=error,
                    advance=self.stage not in FORK_STAGES,
                    **annotations,
                )
                fr = self.tracer.flightrec
                if fr is not None:
                    self._fr_tick += 1
                    # tail-blindness guard: the stride may skip the exact
                    # batch that breached/retried — any span whose trace
                    # the tail sampler will retain records unconditionally,
                    # so the incident snapshot holds the slow event's own
                    # timings (forced records do not reset the stride; the
                    # steady cadence stays intact around an incident)
                    hot = bool(error) or self.tracer.trace_is_hot(ctx)
                    if hot or self._fr_tick >= self.FLIGHTREC_STRIDE:
                        if self._fr_tick >= self.FLIGHTREC_STRIDE:
                            self._fr_tick = 0
                        rec = fr.record(
                            "stage", f"{self.tenant}/{self.stage}",
                            service_ms=round(max(0.0, end_ms - start_ms), 3),
                            queue_wait_ms=round(max(0.0, queue_wait_ms), 3),
                            n_events=n_events,
                        )
                        if error:
                            rec["error"] = error
                        if hot and not error:
                            rec["forced"] = "tail"
        if t_observe:
            ledger.observe_from(t_observe)


def queue_wait_from(item: Any, start_ms: float) -> float:
    """Queue wait = handler start minus the previous stage's publish
    stamp (the newest mark in the payload's ``trace`` dict)."""
    marks = getattr(item, "trace", None)
    if not marks:
        return 0.0
    try:
        return max(0.0, start_ms - max(marks.values()))
    except (TypeError, ValueError):
        return 0.0


def chrome_trace_events(tr: TraceRecord) -> List[Dict[str, Any]]:
    """Chrome trace-event JSON (``chrome://tracing`` / Perfetto "JSON"
    format): one complete ('X') slice per queue wait and per service
    interval, pid = tenant, tid = stage."""
    out: List[Dict[str, Any]] = []
    for s in sorted(tr.spans, key=lambda s: s.start_ms):
        if s.queue_wait_ms > 0:
            out.append({
                "name": f"{s.stage}:queue",
                "cat": "queue",
                "ph": "X",
                "ts": (s.start_ms - s.queue_wait_ms) * 1000.0,
                "dur": s.queue_wait_ms * 1000.0,
                "pid": s.tenant or tr.tenant,
                "tid": s.stage,
            })
        args: Dict[str, Any] = {
            "span_id": s.span_id,
            "parent_id": s.parent_id,
            "n_events": s.n_events,
        }
        if s.error:
            args["error"] = s.error
        args.update(s.annotations)
        out.append({
            "name": s.stage,
            "cat": "pipeline",
            "ph": "X",
            "ts": s.start_ms * 1000.0,
            "dur": max(s.service_ms, 0.001) * 1000.0,
            "pid": s.tenant or tr.tenant,
            "tid": s.stage,
            "args": args,
        })
    return out
