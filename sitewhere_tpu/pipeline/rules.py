"""Rule processing: Siddhi-equivalent CEP over the enriched event stream.

Capability parity with the reference's service-rule-processing (embedded
Siddhi engine per tenant: stream definitions mapped from event topics,
filter/window/aggregate queries, callbacks re-emitting derived events,
zone-test geofence rules — SURVEY.md §2.2/§5 [U]; reference mount empty,
see provenance banner).

Redesign: rules are Python objects evaluated per event batch — filters are
predicates, windows are per-group-key sliding count/time windows with
numpy aggregation, actions emit derived events (alerts / command
invocations) back into the pipeline. The north-star extension is
``ModelUdf``: a rule action can invoke a TPU-hosted model (forecast or
score) on the window's values — the "Siddhi CEP queries gain a UDF that
invokes TPU-hosted anomaly/forecast models" capability (BASELINE.json
north_star; SURVEY.md §2.3).
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (
    Any,
    Awaitable,
    Callable,
    Deque,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.core.events import (
    AlertLevel,
    DeviceAlert,
    DeviceCommandInvocation,
    DeviceEvent,
    DeviceLocation,
    DeviceMeasurement,
    EventType,
)
from sitewhere_tpu.runtime.bus import EventBus, RetryingConsumer
from sitewhere_tpu.runtime.config import FaultTolerancePolicy
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait
from sitewhere_tpu.runtime.metrics import MetricsRegistry

Predicate = Callable[[DeviceEvent], bool]
Action = Callable[[DeviceEvent, Dict[str, Any]], Awaitable[Optional[List[DeviceEvent]]]]

AGGREGATES: Dict[str, Callable[[np.ndarray], float]] = {
    "avg": lambda v: float(np.mean(v)),
    "sum": lambda v: float(np.sum(v)),
    "min": lambda v: float(np.min(v)),
    "max": lambda v: float(np.max(v)),
    "count": lambda v: float(len(v)),
    "std": lambda v: float(np.std(v)),
    "last": lambda v: float(v[-1]),
}

_OPS: Dict[str, Callable[[float, float], bool]] = {
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
}


@dataclass
class SlidingWindow:
    """Per-group sliding window: count-bounded and/or time-bounded."""

    length: int = 0          # 0 = unbounded by count
    time_ms: int = 0         # 0 = unbounded by time
    _items: Deque[Tuple[int, float]] = field(default_factory=deque)

    def push(self, ts: int, value: float) -> None:
        self._items.append((ts, value))
        if self.length:
            while len(self._items) > self.length:
                self._items.popleft()
        if self.time_ms:
            cutoff = ts - self.time_ms
            while self._items and self._items[0][0] < cutoff:
                self._items.popleft()

    def values(self) -> np.ndarray:
        return np.asarray([v for _, v in self._items], np.float32)

    def __len__(self) -> int:
        return len(self._items)


@dataclass
class Rule:
    """One CEP query: filter → (optional window+aggregate+having) → action.

    ``group_by`` defaults to per-(device, measurement-name) grouping; the
    windowed aggregate value is passed to ``action`` in the context dict.

    ``vector_where``, when set, is the columnar fast path: it takes a
    ``MeasurementBatch`` and returns a bool row mask of candidate hits;
    only hit rows are materialized into event objects for the (stateful)
    per-event ``evaluate``. Stateless filter rules (threshold, anomaly
    score) provide it; windowed rules fall back to full materialization.
    """

    name: str
    event_type: Optional[EventType] = EventType.MEASUREMENT
    where: Optional[Predicate] = None
    vector_where: Optional[Callable[[Any], np.ndarray]] = None
    window: int = 0
    window_time_ms: int = 0
    aggregate: str = ""                      # key into AGGREGATES
    having: Optional[Callable[[float], bool]] = None
    min_window: int = 1
    group_by: Optional[Callable[[DeviceEvent], str]] = None
    action: Optional[Action] = None
    cooldown_ms: int = 0                     # suppress re-fire per group
    # declares vector_where EXACTLY row-equivalent to the scalar where —
    # enables the engine's cooldown pre-compaction (first hit per group).
    # A rule whose vector_where over-approximates where must leave this
    # False, or non-first rows that where would have accepted get dropped
    vector_exact: bool = False

    _windows: Dict[str, SlidingWindow] = field(default_factory=dict)
    _last_fired: Dict[str, float] = field(default_factory=dict)
    fired: int = 0

    def _group(self, e: DeviceEvent) -> str:
        if self.group_by is not None:
            return self.group_by(e)
        name = getattr(e, "name", "")
        return f"{e.device_token}:{name}"

    async def evaluate(self, e: DeviceEvent) -> Optional[List[DeviceEvent]]:
        if self.event_type is not None and e.EVENT_TYPE is not self.event_type:
            return None
        if self.where is not None and not self.where(e):
            return None
        ctx: Dict[str, Any] = {"rule": self.name}
        if self.window or self.window_time_ms:
            key = self._group(e)
            w = self._windows.get(key)
            if w is None:
                w = self._windows[key] = SlidingWindow(self.window, self.window_time_ms)
            value = float(getattr(e, "value", getattr(e, "score", 0.0)) or 0.0)
            w.push(e.event_ts, value)
            if len(w) < self.min_window:
                return None
            vals = w.values()
            ctx["window_values"] = vals
            if self.aggregate:
                agg = AGGREGATES[self.aggregate](vals)
                ctx["aggregate"] = agg
                if self.having is not None and not self.having(agg):
                    return None
        if self.cooldown_ms:
            key = self._group(e)
            now = time.time() * 1000.0
            if now - self._last_fired.get(key, 0.0) < self.cooldown_ms:
                return None
            self._last_fired[key] = now
        self.fired += 1
        if self.action is None:
            return None
        return await self.action(e, ctx)


# -- built-in rule factories ----------------------------------------------

def alert_action(
    alert_type: str,
    level: AlertLevel = AlertLevel.WARNING,
    message: str = "",
) -> Action:
    async def act(e: DeviceEvent, ctx: Dict[str, Any]):
        agg = ctx.get("aggregate")
        msg = message or f"rule '{ctx['rule']}' fired"
        if agg is not None:
            msg += f" (aggregate={agg:.4f})"
        return [
            DeviceAlert(
                device_token=e.device_token,
                assignment_token=e.assignment_token,
                tenant=e.tenant,
                area_token=e.area_token,
                asset_token=e.asset_token,
                customer_token=e.customer_token,
                source="rule",
                level=level,
                alert_type=alert_type,
                message=msg,
                metadata={"rule": ctx["rule"], "origin_event": e.id},
            )
        ]

    return act


def command_action(command_token: str, parameters: Optional[Dict[str, str]] = None) -> Action:
    async def act(e: DeviceEvent, ctx: Dict[str, Any]):
        return [
            DeviceCommandInvocation(
                device_token=e.device_token,
                assignment_token=e.assignment_token,
                tenant=e.tenant,
                command_token=command_token,
                initiator="rule",
                initiator_id=ctx["rule"],
                parameters=dict(parameters or {}),
            )
        ]

    return act


def threshold_rule(
    name: str,
    measurement: str,
    op: str,
    threshold: float,
    level: AlertLevel = AlertLevel.WARNING,
    alert_type: str = "threshold",
    cooldown_ms: int = 0,
) -> Rule:
    """measurement <op> threshold → alert. The CPU-baseline config's rule
    (BASELINE.json:7)."""
    cmp = _OPS[op]
    _np_ops = {">": np.greater, ">=": np.greater_equal, "<": np.less,
               "<=": np.less_equal, "==": np.equal, "!=": np.not_equal}
    np_cmp = _np_ops[op]

    def vec(batch) -> np.ndarray:
        mask = np_cmp(batch.values, threshold)
        if batch.names is not None:
            mask &= batch.names == measurement
        return mask & batch.valid

    return Rule(
        name=name,
        event_type=EventType.MEASUREMENT,
        where=lambda e: e.name == measurement and cmp(e.value, threshold),  # type: ignore[attr-defined]
        vector_where=vec,
        action=alert_action(alert_type, level, f"{measurement} {op} {threshold}"),
        cooldown_ms=cooldown_ms,
        vector_exact=True,
    )


def anomaly_score_rule(
    name: str,
    min_score: float = 3.0,
    level: AlertLevel = AlertLevel.ERROR,
    cooldown_ms: int = 0,
) -> Rule:
    """TPU anomaly score → alert: the scored-stream consumer rule [B:8]."""

    def vec(batch) -> np.ndarray:
        if batch.scores is None:
            return np.zeros((batch.n,), bool)
        with np.errstate(invalid="ignore"):
            return (batch.scores >= min_score) & batch.valid

    return Rule(
        name=name,
        event_type=EventType.MEASUREMENT,
        where=lambda e: e.score is not None and e.score >= min_score,  # type: ignore[attr-defined]
        vector_where=vec,
        action=alert_action("anomaly", level, "tpu anomaly score"),
        cooldown_ms=cooldown_ms,
        vector_exact=True,
    )


def _point_in_polygon(lat: float, lon: float, poly: Sequence[Tuple[float, float]]) -> bool:
    """Ray casting; poly = [(lat, lon), ...]."""
    inside = False
    n = len(poly)
    for i in range(n):
        la1, lo1 = poly[i]
        la2, lo2 = poly[(i + 1) % n]
        if (lo1 > lon) != (lo2 > lon):
            t = (lon - lo1) / (lo2 - lo1)
            if lat < la1 + t * (la2 - la1):
                inside = not inside
    return inside


def geofence_rule(
    name: str,
    bounds: Sequence[Tuple[float, float]],
    inside: bool = False,
    level: AlertLevel = AlertLevel.WARNING,
    cooldown_ms: int = 0,
) -> Rule:
    """Fire when a DeviceLocation is inside (or outside) a zone polygon —
    the reference's zone-test rules (SURVEY.md §2.2 rule-processing [?])."""

    def where(e: DeviceEvent) -> bool:
        assert isinstance(e, DeviceLocation)
        hit = _point_in_polygon(e.latitude, e.longitude, bounds)
        return hit if inside else not hit

    return Rule(
        name=name,
        event_type=EventType.LOCATION,
        where=where,
        action=alert_action("geofence", level, "zone boundary"),
        cooldown_ms=cooldown_ms,
    )


class ModelUdf:
    """TPU-model UDF callable from rule actions (the north-star CEP↔TPU
    bridge [B:5]): wraps a model-zoo forecaster/scorer; evaluates on the
    rule window's values under jit."""

    def __init__(
        self,
        family: str,
        model_config: Optional[Dict[str, Any]] = None,
        seed: int = 0,
        params_source: Optional[Callable[[], Any]] = None,
    ):
        import jax

        from sitewhere_tpu.models import get_model, make_config

        self.spec = get_model(family)
        self.cfg = make_config(family, model_config)
        self.params = self.spec.init(jax.random.PRNGKey(seed), self.cfg)
        # live binding: evaluate with the tenant's CURRENT slot params
        # (trained/restored) instead of the fresh init above — wire via
        # TpuInferenceService.params_source(tenant)
        self.params_source = params_source
        self._jit_cache: Dict[Tuple[str, int], Callable] = {}
        self._key = jax.random.PRNGKey(seed + 1)

    def bind_params_source(self, source: Callable[[], Any]) -> "ModelUdf":
        self.params_source = source
        return self

    def _live_params(self):
        if self.params_source is not None:
            live = self.params_source()
            if live is not None:
                return live
        return self.params

    def _padded(self, values: np.ndarray, target: int) -> np.ndarray:
        v = values[-target:]
        if len(v) < target:
            v = np.concatenate([np.full(target - len(v), v[0] if len(v) else 0.0, np.float32), v])
        return v.astype(np.float32)

    def forecast(self, values: np.ndarray) -> np.ndarray:
        """values [T] → mean forecast [horizon]."""
        import jax
        import jax.numpy as jnp

        if self.spec.forecast is None:
            raise ValueError(f"model '{self.spec.name}' cannot forecast")
        ctx = getattr(self.cfg, "context", 128)
        fn = self._jit_cache.get(("forecast", ctx))
        if fn is None:
            fn = jax.jit(self.spec.forecast, static_argnums=1)
            self._jit_cache[("forecast", ctx)] = fn
        self._key, sub = jax.random.split(self._key)
        window = jnp.asarray(self._padded(values, ctx))[None]
        _, mean = fn(self._live_params(), self.cfg, window, sub)
        return np.asarray(mean[0])

    def score(self, values: np.ndarray) -> float:
        """values [T] → anomaly score of the latest sample."""
        import jax
        import jax.numpy as jnp

        w = getattr(self.cfg, "window", getattr(self.cfg, "context", 32))
        fn = self._jit_cache.get(("score", w))
        if fn is None:
            fn = jax.jit(self.spec.score, static_argnums=1)
            self._jit_cache[("score", w)] = fn
        window = jnp.asarray(self._padded(values, w))[None]
        n = jnp.asarray([min(len(values), w)], jnp.int32)
        return float(fn(self._live_params(), self.cfg, window, n)[0])


def forecast_breach_rule(
    name: str,
    udf: ModelUdf,
    measurement: str,
    op: str,
    threshold: float,
    window: int = 64,
    level: AlertLevel = AlertLevel.WARNING,
    cooldown_ms: int = 60_000,
) -> Rule:
    """Fire when the UDF's *forecast* breaches a threshold — alerts before
    the physical value does (the predictive-CEP capability [B:5])."""
    cmp = _OPS[op]

    async def act(e: DeviceEvent, ctx: Dict[str, Any]):
        vals = ctx["window_values"]
        mean = await asyncio.get_running_loop().run_in_executor(
            None, udf.forecast, vals
        )
        breach = [float(v) for v in mean if cmp(float(v), threshold)]
        if not breach:
            return None
        return [
            DeviceAlert(
                device_token=e.device_token,
                assignment_token=e.assignment_token,
                tenant=e.tenant,
                area_token=e.area_token,
                asset_token=e.asset_token,
                customer_token=e.customer_token,
                source="rule",
                level=level,
                alert_type="forecast-breach",
                message=(
                    f"forecast breaches {measurement} {op} {threshold} "
                    f"(first={breach[0]:.3f})"
                ),
                metadata={"rule": ctx["rule"], "origin_event": e.id},
            )
        ]

    return Rule(
        name=name,
        event_type=EventType.MEASUREMENT,
        where=lambda e: e.name == measurement,  # type: ignore[attr-defined]
        window=window,
        min_window=window // 2,
        action=act,
        cooldown_ms=cooldown_ms,
    )


class RuleEngine(LifecycleComponent):
    """Per-tenant rule engine over the persisted (enriched) event stream."""

    def __init__(
        self,
        tenant: str,
        bus: EventBus,
        rules: Optional[List[Rule]] = None,
        metrics: Optional[MetricsRegistry] = None,
        poll_batch: int = 4096,
        policy: Optional[FaultTolerancePolicy] = None,
        tracer=None,
        overload=None,
    ) -> None:
        super().__init__(f"rule-processing[{tenant}]")
        self.tenant = tenant
        self.bus = bus
        self.rules: List[Rule] = list(rules or [])
        self.metrics = metrics or MetricsRegistry()
        self.poll_batch = poll_batch
        from sitewhere_tpu.runtime.overload import DeadlineGate
        from sitewhere_tpu.runtime.tracing import StageTimer

        self.stage_timer = StageTimer(tracer, self.metrics, tenant, "rules")
        # overload control: expired measurement batches skip rule work
        # (they are already persisted — only derived fan-out is saved),
        # and the 'persist_only' degradation rung pauses evaluation of
        # measurement batches entirely while engaged
        self.overload = overload
        self.deadline_gate = DeadlineGate(
            bus, tenant, "rules", self.metrics, tracer=tracer,
            controller=overload, route_payload=False,
        )
        self.retry = RetryingConsumer(
            bus, tenant, "rules", self.group, policy=policy,
            metrics=self.metrics, tracer=tracer,
        )
        self._task: Optional[asyncio.Task] = None

    @property
    def group(self) -> str:
        return f"rule-processing[{self.tenant}]"

    def add_rule(self, rule: Rule) -> None:
        self.rules.append(rule)

    def remove_rule(self, name: str) -> None:
        self.rules = [r for r in self.rules if r.name != name]

    async def on_start(self) -> None:
        self.bus.subscribe(
            self.bus.naming.persisted_events(self.tenant), self.group
        )
        self._task = asyncio.create_task(self._run(), name=self.name)

    async def on_stop(self) -> None:
        await cancel_and_wait(self._task)
        self._task = None

    async def _run(self) -> None:
        # per-rule faults are isolated inside process_batch/process_event;
        # the retry wrapper covers stage-level faults (derived-event
        # publishes, batch materialization) and dead-letters poison items
        await self.retry.run(
            self.bus.naming.persisted_events(self.tenant),
            self._handle,
            self.poll_batch,
        )

    async def _handle(self, item) -> None:
        t0 = time.time() * 1000.0
        if self.deadline_gate.check(item):
            return  # already persisted; only the derived fan-out is shed
        if (
            isinstance(item, MeasurementBatch)
            and self.overload is not None
            and self.overload.degraded(self.tenant, "persist_only")
        ):
            # persist-only degradation: rule evaluation over measurement
            # batches pauses while the rung is engaged (alerts and other
            # object events still evaluate — they are the valuable ones)
            self.metrics.counter("rules.skipped_degraded").inc(item.n)
            return
        if isinstance(item, MeasurementBatch):
            derived = await self.process_batch(item)
            n = item.n
        else:
            derived = await self.process_event(item)
            n = 1
        self.stage_timer.observe(
            item, t0, time.time() * 1000.0, n_events=n,
            fired=len(derived),
        )

    async def process_batch(self, batch: MeasurementBatch) -> List[DeviceEvent]:
        """Columnar evaluation: rules with a ``vector_where`` run one numpy
        mask over the batch and materialize ONLY hit rows; rules without
        one (windowed/UDF rules) need every row, so the batch materializes
        once and runs the per-event path."""
        evaluated = self.metrics.counter("rules.evaluated")
        derived_out: List[DeviceEvent] = []
        need_full = [
            r for r in self.rules
            if r.vector_where is None
            and r.event_type in (None, EventType.MEASUREMENT)
        ]
        if need_full:
            for e in batch.to_events():
                derived_out.extend(await self.process_event(e))
            return derived_out
        fired = self.metrics.counter("rules.fired")
        for rule in self.rules:
            if rule.event_type not in (None, EventType.MEASUREMENT):
                continue
            evaluated.inc(batch.n)
            try:
                mask = rule.vector_where(batch)
                hits = np.nonzero(mask)[0]
            except Exception as exc:  # noqa: BLE001
                self._record_error(f"rule '{rule.name}' (vector)", exc)
                continue
            if hits.size == 0:
                continue
            # stateless + cooldown rules: within ONE batch only the first
            # hit per (device:name) group can pass the cooldown gate, and
            # groups still cooling down can be skipped outright — compact
            # BEFORE materializing (an alert-storm batch would otherwise
            # objectify thousands of rows just to drop them)
            if (
                rule.cooldown_ms
                and rule.vector_exact
                and not rule.window
                and not rule.window_time_ms
                and rule.group_by is None
            ):
                codes = batch.pair_codes()[hits]
                _, first = np.unique(codes, return_index=True)
                hits = hits[np.sort(first)]
                lf = rule._last_fired
                if lf:
                    now = time.time() * 1000.0
                    toks, nms = batch.device_tokens, batch.names
                    keep = [
                        j
                        for j, i in enumerate(hits.tolist())
                        if now - lf.get(f"{toks[i]}:{nms[i]}", 0.0)
                        >= rule.cooldown_ms
                    ]
                    if len(keep) != len(hits):
                        hits = (
                            hits[np.asarray(keep, np.intp)]
                            if keep
                            else hits[:0]
                        )
                if hits.size == 0:
                    continue
            # hit rows materialize to objects; evaluate() re-applies the
            # scalar filter plus cooldown/window state and runs the action
            for e in batch.select(hits).to_events():
                try:
                    derived = await rule.evaluate(e)
                except Exception as exc:  # noqa: BLE001
                    self._record_error(f"rule '{rule.name}'", exc)
                    continue
                if derived:
                    fired.inc()
                    derived_out.extend(derived)
        await self._emit_derived(derived_out, parent=batch)
        return derived_out

    async def _emit_derived(
        self, derived_out: List[DeviceEvent], parent=None
    ) -> None:
        from sitewhere_tpu.core.trace import trace_ctx_of

        parent_ctx = trace_ctx_of(parent) if parent is not None else None
        for d in derived_out:
            if d.trace_ctx is None and parent_ctx is not None:
                # derived events (alerts, command invocations) stay on the
                # origin event's trace: their persistence/outbound spans
                # show up as children of the rule that fired
                d.trace_ctx = parent_ctx.child()
            d.mark("rule")
            if d.EVENT_TYPE is EventType.COMMAND_INVOCATION:
                await self.retry.publish(
                    self.bus.naming.command_invocations(self.tenant), d
                )
            else:
                await self.retry.publish(
                    self.bus.naming.scored_events(self.tenant), d
                )

    async def process_event(self, e: DeviceEvent) -> List[DeviceEvent]:
        """Evaluate all rules; publish derived events into the pipeline."""
        evaluated = self.metrics.counter("rules.evaluated")
        fired = self.metrics.counter("rules.fired")
        derived_out: List[DeviceEvent] = []
        for rule in self.rules:
            evaluated.inc()
            try:
                derived = await rule.evaluate(e)
            except Exception as exc:  # noqa: BLE001 - a bad rule must not kill the engine
                self._record_error(f"rule '{rule.name}'", exc)
                continue
            if derived:
                fired.inc()
                derived_out.extend(derived)
        # derived alerts re-enter at the scored stage (they get persisted +
        # fanned out); alerts don't match measurement rules so no feedback loop
        await self._emit_derived(derived_out, parent=e)
        return derived_out
