"""Overload control & graceful degradation (the PR-3 robustness layer).

PR 1 made failures survivable (retries / DLQ / breakers) and PR 2 made
them visible (traces, lag gauges). This module makes *overload*
survivable: bounded latency, per-tenant isolation, and controlled
degradation instead of congestion collapse. Four cooperating mechanisms
(see docs/ROBUSTNESS.md "Overload & degradation"):

- **Admission control** (``PriorityClassQueue``): every receiver queue
  becomes priority-classed (alerts > commands > measurements). Under
  burst, the lowest class sheds first — a flood of measurements can
  never evict an alert — and each class has its own fill watermark so
  alerts still admit when measurements are already shedding. Accepted
  payloads get a deadline stamp derived from the tenant SLO
  (``stamp_deadline``) that rides the payload through every stage and
  across the netbus wire (``MeasurementBatch.deadline_ms`` /
  ``DeviceEvent.deadline_ms`` / the ``"_deadline"`` dict key — the same
  propagation seam as PR 2's trace context).

- **Deadline propagation** (``DeadlineGate``): each stage consults the
  remaining budget before doing work. Expired measurements route to the
  tenant's ``expired-events`` topic (payload attached — accounting
  stays exact: store ∪ DLQ ∪ expired) with
  ``pipeline_expired_total{tenant,stage}`` accounting and a forced
  trace retention (tail sampling keeps every expired trace), *before*
  a TPU flush is spent on them. Alerts / commands / other
  non-measurement events never expire, and the persistence stage
  observes lateness but does not drop by default: at the
  system-of-record boundary, at-least-once beats deadline
  (``OverloadPolicy.drop_expired_at_persist`` opts into strict mode).

- **Per-tenant weighted fair queuing + credit backpressure**
  (``DeficitRoundRobin`` + ``OverloadController.credit``): the
  tpu-inference consumption loop rations bus→lane intake by deficit
  round-robin over ``OverloadPolicy.weight``, so a hostile tenant's
  backlog stays in *its* bus topic instead of flooding shared lanes.
  That lag feeds back as a per-tenant credit signal (1.0 healthy → 0.0
  saturated) which shrinks the receiver queue's measurement watermark —
  receivers throttle intake cooperatively instead of buffering
  unboundedly.

- **Degradation ladder** (``OverloadController``): an ordered list of
  sheddable features per tenant (``OverloadPolicy.ladder`` — sampling
  non-alert inference, persist-only mode, pausing rules/outbound
  fan-out) engages rung by rung from sustained lag / deadline-miss
  signals and disengages with hysteresis once the pressure clears.
  State is served at ``GET /api/tenants/{t}/overload``.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from sitewhere_tpu.core.events import DeviceEvent, EventType
from sitewhere_tpu.core.trace import trace_ctx_of
from sitewhere_tpu.runtime.config import OverloadPolicy, TenantEngineConfig
from sitewhere_tpu.runtime.metrics import MetricsRegistry

# priority classes, in shed order (highest value sheds first)
PRIORITY_ALERT = 0
PRIORITY_COMMAND = 1
PRIORITY_MEASUREMENT = 2
PRIORITY_NAMES = ("alert", "command", "measurement")


def classify_priority(context: Dict[str, Any]) -> int:
    """Admission-time priority of a raw payload. Cheap by design (no
    payload parse at ingest rate): an explicit ``priority`` context hint
    wins, else the transport topic string decides."""
    p = context.get("priority")
    if p is not None:
        if isinstance(p, int):
            return min(max(p, PRIORITY_ALERT), PRIORITY_MEASUREMENT)
        p = str(p)
        if p in PRIORITY_NAMES:
            return PRIORITY_NAMES.index(p)
    topic = str(context.get("topic", ""))
    if "alert" in topic:
        return PRIORITY_ALERT
    if "command" in topic:
        return PRIORITY_COMMAND
    return PRIORITY_MEASUREMENT


# -- deadline propagation --------------------------------------------------

def stamp_deadline(item: Any, deadline_epoch_ms: float) -> None:
    """Attach an absolute deadline (epoch ms) to any pipeline payload
    shape — batch, event object, or decoded request dict. The stamp
    rides the payload (pickled whole) across the netbus/dlog wire."""
    if isinstance(item, dict):
        item["_deadline"] = float(deadline_epoch_ms)
    else:
        try:
            item.deadline_ms = float(deadline_epoch_ms)
        except AttributeError:
            pass  # foreign payload shape: no deadline semantics


def deadline_of(item: Any) -> Optional[float]:
    """The one extractor every stage uses: the payload's absolute
    deadline (epoch ms), or None when unstamped."""
    dl = getattr(item, "deadline_ms", None)
    if dl is not None:
        return float(dl)
    if isinstance(item, dict):
        dl = item.get("_deadline")
        if dl is not None:
            return float(dl)
    return None


def clear_deadline(item: Any) -> None:
    """Strip the deadline stamp — operator-driven DLQ requeue is a
    re-admission: an entry that sat in a dead-letter topic for minutes
    must not be expired the moment it re-enters the pipeline."""
    if isinstance(item, dict):
        item.pop("_deadline", None)
        payload = item.get("payload")
        if payload is not None and payload is not item:
            clear_deadline(payload)
        return
    if getattr(item, "deadline_ms", None) is not None:
        try:
            item.deadline_ms = None
        except AttributeError:
            pass


def _expirable(item: Any) -> bool:
    """Only measurement work expires: alerts, command invocations and
    other object events must deliver even late (they are low-volume and
    high-value — expiring them would trade correctness for nothing)."""
    if isinstance(item, DeviceEvent):
        return item.EVENT_TYPE is EventType.MEASUREMENT
    if isinstance(item, dict):
        return item.get("type", "measurement") == "measurement"
    return True  # MeasurementBatch (and anything batch-shaped)


class DeadlineGate:
    """One stage's budget check: expired payloads route to the tenant's
    ``expired-events`` topic (payload attached, trace force-retained)
    with ``pipeline_expired_total{tenant,stage}`` accounting. Returns
    True from ``check`` when the item was expired-routed — the caller
    must then skip its normal handling.

    Dropping is a LOAD-SHEDDING action, not a correctness rule: with a
    controller attached, an expired item is only dropped while its
    tenant is actually under pressure (degradation engaged or credit
    below 1.0). A lone latency excursion — an XLA compile stall, a GC
    pause — makes events late without the system being overloaded, and
    dropping them then would turn a hiccup into data loss. Late-but-not-
    shed events are still counted (``pipeline_deadline_late_total``)
    and noted to the controller as a deadline-miss pressure signal."""

    def __init__(
        self,
        bus,
        tenant: str,
        stage: str,
        metrics: Optional[MetricsRegistry] = None,
        tracer=None,
        controller: Optional["OverloadController"] = None,
        clock: Callable[[], float] = time.time,
        drop: bool = True,
        route_payload: bool = True,
    ) -> None:
        self.bus = bus
        self.tenant = tenant
        self.stage = stage
        self.tracer = tracer
        self.controller = controller
        self.clock = clock
        # drop=False (persistence): observe lateness, never drop — the
        # store is the system of record and at-least-once wins there
        self.drop = drop
        # route_payload=False (rules/outbound, post-store): the event is
        # already persisted, so dropping its fan-out must not duplicate
        # the payload into the expired accounting topic — count only
        self.route_payload = route_payload
        m = metrics or MetricsRegistry()
        m.describe(
            "pipeline_expired_total",
            "events dropped to the expired topic after blowing their "
            "admission deadline, per tenant and stage",
        )
        m.describe(
            "pipeline_deadline_late_total",
            "events observed past deadline at a non-dropping stage "
            "(persistence), per tenant and stage",
        )
        self.expired_c = m.counter(
            "pipeline_expired_total", tenant=tenant, stage=stage
        )
        self.late_c = m.counter(
            "pipeline_deadline_late_total", tenant=tenant, stage=stage
        )
        self.topic = bus.naming.expired_events(tenant)

    def check(self, item: Any) -> bool:
        dl = deadline_of(item)
        if dl is None or not _expirable(item):
            return False
        now = self.clock() * 1000.0
        if now < dl:
            return False
        n = int(getattr(item, "n", 1))
        shed = self.drop and (
            self.controller is None
            or self.controller.under_pressure(self.tenant)
        )
        if not shed:
            # observe-only: lateness WITHOUT pressure is a latency
            # excursion (fault-recovery backoff, compile stall), not
            # overload — feeding it to the engage signal would let a
            # transient fault burst flip the gates into dropping and
            # trade at-least-once for nothing. Pressure originates from
            # the lag/credit loop; deadline-miss drops then sustain it.
            self.late_c.inc(n)
            return False
        ctx = trace_ctx_of(item)
        if ctx is not None and self.tracer is not None:
            # expired work is exactly what tail sampling must keep
            self.tracer.mark_hit(ctx, "expired")
        if self.route_payload:
            entry = {
                "stage": self.stage,
                "tenant": self.tenant,
                "deadline_ms": dl,
                "expired_at_ms": now,
                "late_ms": now - dl,
                "rows": n,
                "payload": item,
            }
            if ctx is not None:
                entry["trace_id"] = ctx.trace_id
            # non-blocking like every DLQ-style write: the expired topic
            # is the lossless accounting fallback and must never
            # backpressure (or be fault-injected) shut
            self.bus.publish_nowait(self.topic, entry)
        self.expired_c.inc(n)
        if self.controller is not None:
            self.controller.note_expired(self.tenant, n)
        return True


# -- admission control -----------------------------------------------------

class PriorityClassQueue:
    """Bounded receiver queue with priority-classed admission.

    Three FIFO classes (alert > command > measurement) behind the same
    ``get``/``get_nowait``/``qsize`` surface as the ``asyncio.Queue`` it
    replaces. Dequeue serves the highest class first. Admission:

    - each class has a fill watermark (fraction of ``maxsize``) above
      which *that class* sheds; alerts admit up to ~the full queue,
      measurements shed earliest;
    - the measurement watermark additionally scales with the tenant's
      credit signal (``credit_fn``) — downstream consumer lag shrinks
      intake cooperatively before anything buffers unboundedly;
    - shedding always takes the OLDEST entry of the LOWEST present
      class at-or-below the arriving priority (newest data wins within
      a class; a lower class is never protected from a higher arrival;
      a higher class is never evicted by a lower arrival);
    - the awaited ``put`` keeps the legacy backpressure contract while
      the tenant is healthy (credit 1.0): in-proc producers block on a
      genuinely full queue instead of shedding.

    Sheds are counted per class via ``on_shed(priority, n)`` (wired by
    ``EventSource`` to metrics + the tail trace sampler).
    """

    def __init__(self, maxsize: int = 65536) -> None:
        self.maxsize = maxsize
        self._classes: Tuple[deque, deque, deque] = (deque(), deque(), deque())
        self._data = None  # asyncio.Event, created lazily on first get
        self._space = None
        self.shed_total = 0
        self.on_shed: Optional[Callable[[int, int], None]] = None
        self.credit_fn: Optional[Callable[[], float]] = None
        # per-class fill watermarks (fractions of maxsize), overridden
        # from OverloadPolicy by the owning EventSource
        self.fill = [0.98, 0.90, 0.75]

    # -- introspection (asyncio.Queue-compatible surface) -----------------
    def qsize(self) -> int:
        return sum(len(c) for c in self._classes)

    def class_depths(self) -> Tuple[int, int, int]:
        return tuple(len(c) for c in self._classes)  # type: ignore[return-value]

    def _events(self):
        import asyncio

        if self._data is None:
            self._data = asyncio.Event()
            self._space = asyncio.Event()
            self._space.set()
        return self._data, self._space

    # -- admission ---------------------------------------------------------
    def _cap(self, priority: int) -> int:
        cap = self.fill[priority] * self.maxsize
        if priority == PRIORITY_MEASUREMENT and self.credit_fn is not None:
            # credit 1.0 → full watermark; 0.0 → a sliver (never zero:
            # trickle intake keeps the pipeline's signals alive)
            cap *= max(0.02, min(1.0, self.credit_fn()))
        return max(1, int(cap))

    def _shed_one(self, arriving_priority: int) -> bool:
        """Drop the oldest entry of the lowest present class that is not
        higher-priority than the arrival. True if something was shed."""
        for pr in range(PRIORITY_MEASUREMENT, arriving_priority - 1, -1):
            cls = self._classes[pr]
            if cls:
                cls.popleft()
                self._note_shed(pr)
                return True
        return False

    def _note_shed(self, priority: int, n: int = 1) -> None:
        self.shed_total += n
        if self.on_shed is not None:
            self.on_shed(priority, n)

    def put_nowait(self, item: Any, priority: int = PRIORITY_MEASUREMENT) -> bool:
        """Admit or shed (never raises). Returns True when the item was
        admitted, False when it was shed at admission."""
        if self.qsize() < self._cap(priority):
            self._append(item, priority)
            return True
        if self._shed_one(priority):
            self._append(item, priority)
            return True
        # queue is full of strictly higher-priority work: the arrival
        # itself sheds (counted against ITS class)
        self._note_shed(priority)
        return False

    async def put(self, item: Any, priority: int = PRIORITY_MEASUREMENT) -> bool:
        """Awaited admission. Healthy tenants (credit 1.0) keep the
        legacy backpressure contract — block until space. Once the
        credit signal is degraded, measurements shed instead of
        blocking (cooperative throttle; the producer is typically a
        broker fan-out loop that must not stall other tenants)."""
        data, space = self._events()
        while True:
            if self.qsize() < self._cap(priority):
                self._append(item, priority)
                return True
            credit = self.credit_fn() if self.credit_fn is not None else 1.0
            if priority == PRIORITY_MEASUREMENT and credit < 1.0:
                return self.put_nowait(item, priority)
            if priority < PRIORITY_MEASUREMENT and self._shed_one(priority):
                # alerts/commands evict lower-class work rather than wait
                self._append(item, priority)
                return True
            space.clear()
            await space.wait()

    def _append(self, item: Any, priority: int) -> None:
        self._classes[priority].append(item)
        if self._data is not None:
            self._data.set()

    # -- consumer ----------------------------------------------------------
    def get_nowait(self) -> Any:
        import asyncio

        for cls in self._classes:
            if cls:
                item = cls.popleft()
                if self._space is not None:
                    self._space.set()
                return item
        raise asyncio.QueueEmpty

    async def get(self) -> Any:
        import asyncio

        data, _space = self._events()
        while True:
            try:
                return self.get_nowait()
            except asyncio.QueueEmpty:
                data.clear()
                await data.wait()


# -- per-tenant weighted fair queuing --------------------------------------

class DeficitRoundRobin:
    """Deficit round-robin rationing of a shared consumption loop.

    Each registered tenant accrues ``quantum × weight`` units of budget
    per ``replenish`` (one scoring-loop pass), capped at a 2-round
    burst. The loop consumes while a tenant's budget is positive and
    charges actual rows consumed; a tenant that overdraws (one poll can
    exceed the remainder) sits out following rounds until its deficit
    refills — so sustained throughput converges to the weight ratio
    while bursts stay cheap. Unregistered tenants, and a tenant that is
    the only one registered, are unthrottled."""

    def __init__(self, quantum: int = 4096) -> None:
        self.quantum = quantum
        self.weights: Dict[str, float] = {}
        self.deficits: Dict[str, float] = {}

    def configure(self, tenant: str, weight: float = 1.0) -> None:
        self.weights[tenant] = max(0.01, float(weight))
        self.deficits.setdefault(tenant, self.quantum * self.weights[tenant])

    def remove(self, tenant: str) -> None:
        self.weights.pop(tenant, None)
        self.deficits.pop(tenant, None)

    def replenish(self) -> None:
        for tenant, w in self.weights.items():
            cap = 2.0 * self.quantum * w
            self.deficits[tenant] = min(
                self.deficits.get(tenant, 0.0) + self.quantum * w, cap
            )

    def budget(self, tenant: str) -> float:
        # a tenant alone on the loop is not rationed: there is no one to
        # be fair to, and sitting a pass out only delays its own rows (a
        # single tenant's 512-row bulk messages overdrew the two-round
        # burst whenever its lanes had been full for a poll)
        if tenant not in self.weights or len(self.weights) == 1:
            return float("inf")
        return self.deficits.get(tenant, 0.0)

    def charge(self, tenant: str, rows: int) -> None:
        if tenant in self.weights:
            self.deficits[tenant] = self.deficits.get(tenant, 0.0) - rows

    def describe(self) -> Dict[str, Dict[str, float]]:
        return {
            t: {"weight": w, "deficit": round(self.deficits.get(t, 0.0), 1)}
            for t, w in self.weights.items()
        }


# -- degradation ladder + credit signal ------------------------------------

class _TenantOverloadState:
    __slots__ = (
        "policy", "deadline_budget_ms", "credit", "level",
        "above_since", "below_since", "expired_marks", "engaged_at",
        "lag", "lag_prev", "shed_recent",
    )

    def __init__(self, policy: OverloadPolicy, deadline_budget_ms: float) -> None:
        self.policy = policy
        self.deadline_budget_ms = deadline_budget_ms
        self.credit = 1.0
        self.level = 0
        self.above_since: Optional[float] = None
        self.below_since: Optional[float] = None
        self.expired_marks: deque = deque(maxlen=256)  # (epoch-s, n) drops
        self.engaged_at: Optional[float] = None
        self.lag = 0
        self.lag_prev = 0  # previous refresh tick's lag (trend signal)
        self.shed_recent = 0


class OverloadController:
    """Per-instance overload brain: one controller shared by every stage
    of every tenant (like PR 2's Tracer). Holds each tenant's
    ``OverloadPolicy``, computes the credit signal from bus consumer
    lag, and runs the degradation ladder state machine with hysteresis.

    Signals in: ``refresh(bus.lags())`` (periodic, from the instance)
    and ``note_expired`` (deadline gates). Signals out:
    ``credit(tenant)`` (receivers), ``degraded(tenant, feature)``
    (inference / rules / outbound), ``deadline_ms(tenant)`` (ingest
    stamping), ``weight(tenant)`` (the DRR fair queue), gauges
    ``overload_credit{tenant}`` / ``overload_degradation_level{tenant}``
    and counters ``overload_transitions_total{tenant,direction}``."""

    def __init__(
        self,
        metrics: Optional[MetricsRegistry] = None,
        tracer=None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.metrics = metrics or MetricsRegistry()
        self.tracer = tracer
        self.clock = clock
        self._tenants: Dict[str, _TenantOverloadState] = {}
        self.metrics.describe(
            "overload_credit",
            "per-tenant intake credit (1 healthy .. 0 saturated) fed "
            "back to receivers from bus consumer lag",
        )
        self.metrics.describe(
            "overload_degradation_level",
            "engaged rungs of the tenant's degradation ladder "
            "(0 = full service)",
        )
        self.metrics.describe(
            "overload_transitions_total",
            "degradation ladder transitions per tenant and direction",
        )

    # -- registration ------------------------------------------------------
    def configure_tenant(self, cfg: TenantEngineConfig) -> None:
        pol = cfg.overload
        budget = pol.deadline_ms if pol.deadline_ms > 0 else (
            2.0 * cfg.tracing.slo_ms
        )
        self._tenants[cfg.tenant] = _TenantOverloadState(pol, budget)
        self.metrics.gauge("overload_credit", tenant=cfg.tenant).set(1.0)
        self.metrics.gauge(
            "overload_degradation_level", tenant=cfg.tenant
        ).set(0.0)

    def remove_tenant(self, tenant: str) -> None:
        self._tenants.pop(tenant, None)

    def policy_for(self, tenant: str) -> Optional[OverloadPolicy]:
        st = self._tenants.get(tenant)
        return st.policy if st is not None else None

    # -- signals out -------------------------------------------------------
    def deadline_ms(self, tenant: str) -> Optional[float]:
        """The tenant's admission deadline budget (relative ms), or None
        when overload control is off for the tenant."""
        st = self._tenants.get(tenant)
        if st is None or not st.policy.enabled:
            return None
        return st.deadline_budget_ms

    def credit(self, tenant: str) -> float:
        st = self._tenants.get(tenant)
        return st.credit if st is not None else 1.0

    def weight(self, tenant: str) -> float:
        st = self._tenants.get(tenant)
        return st.policy.weight if st is not None else 1.0

    def level(self, tenant: str) -> int:
        st = self._tenants.get(tenant)
        return st.level if st is not None else 0

    def under_pressure(self, tenant: str) -> bool:
        """True while the tenant shows overload signals (reduced credit
        or an engaged degradation rung) — the gate that turns deadline
        expiry from an observation into an actual shed."""
        st = self._tenants.get(tenant)
        if st is None:
            return True  # unregistered (standalone gates): shed freely
        return st.credit < 1.0 or st.level > 0

    def any_pressure(self) -> bool:
        """True while ANY registered tenant shows overload signals —
        the probation prober's defer gate: a synthetic probe flush on a
        quarantined slice is pure recovery bookkeeping and must not
        contend for device time while live traffic is already shedding
        (the same live-traffic-wins posture as the replay pump and the
        train lane)."""
        return any(
            st.credit < 1.0 or st.level > 0
            for st in self._tenants.values()
        )

    def degraded(self, tenant: str, feature: str) -> bool:
        st = self._tenants.get(tenant)
        if st is None or not st.policy.enabled or st.level == 0:
            return False
        ladder = st.policy.ladder
        return feature in ladder[: st.level]

    def active_features(self, tenant: str) -> List[str]:
        st = self._tenants.get(tenant)
        if st is None:
            return []
        return list(st.policy.ladder[: st.level])

    # -- signals in --------------------------------------------------------
    def note_expired(self, tenant: str, n: int = 1) -> None:
        # (timestamp, event_count) — the engage threshold is documented
        # as deadline misses per SECOND OF EVENTS, so a dropped 4096-row
        # batch must weigh 4096, not 1
        st = self._tenants.get(tenant)
        if st is not None:
            st.expired_marks.append((self.clock(), max(1, int(n))))

    def note_shed(self, tenant: str, n: int = 1) -> None:
        st = self._tenants.get(tenant)
        if st is not None:
            st.shed_recent += n

    def _tenant_lag(self, tenant: str, lags: Dict[str, dict]) -> int:
        """Max consumer lag across the tenant's pipeline topics (the
        dead-letter / expired accounting topics are excluded: parked DLQ
        backlogs are an operator queue, not pipeline pressure)."""
        needle = f".tenant.{tenant}."
        worst = 0
        for topic, info in lags.items():
            if needle not in topic:
                continue
            if ".dead-letter." in topic or topic.endswith("expired-events"):
                continue
            if topic.endswith("replay-train-feed"):
                # the train lane's backlog is low-priority history, not
                # pipeline pressure — and its consumer is credit-GATED,
                # so counting it would latch a feedback loop: throttled
                # ⇒ feed unconsumed ⇒ lag ⇒ credit stays low forever
                continue
            groups = info.get("groups", {})
            if groups:
                worst = max(worst, max(groups.values()))
        return worst

    def refresh(self, lags: Dict[str, dict], now: Optional[float] = None) -> None:
        """One control tick: recompute credit + run the ladder state
        machine for every tenant. Called periodically by the instance
        (in-proc bus) — remote deployments feed ``await bus.lags()``."""
        now = self.clock() if now is None else now
        for tenant, st in self._tenants.items():
            pol = st.policy
            if not pol.enabled:
                continue
            lag = self._tenant_lag(tenant, lags)
            st.lag_prev = st.lag
            st.lag = lag
            # credit: 1.0 at/below lo, linear to 0.0 at hi
            lo, hi = pol.credit_lag_lo, max(pol.credit_lag_hi, pol.credit_lag_lo + 1)
            credit = 1.0 - (lag - lo) / (hi - lo)
            st.credit = max(0.0, min(1.0, credit))
            self.metrics.gauge("overload_credit", tenant=tenant).set(st.credit)
            # recent deadline misses count as pressure even when lag is
            # low (the TPU can be the bottleneck with short queues)
            recent_expired = sum(
                n for t, n in st.expired_marks if now - t <= 1.0
            )
            over = lag >= pol.engage_lag or recent_expired >= pol.engage_expired_per_s
            under = lag <= pol.disengage_lag and recent_expired == 0
            if over:
                st.below_since = None
                if st.above_since is None:
                    st.above_since = now
                if (
                    now - st.above_since >= pol.engage_hold_s
                    and st.level < len(pol.ladder)
                ):
                    st.level += 1
                    st.above_since = now  # next rung needs its own hold
                    st.engaged_at = now
                    self.metrics.counter(
                        "overload_transitions_total",
                        tenant=tenant, direction="engage",
                    ).inc()
                    self.metrics.gauge(
                        "overload_degradation_level", tenant=tenant
                    ).set(st.level)
            elif under:
                st.above_since = None
                if st.below_since is None:
                    st.below_since = now
                if (
                    now - st.below_since >= pol.hysteresis_s
                    and st.level > 0
                ):
                    st.level -= 1
                    st.below_since = now
                    self.metrics.counter(
                        "overload_transitions_total",
                        tenant=tenant, direction="disengage",
                    ).inc()
                    self.metrics.gauge(
                        "overload_degradation_level", tenant=tenant
                    ).set(st.level)
            else:
                # between thresholds: hold the current level, reset both
                # clocks (hysteresis measures *sustained* pressure/calm)
                st.above_since = None
                st.below_since = None

    # -- traffic signals (weight paging reads these) -----------------------
    def tenant_lag(self, tenant: str) -> int:
        """The tenant's pipeline consumer lag as of the last refresh
        tick — the per-tenant traffic-rate signal the weight pager's
        LRU eviction discounts by (runtime.paging: a lagging tenant is
        about to need its slot)."""
        st = self._tenants.get(tenant)
        return st.lag if st is not None else 0

    def lag_rising(self, tenant: str) -> bool:
        """Did the tenant's lag GROW across the last two refresh ticks?
        Rising lag on a non-resident tenant is the predictive-prefetch
        trigger: rows are accumulating on the bus faster than they
        drain, so page the weights in before the rows arrive."""
        st = self._tenants.get(tenant)
        return st is not None and st.lag > st.lag_prev

    def rising_tenants(self):
        """Tenants whose lag rose this tick (prefetch candidates)."""
        return [
            t for t, st in self._tenants.items()
            if st.policy.enabled and st.lag > st.lag_prev and st.lag > 0
        ]

    # -- introspection -----------------------------------------------------
    def report(self, tenant: str) -> Optional[dict]:
        st = self._tenants.get(tenant)
        if st is None:
            return None
        pol = st.policy
        return {
            "tenant": tenant,
            "enabled": pol.enabled,
            "deadline_budget_ms": st.deadline_budget_ms,
            "weight": pol.weight,
            "credit": round(st.credit, 4),
            "pipeline_lag": st.lag,
            "degradation_level": st.level,
            "ladder": list(pol.ladder),
            "active_features": self.active_features(tenant),
            "sheds_noted": st.shed_recent,
            "watermarks": {
                "alert": pol.shed_alerts_fill,
                "command": pol.shed_commands_fill,
                "measurement": pol.shed_measurements_fill,
            },
        }
