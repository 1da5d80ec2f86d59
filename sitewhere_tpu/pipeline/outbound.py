"""Outbound connectors: fan-out of enriched events to external systems.

Capability parity with the reference's service-outbound-connectors
(``IOutboundConnector`` impls — MQTT publisher, Solr indexer, EventHub/SQS/
RabbitMQ, webhook, Groovy-scripted — each with filter chains and bounded
processing — SURVEY.md §2.2 [U]; reference mount empty, see provenance
banner).

Redesign: connectors are lifecycle components with a filter chain and an
async ``deliver``; network-less equivalents ship in-image (log, file/JSONL,
in-proc MQTT-topic publisher backed by the sim broker, callback) and the
network ones (webhook via aiohttp, real MQTT) activate when their transport
is reachable. Per-connector supervised delivery with bounded concurrency
mirrors the reference's bounded thread pools.
"""

from __future__ import annotations

import asyncio
import json
from pathlib import Path
from typing import Awaitable, Callable, Dict, List, Optional, Sequence

import numpy as np

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.core.events import DeviceEvent, EventType
from sitewhere_tpu.runtime.bus import (
    CircuitBreaker,
    EventBus,
    RetryingConsumer,
)
from sitewhere_tpu.runtime.config import FaultTolerancePolicy
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait
from sitewhere_tpu.runtime.metrics import MetricsRegistry

EventFilter = Callable[[DeviceEvent], bool]


class CircuitOpenError(RuntimeError):
    """Delivery short-circuited because the connector's breaker is open."""


def type_filter(*types: EventType) -> EventFilter:
    allowed = set(types)
    return lambda e: e.EVENT_TYPE in allowed


def area_filter(*area_tokens: str) -> EventFilter:
    allowed = set(area_tokens)
    return lambda e: e.area_token in allowed


def device_filter(*device_tokens: str) -> EventFilter:
    allowed = set(device_tokens)
    return lambda e: e.device_token in allowed


class OutboundConnector(LifecycleComponent):
    """Base connector: filter chain + async deliver with bounded concurrency."""

    def __init__(
        self,
        name: str,
        filters: Optional[Sequence[EventFilter]] = None,
        concurrency: int = 8,
    ) -> None:
        super().__init__(f"connector[{name}]")
        self.connector_id = name
        self.filters: List[EventFilter] = list(filters or [])
        self._sem = asyncio.Semaphore(concurrency)
        self.delivered = 0
        self.failed = 0
        self.retried = 0
        self.parked = 0  # deliveries short-circuited by an open breaker
        # fault-tolerance bindings (installed by OutboundDispatcher when a
        # FaultTolerancePolicy is configured; None = legacy single-attempt
        # delivery with isolated errors, exactly the pre-policy behavior)
        self.breaker: Optional[CircuitBreaker] = None
        self._ft: Optional[RetryingConsumer] = None
        self._ft_source_topic = ""

    def bind_fault_tolerance(
        self, ft: RetryingConsumer, breaker: CircuitBreaker,
        source_topic: str,
    ) -> None:
        """Install retry budget + breaker + DLQ routing (dispatcher call)."""
        self._ft = ft
        self.breaker = breaker
        self._ft_source_topic = source_topic

    def accepts(self, e: DeviceEvent) -> bool:
        return all(f(e) for f in self.filters)

    async def deliver(self, e: DeviceEvent) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    async def deliver_batch(self, batch: MeasurementBatch) -> int:
        """Columnar delivery. Default: materialize rows and deliver each
        (connectors whose sink is inherently per-message, e.g. MQTT).
        High-volume-friendly connectors override with a bulk write."""
        n = 0
        for e in batch.to_events():
            if self.accepts(e):
                await self.deliver(e)
                n += 1
        return n

    _FAILED = object()  # _attempt sentinel (deliver() legitimately returns None)

    async def _attempt(self, fn, item, kind: str):
        """One delivery under breaker gating + the retry budget; exhausted
        (or breaker-parked) items dead-letter instead of vanishing.
        Returns fn's result, or ``_FAILED`` when delivery failed."""
        max_attempts = max(
            1, self._ft.policy.max_attempts if self._ft is not None else 1
        )
        last: Optional[BaseException] = None
        calls = 0
        for attempt in range(1, max_attempts + 1):
            if self.breaker is not None and not self.breaker.allow():
                # park instead of hammering a dead target: route straight
                # to the connector's DLQ with the breaker named
                self.parked += 1
                last = CircuitOpenError(f"breaker '{self.breaker.name}' open")
                break
            try:
                calls += 1
                result = await fn(item)
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001 - connector errors are isolated
                last = exc
                if self.breaker is not None:
                    self.breaker.record_failure()
                if attempt < max_attempts:
                    self.retried += 1
                    await asyncio.sleep(self._ft._backoff(attempt))
                continue
            if self.breaker is not None:
                self.breaker.record_success()
            return result
        self.failed += 1
        self._record_error(kind, last)
        if self._ft is not None:
            await self._ft.dead_letter(item, self._ft_source_topic, calls, last)
        return self._FAILED

    async def process(self, e: DeviceEvent) -> bool:
        if not self.accepts(e):
            return False
        async with self._sem:
            result = await self._attempt(self.deliver, e, "deliver")
            if result is self._FAILED:
                return False
            self.delivered += 1
            return True

    async def process_batch(self, batch: MeasurementBatch) -> int:
        async with self._sem:
            n = await self._attempt(self.deliver_batch, batch, "deliver_batch")
            if n is self._FAILED:
                return 0
            self.delivered += n
            return n


class LogConnector(OutboundConnector):
    """Collects events in memory / logs them — the dev default."""

    def __init__(self, name: str = "log", capacity: int = 10000, **kw) -> None:
        super().__init__(name, **kw)
        self.capacity = capacity
        self.events: List[DeviceEvent] = []
        self.batch_rows = 0

    async def deliver(self, e: DeviceEvent) -> None:
        self.events.append(e)
        if len(self.events) > self.capacity:
            del self.events[: len(self.events) // 2]

    async def deliver_batch(self, batch: MeasurementBatch) -> int:
        if self.filters:
            # filters are per-event predicates; fall back to the
            # materialize-and-filter base path so counts stay honest
            return await super().deliver_batch(batch)
        # count rows + keep a one-row sample; materializing 10^5 rows/s of
        # objects into a dev log would defeat the columnar path
        self.batch_rows += batch.n
        if batch.n:
            sample = batch.select(np.asarray([batch.n - 1]))
            self.events.extend(sample.to_events())
            if len(self.events) > self.capacity:
                del self.events[: len(self.events) // 2]
        return batch.n


class JsonlFileConnector(OutboundConnector):
    """Appends events as JSON lines to a file (the Solr-indexer stand-in)."""

    def __init__(self, name: str, path: str | Path, **kw) -> None:
        super().__init__(name, **kw)
        self.path = Path(path)
        self._fh = None

    async def on_start(self) -> None:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._fh = self.path.open("a")

    async def on_stop(self) -> None:
        if self._fh:
            self._fh.close()
            self._fh = None

    async def deliver(self, e: DeviceEvent) -> None:
        assert self._fh is not None, "connector not started"
        self._fh.write(e.to_json() + "\n")


class MqttTopicConnector(OutboundConnector):
    """Publishes events to per-device topics on the in-proc sim broker
    (``sim.broker.SimBroker``) — the reference's MQTT outbound analog.
    Topic pattern supports {device}, {type}, {tenant} placeholders."""

    def __init__(
        self,
        name: str,
        broker,
        topic_pattern: str = "sitewhere/output/{device}/{type}",
        publish_measurement_batches: bool = False,
        **kw,
    ) -> None:
        super().__init__(name, **kw)
        self.broker = broker
        self.topic_pattern = topic_pattern
        # per-message MQTT fan-out of the full measurement firehose defeats
        # the columnar path; default off — alerts/commands (objects) still
        # publish per event, opt in for full measurement mirroring
        self.publish_measurement_batches = publish_measurement_batches

    async def deliver(self, e: DeviceEvent) -> None:
        topic = self.topic_pattern.format(
            device=e.device_token, type=e.EVENT_TYPE.value, tenant=e.tenant
        )
        await self.broker.publish(topic, e.to_json().encode())

    async def deliver_batch(self, batch: MeasurementBatch) -> int:
        if not self.publish_measurement_batches:
            return 0
        return await super().deliver_batch(batch)


class SearchIndexConnector(OutboundConnector):
    """Local search indexer — the Solr-indexer analog (reference:
    solr outbound connector [U]) without an external service: events index
    into an in-proc inverted index, queryable by term with AND semantics.

    Segment design (bounded memory, columnar-friendly): each delivered
    MeasurementBatch becomes ONE segment carrying the batch's columns plus
    a per-unique-(device,name) term map; object events batch into small
    segments. Queries walk segments newest-first; eviction drops whole
    segments (no per-doc index surgery). Terms are lowercase
    whitespace/punct-split tokens of device token, measurement name,
    alert type/message, area/assignment tokens."""

    def __init__(self, name: str = "search", max_segments: int = 256, **kw) -> None:
        super().__init__(name, **kw)
        self.max_segments = max_segments
        self._segments: List[dict] = []  # newest last
        self.indexed = 0

    @staticmethod
    def _tokens(*fields: str) -> set:
        out: set = set()
        for f in fields:
            if not f:
                continue
            for t in str(f).lower().replace("-", " ").replace("/", " ") \
                    .replace(":", " ").replace("_", " ").split():
                out.add(t)
        return out

    def _push(self, seg: dict) -> None:
        self._segments.append(seg)
        if len(self._segments) > self.max_segments:
            del self._segments[: len(self._segments) - self.max_segments]

    async def deliver(self, e: DeviceEvent) -> None:
        terms = self._tokens(
            e.device_token,
            getattr(e, "name", ""),
            getattr(e, "alert_type", ""),
            getattr(e, "message", ""),
            e.area_token,
            e.assignment_token,
            e.EVENT_TYPE.value,
        )
        self._push({"kind": "event", "event": e, "terms": terms})
        self.indexed += 1

    async def deliver_batch(self, batch: MeasurementBatch) -> int:
        if self.filters:
            return await super().deliver_batch(batch)
        if batch.n == 0:
            return 0
        # one segment per batch: per-unique-pair terms → row indices, no
        # per-row Python (uniques come from the batch's cached indices)
        pair = batch.pair_codes()
        terms_by_pair: Dict[int, set] = {}
        rows_by_pair: Dict[int, list] = {}
        for code in np.unique(pair):
            sel = np.nonzero(pair == code)[0]
            rows_by_pair[int(code)] = sel
            i = sel[0]
            terms_by_pair[int(code)] = self._tokens(
                str(batch.device_tokens[i]), str(batch.names[i]),
                "measurement",
            )
        self._push({
            "kind": "batch", "batch": batch,
            "terms_by_pair": terms_by_pair, "rows_by_pair": rows_by_pair,
        })
        self.indexed += batch.n
        return batch.n

    def search(self, query: str, limit: int = 100) -> List[DeviceEvent]:
        """All-terms-must-match search, newest first."""
        want = self._tokens(query)
        if not want:
            return []
        out: List[DeviceEvent] = []
        for seg in reversed(self._segments):
            if len(out) >= limit:
                break
            if seg["kind"] == "event":
                if want <= seg["terms"]:
                    out.append(seg["event"])
                continue
            batch = seg["batch"]
            for code, terms in seg["terms_by_pair"].items():
                if not want <= terms:
                    continue
                rows = seg["rows_by_pair"][code]
                take = rows[: max(0, limit - len(out))]
                out.extend(batch.select(np.asarray(take)).to_events())
                if len(out) >= limit:
                    break
        return out[:limit]


class QueueConnector(OutboundConnector):
    """Generic queue bridge — the SQS/EventHub/RabbitMQ-connector analog.
    Two backends share the connector:

    - ``bus``: republish onto a named in-proc bus topic (columnar batches
      forwarded as-is — zero-copy fan-out to any in-process consumer);
    - ``amqp``: publish event JSON to a queue over a REAL AMQP 0-9-1
      socket via the in-repo protocol client (``comm.amqp``)."""

    def __init__(
        self,
        name: str,
        backend: str = "bus",
        bus: Optional[EventBus] = None,
        topic: str = "sitewhere.outbound",
        host: str = "127.0.0.1",
        port: int = 5672,
        queue: str = "sitewhere.outbound",
        **kw,
    ) -> None:
        super().__init__(name, **kw)
        if backend not in ("bus", "amqp"):
            raise ValueError(f"unknown queue backend '{backend}'")
        if backend == "bus" and bus is None:
            raise ValueError("bus backend needs a bus")
        self.backend = backend
        self.bus = bus
        self.topic = topic
        self.host, self.port, self.queue = host, port, queue
        self._amqp = None
        self._amqp_lock = asyncio.Lock()  # one dial/drop at a time: the
        # base class runs deliveries concurrently, and a double-connect
        # would leak the overwritten client's socket + read loop

    async def on_stop(self) -> None:
        await self._drop_amqp(None)

    async def _drop_amqp(self, failed) -> None:
        """Close + clear the current client — but only if it IS the one
        that failed (None = unconditional, for shutdown). A concurrent
        delivery may already have re-dialed; its healthy client must not
        be torn down by a late-arriving error from the old one."""
        async with self._amqp_lock:
            if failed is not None and self._amqp is not failed:
                client = failed  # stale: close it, keep the current one
            else:
                client, self._amqp = self._amqp, None
        if client is not None:
            try:
                await client.close()
            except Exception:  # noqa: BLE001 - already broken
                pass

    async def _amqp_client(self):
        async with self._amqp_lock:
            if self._amqp is None:
                from sitewhere_tpu.comm.amqp import AmqpClient

                client = await asyncio.wait_for(
                    AmqpClient(self.host, self.port).connect(), 10.0
                )
                try:
                    await client.queue_declare(self.queue)
                except BaseException:
                    await client.close()
                    raise
                self._amqp = client
            return self._amqp

    async def deliver(self, e: DeviceEvent) -> None:
        if self.backend == "bus":
            await self.bus.publish(self.topic, e)
            return
        client = await self._amqp_client()
        try:
            await client.publish(self.queue, e.to_json().encode())
        except Exception:
            await self._drop_amqp(client)  # close + reconnect next delivery
            raise

    async def deliver_batch(self, batch: MeasurementBatch) -> int:
        if self.filters:
            return await super().deliver_batch(batch)
        if self.backend == "bus":
            # columnar fast path: the batch rides the topic unchanged
            await self.bus.publish(self.topic, batch)
            return batch.n
        # AMQP wire is per-message JSON: one compact message per row
        client = await self._amqp_client()
        n = 0
        try:
            for e in batch.to_events():
                await client.publish(self.queue, e.to_json().encode())
                n += 1
        except Exception:
            await self._drop_amqp(client)
            raise
        return n


class WebhookConnector(OutboundConnector):
    """HTTP POST per event via aiohttp (gated on a reachable endpoint)."""

    def __init__(self, name: str, url: str, timeout_s: float = 5.0, **kw) -> None:
        super().__init__(name, **kw)
        self.url = url
        self.timeout_s = timeout_s
        self._session = None

    async def on_start(self) -> None:
        import aiohttp

        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=self.timeout_s)
        )

    async def on_stop(self) -> None:
        if self._session:
            await self._session.close()
            self._session = None

    async def deliver(self, e: DeviceEvent) -> None:
        assert self._session is not None, "connector not started"
        async with self._session.post(self.url, json=e.to_dict()) as resp:
            resp.raise_for_status()


class CallbackConnector(OutboundConnector):
    """Invokes a user coroutine per event (the Groovy-scripted analog)."""

    def __init__(
        self, name: str, fn: Callable[[DeviceEvent], Awaitable[None]], **kw
    ) -> None:
        super().__init__(name, **kw)
        self._fn = fn

    async def deliver(self, e: DeviceEvent) -> None:
        await self._fn(e)


class OutboundDispatcher(LifecycleComponent):
    """Per-tenant stage: persisted-events → every registered connector."""

    def __init__(
        self,
        tenant: str,
        bus: EventBus,
        connectors: Optional[Sequence[OutboundConnector]] = None,
        metrics: Optional[MetricsRegistry] = None,
        poll_batch: int = 4096,
        policy: Optional[FaultTolerancePolicy] = None,
        tracer=None,
        overload=None,
    ) -> None:
        super().__init__(f"outbound-connectors[{tenant}]")
        self.tenant = tenant
        self.bus = bus
        self.metrics = metrics or MetricsRegistry()
        self.poll_batch = poll_batch
        self.policy = policy
        self.tracer = tracer
        # overload control: expired measurement batches skip connector
        # fan-out (count-only — they are already persisted), and the
        # 'pause_fanout' degradation rung pauses measurement fan-out
        # entirely while engaged. The terminal span still records either
        # way so tail sampling can seal the trace.
        self.overload = overload
        from sitewhere_tpu.runtime.overload import DeadlineGate
        from sitewhere_tpu.runtime.tracing import StageTimer

        self.deadline_gate = DeadlineGate(
            bus, tenant, "outbound", self.metrics, tracer=tracer,
            controller=overload, route_payload=False,
        )

        # outbound is the TERMINAL stage: its span seals the trace and
        # triggers the tail-based sampling decision (runtime.tracing)
        self.stage_timer = StageTimer(tracer, self.metrics, tenant, "outbound")
        self._task: Optional[asyncio.Task] = None
        for c in connectors or []:
            self.add_child(c)

    @property
    def connectors(self) -> List[OutboundConnector]:
        return [c for c in self.children if isinstance(c, OutboundConnector)]

    def add_connector(self, c: OutboundConnector) -> None:
        self.add_child(c)
        self._bind_connector(c)

    def _bind_connector(self, c: OutboundConnector) -> None:
        """Give one connector its retry budget, breaker, and per-connector
        DLQ (``dead-letter.outbound.<connector_id>``). Requeued entries
        re-enter at the persisted-events topic — the normal path."""
        if self.policy is None or c._ft is not None:
            return
        c.bind_fault_tolerance(
            RetryingConsumer(
                self.bus, self.tenant, f"outbound.{c.connector_id}",
                self.group, policy=self.policy, metrics=self.metrics,
                tracer=self.tracer,
            ),
            CircuitBreaker(
                f"outbound[{self.tenant}].{c.connector_id}",
                policy=self.policy, metrics=self.metrics,
            ),
            self.bus.naming.persisted_events(self.tenant),
        )

    @property
    def group(self) -> str:
        return f"outbound-connectors[{self.tenant}]"

    async def on_start(self) -> None:
        for c in self.connectors:
            self._bind_connector(c)
        self.bus.subscribe(
            self.bus.naming.persisted_events(self.tenant), self.group
        )
        self._task = asyncio.create_task(self._run(), name=self.name)

    async def on_stop(self) -> None:
        await cancel_and_wait(self._task)
        self._task = None

    async def _run(self) -> None:
        import time as _time

        src = self.bus.naming.persisted_events(self.tenant)
        delivered = self.metrics.counter("outbound.delivered")
        skipped = self.metrics.counter("outbound.skipped_degraded")
        egress = self.metrics.histogram("pipeline.egress", unit="s")
        while True:
            items = await self.bus.consume(src, self.group, self.poll_batch)
            for item in items:
                t0 = _time.time() * 1000.0
                shed_fanout = False
                if isinstance(item, MeasurementBatch):
                    shed_fanout = self.deadline_gate.check(item) or (
                        self.overload is not None
                        and self.overload.degraded(
                            self.tenant, "pause_fanout"
                        )
                    )
                if shed_fanout:
                    # fan-out shed (expired or degraded): no connector
                    # work, but the TERMINAL span must still seal the
                    # trace or tail sampling would idle-time-out it
                    skipped.inc(item.n)
                    self.stage_timer.observe(
                        item, t0, _time.time() * 1000.0, n_events=item.n,
                        delivered=0, shed="overload",
                    )
                    continue
                if isinstance(item, MeasurementBatch):
                    results = await asyncio.gather(
                        *(c.process_batch(item) for c in self.connectors)
                    )
                    n_del = sum(results)
                    delivered.inc(n_del)
                    n = item.n
                else:
                    results = await asyncio.gather(
                        *(c.process(item) for c in self.connectors)
                    )
                    n_del = sum(bool(r) for r in results)
                    delivered.inc(n_del)
                    n = 1
                self.stage_timer.observe(
                    item, t0, _time.time() * 1000.0, n_events=n,
                    delivered=n_del,
                )
                if isinstance(item, MeasurementBatch) and item.t_scored:
                    # published on scored-events → the last connector
                    # is done with the batch: persist and the slowest
                    # connector lie inside (the rules fork runs beside)
                    egress.record(_time.perf_counter() - item.t_scored)
