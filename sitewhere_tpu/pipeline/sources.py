"""Event sources: protocol termination → decode → decoded-events topic.

Capability parity with the reference's service-event-sources
(``IInboundEventSource``/``IInboundEventReceiver`` + decoder chain; MQTT/
AMQP/CoAP/WebSocket receivers — SURVEY.md §2.2/§3.1 [U]; reference mount
empty, see provenance banner).

Redesign: receivers push raw payloads into an asyncio queue; an
``EventSource`` drains the queue, decodes, dedups, and publishes request
dicts to the tenant's decoded-events topic (failed decodes go to the
failed-decode topic with the raw payload attached). Network receivers are
pluggable: the in-proc queue the MQTT simulator (``sim.devices``) feeds,
and ``MqttReceiver`` — a real-socket MQTT 3.1.1 subscriber built on the
in-repo wire-protocol client (``comm.mqtt``).
"""

from __future__ import annotations

import asyncio
import base64
import json
import time
from typing import Any, Dict, List, Optional

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.core.events import now_ms
from sitewhere_tpu.pipeline.decoders import (
    Deduplicator,
    EventDecoder,
    get_decoder,
)
from sitewhere_tpu.runtime.bus import EventBus, RetryingConsumer
from sitewhere_tpu.runtime.config import FaultTolerancePolicy
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait
from sitewhere_tpu.runtime.loopledger import sw
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.overload import (
    PRIORITY_NAMES,
    PriorityClassQueue,
    classify_priority,
)


class InboundReceiver(LifecycleComponent):
    """Base receiver: produces (payload: bytes, context: dict) pairs.

    Admission control (runtime.overload): the queue is priority-classed
    (alerts > commands > measurements, classified from cheap context
    hints). Under burst the lowest class sheds first at its fill
    watermark — a measurement flood can never evict an alert — and the
    measurement watermark shrinks with the tenant's credit signal when
    downstream stages lag (cooperative intake throttle)."""

    def __init__(self, name: str) -> None:
        super().__init__(name)
        self.queue = PriorityClassQueue(maxsize=65536)
        self.queue.on_shed = self._on_shed
        self.shed_total = 0
        # EventSource attaches the instance registry so sheds surface as
        # ``receiver_shed_total`` on the normal /metrics scrape
        self.metrics: Optional[MetricsRegistry] = None
        # EventSource installs a richer hook (tenant-labeled counters +
        # tail-trace visibility) on top of the local accounting
        self.shed_hook = None
        # set by EventSource when the tenant has tracing enabled: payloads
        # get a receive stamp so the decode span's queue-wait (time spent
        # in this receiver queue) is measurable. Guarded — an untraced
        # tenant's submit path stays allocation-identical to before.
        self.stamp_recv_ts = False

    def _on_shed(self, priority: int, n: int) -> None:
        self.shed_total += n
        if self.metrics is not None:
            self.metrics.counter("receiver_shed_total").inc(n)
        if self.shed_hook is not None:
            self.shed_hook(priority, n)

    async def submit(self, payload: bytes, **context: Any) -> None:
        # broker delivery, on the flush record's clock: where the
        # ``pipeline.intake`` interval of the payload's batch starts
        context["_recv_pc"] = time.perf_counter()
        if self.stamp_recv_ts:
            context["_recv_t"] = time.time() * 1000.0
        await self.queue.put(
            (payload, context), classify_priority(context)
        )

    def submit_nowait(self, payload: bytes, **context: Any) -> None:
        """Non-blocking submit for network receiver loops. A full class
        watermark sheds the OLDEST queued payload of the lowest present
        class (newest data wins under burst — counted, never raised
        into the receiver loop)."""
        context["_recv_pc"] = time.perf_counter()
        if self.stamp_recv_ts:
            context["_recv_t"] = time.time() * 1000.0
        self.queue.put_nowait((payload, context), classify_priority(context))


class QueueReceiver(InboundReceiver):
    """In-proc receiver — the broker-less MQTT stand-in the simulator and
    tests feed directly. ``topic`` context mimics an MQTT topic string."""


class MqttReceiver(InboundReceiver):
    """MQTT receiver over a REAL socket: connects to any MQTT 3.1.1
    broker (external, or the in-repo ``comm.mqtt.MqttBroker``) with the
    in-repo wire-protocol client — no third-party MQTT stack needed."""

    def __init__(self, name: str, host: str = "localhost", port: int = 1883,
                 topics: Optional[List[str]] = None, qos: int = 0,
                 username: str = "", password: str = "") -> None:
        super().__init__(name)
        self.host, self.port = host, port
        self.topics = topics or ["sitewhere/input/#"]
        self.qos = qos
        self.username, self.password = username, password
        self._client = None

    async def on_start(self) -> None:
        from sitewhere_tpu.comm.mqtt import MqttClient

        client = MqttClient(self.host, self.port, client_id=self.name,
                            username=self.username, password=self.password)
        await client.connect()

        async def on_message(topic: str, payload: bytes) -> None:
            await self.submit(payload, topic=topic)

        for t in self.topics:
            await client.subscribe(t, on_message, qos=self.qos)
        self._client = client

    async def on_stop(self) -> None:
        if self._client is not None:
            await self._client.disconnect()
            self._client = None


class AmqpReceiver(InboundReceiver):
    """AMQP 0-9-1 receiver over a real socket (reference: RabbitMQ
    receivers in service-event-sources [U]): consumes wire payloads from
    the named queues with the in-repo protocol client (``comm.amqp``)."""

    def __init__(self, name: str, host: str = "localhost", port: int = 5672,
                 queues: Optional[List[str]] = None) -> None:
        super().__init__(name)
        self.host, self.port = host, port
        self.queues = queues or ["sitewhere.input"]
        self._client = None

    async def on_start(self) -> None:
        from sitewhere_tpu.comm.amqp import AmqpClient

        client = await AmqpClient(self.host, self.port).connect()

        async def on_message(body: bytes, queue: str) -> None:
            await self.submit(body, topic=f"amqp/{queue}")

        try:
            for q in self.queues:
                await client.queue_declare(q)
                await client.consume(q, on_message)
        except BaseException:
            # a failed subscribe must not leak the connected client (a
            # retrying supervisor would accumulate sockets)
            await client.close()
            raise
        self._client = client

    async def on_stop(self) -> None:
        if self._client is not None:
            await self._client.close()
            self._client = None


class SocketReceiver(InboundReceiver):
    """Raw TCP socket termination (reference: raw socket receivers in
    service-event-sources [U]): devices connect and send length-prefixed
    wire payloads (4-byte big-endian length + body, the simplest framing
    a constrained device can emit). Each frame is one payload for the
    tenant's decoder."""

    MAX_FRAME = 16 * 1024 * 1024

    def __init__(self, name: str, host: str = "127.0.0.1", port: int = 0) -> None:
        super().__init__(name)
        self.host, self.port = host, port
        self.bound_port = None
        self._server = None
        self._conns: set = set()

    async def on_start(self) -> None:
        self._server = await asyncio.start_server(
            self._serve, self.host, self.port
        )
        self.bound_port = self._server.sockets[0].getsockname()[1]

    async def on_stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # connections first: since Python 3.12.1 wait_closed() waits
            # for every accepted connection to drop, so stopping with a
            # client still attached would never return
            for t in list(self._conns):
                await cancel_and_wait(t)
            await self._server.wait_closed()
            self._server = None

    async def _serve(self, reader, writer) -> None:
        task = asyncio.current_task()
        self._conns.add(task)
        peer = writer.get_extra_info("peername")
        try:
            while True:
                head = await reader.readexactly(4)
                n = int.from_bytes(head, "big")
                if n == 0 or n > self.MAX_FRAME:
                    return  # malformed framing: drop the connection
                payload = await reader.readexactly(n)
                await self.submit(payload, topic=f"socket/{peer}")
        except (asyncio.IncompleteReadError, ConnectionResetError):
            return
        finally:
            self._conns.discard(task)
            writer.close()


class EventSource(LifecycleComponent):
    """One (receiver, decoder) pair publishing decoded event requests."""

    def __init__(
        self,
        source_id: str,
        tenant: str,
        bus: EventBus,
        receiver: InboundReceiver,
        decoder: EventDecoder | str = "json",
        metrics: Optional[MetricsRegistry] = None,
        dedup: bool = True,
        policy: Optional[FaultTolerancePolicy] = None,
        tracer=None,
        overload=None,
    ) -> None:
        super().__init__(f"event-source[{source_id}]")
        self.source_id = source_id
        self.tenant = tenant
        self.bus = bus
        self.receiver = receiver
        self.decoder = get_decoder(decoder) if isinstance(decoder, str) else decoder
        self.metrics = metrics or MetricsRegistry()
        self.dedup = Deduplicator() if dedup else None
        self._pump: Optional[asyncio.Task] = None
        receiver.metrics = self.metrics
        # overload control (runtime.overload.OverloadController | None):
        # admission watermarks + credit feedback on the receiver queue,
        # and the deadline budget stamped onto every accepted payload
        self.overload = overload
        self.metrics.describe(
            "pipeline_shed_total",
            "payloads shed at receiver admission, per tenant and "
            "priority class",
        )
        receiver.shed_hook = self._shed_hook
        if overload is not None:
            pol = overload.policy_for(tenant)
            if pol is not None:
                receiver.queue.fill = [
                    pol.shed_alerts_fill,
                    pol.shed_commands_fill,
                    pol.shed_measurements_fill,
                ]
                receiver.queue.credit_fn = lambda: overload.credit(tenant)
            if overload.deadline_ms(tenant) is not None:
                # the deadline budget is anchored at ADMISSION (receiver
                # enqueue), not decode — without the receive stamp a
                # decode-bound pump would grant queue-aged payloads the
                # full budget and the bounded-latency guarantee would
                # have a blind spot upstream of the bus lag signal
                receiver.stamp_recv_ts = True
        # THE trace mint edge: every ingest transport (in-proc broker,
        # real MQTT, HTTP, WS, CoAP, socket) funnels payloads through a
        # receiver into this source, so minting here covers them all
        self.tracer = tracer
        from sitewhere_tpu.runtime.tracing import StageTimer

        self.stage_timer = StageTimer(tracer, self.metrics, tenant, "decode")
        if tracer is not None and tracer.enabled_for(tenant):
            receiver.stamp_recv_ts = True
        # decode is the first at-least-once stage: publishes ride a retry
        # budget; undecodable payloads dead-letter to failed-decode
        self.retry = RetryingConsumer(
            bus, tenant, "decode", f"event-source[{source_id}]",
            policy=policy, metrics=self.metrics, tracer=tracer,
        )
        self.add_child(receiver)

    _last_shed_trace = 0.0

    def _shed_hook(self, priority: int, n: int) -> None:
        """Receiver sheds become observable: tenant+class-labeled
        counters always, plus a retained 'shed' trace (tail sampling)
        at most once per second per source — receiver shedding used to
        be invisible to tracing entirely."""
        self.metrics.counter(
            "pipeline_shed_total",
            tenant=self.tenant, priority=PRIORITY_NAMES[priority],
        ).inc(n)
        if self.overload is not None:
            self.overload.note_shed(self.tenant, n)
        tracer = self.tracer
        if tracer is None or not tracer.enabled_for(self.tenant):
            return
        now = time.time()
        if now - self._last_shed_trace < 1.0:
            return
        self._last_shed_trace = now
        ctx = tracer.mint(self.tenant, source_topic=f"shed:{self.source_id}")
        if ctx is not None:
            tracer.mark_hit(ctx, "shed")
            tracer.record_span(
                ctx, "receiver", now * 1000.0, now * 1000.0,
                n_events=n, terminal=True,
                priority=PRIORITY_NAMES[priority],
            )

    async def on_start(self) -> None:
        self._pump = asyncio.create_task(
            self._run(), name=f"pump:{self.name}"
        )

    async def on_stop(self) -> None:
        await cancel_and_wait(self._pump)
        self._pump = None

    # per-cycle caps → bound the columnar batch size. DRAIN caps raw
    # payloads; EVENT_CAP caps decoded EVENTS, so bulk/burst wire messages
    # (100s of samples each) can't snowball into monster batches that
    # destabilize downstream flush sizing
    DRAIN = 8192
    EVENT_CAP = 32768

    async def _run(self) -> None:
        decoded_topic = self.bus.naming.decoded_events(self.tenant)
        failed_topic = self.bus.naming.failed_decode(self.tenant)
        received = self.metrics.counter("event_sources.received")
        decoded_ctr = self.metrics.counter("event_sources.decoded")
        failed = self.metrics.counter("event_sources.failed_decode")
        duped = self.metrics.counter("event_sources.deduplicated")
        q = self.receiver.queue
        while True:
            # block for the first payload, then drain whatever is queued —
            # the columnar fast path forms one MeasurementBatch per cycle
            # instead of publishing per-event objects (SURVEY.md §7 step 1).
            # Payloads decode AS they drain so the event cap can stop the
            # cycle mid-queue.
            measurements: list = []
            # columnar accumulators (zero-dict decode fast path)
            c_toks: list = []
            c_names: list = []
            c_vals: list = []
            c_ets: list = []
            # array-chunk accumulator (bulk binary wire: zero per-row work)
            np_chunks: list = []
            decode_any = getattr(self.decoder, "decode_any", None)
            n_payloads = 0
            n_events = 0
            now = 0  # stamped AFTER the blocking get — idle wait must not
            # count toward the rows' ingest latency

            async def report_failed(payload, context, exc) -> None:
                failed.inc()
                # failed-decode IS the decode stage's dead-letter topic:
                # carry the same stage/attempt metadata the uniform DLQ
                # entries do, so the REST surface lists them together.
                # Non-blocking like every DLQ write: an idle requeue
                # cursor must never backpressure the decode pump shut
                self.bus.publish_nowait(
                    failed_topic,
                    {
                        "stage": "decode",
                        "tenant": self.tenant,
                        "attempts": 1,  # decode is deterministic: poison
                        "source": self.source_id,
                        "error": f"{type(exc).__name__}: {exc}",
                        "payload_b64": base64.b64encode(payload).decode(),
                        "context": {k: str(v) for k, v in context.items()},
                        "ts": now,
                    },
                )

            item = await q.get()
            with sw("intake"):
                now = now_ms()
                first_context = item[1]  # decode-span baggage + queue wait
                while True:
                    payload, context = item
                    n_payloads += 1
                    try:
                        if decode_any is not None:
                            kind, out = decode_any(payload, context)
                        else:
                            kind, out = "requests", self.decoder.decode(payload, context)
                    except Exception as exc:  # noqa: BLE001 - any bad payload (incl.
                        # UnicodeDecodeError from garbled bytes) must not kill the pump
                        await report_failed(payload, context, exc)
                        kind, out = "requests", []
                    if kind == "columns":
                        toks, names, vals, ets = out
                        c_toks.extend(toks)
                        c_names.extend(names)
                        c_vals.extend(vals)
                        c_ets.extend(ets)
                        n_events += len(vals)
                    elif kind == "columns_np":
                        np_chunks.extend(out)
                        n_events += sum(len(c[2]) for c in out)
                    else:
                        n_events += len(out)
                        await self._route_requests(
                            out, measurements, decoded_topic, duped, decoded_ctr, now
                        )
                    if n_events >= self.EVENT_CAP or n_payloads >= self.DRAIN:
                        break
                    try:
                        item = q.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                received.inc(n_payloads)
                out_batches = []
                # batch construction must not kill the pump on one malformed
                # row (e.g. a string value the decoder didn't vet) — drop the
                # offending group to the failed topic instead
                if np_chunks:
                    try:
                        out_batches.append(MeasurementBatch.from_column_chunks(
                            self.tenant, np_chunks, received_ms=float(now),
                        ))
                    except Exception as exc:  # noqa: BLE001
                        await report_failed(b"<bulk chunk batch>", {}, exc)
                if c_vals:
                    try:
                        out_batches.append(MeasurementBatch.from_columns(
                            self.tenant, c_toks, c_names, c_vals, c_ets,
                            received_ms=float(now),
                        ))
                    except Exception as exc:  # noqa: BLE001
                        await report_failed(b"<columnar batch>", {}, exc)
                if measurements:
                    try:
                        out_batches.append(
                            MeasurementBatch.from_requests(self.tenant, measurements)
                        )
                    except Exception:  # noqa: BLE001 - salvage: re-try row by
                        # row so one bad request doesn't drop its whole group
                        good = []
                        for req in measurements:
                            try:
                                float(req.get("value", 0.0))
                                float(req.get("event_ts", now))
                                good.append(req)
                            except (TypeError, ValueError) as exc:
                                await report_failed(
                                    json.dumps(req, default=str).encode(), {}, exc
                                )
                        if good:
                            out_batches.append(
                                MeasurementBatch.from_requests(self.tenant, good)
                            )
                t_done = time.time() * 1000.0
                src_topic = str(first_context.get("topic", self.source_id))
                recv_t = first_context.get("_recv_t")
                queue_wait = max(0.0, float(now) - recv_t) if recv_t else 0.0
                traced = self.tracer is not None and self.tracer.enabled_for(
                    self.tenant
                )
                # admission deadline: accepted work gets `admission + budget`
                # from the tenant's OverloadPolicy — anchored at the receiver
                # enqueue stamp when present so receiver-queue wait spends
                # budget too; every downstream stage consults the remainder
                # (runtime.overload.DeadlineGate)
                budget = (
                    self.overload.deadline_ms(self.tenant)
                    if self.overload is not None
                    else None
                )
                deadline_base = float(recv_t) if recv_t else float(now)
                t_intake = first_context.get("_recv_pc", 0.0)
                for mb in out_batches:
                    mb.t_intake = t_intake
                    if budget is not None:
                        mb.deadline_ms = deadline_base + budget
                    if traced:
                        # mint at the edge; the context rides the batch through
                        # every stage (and over the netbus wire, pickled)
                        dev = (
                            str(mb.device_tokens[0])
                            if mb.device_tokens is not None and mb.n
                            else ""
                        )
                        mb.trace_ctx = self.tracer.mint(
                            self.tenant, device=dev, source_topic=src_topic,
                            # the admission class rides the context so the
                            # latency ledger cohorts by (tenant, priority)
                            priority=PRIORITY_NAMES[
                                classify_priority(first_context)
                            ],
                        )
                    # span recorded BEFORE the publish so the downstream
                    # stage's span parents under this one deterministically
                    self.stage_timer.observe(
                        mb, float(now), t_done, n_events=mb.n,
                        queue_wait_ms=queue_wait,
                    )
                    mb.mark("decoded")
                    await self.retry.publish(decoded_topic, mb)
                    decoded_ctr.inc(mb.n)

    async def _route_requests(
        self, reqs, measurements, decoded_topic, duped, decoded_ctr, now
    ) -> None:
        """Non-columnar requests: dedup, split measurements (batched later)
        from other event types (published as objects immediately)."""
        for req in reqs:
            rid = req.get("id")
            if self.dedup and rid and self.dedup.seen(str(rid)):
                duped.inc()
                continue
            req.setdefault("received_ts", now)
            if req.get("type", "measurement") == "measurement":
                measurements.append(req)
            else:
                req["_source"] = self.source_id
                if self.overload is not None:
                    budget = self.overload.deadline_ms(self.tenant)
                    if budget is not None:
                        # non-measurement events never expire (DeadlineGate
                        # skips them) but carry the stamp for observability
                        req["_deadline"] = float(now) + budget
                if "_trace" not in req and self.tracer is not None:
                    ev_type = str(req.get("type", ""))
                    ctx = self.tracer.mint(
                        self.tenant,
                        device=str(req.get("device_token", "")),
                        source_topic=self.source_id,
                        priority=(
                            "alert" if "alert" in ev_type else "command"
                        ),
                    )
                    if ctx is not None:  # None = tracing disabled: no key
                        req["_trace"] = ctx
                await self.retry.publish(decoded_topic, req)
                decoded_ctr.inc()


def make_source(
    source_id: str,
    tenant: str,
    bus: EventBus,
    decoder: str = "json",
    metrics: Optional[MetricsRegistry] = None,
) -> EventSource:
    """Convenience: an EventSource over a fresh QueueReceiver."""
    return EventSource(
        source_id, tenant, bus, QueueReceiver(f"recv[{source_id}]"), decoder, metrics
    )
