"""Inbound processing: validate, enrich, re-emit for scoring/persistence.

Capability parity with the reference's service-inbound-processing (consume
decoded events; look up device + active assignment via device-management;
route unregistered devices to the registration topic; re-emit enriched
events — SURVEY.md §2.2/§3.1 [U]; reference mount empty, see provenance
banner).

Redesign: the lookup is an in-proc call into the tenant's
``DeviceManagement`` store (the reference pays a cached gRPC hop here);
enriched requests are materialized into typed events
(``core.events``) with the assignment/area/asset context attached, and
published to the inbound-events topic that the tpu-inference stage consumes.
"""

from __future__ import annotations

import asyncio
from typing import Dict, Optional

import numpy as np

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.core.events import (
    DeviceEvent,
    event_from_dict,
    now_ms,
)
from sitewhere_tpu.runtime.bus import EventBus, RetryingConsumer
from sitewhere_tpu.runtime.config import FaultTolerancePolicy
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait
from sitewhere_tpu.runtime.loopledger import spanned
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.services.device_management import DeviceManagement


class InboundProcessor(LifecycleComponent):
    """Per-tenant inbound stage: decoded-events → inbound-events."""

    def __init__(
        self,
        tenant: str,
        bus: EventBus,
        device_management: DeviceManagement,
        metrics: Optional[MetricsRegistry] = None,
        poll_batch: int = 1024,
        policy: Optional[FaultTolerancePolicy] = None,
        tracer=None,
        overload=None,
    ) -> None:
        super().__init__(f"inbound-processing[{tenant}]")
        self.tenant = tenant
        self.bus = bus
        self.dm = device_management
        self.metrics = metrics or MetricsRegistry()
        self.poll_batch = poll_batch
        self.tracer = tracer
        from sitewhere_tpu.runtime.overload import DeadlineGate
        from sitewhere_tpu.runtime.tracing import StageTimer

        self.stage_timer = StageTimer(tracer, self.metrics, tenant, "inbound")
        # overload control: expired work drops to the tenant's expired
        # topic here, before device lookups and the TPU leg spend on it
        self.deadline_gate = DeadlineGate(
            bus, tenant, "inbound", self.metrics, tracer=tracer,
            controller=overload,
        )
        self.retry = RetryingConsumer(
            bus, tenant, "inbound", self.group, policy=policy,
            metrics=self.metrics, tracer=tracer,
        )
        self._task: Optional[asyncio.Task] = None

    @property
    def group(self) -> str:
        return f"inbound-processing[{self.tenant}]"

    async def on_start(self) -> None:
        self.bus.subscribe(self.bus.naming.decoded_events(self.tenant), self.group)
        self._task = asyncio.create_task(self._run(), name=self.name)

    async def on_stop(self) -> None:
        await cancel_and_wait(self._task)
        self._task = None

    async def _run(self) -> None:
        # at-least-once: each item runs under the stage retry budget;
        # exhausted/poison items dead-letter instead of vanishing
        await self.retry.run(
            self.bus.naming.decoded_events(self.tenant),
            self._handle,
            self.poll_batch,
        )

    @spanned("intake")
    async def _handle(self, req) -> None:
        if self.deadline_gate.check(req):
            return  # expired: routed to the expired topic, budget saved
        if isinstance(req, MeasurementBatch):
            await self.process_batch(req)
        else:
            await self.process_request(req)

    async def process_batch(self, batch: MeasurementBatch) -> Optional[MeasurementBatch]:
        """Columnar fast path: validate/enrich a whole batch with ONE
        device+assignment lookup per unique device, not per row."""
        processed = self.metrics.counter("inbound.processed")
        unregistered = self.metrics.counter("inbound.unregistered")
        rejected = self.metrics.counter("inbound.rejected")
        import time as _time

        t0 = _time.time() * 1000.0
        if (
            batch.trace_ctx is None
            and self.tracer is not None
            and self.tracer.enabled_for(self.tenant)
        ):
            # netbus-published batches enter decoded-events without a
            # context (remote producer may predate tracing) — mint here so
            # the rest of the pipeline still traces them
            batch.trace_ctx = self.tracer.mint(
                self.tenant, source_topic="bus"
            )

        tokens = batch.device_tokens
        uniq, inverse = batch.token_index()
        asg_by_u = np.empty((len(uniq),), object)
        area_by_u = np.empty((len(uniq),), object)
        status = np.zeros((len(uniq),), np.int8)  # 0 ok, 1 unknown, 2 no-asg
        for i, tok in enumerate(uniq):
            if self.dm.get_device(str(tok)) is None:
                status[i] = 1
                asg_by_u[i] = area_by_u[i] = ""
                continue
            a = self.dm.active_assignment_for(str(tok))
            if a is None:
                status[i] = 2
                asg_by_u[i] = area_by_u[i] = ""
            else:
                asg_by_u[i] = a.token
                area_by_u[i] = a.area_token
        row_status = status[inverse]
        unknown_rows = np.nonzero(row_status == 1)[0]
        if unknown_rows.size:
            # unknown devices route to registration (low volume: one request
            # per unique unknown device, not per row — registration is
            # idempotent on the token)
            seen: set = set()
            for i in unknown_rows:
                tok = str(tokens[i])
                if tok in seen:
                    continue
                seen.add(tok)
                await self.bus.publish(
                    self.bus.naming.unregistered_devices(self.tenant),
                    {
                        "type": "measurement",
                        "device_token": tok,
                        "name": str(batch.names[i]) if batch.names is not None else "",
                        "value": float(batch.values[i]),
                        "event_ts": int(batch.event_ts[i]),
                    },
                )
            unregistered.inc(unknown_rows.size)
        rejected.inc(int((row_status == 2).sum()))
        keep = np.nonzero(row_status == 0)[0]
        if keep.size == 0:
            return None
        out = batch if keep.size == batch.n else batch.select(keep)
        out.assignment_tokens = asg_by_u[inverse][keep] if keep.size != batch.n \
            else asg_by_u[inverse]
        out.area_tokens = area_by_u[inverse][keep] if keep.size != batch.n \
            else area_by_u[inverse]
        self.stage_timer.observe(
            out, t0, _time.time() * 1000.0, n_events=int(keep.size),
            unregistered=int(unknown_rows.size),
        )
        out.mark("inbound")
        await self.bus.publish(self.bus.naming.inbound_events(self.tenant), out)
        processed.inc(keep.size)
        return out

    async def process_request(self, req: Dict) -> Optional[DeviceEvent]:
        """Process one decoded request; returns the enriched event if one
        was emitted (None for registrations / rejects)."""
        processed = self.metrics.counter("inbound.processed")
        unregistered = self.metrics.counter("inbound.unregistered")
        rejected = self.metrics.counter("inbound.rejected")

        rtype = req.get("type", "measurement")
        if rtype == "register":
            await self.bus.publish(
                self.bus.naming.unregistered_devices(self.tenant), req
            )
            unregistered.inc()
            return None

        device_token = req.get("device_token", "")
        device = self.dm.get_device(device_token)
        if device is None:
            # unknown device → registration pipeline decides (SURVEY.md §3.1)
            await self.bus.publish(
                self.bus.naming.unregistered_devices(self.tenant), dict(req)
            )
            unregistered.inc()
            return None
        assignment = self.dm.active_assignment_for(device_token)
        if assignment is None:
            rejected.inc()
            return None

        import time as _time

        t0 = _time.time() * 1000.0
        enriched = dict(req)
        enriched.pop("_source", None)
        trace_ctx = enriched.pop("_trace", None)
        deadline = enriched.pop("_deadline", None)
        enriched["tenant"] = self.tenant
        enriched["assignment_token"] = assignment.token
        enriched["area_token"] = assignment.area_token
        enriched["asset_token"] = assignment.asset_token
        enriched["customer_token"] = assignment.customer_token
        enriched.setdefault("received_ts", now_ms())
        try:
            event = event_from_dict(enriched)
        except (ValueError, KeyError):
            rejected.inc()
            return None
        event.trace_ctx = trace_ctx
        if deadline is not None:
            event.deadline_ms = float(deadline)
        self.stage_timer.observe(event, t0, _time.time() * 1000.0)
        event.mark("inbound")
        await self.bus.publish(
            self.bus.naming.inbound_events(self.tenant), event
        )
        processed.inc()
        return event
