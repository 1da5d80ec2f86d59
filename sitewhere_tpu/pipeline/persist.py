"""Event persistence stage: scored-events → EventStore → outbound-events.

Capability parity with the reference's event-persistence pipeline inside
service-event-management (batch insert loop → TSDB → re-emit enriched
events to the outbound topic for rules/connectors — SURVEY.md §3.1 [U];
reference mount empty, see provenance banner).
"""

from __future__ import annotations

import asyncio
from typing import Optional

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.runtime.bus import EventBus, RetryingConsumer
from sitewhere_tpu.runtime.config import FaultTolerancePolicy
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.services.event_store import EventStore


class EventPersistence(LifecycleComponent):
    """Per-tenant persistence stage."""

    def __init__(
        self,
        tenant: str,
        bus: EventBus,
        store: EventStore,
        metrics: Optional[MetricsRegistry] = None,
        poll_batch: int = 4096,
        policy: Optional[FaultTolerancePolicy] = None,
        tracer=None,
        overload=None,
    ) -> None:
        super().__init__(f"event-persistence[{tenant}]")
        self.tenant = tenant
        self.bus = bus
        self.store = store
        self.metrics = metrics or MetricsRegistry()
        self.poll_batch = poll_batch
        from sitewhere_tpu.runtime.overload import DeadlineGate
        from sitewhere_tpu.runtime.tracing import StageTimer

        self.stage_timer = StageTimer(
            tracer, self.metrics, tenant, "persistence"
        )
        # the store is the system of record: by default the gate only
        # OBSERVES lateness here (pipeline_deadline_late_total) — an
        # admitted event that made it this far persists regardless
        # (at-least-once beats deadline at the store boundary) unless
        # the tenant opted into strict mode
        pol = overload.policy_for(tenant) if overload is not None else None
        self.deadline_gate = DeadlineGate(
            bus, tenant, "persistence", self.metrics, tracer=tracer,
            controller=overload,
            drop=bool(pol.drop_expired_at_persist) if pol else False,
        )
        self.retry = RetryingConsumer(
            bus, tenant, "persistence", self.group,
            policy=policy, metrics=self.metrics, tracer=tracer,
        )
        # hoisted out of the per-item handler (hot path)
        self._out_topic = bus.naming.persisted_events(tenant)
        self._persisted = self.metrics.counter("event_management.persisted")
        # replay-to-rescore output: rows that are ALREADY rows of this
        # store come back around with fresh scores (pipeline/replay.py);
        # appending them again would duplicate history
        self._replay_rescored = self.metrics.counter(
            "replay_rescored_total", tenant=tenant
        )
        self._task: Optional[asyncio.Task] = None

    @property
    def group(self) -> str:
        return f"event-persistence[{self.tenant}]"

    async def on_start(self) -> None:
        self.bus.subscribe(self.bus.naming.scored_events(self.tenant), self.group)
        self._task = asyncio.create_task(self._run(), name=self.name)

    async def on_stop(self) -> None:
        await cancel_and_wait(self._task)
        self._task = None

    async def _run(self) -> None:
        await self.retry.run(
            self.bus.naming.scored_events(self.tenant),
            self._handle,
            self.poll_batch,
        )

    async def _handle(self, item) -> None:
        import time as _time

        if isinstance(item, MeasurementBatch) and "replay" in item.trace:
            # replayed rescore batch: its rows are the store's own rows
            # riding the scoring path again (docs/STORAGE.md "Replay").
            # Never re-append (zero duplicate history) and never re-fan
            # downstream (rules/outbound already fired on the original
            # pass; the scored topic carried the fresh scores to any
            # subscriber that wants them). The fresh scores DO write
            # back onto the sealed rows (copy-on-write overlays), so a
            # later rescore job's only_unscored dedupe skips them — no
            # re-publish of already-rescored history. Counted so
            # store ∪ replay accounting stays exact.
            if item.scores is not None and item.event_ids is not None:
                self.store.measurements.write_back_scores(
                    item.event_ids, item.scores
                )
            self._replay_rescored.inc(item.n)
            return
        if self.deadline_gate.check(item):
            return  # strict mode only; default gate never drops here
        t0 = _time.time() * 1000.0
        if isinstance(item, MeasurementBatch):
            # columnar fast path: ONE append + ONE re-publish per batch
            self.store.add_measurement_batch(item)
            self._persisted.inc(item.n)
            self.stage_timer.observe(
                item, t0, _time.time() * 1000.0, n_events=item.n
            )
            item.mark("persisted")
            await self.retry.publish(self._out_topic, item)
        else:
            self.store.add_event(item)
            self._persisted.inc()
            self.stage_timer.observe(item, t0, _time.time() * 1000.0)
            item.mark("persisted")
            await self.retry.publish(self._out_topic, item)
