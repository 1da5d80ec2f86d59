"""SiteWhereInstance: one-process assembly of the whole platform.

Capability parity with the reference's service-instance-management
(instance bootstrap from templates: default tenant/users/datasets; instance
topology/status — SURVEY.md §2.2 [U]; reference mount empty, see provenance
banner) — plus the process-level redesign SURVEY.md §7 prescribes: instead
of 18 Spring Boot apps, ONE process hosts every service as lifecycle
components over the in-proc bus, with the TPU mesh shared by all tenants.

Per tenant, the instance wires the full §3.1 pipeline:

  sim/MQTT broker → EventSource → InboundProcessor → [tpu-inference] →
  EventPersistence → RuleEngine → OutboundDispatcher
                                → DeviceStateService
  + RegistrationService, CommandDelivery, BatchOperationManager,
    ScheduleManager, LabelGeneration, AssetManagement, StreamingMedia

Tenant lifecycle changes arrive via the tenant-model-updates topic
(TenantManagement.broadcast) and are applied by the instance's drain loop.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.pipeline.commands import (
    BrokerCommandDestination,
    CommandDelivery,
)
from sitewhere_tpu.pipeline.inbound import InboundProcessor
from sitewhere_tpu.pipeline.inference import TpuInferenceService
from sitewhere_tpu.pipeline.outbound import (
    LogConnector,
    MqttTopicConnector,
    OutboundDispatcher,
)
from sitewhere_tpu.pipeline.persist import EventPersistence
from sitewhere_tpu.pipeline.rules import (
    RuleEngine,
    anomaly_score_rule,
    threshold_rule,
)
from sitewhere_tpu.pipeline.sources import EventSource, QueueReceiver
from sitewhere_tpu.runtime.bus import EventBus, TopicNaming
from sitewhere_tpu.runtime.checkpoint import CheckpointManager
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    TenantEngineConfig,
    tenant_config_from_dict,
    tenant_config_from_template,
    tenant_config_to_dict,
)
from sitewhere_tpu.runtime.lifecycle import (
    LifecycleComponent,
    LifecycleState,
    cancel_and_wait,
)
from sitewhere_tpu.runtime.loopledger import sw
from sitewhere_tpu.runtime.metrics import MetricsRegistry
from sitewhere_tpu.runtime.tracing import Tracer
from sitewhere_tpu.services.asset_management import AssetManagement
from sitewhere_tpu.services.batch_operations import BatchOperationManager
from sitewhere_tpu.services.device_management import DeviceManagement
from sitewhere_tpu.services.device_state import DeviceStateService
from sitewhere_tpu.services.event_store import EventStore
from sitewhere_tpu.services.label_generation import LabelGeneration
from sitewhere_tpu.services.registration import RegistrationService
from sitewhere_tpu.services.schedule_management import ScheduleManager
from sitewhere_tpu.services.streaming_media import StreamingMedia
from sitewhere_tpu.services.tenant_management import TenantManagement
from sitewhere_tpu.services.user_management import (
    AUTH_ADMIN,
    UserManagement,
)
from sitewhere_tpu.sim.broker import SimBroker


def _count_by(values) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for v in values:
        out[v] = out.get(v, 0) + 1
    return out


@dataclass
class TenantRuntime:
    """Everything one tenant owns inside the instance."""

    tenant: str
    config: TenantEngineConfig
    device_management: DeviceManagement
    event_store: EventStore
    asset_management: AssetManagement
    labels: LabelGeneration
    media: StreamingMedia
    source: EventSource
    inbound: InboundProcessor
    persistence: EventPersistence
    rules: RuleEngine
    outbound: OutboundDispatcher
    state: DeviceStateService
    registration: RegistrationService
    commands: CommandDelivery
    batch: BatchOperationManager
    schedules: ScheduleManager
    broker_handler: object = None  # tenant input handler (for unsubscribe)
    media_pipeline: object = None  # MediaClassificationPipeline | None
    mqtt_source: object = None     # EventSource over a real MQTT socket
    search: object = None          # SearchIndexConnector | None

    def components(self) -> List[LifecycleComponent]:
        out = [
            self.source, self.inbound, self.persistence, self.rules,
            self.outbound, self.state, self.registration, self.commands,
            self.batch, self.schedules,
        ]
        if self.media_pipeline is not None:
            out.append(self.media_pipeline)
        if self.mqtt_source is not None:
            out.append(self.mqtt_source)
        return out


class SiteWhereInstance(LifecycleComponent):
    """The whole platform in one lifecycle tree."""

    def __init__(
        self,
        config: Optional[InstanceConfig] = None,
        mesh: Optional[MeshManager] = None,
        metrics: Optional[MetricsRegistry] = None,
        bus=None,
    ) -> None:
        cfg = config or InstanceConfig()
        super().__init__(f"instance[{cfg.instance_id}]")
        self.config = cfg
        self.metrics = metrics or MetricsRegistry()
        # pluggable bus backend: default in-proc; pass e.g. a connected
        # netbus.RemoteEventBus to run every service over a socket broker
        self.bus = bus or EventBus(TopicNaming(cfg.instance_id), cfg.bus_retention)
        if bus is not None and isinstance(
            getattr(bus, "metrics", None), MetricsRegistry
        ):
            # a remote bus client defaults to a private registry nothing
            # scrapes — rebind it so its reconnect/clamp counters ride
            # the instance /metrics endpoint
            bus.metrics = self.metrics
        self.broker = SimBroker()  # in-proc MQTT; external broker swaps in
        self.mesh = mesh or MeshManager(
            tenant=cfg.mesh.tenant_axis if cfg.mesh.tenant_axis > 1 else 0,
            data=cfg.mesh.data_axis if cfg.mesh.data_axis > 1 else 0,
            model=cfg.mesh.model_axis,
        )
        self.users = UserManagement()
        self.tenant_management = TenantManagement(self.bus)
        self.checkpoints = (
            CheckpointManager(cfg.data_dir) if cfg.checkpointing else None
        )
        # end-to-end tracing: ONE tracer shared by every stage of every
        # tenant; per-tenant knobs (enabled/sample_rate/slo_ms) register
        # from TenantEngineConfig.tracing at tenant build time
        self.tracer = Tracer(self.metrics)
        # overload control: ONE controller shared by every stage of every
        # tenant (admission deadlines, credit feedback from consumer lag,
        # degradation ladder) — per-tenant knobs come from
        # TenantEngineConfig.overload at tenant build time
        from sitewhere_tpu.runtime.overload import OverloadController

        self.overload = OverloadController(self.metrics, tracer=self.tracer)
        # flight recorder + metrics history + watchdog: the always-on
        # blackbox (per-flush/per-stage recent history, dump-on-incident)
        # and the 15-minute time-series memory its rules watch
        from sitewhere_tpu.runtime.flightrec import FlightRecorder
        from sitewhere_tpu.runtime.history import (
            WATCHDOG_REQUIRED,
            MetricsHistory,
            Watchdog,
        )

        self.flightrec = FlightRecorder()
        self.tracer.flightrec = self.flightrec  # SLO-breach snapshots
        from sitewhere_tpu.runtime.loopledger import GcAccount

        self._gc_account = GcAccount(self.metrics)
        # latency attribution (runtime.latency): the engine every tail
        # decision feeds — per-(tenant, priority) stage ledgers, p99
        # decomposition, SLO burn rates. Shared by the tracer (feed),
        # the watchdog (slo_burn rule), REST (/api/latency), and the
        # flight recorder (snapshot context)
        from sitewhere_tpu.runtime.latency import LatencyEngine

        self.latency = LatencyEngine(self.metrics)
        self.latency.tracer = self.tracer
        self.tracer.latency = self.latency
        self.flightrec.add_context(
            "latency", self.latency.snapshot_context
        )
        allowlist = (
            tuple(cfg.metrics_history_allowlist)
            if cfg.metrics_history_allowlist
            else None
        )
        if allowlist is not None and cfg.watchdog_enabled:
            # a trimmed allowlist must not starve the watchdog's rules
            # of the families they read — that would silently disable
            # every rule while the config still claims watchdog_enabled
            allowlist += tuple(
                n for n in WATCHDOG_REQUIRED if n not in allowlist
            )
        self.history = MetricsHistory(
            self.metrics,
            allowlist=allowlist,
            resolution_s=cfg.history_resolution_s,
        )
        # score-quality health (runtime.scorehealth): ONE account shared
        # by the scoring service (which feeds it device-side sketches)
        # and the watchdog (whose score rules stamp the drifting tenant's
        # active kernel variant into incident snapshots)
        from sitewhere_tpu.runtime.scorehealth import ScoreHealth

        self.scorehealth = ScoreHealth(self.metrics)
        self.watchdog = (
            Watchdog(
                self.metrics, self.history,
                flightrec=self.flightrec, tracer=self.tracer,
                scorehealth=self.scorehealth,
                latency=self.latency,
            )
            if cfg.watchdog_enabled
            else None
        )
        self.inference = TpuInferenceService(
            self.bus, self.mesh, self.metrics,
            slots_per_shard=cfg.mesh.slots_per_shard,
            max_inflight=cfg.inference_max_inflight,
            checkpoints=self.checkpoints,
            tracer=self.tracer,
            overload=self.overload,
            flightrec=self.flightrec,
            scorehealth=self.scorehealth,
        )
        # replay-to-rescore engine (pipeline/replay.py): streams the
        # segment store back through the live feed path as a low-priority
        # lane arbitrated by the overload controller; job cursors persist
        # under data_dir when checkpointing so crashed replays resume
        from pathlib import Path as _Path

        from sitewhere_tpu.pipeline.replay import ReplayEngine

        self.replay = ReplayEngine(
            self.bus, self.metrics,
            overload=self.overload,
            flightrec=self.flightrec,
            tracer=self.tracer,
            state_dir=(
                _Path(cfg.data_dir) / "replay" if cfg.checkpointing else None
            ),
        )
        # the ledger splits each batch's inference span on the record of
        # the flush that scored it, found by the span's flush_id
        self.latency.flushes = self.inference.flush_records
        self.add_child(self.inference)
        self.tenants: Dict[str, TenantRuntime] = {}
        self.coap: object = None
        if cfg.coap_ingest_port is not None:
            from sitewhere_tpu.comm.coap import CoapIngestServer

            self.coap = CoapIngestServer(
                self._coap_submit, port=cfg.coap_ingest_port
            )
            self.add_child(self.coap)
        self.mqtt_broker: object = None
        if cfg.mqtt_broker_port is not None:
            from sitewhere_tpu.comm.mqtt import MqttBroker

            # embedded real-socket broker; CONNECT creds = tenant token +
            # tenant auth secret, through the same gate as every transport
            self.mqtt_broker = MqttBroker(
                port=cfg.mqtt_broker_port,
                authenticator=lambda cid, user, pw: (
                    self.authenticate_device(user, pw) is not None
                ),
            )
            self.add_child(self.mqtt_broker)
        self._updates_task: Optional[asyncio.Task] = None
        self._autosave_task: Optional[asyncio.Task] = None
        self._overload_task: Optional[asyncio.Task] = None
        self._history_task: Optional[asyncio.Task] = None
        self._shared_targets: Optional[list] = None  # see _on_shared_input
        self._profiling = False  # jax.profiler trace active (profile_dir)
        self._debug_nans_set = False  # we flipped the global NaN flag
        self._debug_nans_prev = False  # value to restore on stop
        # ONE instance-level subscription for the shared input pattern; it
        # routes to opted-in tenants (cfg.shared_input) or — if none opted
        # in — to the sole tenant. With >=2 tenants and no flag it routes
        # nowhere: the shared pattern must never fan one device's telemetry
        # into every tenant (tenant isolation).
        self.broker.subscribe("sitewhere/input/+", self._on_shared_input)

    def authenticate_device(self, tenant_token: str, supplied_auth: str):
        """THE device-facing auth check, shared by every transport
        (HTTP/WS via RestApi, CoAP here, future receivers): tenant token
        + tenant auth secret → TenantRuntime or None. Constant-time
        compare; callers answer uniformly on None so no transport can
        enumerate tenants."""
        import hmac

        rt = self.tenants.get(tenant_token)
        rec = self.tenant_management.get_tenant(tenant_token)
        expected = rec.auth_token if rec is not None else ""
        # compare BYTES: compare_digest on str raises TypeError for
        # non-ASCII input, which would turn a bad credential into a 500.
        # The digest compare runs UNCONDITIONALLY (expected="" for unknown
        # tenants) so unknown tokens take the same time as bad secrets —
        # short-circuiting before it leaks a tenant-enumeration timing
        # oracle through any transport.
        ok = hmac.compare_digest(supplied_auth.encode(), expected.encode())
        if not (ok and rt is not None and rec is not None):
            return None
        return rt

    async def _coap_submit(self, tenant: str, payload: bytes, ctx: dict) -> bool:
        rt = self.authenticate_device(tenant, ctx.get("auth", ""))
        if rt is None:
            return False
        await rt.source.receiver.submit(payload, topic=f"coap/{tenant}/input")
        return True

    async def _on_shared_input(self, topic: str, payload: bytes) -> None:
        # routing runs at full ingest rate — recompute only when the
        # tenant set changes (add/remove invalidate _shared_targets; a
        # registry-size check catches the create_tenant→apply window so a
        # second tenant's registration closes the sole-tenant fallback
        # IMMEDIATELY, before its runtime exists — isolation)
        targets = self._shared_targets
        if targets is not None and len(targets) == 1 and not targets[0].config.shared_input:
            if self.tenant_management.count() > 1:
                targets = self._shared_targets = None
        if targets is None:
            targets = [
                rt for rt in self.tenants.values() if rt.config.shared_input
            ]
            if not targets and len(self.tenants) == 1:
                # sole-tenant convenience fallback — but gate on the tenant
                # REGISTRY, not the live runtime map: during an 'update' op
                # the runtime is transiently absent while its registration
                # remains, and shared input must not leak then
                if len(self.tenant_management.list_tenants()) <= 1:
                    targets = list(self.tenants.values())
            self._shared_targets = targets
        for rt in targets:
            await rt.source.receiver.submit(payload, topic=topic)

    # -- bootstrap (instance-management parity) --------------------------
    async def bootstrap(
        self,
        default_tenant: str = "default",
        template: str = "iot-temperature",
        admin_user: str = "admin",
        admin_password: str = "password",
        dataset_devices: int = 0,
    ) -> None:
        """Apply the instance template: admin user + default tenant (+
        optional synthetic device dataset), like the reference's instance
        bootstrapper [U]."""
        if self.users.get_user(admin_user) is None:
            self.users.create_user(admin_user, admin_password, [AUTH_ADMIN])
        if self.tenant_management.get_tenant(default_tenant) is None:
            await self.tenant_management.create_tenant(
                default_tenant, template=template
            )
            await self.drain_tenant_updates()
        if dataset_devices and default_tenant in self.tenants:
            self.tenants[default_tenant].device_management.bootstrap_fleet(
                dataset_devices
            )

    def _command_destination(self, cfg: TenantEngineConfig):
        """Build the tenant's command destination: in-proc sim broker by
        default; real-wire MQTT/CoAP when the tenant config asks
        (SURVEY.md §3.2 — the cloud→device half over actual sockets)."""
        tenant = cfg.tenant
        spec = cfg.command_destination
        if not spec:
            return BrokerCommandDestination(
                self.broker, f"sitewhere/{tenant}/command/{{device}}"
            )
        kind = spec.get("type", "mqtt")
        if kind == "mqtt":
            from sitewhere_tpu.pipeline.commands import MqttCommandDestination

            port = int(spec.get("port", 0))
            if port == 0:
                # the instance's embedded broker (requires tenants added
                # after start, when the ephemeral port is bound)
                if self.mqtt_broker is None or self.mqtt_broker.bound_port is None:
                    raise ValueError(
                        "command_destination port 0 needs the embedded "
                        "MQTT broker running (InstanceConfig.mqtt_broker_port)"
                    )
                port = self.mqtt_broker.bound_port
            # default creds: the tenant's own token/auth secret — the
            # embedded broker gates CONNECT through authenticate_device
            rec = self.tenant_management.get_tenant(tenant)
            return MqttCommandDestination(
                host=str(spec.get("host", "127.0.0.1")),
                port=port,
                topic_pattern=str(spec.get(
                    "topic_pattern", f"sitewhere/{tenant}/command/{{device}}"
                )),
                username=str(spec.get("username", tenant)),
                password=str(spec.get(
                    "password", rec.auth_token if rec is not None else ""
                )),
                qos=int(spec.get("qos", 1)),
                client_id=f"cmd-dest-{tenant}",
            )
        if kind == "coap":
            from sitewhere_tpu.pipeline.commands import CoapCommandDestination

            return CoapCommandDestination(
                path=str(spec.get("path", "command")),
                timeout_s=float(spec.get("timeout_s", 5.0)),
            )
        raise ValueError(f"unknown command_destination type '{kind}'")

    # -- tenant runtime construction -------------------------------------
    def _build_tenant(self, cfg: TenantEngineConfig) -> TenantRuntime:
        tenant = cfg.tenant
        dm = store = None
        if self.checkpoints is not None:
            # resume path: persisted device model + event history win over
            # fresh stores (crash-restart keeps every persisted event)
            dm = self.checkpoints.load_device_management(tenant)
            store = self.checkpoints.load_event_store(tenant)
        dm = dm or DeviceManagement(tenant)
        store = store or EventStore(tenant)
        ft = cfg.fault_tolerance
        # register the tenant's tracing + overload policies BEFORE
        # building stages (the event source reads both at build time)
        self.tracer.configure_tenant(tenant, cfg.tracing)
        self.overload.configure_tenant(cfg)
        receiver = QueueReceiver(f"recv[{tenant}]")
        source = EventSource(
            f"mqtt[{tenant}]", tenant, self.bus, receiver, cfg.decoder,
            self.metrics, policy=ft, tracer=self.tracer,
            overload=self.overload,
        )

        async def on_broker_msg(topic: str, payload: bytes) -> None:
            await receiver.submit(payload, topic=topic)

        self.broker.subscribe(f"sitewhere/{tenant}/input/+", on_broker_msg)
        # shared 'sitewhere/input/+' routing happens at instance level
        # (_on_shared_input) so multi-tenant isolation holds

        rules = RuleEngine(tenant, self.bus, [
            anomaly_score_rule(
                f"{tenant}-anomaly", min_score=cfg.rule_min_score,
                cooldown_ms=5000,
            ),
        ], self.metrics, policy=ft, tracer=self.tracer,
            overload=self.overload)
        connectors = [
            LogConnector(f"log[{tenant}]"),
            MqttTopicConnector(
                f"mqtt-out[{tenant}]", self.broker,
                topic_pattern=f"sitewhere/{tenant}/output/{{device}}/{{type}}",
            ),
        ]
        search = None
        if cfg.search_index:
            from sitewhere_tpu.pipeline.outbound import SearchIndexConnector

            search = SearchIndexConnector(f"search[{tenant}]")
            connectors.append(search)
        outbound = OutboundDispatcher(
            tenant, self.bus, connectors, self.metrics, policy=ft,
            tracer=self.tracer, overload=self.overload,
        )
        mqtt_source = None
        if cfg.mqtt_ingest:
            from sitewhere_tpu.pipeline.sources import MqttReceiver

            mq = dict(cfg.mqtt_ingest)
            # port 0 = the instance's embedded broker (mirrors the
            # command_destination convention); omitted = standard 1883
            # against an external broker, exactly as before round 5
            port = int(mq.get("port", 1883))
            embedded = port == 0
            if embedded:
                if self.mqtt_broker is None or self.mqtt_broker.bound_port is None:
                    raise ValueError(
                        "mqtt_ingest port 0 needs the embedded MQTT "
                        "broker running (InstanceConfig.mqtt_broker_port)"
                    )
                port = self.mqtt_broker.bound_port
            # embedded-broker creds default to the tenant's own token/auth
            # secret (its subscriber passes the same CONNECT gate as
            # devices); external brokers keep the anonymous default
            rec = self.tenant_management.get_tenant(tenant) if embedded else None
            mqtt_source = EventSource(
                f"mqtt-net[{tenant}]", tenant, self.bus,
                MqttReceiver(
                    f"mqtt-recv[{tenant}]",
                    host=mq.get("host", "127.0.0.1"),
                    port=port,
                    # default is TENANT-SCOPED: subscribing every tenant
                    # to the shared 'sitewhere/input/#' would fan one
                    # device's telemetry into every tenant (isolation)
                    topics=list(mq.get(
                        "topics", [f"sitewhere/{tenant}/input/#"]
                    )),
                    qos=int(mq.get("qos", 0)),
                    username=str(mq.get(
                        "username", tenant if embedded else ""
                    )),
                    password=str(mq.get(
                        "password",
                        rec.auth_token if rec is not None else "",
                    )),
                ),
                cfg.decoder, self.metrics, policy=ft, tracer=self.tracer,
                overload=self.overload,
            )
        media = StreamingMedia(tenant)
        media_pipe = None
        if cfg.media_pipeline:
            from sitewhere_tpu.pipeline.media import MediaClassificationPipeline

            media_pipe = MediaClassificationPipeline(
                tenant, self.bus, media, self.metrics, tiny=cfg.media_tiny,
                flightrec=self.flightrec,
            )
        return TenantRuntime(
            tenant=tenant,
            config=cfg,
            device_management=dm,
            event_store=store,
            asset_management=AssetManagement(tenant),
            labels=LabelGeneration(tenant),
            media=media,
            media_pipeline=media_pipe,
            mqtt_source=mqtt_source,
            source=source,
            inbound=InboundProcessor(
                tenant, self.bus, dm, self.metrics, policy=ft,
                tracer=self.tracer, overload=self.overload,
            ),
            persistence=EventPersistence(
                tenant, self.bus, store, self.metrics, policy=ft,
                tracer=self.tracer, overload=self.overload,
            ),
            rules=rules,
            outbound=outbound,
            state=DeviceStateService(tenant, self.bus, self.metrics),
            registration=RegistrationService(tenant, self.bus, dm, self.metrics),
            commands=CommandDelivery(
                tenant, self.bus, dm,
                self._command_destination(cfg),
                metrics=self.metrics,
            ),
            batch=BatchOperationManager(tenant, self.bus, dm, self.metrics),
            schedules=ScheduleManager(tenant, self.bus, self.metrics),
            broker_handler=on_broker_msg,
            search=search,
        )

    async def add_tenant(self, cfg: TenantEngineConfig) -> TenantRuntime:
        if cfg.tenant in self.tenants:
            raise ValueError(f"tenant '{cfg.tenant}' already running")
        # lift any tombstone from a previous removal of this tenant token
        self.bus.undrop(self.bus.naming.tenant_topic(cfg.tenant, ""))
        # tenant build (incl. checkpoint/store recovery: open+mmap+fsync)
        # stays ON the loop by design: it registers broker handlers and
        # tracer/overload policies that loop-side publishers read, so an
        # executor hop would race live traffic — and it is control-plane
        # work that runs once per tenant add, before this tenant serves
        rt = self._build_tenant(cfg)  # async: ok(cold control-plane path; build mutates loop-owned routing state)
        self.tenants[cfg.tenant] = rt
        self._shared_targets = None
        for comp in rt.components():
            self.add_child(comp)
            if self.state is LifecycleState.STARTED:
                await comp.start()
        await self.inference.add_tenant(cfg)
        return rt

    async def remove_tenant(
        self, tenant: str, *, drop_topics: bool = True
    ) -> None:
        """Stop + dismantle one tenant. ``drop_topics=False`` keeps the
        tenant's bus topics and group cursors alive — the multi-host
        drop path (runtime/hostserve.py): when the tenant was ADOPTED by
        another host, its topics on the shared broker are the adopter's
        live state, not ours to destroy."""
        rt = self.tenants.pop(tenant, None)
        self._shared_targets = None
        self.tracer.remove_tenant(tenant)
        self.overload.remove_tenant(tenant)
        self.latency.remove_tenant(tenant)
        if rt is None:
            return
        # stop broker ingress FIRST: the closure would otherwise keep
        # filling the terminated EventSource's bounded queue until it
        # blocks SimBroker.publish for every publisher in the process
        if rt.broker_handler is not None:
            self.broker.unsubscribe(rt.broker_handler)
        await self.replay.cancel_tenant(tenant)
        await self.inference.remove_tenant(tenant)
        for comp in reversed(rt.components()):
            await comp.terminate()
            self.remove_child(comp)
        # drop the tenant's bus topics: stale group cursors on dead topics
        # would backpressure future publishers (topics recreate lazily if
        # the tenant is ever re-added)
        if drop_topics:
            self.bus.drop_topics(self.bus.naming.tenant_topic(tenant, ""))
        # drop the tenant's labeled metric children + inference timer:
        # label cardinality must track LIVE tenants, not historical churn
        self.inference._stage_timers.pop(tenant, None)
        self.metrics.drop_labeled(tenant=tenant)

    async def restart_tenant(self, tenant: str) -> None:
        rt = self.tenants.get(tenant)
        if rt is None:
            return
        for comp in rt.components():
            await comp.restart()
        await self.inference.restart_tenant(tenant)

    def tenant(self, token: str) -> TenantRuntime:
        return self.tenants[token]

    # -- tenant-model-updates application --------------------------------
    async def apply_tenant_update(self, update: dict) -> None:
        op = update.get("op")
        token = update.get("tenant", "")
        if op == "add" and token not in self.tenants:
            cfg = tenant_config_from_template(
                token, update.get("template", "default"),
                **update.get("overrides", {}),
            )
            await self.add_tenant(cfg)
        elif op == "remove":
            await self.remove_tenant(token)
        elif op == "restart":
            await self.restart_tenant(token)
        elif op == "update" and token in self.tenants:
            await self.remove_tenant(token)
            cfg = tenant_config_from_template(
                token, update.get("template", "default"),
                **update.get("overrides", {}),
            )
            await self.add_tenant(cfg)

    async def drain_tenant_updates(self, timeout_s: float = 0) -> int:
        topic = self.bus.naming.tenant_model_updates()
        updates = await self.bus.consume(
            topic, group="instance", timeout_s=timeout_s
        )
        for u in updates:
            try:
                await self.apply_tenant_update(u)
            except Exception as exc:  # noqa: BLE001
                self._record_error("tenant-update", exc)
                # the cursor has already advanced: dead-letter the update
                # so it can be inspected/requeued instead of vanishing
                from sitewhere_tpu.runtime.tenant import dead_letter_update

                dead_letter_update(self.bus, self.name, u, exc)
        return len(updates)

    # -- lifecycle -------------------------------------------------------
    async def on_start(self) -> None:
        if self.config.debug_nans:
            import jax

            # remember the PRIOR value: the flag is process-global, and
            # stop() must restore what was there (another live instance or
            # an external JAX_DEBUG_NANS=1 may own it), not force False
            self._debug_nans_prev = bool(jax.config.jax_debug_nans)
            jax.config.update("jax_debug_nans", True)
            self._debug_nans_set = True
        if self.config.profile_dir and not self._profiling:
            import jax

            try:
                jax.profiler.start_trace(self.config.profile_dir)
                self._profiling = True
            except Exception as exc:  # noqa: BLE001 - the profiler is
                # process-global (an already-active trace raises); losing
                # the trace must not keep the instance from booting
                self._record_error("profiler-start", exc)
        # the event-loop ledger and the collector's account: always on,
        # installed BEFORE any child starts so every task of the
        # instance is created through the ledger's task factory
        self.metrics.loop_ledger.install(asyncio.get_running_loop())
        self._gc_account.install()
        self.bus.subscribe(self.bus.naming.tenant_model_updates(), "instance")
        self._updates_task = asyncio.create_task(
            self._updates_loop(), name=f"{self.name}-tenant-updates"
        )
        if self.checkpoints is not None and self.config.checkpoint_interval_s > 0:
            self._autosave_task = asyncio.create_task(
                self._autosave_loop(), name=f"{self.name}-autosave"
            )
        # overload control tick: consumer lag → per-tenant credit +
        # degradation ladder (the in-proc bus answers lags() synchronously;
        # a RemoteEventBus deployment runs the same loop over the wire)
        self._overload_task = asyncio.create_task(
            self._overload_loop(), name=f"{self.name}-overload"
        )
        # metrics history tick: sample the allowlisted families into the
        # 15-minute ring and run the watchdog rules over it
        self._history_task = asyncio.create_task(
            self._history_loop(), name=f"{self.name}-history"
        )

    OVERLOAD_TICK_S = 0.1

    async def _overload_loop(self) -> None:
        while True:
            await asyncio.sleep(self.OVERLOAD_TICK_S)
            try:
                if isinstance(self.bus, EventBus):
                    lags = self.bus.lags()
                else:
                    lags = await self.bus.lags()
                self.overload.refresh(lags)
            except Exception as exc:  # noqa: BLE001 - a control-loop
                # fault must not kill overload protection; next tick retries
                self._record_error("overload-tick", exc)

    def _refresh_mfu(self) -> None:
        """Decay every idle MFU gauge — the scoring families AND each
        tenant's media pipeline account (a stopped video stream must
        read 0, not its last busy value)."""
        self.inference.refresh_mfu()
        for rt in list(self.tenants.values()):
            if rt.media_pipeline is not None:
                rt.media_pipeline.refresh_mfu()

    async def _history_loop(self) -> None:
        while True:
            await asyncio.sleep(self.history.resolution_s)
            try:
                # the tick is the tracing's own cost: the loop ledger's
                # ``observe`` stage, not ``other`` (``sw/observe`` under a
                # capture)
                ledger = self.metrics.loop_ledger
                t0 = ledger.clock()
                with sw("observe"):
                    # decay idle families' MFU gauges BEFORE sampling so
                    # the ring never records a stale "last busy" value
                    self._refresh_mfu()
                    # publish the latency ledgers' rolling p99s / burn
                    # rates as gauges BEFORE sampling so the ring sees
                    # this tick's attribution state, not last tick's
                    self.latency.refresh_gauges()
                    self.history.sample()
                    if self.watchdog is not None:
                        self.watchdog.evaluate()
                ledger.observe_from(t0)
            except Exception as exc:  # noqa: BLE001 - a sampling fault
                # must not kill the blackbox; next tick retries
                self._record_error("history-tick", exc)
            # background storage maintenance: retention horizon +
            # small-segment compaction per tenant store (O(segments)
            # no-op when there is nothing to do — docs/STORAGE.md).
            # Faults isolate PER TENANT: one tenant's broken store
            # directory must not starve every later tenant's retention.
            # max_units=2 bounds the inline re-encode work per tick: a
            # fully-rescored store durable-izes over several ticks
            # instead of stalling the loop (and every REST handler) for
            # one giant synchronous pass
            for rt in list(self.tenants.values()):
                try:
                    rt.event_store.maintain(max_units=2)
                except Exception as exc:  # noqa: BLE001 - storage upkeep
                    # must not kill the history loop; next tick retries
                    self._record_error("storage-maintain", exc)

    async def _autosave_loop(self) -> None:
        """Periodic live checkpoint: bounds the loss window of a HARD kill
        (no polite stop) to one interval."""
        interval = self.config.checkpoint_interval_s
        while True:
            await asyncio.sleep(interval)
            try:
                await self.checkpoint()
                self.metrics.counter("instance.autosaves").inc()
            except Exception as exc:  # noqa: BLE001 - an autosave failure
                # must not kill the loop; the next tick retries
                self._record_error("autosave", exc)

    async def stop(self) -> None:
        was_started = self.state is LifecycleState.STARTED
        # quiesce the updates + autosave loops FIRST: they mutate the
        # child tree / snapshot it, so they must not race the cascade
        await cancel_and_wait(self._updates_task)
        self._updates_task = None
        await cancel_and_wait(self._autosave_task)
        self._autosave_task = None
        await cancel_and_wait(self._overload_task)
        self._overload_task = None
        await cancel_and_wait(self._history_task)
        self._history_task = None
        # park replay jobs BEFORE the stop cascade takes consumers down
        # (cursors persist; unfinished jobs resume after restore)
        await self.replay.stop()
        await super().stop()
        # checkpoint-on-stop: a clean shutdown always leaves a current
        # snapshot (engines already saved their params in the cascade)
        if was_started and self.checkpoints is not None:
            try:
                await self.checkpoint()
            except Exception as exc:  # noqa: BLE001
                self._record_error("checkpoint-on-stop", exc)

    async def on_stop(self) -> None:
        await cancel_and_wait(self._updates_task)
        self._updates_task = None
        await cancel_and_wait(getattr(self, "_autosave_task", None))
        self._autosave_task = None
        await cancel_and_wait(getattr(self, "_overload_task", None))
        self._overload_task = None
        await cancel_and_wait(getattr(self, "_history_task", None))
        self._history_task = None
        await self.replay.stop()
        self._gc_account.uninstall()
        self.metrics.loop_ledger.uninstall()
        if self._profiling:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception as exc:  # noqa: BLE001 - a profiler fault
                # must not break shutdown
                self._record_error("profiler-stop", exc)
            self._profiling = False
        if self._debug_nans_set:
            # restore the pre-start value (see on_start) — a debug
            # session's instance must not leak raise-on-NaN into later
            # instances, nor clobber a concurrent owner's setting
            import jax

            jax.config.update("jax_debug_nans", self._debug_nans_prev)
            self._debug_nans_set = False

    async def _updates_loop(self) -> None:
        while True:
            await self.drain_tenant_updates(timeout_s=None)

    # -- checkpoint / restore ---------------------------------------------
    async def checkpoint(self) -> None:
        """Persist the whole instance: bus (topic logs + group cursors),
        per-tenant device model + event store, tenant manifest.

        Safe on a LIVE instance: the state cut happens synchronously on the
        event loop (no awaits between reads, so nothing mutates mid-
        snapshot), and only serialization + file writes run on an executor
        thread. Per-tenant model params are captured here too
        (``inference.snapshot_params``) so a live checkpoint preserves
        on-device training — engines additionally save params on stop."""
        ck = self.checkpoints
        if ck is None:
            raise RuntimeError("checkpointing disabled (InstanceConfig)")
        # phase 1 — consistent cut, no awaits. Params are materialized to
        # copied numpy HERE on the loop thread: np.asarray of jax arrays on
        # the executor thread races the jax runtime (heap corruption)
        from sitewhere_tpu.runtime.checkpoint import host_copy_params

        # bus durability belongs to whoever OWNS the log: the in-proc bus
        # is ours to snapshot; an external broker (RemoteEventBus) owns its
        # own durable state — exactly the reference's posture toward Kafka.
        # The consumer-group CURSORS over this instance's tenant topics are
        # ours though: captured BEFORE the store cut (an older cursor only
        # redelivers — at-least-once; a newer one would lose rows), so a
        # hard-killed host restores with cursors rewound to this cut and
        # nothing consumed-after-checkpoint goes missing
        bus_bytes = None
        bus_offsets = None
        if isinstance(self.bus, EventBus):
            bus_bytes = ck.snapshot_bus(self.bus)
        elif hasattr(self.bus, "snapshot_offsets"):
            snap = await self.bus.snapshot_offsets()
            prefixes = tuple(
                self.bus.naming.tenant_topic(t, "") for t in self.tenants
            )
            bus_offsets = {
                topic: groups for topic, groups in snap.items()
                if prefixes and topic.startswith(prefixes)
            }
        param_snaps = {
            key: host_copy_params(tree)
            for key, tree in self.inference.snapshot_params().items()
        }
        tenant_snaps = {
            token: ck.snapshot_tenant_stores(rt.device_management, rt.event_store)
            for token, rt in self.tenants.items()
        }
        manifest = [
            {
                "token": t,
                "template": rt.config.template,
                "config": tenant_config_to_dict(rt.config),
            }
            for t, rt in self.tenants.items()
        ]

        # phase 2 — serialization/IO off the loop
        def _write() -> None:
            if bus_bytes is not None:
                ck.write_bus(bus_bytes)
            if bus_offsets is not None:
                ck.save_offsets(bus_offsets)
            for (token, family), params in param_snaps.items():
                ck.save_params(token, family, params)
            for token, snap in tenant_snaps.items():
                ck.write_tenant_stores(token, snap)
            ck.save_manifest(manifest)

        await asyncio.get_running_loop().run_in_executor(None, _write)

    async def restore(self) -> int:
        """Resume from the data_dir checkpoint: bus state FIRST (so newly
        subscribing consumer groups find their saved cursors), then the
        tenant set from the manifest (tenant builders pick up persisted
        device models / event stores automatically). Returns the number of
        tenants restored."""
        ck = self.checkpoints
        if ck is None or not ck.exists():
            return 0
        if isinstance(self.bus, EventBus):  # external brokers own their log
            await asyncio.get_running_loop().run_in_executor(
                None, ck.load_bus, self.bus
            )
        elif hasattr(self.bus, "restore_offsets"):
            # remote broker: rewind OUR consumer groups to the checkpoint
            # cut before any tenant consumer starts — rows the dead
            # process consumed after its last checkpoint redeliver
            # (at-least-once), instead of vanishing behind an advanced
            # cursor. The snapshot was filtered to this instance's
            # tenant topics, so co-hosted tenants elsewhere are untouched.
            snap = ck.load_offsets()
            if snap:
                await self.bus.restore_offsets(snap)
        manifest = ck.load_manifest() or []
        for entry in manifest:
            if entry["token"] in self.tenants:
                continue
            if "config" in entry:
                # full saved config wins: tenants added with overrides
                # (model/decoder/…) must resume identically, or restored
                # params can fail the pytree-structure match in set_slot
                cfg = tenant_config_from_dict(entry["config"])
            else:  # legacy manifest (round-2 format)
                cfg = tenant_config_from_template(
                    entry["token"], entry.get("template", "default")
                )
            await self.add_tenant(cfg)
        # relaunch replay jobs a crash interrupted: cursors committed
        # after each published batch, so resume is exactly-once; with
        # replay_recover_unscored, a HARD-killed rescore job (file still
        # says "running") also rewinds to re-cover the published-but-
        # unscored NaN window its crash left (docs/STORAGE.md "Replay")
        self.replay.resume_jobs(
            {t: rt.event_store for t, rt in self.tenants.items()},
            recover_unscored=self.config.replay_recover_unscored,
        )
        return len(manifest)

    # -- observability ---------------------------------------------------
    def collect_bus_gauges(self) -> None:
        """Refresh per-topic depth + per-group consumer-lag gauges (and
        per-tenant receiver queue depths) from live state. Called by the
        /metrics scrape handler so the labels are current at scrape time —
        a 10^3-topic instance pays this only when someone is looking."""
        m = self.metrics
        # scrape-time MFU decay: an idle family must scrape as ~0, not
        # hold its last busy window value
        self._refresh_mfu()
        m.describe("bus_topic_depth", "retained entries per bus topic")
        m.describe(
            "bus_consumer_lag",
            "unconsumed entries per (topic, consumer group)",
        )
        m.describe(
            "receiver_queue_depth", "pending raw payloads per tenant receiver"
        )
        m.describe(
            "media_queue_depth", "pending frames per tenant media pipeline"
        )
        m.describe(
            "media_ring_bytes",
            "resident compressed-frame ring bytes per tenant media "
            "pipeline (the byte watermark the arena bounds)",
        )
        if isinstance(self.bus, EventBus):
            # remote buses answer lags() over the wire — the async
            # /metrics handler awaits it and feeds apply_lag_gauges
            self.apply_lag_gauges(self.bus.lags())
        m.describe(
            "receiver_queue_class_depth",
            "pending raw payloads per tenant receiver, per priority "
            "class (sums to receiver_queue_depth)",
        )
        for token, rt in self.tenants.items():
            q = rt.source.receiver.queue
            m.gauge("receiver_queue_depth", tenant=token).set(q.qsize())
            depths = getattr(q, "class_depths", None)
            if depths is not None:
                # a SEPARATE family: mixing {tenant} and {tenant,priority}
                # children under one name would double-count any
                # sum(receiver_queue_depth) aggregation
                for pr_name, d in zip(("alert", "command", "measurement"),
                                      depths()):
                    m.gauge(
                        "receiver_queue_class_depth", tenant=token,
                        priority=pr_name,
                    ).set(d)
            if rt.media_pipeline is not None:
                m.gauge("media_queue_depth", tenant=token).set(
                    rt.media_pipeline.pending_frames()
                )
                m.gauge("media_ring_bytes", tenant=token).set(
                    rt.media_pipeline.pending_bytes()
                )

    def apply_lag_gauges(self, lags: Dict[str, dict]) -> None:
        """Feed one ``bus.lags()`` result (in-proc or RemoteEventBus) into
        the per-topic depth / per-group lag gauges."""
        m = self.metrics
        for topic, info in lags.items():
            m.gauge("bus_topic_depth", topic=topic).set(info["depth"])
            for group, lag in info["groups"].items():
                m.gauge(
                    "bus_consumer_lag", topic=topic, group=group
                ).set(lag)

    def tenant_slo_report(self, tenant: str) -> dict:
        """Per-tenant SLO view: the tracing policy, per-stage latency
        summaries (from the labeled stage histograms), and tail-sampling
        retention counters — the GET /api/tenants/{t}/slo payload."""
        pol = self.tracer.policy_for(tenant)
        stages: Dict[str, dict] = {}
        fam = self.metrics._labeled.get("pipeline_stage_seconds", {})
        wait_fam = self.metrics._labeled.get(
            "pipeline_stage_queue_wait_seconds", {}
        )
        for key, h in fam.items():
            labels = dict(key)
            if labels.get("tenant") != tenant:
                continue
            stage = labels.get("stage", "?")
            stages[stage] = {"service": h.summary()}
        for key, h in wait_fam.items():
            labels = dict(key)
            if labels.get("tenant") != tenant:
                continue
            stages.setdefault(labels.get("stage", "?"), {})[
                "queue_wait"
            ] = h.summary()
        self.tracer.gc()
        traces = self.tracer.store.list(tenant=tenant, limit=10_000,
                                        include_active=False)
        breaches = sum(1 for t in traces if t.duration_ms >= pol.slo_ms)
        return {
            "tenant": tenant,
            "slo_ms": pol.slo_ms,
            "tracing_enabled": pol.enabled,
            "sample_rate": pol.sample_rate,
            "stages": stages,
            "traces_retained": len(traces),
            "slo_breach_traces": breaches,
            "retained_by_reason": _count_by(
                t.decision for t in traces
            ),
        }

    def tenant_overload_report(self, tenant: str) -> Optional[dict]:
        """Per-tenant overload state: policy, credit, degradation level,
        fair-queue standing, per-stage expired/late/shed accounting —
        the GET /api/tenants/{t}/overload payload."""
        rep = self.overload.report(tenant)
        if rep is None:
            return None
        rep["fair_queue"] = self.inference.fair.describe().get(tenant)

        def _by_stage(family: str, label: str = "stage") -> Dict[str, float]:
            out: Dict[str, float] = {}
            for key, c in list(
                self.metrics._labeled.get(family, {}).items()
            ):
                labels = dict(key)
                if labels.get("tenant") == tenant:
                    out[labels.get(label, "?")] = c.value
            return out

        rep["expired_by_stage"] = _by_stage("pipeline_expired_total")
        rep["late_by_stage"] = _by_stage("pipeline_deadline_late_total")
        rep["shed_by_priority"] = _by_stage("pipeline_shed_total", "priority")
        rt = self.tenants.get(tenant)
        if rt is not None:
            q = rt.source.receiver.queue
            rep["receiver"] = {
                "depth": q.qsize(),
                "class_depths": dict(zip(
                    ("alert", "command", "measurement"), q.class_depths()
                )),
                "shed_total": rt.source.receiver.shed_total,
            }
        rep["expired_topic"] = self.bus.naming.expired_events(tenant)
        return rep

    def tenant_health_report(self, tenant: str) -> Optional[dict]:
        """Per-tenant model-health verdict: drift statistics vs the
        frozen reference, score quantiles, delivery-quality rates, the
        active kernel variant, and the family's canary status — the
        GET /api/tenants/{t}/health payload (docs/OBSERVABILITY.md
        "Score health & canaries")."""
        rep = self.scorehealth.health_report(tenant)
        if rep is None:
            return None
        # fold the deadline gates' expired-delivery accounting in: rows
        # that never reached a scorer are quality loss a score-only view
        # would miss
        expired = 0.0
        for key, c in list(
            self.metrics._labeled.get("pipeline_expired_total", {}).items()
        ):
            if dict(key).get("tenant") == tenant:
                expired += c.value
        rep["expired_total"] = expired
        return rep

    def tenant_scores_dist(self, tenant: str) -> Optional[dict]:
        """The tenant's score distribution (current rolling window vs the
        frozen reference, log-spaced bin edges) — the
        GET /api/tenants/{t}/scores/dist payload."""
        return self.scorehealth.dist_report(tenant)

    # -- introspection ---------------------------------------------------
    def topology(self) -> dict:
        """Instance topology/status (reference: instance topology updates [U])."""
        return {
            "instance_id": self.config.instance_id,
            "mesh": self.mesh.describe(),
            "tenants": {
                t: {
                    "template": rt.config.template,
                    "model": rt.config.model,
                    "components": {
                        c.name: c.state.value for c in rt.components()
                    },
                }
                for t, rt in self.tenants.items()
            },
            "inference": self.inference.describe(),
            "status": self.status_tree(),
        }
