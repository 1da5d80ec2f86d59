"""Serving-host process for multi-host deployments: one
``SiteWhereInstance`` over a shared netbus broker, wrapped in the host
fault domain (docs/ROBUSTNESS.md "Host fault domains").

``python -m sitewhere_tpu.runtime.hostserve --broker-port P --host-id h0
--lease-ttl 2.0 ...`` runs one host:

- a ``RemoteEventBus`` connection to the shared broker;
- with ``--lease-ttl > 0``, a :class:`HostLeaseClient` heartbeating the
  health summary and a :class:`FencedBus` wrapping the DATA plane, so
  every tenant-topic publish carries the host's lease epoch (stale-epoch
  publishes are rejected + DLQ'd at the broker — the zombie guarantee).
  With ``--lease-ttl 0`` (the default) neither is constructed and the
  process is bit-for-bit a single-host deployment over netbus;
- a host-control consumer on ``hostctl.<host_id>`` executing the
  coordinator's ops: ``adopt`` (tenant handoff in — config + the donor's
  already-encoded params checkpoint bytes, PR 7's encode-once contract:
  the segment bytes are COPIED, never decoded), ``drop`` (tenant handoff
  out — topics stay, they are the adopter's state now), ``probe``
  (probation probes via ``TpuInferenceService.host_probe``),
  ``checkpoint``, ``report`` (accounting snapshot to a reply topic),
  ``inject_fault`` / ``clear_faults`` (the in-process half of
  :class:`HostFaultPlan` — kill -9 / SIGSTOP come from the harness).

Control-plane traffic (reports, heartbeats) rides the RAW bus on
purpose: a fenced host must still be able to report and earn probation —
the fence is a data-plane guarantee, not a gag order.

Lease-loss policy (``on_lease_lost``): drop every tenant (they were
adopted elsewhere the moment the supervisor fenced us — serving them
again would double-serve), then re-acquire at a fresh epoch and start
earning probation probes; the coordinator brings tenants home with
``adopt`` ops once the probation bar clears.
"""

from __future__ import annotations

import asyncio
import logging
import shutil
from pathlib import Path
from typing import Dict, Optional

from sitewhere_tpu.runtime.faultplan import HostFault, HostFaultPlan
from sitewhere_tpu.runtime.hostlease import FencedBus, HostLeaseClient
from sitewhere_tpu.runtime.lifecycle import LifecycleComponent, cancel_and_wait

logger = logging.getLogger("sitewhere.hostserve")


class HostServer(LifecycleComponent):
    """The host-control consumer + heartbeat-health provider for one
    serving process. ``raw_bus`` is the unfenced RemoteEventBus (control
    plane); the instance's own bus may be a :class:`FencedBus` over it."""

    def __init__(
        self,
        raw_bus,
        inst,
        host_id: str,
        *,
        lease_client: Optional[HostLeaseClient] = None,
        faultplan: Optional[HostFaultPlan] = None,
        probation_probes: int = 2,
    ) -> None:
        super().__init__(f"hostserve-{host_id}")
        self.raw_bus = raw_bus
        self.inst = inst
        self.host_id = str(host_id)
        self.lease_client = lease_client
        self.faultplan = faultplan if faultplan is not None else HostFaultPlan()
        self.probation_probes = int(probation_probes)
        self.probes_ok = 0
        self._prev_flushes = 0.0
        self._prev_timeouts = 0.0
        self._ctl_task: Optional[asyncio.Task] = None
        self._rebirth_task: Optional[asyncio.Task] = None
        if lease_client is not None:
            lease_client.health_fn = self.health
            lease_client.faultplan = self.faultplan
            lease_client.on_lease_lost = self._on_lease_lost

    @property
    def ctl_topic(self) -> str:
        return self.raw_bus.naming.global_topic(f"hostctl.{self.host_id}")

    async def on_start(self) -> None:
        self.raw_bus.subscribe(self.ctl_topic, f"hostctl[{self.host_id}]")
        self._ctl_task = asyncio.create_task(
            self._ctl_loop(), name=f"hostctl-{self.host_id}"
        )

    async def on_stop(self) -> None:
        await cancel_and_wait(self._ctl_task)
        await cancel_and_wait(self._rebirth_task)
        self._ctl_task = self._rebirth_task = None

    # -- heartbeat health --------------------------------------------------
    def _fam_sum(self, family: str) -> float:
        return sum(
            v
            for v in self.inst.metrics.snapshot_families((family,)).values()
            if isinstance(v, (int, float))
        )

    def health(self) -> dict:
        """The lease heartbeat's health summary: flush-timeout rate over
        the last heartbeat interval, quarantined-slice population,
        overload credit, and the probation-probe count the supervisor
        reads while we are on probation."""
        flushes = self._fam_sum("tpu_inference.flushes")
        timeouts = self._fam_sum("tpu_flush_timeout_total")
        df = flushes - self._prev_flushes
        dt = timeouts - self._prev_timeouts
        self._prev_flushes, self._prev_timeouts = flushes, timeouts
        return {
            "flush_timeout_rate": (dt / df) if df > 0 else (1.0 if dt > 0 else 0.0),
            "quarantined_slices": self.inst.inference.quarantined_slices(),
            "overload_credit": self._fam_sum("overload_credit"),
            "probes_ok": self.probes_ok,
            "tenants": sorted(self.inst.tenants),
        }

    # -- lease-loss policy -------------------------------------------------
    def _on_lease_lost(self, _client: HostLeaseClient) -> None:
        if self._rebirth_task is None or self._rebirth_task.done():
            self._rebirth_task = asyncio.get_running_loop().create_task(
                self._rebirth(), name=f"host-rebirth-{self.host_id}"
            )

    async def _rebirth(self) -> None:
        """We were fenced: our tenants live elsewhere now. Quiesce them
        locally (keeping their shared-broker topics — the adopter's
        state), re-acquire at a fresh epoch, and start earning probation
        probes for the supervisor to read."""
        self.probes_ok = 0
        for t in list(self.inst.tenants):
            try:
                await self.inst.remove_tenant(t, drop_topics=False)
            except Exception as exc:  # noqa: BLE001 - quiesce must finish
                self._record_error("rebirth-drop", exc)
        client = self.lease_client
        if client is None:
            return
        while True:
            try:
                await client.acquire()
                break
            except (ConnectionError, OSError, RuntimeError):
                await asyncio.sleep(client.renew_interval_s)
        self.probes_ok += await self.inst.inference.host_probe(
            self.probation_probes
        )

    # -- host-control ops --------------------------------------------------
    async def _ctl_loop(self) -> None:
        topic, group = self.ctl_topic, f"hostctl[{self.host_id}]"
        while True:
            try:
                ops = await self.raw_bus.consume(topic, group, 32, timeout_s=1.0)
            except asyncio.CancelledError:
                raise
            except (ConnectionError, OSError, RuntimeError):
                await asyncio.sleep(0.2)  # broker bounce: retry
                continue
            for op in ops:
                try:
                    await self._handle(op)
                except asyncio.CancelledError:
                    raise
                except Exception as exc:  # noqa: BLE001 - one bad op
                    # must not kill the control plane
                    self._record_error("hostctl", exc)

    async def _handle(self, op: dict) -> None:
        kind = op.get("op")
        if kind == "adopt":
            await self._adopt(op)
        elif kind == "drop":
            await self.inst.remove_tenant(
                str(op["tenant"]), drop_topics=False
            )
        elif kind == "probe":
            self.probes_ok += await self.inst.inference.host_probe(
                int(op.get("n", 1))
            )
        elif kind == "checkpoint":
            await self.inst.checkpoint()
        elif kind == "report":
            await self._report(str(op["reply_to"]))
        elif kind == "inject_fault":
            self.faultplan.add(HostFault(**op.get("fault", {})))
        elif kind == "clear_faults":
            self.faultplan.clear()
        else:
            logger.warning("hostctl %s: unknown op %r", self.host_id, kind)

    async def _adopt(self, op: dict) -> None:
        """Tenant handoff IN: config + the donor host's params checkpoint
        as already-encoded bytes (a raw file copy into our own checkpoint
        dir — the tenant build then restores them exactly as it would its
        own)."""
        from sitewhere_tpu.runtime.config import tenant_config_from_dict

        cfg = tenant_config_from_dict(dict(op["config"]))
        donor = op.get("params_from")
        ck = self.inst.checkpoints
        if donor and ck is not None:
            src_dir = Path(str(donor)) / "params"
            dst_dir = ck.root / "params"
            if src_dir.is_dir():
                dst_dir.mkdir(parents=True, exist_ok=True)
                for src in src_dir.glob(f"{cfg.tenant}.*.ckpt"):
                    dst = dst_dir / src.name
                    if src.resolve() == dst.resolve():
                        continue  # re-adopting from our own checkpoint
                    await asyncio.get_running_loop().run_in_executor(
                        None, shutil.copyfile, src, dst
                    )
        if cfg.tenant not in self.inst.tenants:
            await self.inst.add_tenant(cfg)
        self.inst.metrics.counter(
            "host_tenants_adopted_total", host=self.host_id
        ).inc()

    async def _report(self, reply_to: str) -> None:
        """Accounting snapshot to the coordinator, over the RAW bus (a
        fenced host must still account for itself). ``rounds`` decodes
        the chaos harness's value convention (value = 100*round + i) so
        the coordinator can assert zero loss and FIFO per tenant."""
        rounds: Dict[str, list] = {}
        round_rows: Dict[str, dict] = {}
        round_order: Dict[str, list] = {}
        store_rows: Dict[str, int] = {}
        for t, rt in self.inst.tenants.items():
            try:
                vals = rt.event_store.measurements.columns()["value"]
                store_rows[t] = int(len(vals))
                # DISTINCT values per round: at-least-once redelivery
                # collapses, a missing row shows as a short count
                per: Dict[int, set] = {}
                order: list = []
                for v in vals:
                    r = int(v) // 100
                    if r not in per:
                        order.append(r)
                    per.setdefault(r, set()).add(float(v))
                rounds[t] = sorted(per)
                round_rows[t] = {r: len(s) for r, s in sorted(per.items())}
                round_order[t] = order
            except Exception:  # noqa: BLE001 - a half-built tenant
                # reports empty, not a dead control plane
                store_rows[t] = 0
                rounds[t] = []
                round_rows[t] = {}
                round_order[t] = []
        client = self.lease_client
        report = {
            "host": self.host_id,
            "epoch": client.epoch if client is not None else 0,
            "held": bool(client.held) if client is not None else False,
            "tenants": sorted(self.inst.tenants),
            "persisted": float(
                self.inst.metrics.counter("event_management.persisted").value
            ),
            "scored": self._fam_sum("tpu_inference.scored_total"),
            "expired": self._fam_sum("pipeline_expired_total"),
            "fenced_publishes": getattr(self.inst.bus, "fenced", 0),
            "probes_ok": self.probes_ok,
            "rounds": rounds,
            "round_rows": round_rows,
            # first-appearance order of rounds in the append-ordered
            # store: the per-tenant FIFO witness (sorted == in-order)
            "round_order": round_order,
            "store_rows": store_rows,
            "faults_injected": self.faultplan.injected,
            # a failed hostctl op must not vanish: the coordinator reads
            # the tail of our error log off the same accounting snapshot
            "errors": list(self.errors)[-5:],
        }
        await self.raw_bus.publish(reply_to, report)


# ------------------------------------------------------------------ main
def main(argv=None) -> None:
    """One serving host against a shared broker. Prints a READY json
    line (pid + host id) once serving, then runs until killed — the
    multi-process chaos harness's unit of failure."""
    import argparse
    import json
    import os
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--broker-host", default="127.0.0.1")
    ap.add_argument("--broker-port", type=int, default=0)
    ap.add_argument("--broker-endpoints", default="",
                    help='failover endpoint list "host:port[,host:port]" '
                         "(primary first, warm standbys after); overrides "
                         "--broker-host/--broker-port")
    ap.add_argument("--host-id", required=True)
    ap.add_argument("--instance-id", default="sw")
    ap.add_argument("--data-dir", default="")
    ap.add_argument("--mesh", default="1,1,8",
                    help="tenant_axis,data_axis,slots_per_shard")
    ap.add_argument("--lease-ttl", type=float, default=0.0,
                    help="lease TTL seconds; 0 disables the lease layer")
    ap.add_argument("--renew-interval", type=float, default=None)
    ap.add_argument("--probation-probes", type=int, default=2)
    ap.add_argument("--restore", action="store_true",
                    help="restore tenants from the data-dir checkpoint")
    ap.add_argument("--recover-unscored", action="store_true",
                    help="on restore, rewind hard-killed rescore jobs to "
                         "re-cover their published-but-unscored window")
    ap.add_argument("--checkpoint-interval", type=float, default=0.0)
    args = ap.parse_args(argv)

    async def run() -> None:
        from sitewhere_tpu.instance import SiteWhereInstance
        from sitewhere_tpu.runtime.bus import TopicNaming
        from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig
        from sitewhere_tpu.runtime.netbus import RemoteEventBus

        t_ax, d_ax, slots = (int(x) for x in args.mesh.split(","))
        naming = TopicNaming(args.instance_id)
        if args.broker_endpoints:
            endpoints = []
            for spec in args.broker_endpoints.split(","):
                h, _, p = spec.strip().rpartition(":")
                endpoints.append((h or "127.0.0.1", int(p)))
        elif args.broker_port:
            endpoints = [(args.broker_host, args.broker_port)]
        else:
            ap.error("--broker-port or --broker-endpoints required")
        raw_bus = RemoteEventBus(
            endpoints=endpoints, naming=naming,
            reconnect_window_s=30.0,
        )
        await raw_bus.connect()

        lease_client = None
        inst_bus = raw_bus
        if args.lease_ttl > 0:
            lease_client = HostLeaseClient(
                raw_bus, args.host_id,
                ttl_s=args.lease_ttl,
                renew_interval_s=args.renew_interval,
            )
            inst_bus = FencedBus(raw_bus, lease_client)

        inst = SiteWhereInstance(
            InstanceConfig(
                instance_id=args.instance_id,
                mesh=MeshConfig(
                    tenant_axis=t_ax, data_axis=d_ax,
                    slots_per_shard=slots,
                ),
                data_dir=args.data_dir or "./_data",
                checkpointing=bool(args.data_dir),
                checkpoint_interval_s=args.checkpoint_interval,
                replay_recover_unscored=bool(args.recover_unscored),
                watchdog_enabled=False,  # the coordinator watches hosts
            ),
            bus=inst_bus,
        )
        if lease_client is not None:
            lease_client.metrics = inst.metrics
            lease_client.flightrec = inst.flightrec
        server = HostServer(
            raw_bus, inst, args.host_id,
            lease_client=lease_client,
            probation_probes=args.probation_probes,
        )
        await inst.start()
        if lease_client is not None:
            await lease_client.start()
        await server.start()
        if args.restore:
            await inst.restore()
        print(
            json.dumps({
                "ready": True, "pid": os.getpid(), "host": args.host_id,
                "epoch": lease_client.epoch if lease_client else 0,
            }),
            flush=True,
        )
        sys.stdout.flush()
        try:
            await asyncio.Event().wait()  # serve until killed
        finally:
            await server.terminate()
            if lease_client is not None:
                await lease_client.terminate()
            await inst.terminate()
            await raw_bus.close()

    asyncio.run(run())


if __name__ == "__main__":
    main()
