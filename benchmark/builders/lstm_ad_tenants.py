"""Builder for the LSTM-AD tenant configurations: a started
``SiteWhereInstance`` serving ``config["tenants"]`` tenants of the
``iot-temperature`` template through the product's own entry points, with
every tenant's weights made from ``--seed``.

A configuration file names this module under ``"builder"``; a new model
family brings a builder of its own. A builder gives the runner:

- ``async build(config, seed, devices) -> System`` — everything up to and
  including ``prewarm()``; no traffic.
- ``reference(config, seed, control=False) -> Reference`` — the plain
  reference for the family (independent of the program), carrying the
  configuration for the check module the configuration names.

The calls a traced run wraps in ``bench/<layer>`` spans are the
configuration file's (``spans``), not this module's.
"""

from __future__ import annotations

import asyncio
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from benchmark.reference import lstm_ad as ref
from benchmark.encoders.bulk_binary import tenant_token


@dataclass
class System:
    inst: object
    tenants: list
    info: dict = field(default_factory=dict)

    @property
    def broker(self):
        return self.inst.broker

    @property
    def bus(self):
        return self.inst.bus

    @property
    def metrics(self):
        return self.inst.metrics

    def scored_topic(self, tenant: str) -> str:
        return self.inst.bus.naming.scored_events(tenant)

    def slice_of(self, tenant: str) -> int:
        return self.inst.inference.engines[tenant].placement.shard

    def slice_label(self, sl: int) -> str:
        return self.inst.inference.mm.slice_device_label(sl)

    def scorer_labels(self) -> list:
        return sorted({s.device_label
                       for s in self.inst.inference.scorers.values()})

    def store_columns(self, tenant: str) -> dict:
        """One tenant's persisted rows, in persist order: device index,
        value, score, event_ts."""
        from sitewhere_tpu.storage.segstore import slice_columns

        cols = [slice_columns(sl) for sl in
                self.inst.tenants[tenant].event_store.measurements.scan()]
        if not cols:
            z = np.zeros((0,))
            return {"device": z.astype(np.int64), "value": z, "score": z,
                    "event_ts": z}
        dev = np.concatenate([
            np.asarray([int(str(t)[4:]) for t in c["tok"][0]],
                       np.int64)[c["tok"][1]] for c in cols])
        return {
            "device": dev,
            "value": np.concatenate([c["values"] for c in cols]),
            "score": np.concatenate([c["scores"] for c in cols]),
            "event_ts": np.concatenate([c["event_ts"] for c in cols]),
        }

    def outbound_rows(self, tenant: str) -> int:
        """Rows of one tenant that have left the last stage (outbound)."""
        return int(self.inst.tenants[tenant].outbound.connectors[0].batch_rows)

    def errors(self) -> list:
        return list(self.inst.errors) + list(self.inst.inference.errors)

    async def stop(self) -> None:
        await self.inst.terminate()


async def _wait_for(pred, timeout_s: float, what: str) -> None:
    t_end = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > t_end:
            raise TimeoutError(f"timed out after {timeout_s}s: {what}")
        await asyncio.sleep(0.02)


def _install_weights(inst, tenants: list, seed: int, hidden: int) -> None:
    """Put each tenant's seeded weights into its slot of its slice's
    stacked parameters (the stack a hot-swap writes one slot of)."""
    import jax

    svc = inst.inference
    for key, scorer in svc.scorers.items():
        host = jax.tree_util.tree_map(
            lambda x: np.array(x, np.float32), scorer.params)
        for i, tok in enumerate(tenants):
            p = svc.engines[tok].placement
            if (svc.engines[tok].config.model, p.shard) != key:
                continue
            w = ref.make_weights(seed, i, hidden)
            for layer, leaves in w.items():
                for leaf, value in leaves.items():
                    host[layer][leaf][p.slot] = value
        scorer.params = jax.tree_util.tree_map(
            lambda new, old: jax.device_put(new.astype(old.dtype),
                                            old.sharding),
            host, scorer.params)
        # the stack's cached kernel-layout copy has to follow; should a
        # later program have no such hook, the comparison with the
        # reference says whether the weights were taken
        invalidate = getattr(scorer, "_invalidate_kernel", None)
        if invalidate is not None:
            invalidate()
        else:
            print("note scorer has no _invalidate_kernel; weights were "
                  "written to scorer.params only", file=sys.stderr)


async def build(config: dict, seed: int, devices: list) -> System:
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.parallel.mesh import MeshManager
    from sitewhere_tpu.runtime.config import (
        InstanceConfig,
        MeshConfig,
        MicroBatchConfig,
    )

    model, mesh = config["model"], config["mesh"]
    info: dict = {}
    t0 = time.perf_counter()
    inst = SiteWhereInstance(
        InstanceConfig(
            instance_id="bench",
            mesh=MeshConfig(tenant_axis=mesh["tenant_axis"],
                            slots_per_shard=mesh["slots_per_shard"]),
            inference_max_inflight=config["inference_max_inflight"],
        ),
        mesh=MeshManager(tenant=mesh["tenant_axis"], data=1,
                         devices=devices[:mesh["tenant_axis"]]),
    )
    await inst.start()
    buckets = tuple(config["buckets"])
    mb = MicroBatchConfig(max_batch=buckets[-1],
                          deadline_ms=config["deadline_ms"],
                          buckets=buckets, window=model["window"])
    tenants = [tenant_token(i) for i in range(config["tenants"])]
    for tok in tenants:
        await inst.tenant_management.create_tenant(
            tok, template=config["template"], microbatch=mb,
            decoder=config["decoder"], max_streams=config["max_streams"],
            wire_dtype=config["wire_dtype"],
            param_dtype=model["param_dtype"],
            model_config={"hidden": model["hidden"]},
        )
    await inst.drain_tenant_updates()
    await _wait_for(lambda: len(inst.tenants) == len(tenants), 300.0,
                    "tenants to start")
    info["tenants_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for tok in tenants:
        inst.tenants[tok].device_management.bootstrap_fleet(
            config["devices_per_tenant"])
    info["fleet_s"] = time.perf_counter() - t0
    _install_weights(inst, tenants, seed, model["hidden"])
    t0 = time.perf_counter()
    await asyncio.get_running_loop().run_in_executor(
        None, inst.inference.prewarm)
    info["prewarm_s"] = time.perf_counter() - t0
    return System(inst, tenants, info)


class Reference:
    """The family's plain reference, as ``benchmark/checks/`` use it;
    with ``control`` the same computed in fp8 — the control."""

    def __init__(self, config: dict, seed: int, control: bool = False):
        self.config, self.seed, self.control = config, seed, control
        self.window = config["model"]["window"]

    def wire(self, values: np.ndarray) -> np.ndarray:
        """Published f32 values as the configured wire delivers them."""
        if self.config["wire_dtype"] == "bf16":
            return ref.bf16_wire(values)
        return values.astype(np.float32)

    def score(self, tenant_index: int, windows: np.ndarray) -> np.ndarray:
        w = ref.make_weights(self.seed, tenant_index,
                             self.config["model"]["hidden"])
        return ref.score_windows(
            w, windows, rounder=ref.fp8_rounder() if self.control else None)


def reference(config: dict, seed: int, control: bool = False) -> Reference:
    return Reference(config, seed, control)
