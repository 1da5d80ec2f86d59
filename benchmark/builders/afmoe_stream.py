"""Builder for the AFMoE stream-scoring configurations: a started
``SiteWhereInstance`` serving ONE tenant whose model fills the chip,
through the product's own entry points, with the weights of
``benchmark/reference/afmoe.py`` made from ``--seed`` and put into the
scorer's stack leaf by leaf (``nemotron_h_stream.install_weights``: the
program keeps the reference's leaf names and shapes).
"""

from __future__ import annotations

import asyncio
import sys
import time

import numpy as np

from benchmark.builders.lstm_ad_tenants import System, _wait_for
from benchmark.builders.nemotron_h_stream import install_weights
from benchmark.encoders.bulk_binary import tenant_token
from benchmark.reference import afmoe as ref


def model_config(model: dict) -> dict:
    """The program's ``model_config`` for a configuration's ``model``
    block (published keys -> ``AfmoeConfig`` fields)."""
    return {
        "attention": model["attention"],
        "mlp": model["pattern"],
        "hidden": model["hidden_size"],
        "vocab": model["vocab_size"],
        "dense_width": model["intermediate_size"],
        "chunk_size": model["chunk_size"],
        "n_experts": model["num_experts_published"],
        "experts_held_lo": model["experts_held"][0],
        "experts_held_hi": model["experts_held"][1],
        "top_k": model["num_experts_per_tok"],
        "routed_scale": model["route_scale"],
        "expert_width": model["moe_intermediate_size"],
        "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"],
        "sliding_window": model["sliding_window"],
        "rope_theta": model["rope_theta"],
        "page_size": model["page_size"],
        "pool_pages": model["pool_pages"],
        "full_context_positions": model["full_context_positions"],
        "eps": model["rms_norm_eps"],
        "dtype": model["compute_dtype"],
    }


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
                          buckets=buckets, window=1)
    tenants = [tenant_token(i) for i in range(config["tenants"])]
    for tok in tenants:
        await inst.tenant_management.create_tenant(
            tok, template=config["template"], microbatch=mb,
            decoder=config["decoder"], max_streams=config["max_streams"],
            wire_dtype=config["wire_dtype"],
            model_config=model_config(model),
            rule_min_score=config["rule"]["min_score"],
        )
    await inst.drain_tenant_updates()
    await _wait_for(lambda: len(inst.tenants) == len(tenants), 600.0,
                    "tenants to start")
    info["tenants_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for tok in tenants:
        inst.tenants[tok].device_management.bootstrap_fleet(
            config["devices_per_tenant"])
    info["fleet_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    weights = ref.make_weights(seed, ref.dims(model))
    svc = inst.inference
    for tok in tenants:
        p = svc.engines[tok].placement
        install_weights(svc.scorers[(model["family"], p.shard)], weights,
                        p.slot)
    del weights
    info["weights_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    await asyncio.get_running_loop().run_in_executor(
        None, inst.inference.prewarm)
    info["prewarm_s"] = time.perf_counter() - t0
    return System(inst, tenants, info)


class Reference:
    """The family's plain reference as ``checks/stream_surprisal_aged.py``
    uses it; with ``control`` the same computed in fp8."""

    def __init__(self, config: dict, seed: int, control: bool = False):
        self.config, self.seed, self.control = config, seed, control
        self.dims = ref.dims(config["model"])
        self.window = 1  # no window: ``scored_events``' comparison is idle
        self._weights, self._host = None, False

    def tokens(self, values: np.ndarray) -> np.ndarray:
        """Published f32 values as the model meets them: the id."""
        return np.clip(np.rint(values), 0, self.dims["vocab"] - 1).astype(
            np.int32)

    def score(self, series: np.ndarray, capacity: int = None) -> np.ndarray:
        """f32[T]: the surprisal of every reading of one stream's whole
        series (int ids [T]); ``capacity`` as ``ref.surprisal`` takes it."""
        import jax

        if self._weights is None:
            self._weights = ref.make_weights(self.seed, self.dims)
            try:
                self._weights = jax.device_put(self._weights)
            except Exception as exc:  # noqa: BLE001 - no room beside what
                # the torn-down program still holds: the host computes it
                print(f"note reference weights stay on the host: {exc!r}"[:300],
                      file=sys.stderr)
                self._host = True
        if self._host:
            with jax.default_device(jax.devices("cpu")[0]):
                return ref.surprisal(self._weights, self.dims, series,
                                     self.control, capacity)
        return ref.surprisal(self._weights, self.dims, series, self.control,
                             capacity)


def reference(config: dict, seed: int, control: bool = False) -> Reference:
    return Reference(config, seed, control)
