"""Builder for the Nemotron-H stream-scoring configurations: a started
``SiteWhereInstance`` serving ONE tenant whose model fills the chip,
through the product's own entry points (the same ``System`` surface the
LSTM builder gives the runner and the checks), with the weights of
``benchmark/reference/nemotron_h.py`` made from ``--seed`` and put into
the scorer's stack leaf by leaf — the chip never holds two sets.
"""

from __future__ import annotations

import asyncio
import sys
import time

import numpy as np

from benchmark.builders.lstm_ad_tenants import System, _wait_for
from benchmark.encoders.bulk_binary import tenant_token
from benchmark.reference import nemotron_h as ref


def model_config(model: dict) -> dict:
    """The program's ``model_config`` for a configuration's ``model``
    block (published keys -> ``NemotronHConfig`` fields)."""
    return {
        "pattern": model["pattern"],
        "hidden": model["hidden_size"],
        "vocab": model["vocab_size"],
        "mamba_heads": model["mamba_num_heads"],
        "mamba_head_dim": model["mamba_head_dim"],
        "n_groups": model["n_groups"],
        "ssm_state": model["ssm_state_size"],
        "conv_kernel": model["conv_kernel"],
        "chunk_size": model["chunk_size"],
        "n_experts": model["n_routed_experts_published"],
        "experts_held_lo": model["experts_held"][0],
        "experts_held_hi": model["experts_held"][1],
        "top_k": model["num_experts_per_tok"],
        "routed_scale": model["routed_scaling_factor"],
        "expert_width": model["moe_intermediate_size"],
        "shared_width": model["moe_shared_expert_intermediate_size"],
        "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"],
        "context_positions": model["context_positions"],
        "eps": model["norm_eps"],
        "dtype": model["compute_dtype"],
    }


def install_weights(scorer, weights: dict, slot: int = 0) -> None:
    """Write the reference's seeded weights into ``scorer.params`` in
    place, a leaf at a time: a matrix crosses as the int8 it was drawn as
    and is widened on the device (exact in bf16), and the leaf it
    replaces is dropped before the next is made."""
    import jax
    import jax.numpy as jnp

    def put(tree: dict, key: str, value) -> None:
        old = tree[key]
        q, scale = value if isinstance(value, tuple) else (value, None)

        @jax.jit
        def widen(q, old):
            # int8 -> bf16 and a power-of-two scale are both exact
            new = q.astype(old.dtype)
            if scale is not None:
                new = new * jnp.asarray(scale, old.dtype)
            # the program stores some leaves padded to whole lane tiles
            new = jnp.pad(new, [(0, a - b) for a, b in
                                zip(old.shape[1:], new.shape)])
            return new[None] if old.shape[0] == 1 else old.at[slot].set(new)

        tree[key] = jax.device_put(widen(jnp.asarray(q), old), old.sharding)

    params = scorer.params
    for key in ("embed", "head", "norm_f"):
        put(params, key, weights[key])
    for lp, lw in zip(params["layers"], weights["layers"]):
        for key, value in lw.items():
            put(lp, key, value)
    scorer._invalidate_kernel()


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
    """The family's plain reference as ``checks/stream_surprisal.py``
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

    def score(self, series: np.ndarray) -> np.ndarray:
        """f32[T]: the surprisal of every reading of one stream's whole
        series (int ids [T])."""
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
                return ref.surprisal(
                    self._weights, self.dims, series, self.control)
        return ref.surprisal(self._weights, self.dims, series, self.control)


def reference(config: dict, seed: int, control: bool = False) -> Reference:
    return Reference(config, seed, control)
