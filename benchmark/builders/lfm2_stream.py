"""Builder for the LFM2-MoE stream-scoring configurations: a started
``SiteWhereInstance`` serving ONE tenant whose model fills the chip,
through the product's own entry points, with the weights of
``benchmark/reference/lfm2_moe.py`` made from ``--seed`` and put into the
scorer's stack leaf by leaf.

What it does is what ``afmoe_stream.py`` does, and it runs that file's
own code: ``build`` and ``Reference`` are its functions with the names
that differ — the ``model_config`` of the configuration's model block,
the reference module and the weights' installer — bound to this
family's. Importing this file fails at once where the program has no
``lfm2_moe`` family.
"""

from __future__ import annotations

import types

import numpy as np

from benchmark.builders import afmoe_stream, nemotron_h_stream
from benchmark.rebind import rebound
from benchmark.reference import lfm2_moe as ref
from sitewhere_tpu.models import lfm2_moe  # noqa: F401 - fails fast without


def model_config(model: dict) -> dict:
    """The program's ``model_config`` for a configuration's ``model``
    block (published keys -> ``Lfm2MoeConfig`` fields)."""
    return {
        "mixers": model["mixers"],
        "mlp": model["pattern"],
        "hidden": model["hidden_size"],
        "vocab": model["vocab_size"],
        "dense_width": model["intermediate_size"],
        "chunk_size": model["chunk_size"],
        "n_experts": model["num_experts"],
        "experts_held_lo": model["experts_held"][0],
        "experts_held_hi": model["experts_held"][1],
        "top_k": model["num_experts_per_tok"],
        "routed_scale": model["routed_scaling_factor"],
        "expert_width": model["moe_intermediate_size"],
        "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"],
        "conv_taps": model["conv_L_cache"],
        "rope_theta": model["rope_theta"],
        "page_size": model["page_size"],
        "pool_pages": model["pool_pages"],
        "full_context_positions": model["full_context_positions"],
        "eps": model["norm_eps"],
        "dtype": model["compute_dtype"],
    }


def install_weights(scorer, weights: dict, slot: int = 0) -> None:
    """``nemotron_h_stream.install_weights`` for a head tied to the
    embedding: that function writes ``embed``, ``head`` and ``norm_f`` by
    name, and neither the program nor the reference has a ``head`` leaf,
    so it runs on a view of the scorer whose ``head`` is a 1 x 1 stand-in
    (widened from one int8 zero, then dropped); every other leaf is
    written where it lives."""
    import jax.numpy as jnp

    view = types.SimpleNamespace(
        params={**scorer.params, "head": jnp.zeros((1, 1), jnp.bfloat16)},
        _invalidate_kernel=scorer._invalidate_kernel)
    nemotron_h_stream.install_weights(
        view, {**weights, "head": np.zeros((1,), np.int8)}, slot)
    scorer.params.update(embed=view.params["embed"],
                         norm_f=view.params["norm_f"])


build, = rebound({"model_config": model_config, "ref": ref,
                  "install_weights": install_weights}, afmoe_stream.build)


class Reference(afmoe_stream.Reference):
    """The family's plain reference as ``checks/stream_surprisal_aged.py``
    uses it; with ``control`` the same computed in fp8."""

    __init__, score = rebound({"ref": ref}, afmoe_stream.Reference.__init__,
                              afmoe_stream.Reference.score)


def reference(config: dict, seed: int, control: bool = False) -> Reference:
    return Reference(config, seed, control)
