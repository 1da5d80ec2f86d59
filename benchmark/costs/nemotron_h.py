"""Nemotron-H stream scorer, per scored row (one token) on this chip's
share, from the configuration's ``model`` block (published keys):

  M layer   2 x H x (d_in + conv_dim + heads) + 2 x d_in x H   projections
            + 6 x heads x head_dim x state                      state update + read-out
  E layer   2 x H x experts (router) + 4 x H x shared_width
            + top_k x held / experts x 4 x H x expert_width     the held pairs
  * layer   2 x H x (2 q + 2 kv) + 4 x q x positions attended
  head      2 x H x vocab

Bytes: a row reads and writes its stream's state; a flush reads, once,
the weights every row shares (mixers, routers, shared experts,
attention, the head — the embedding is one row a token) and the weights
of the DISTINCT held experts its rows hit. ``step_cost`` sees rows and
flushes only, so it takes the expected distinct count under uniform
routing; the reader that has the step's own counter
(``metrics/moe_roofline.py``) gives ``moe_cost`` that instead.
"""

from __future__ import annotations

BF16 = 2


def _sizes(model: dict) -> dict:
    h = model["hidden_size"]
    d_in = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv_dim = d_in + 2 * model["n_groups"] * model["ssm_state_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    lo, hi = model["experts_held"]
    pat = model["pattern"]
    return {
        "h": h, "d_in": d_in, "conv_dim": conv_dim, "q": q, "kv": kv,
        "held": hi - lo, "n_m": pat.count("M"), "n_e": pat.count("E"),
        "n_a": pat.count("*"),
        "ssm": model["mamba_num_heads"] * model["mamba_head_dim"]
        * model["ssm_state_size"],
    }


def expert_params(model: dict) -> int:
    """Parameters of ONE routed expert (up + down)."""
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def mixer_params(model: dict) -> int:
    s = _sizes(model)
    return (s["h"] * (s["d_in"] + s["conv_dim"] + model["mamba_num_heads"])
            + s["d_in"] * s["h"])


def state_bytes_per_stream(model: dict) -> tuple:
    """(state-space + convolution bytes over the M layers, ring bytes
    over the * layers, the last hidden vector and position)."""
    s = _sizes(model)
    ssm = s["n_m"] * 4 * (s["ssm"] + (model["conv_kernel"] - 1) * s["conv_dim"])
    ring = s["n_a"] * 2 * model["context_positions"] * s["kv"] * BF16
    return ssm, ring, 4 * s["h"] + 4


def moe_cost(model: dict, pairs_held: float, experts_hit: float) -> tuple:
    """(FLOPs, bytes) of the grouped products over the held experts:
    ``pairs_held`` (row, expert) pairs fell on held experts and
    ``experts_hit`` distinct experts were hit (both summed over layers
    and steps, from the step's own counters). A second pair on an expert
    reads no more weights."""
    h, width = model["hidden_size"], model["moe_intermediate_size"]
    flops = pairs_held * 2.0 * expert_params(model)
    nbytes = experts_hit * expert_params(model) * BF16
    nbytes += pairs_held * (2 * h * BF16 + 2 * width * 4 + h * 4)
    return flops, nbytes


def shared_cost(model: dict, rows: float, steps: float) -> tuple:
    """(FLOPs, bytes) of the routers and the shared experts."""
    s = _sizes(model)
    shared = 2 * s["h"] * model["moe_shared_expert_intermediate_size"]
    router = s["h"] * model["n_routed_experts_published"]
    flops = s["n_e"] * rows * 2.0 * (shared + router)
    return flops, steps * s["n_e"] * (shared * BF16 + router * 4)


def ssm_cost(model: dict, rows: float, steps: float) -> tuple:
    """(FLOPs, bytes) of the state-space mixers' input projection and
    state: the projection's weights once a step, each row's state read
    and written. (The output projection is counted with the step.)"""
    s = _sizes(model)
    w_in = s["h"] * (s["d_in"] + s["conv_dim"] + model["mamba_num_heads"])
    flops = s["n_m"] * rows * (2.0 * w_in + 6.0 * s["ssm"])
    ssm_bytes, _ring, _y = state_bytes_per_stream(model)
    return flops, steps * s["n_m"] * w_in * BF16 + rows * 2.0 * ssm_bytes


def expected_experts_hit(model: dict, rows_per_step: float) -> float:
    """Distinct held experts a layer's step hits with that many rows, if
    routing is uniform."""
    s = _sizes(model)
    miss = 1.0 - model["num_experts_per_tok"] / model[
        "n_routed_experts_published"]
    return s["held"] * (1.0 - miss ** rows_per_step)


def step_cost(model: dict, wire: dict, valid_rows: float, flushes: float,
              slots_used_per_flush: float) -> tuple:
    s = _sizes(model)
    rows, steps = valid_rows, flushes
    held_share = s["held"] / model["n_routed_experts_published"]
    pairs_held = rows * s["n_e"] * model["num_experts_per_tok"] * held_share
    hit = (steps * s["n_e"] * expected_experts_hit(model, rows / steps)
           if steps else 0.0)
    moe_f, moe_b = moe_cost(model, pairs_held, hit)
    shared_f, shared_b = shared_cost(model, rows, steps)
    ssm_f, ssm_b = ssm_cost(model, rows, steps)
    w_out = s["d_in"] * s["h"]
    ssm_f += s["n_m"] * rows * 2.0 * w_out
    ssm_b += steps * s["n_m"] * w_out * BF16
    _ssm, ring, y = state_bytes_per_stream(model)
    attn_w = s["h"] * (2 * s["q"] + 2 * s["kv"])
    attn_f = s["n_a"] * rows * (
        2.0 * attn_w + 4.0 * s["q"] * model["context_positions"])
    head_f = rows * 2.0 * s["h"] * model["vocab_size"]
    flops = moe_f + shared_f + ssm_f + attn_f + head_f
    nbytes = moe_b + shared_b + ssm_b
    nbytes += steps * (s["n_a"] * attn_w + s["h"] * model["vocab_size"]) * BF16
    nbytes += rows * (ring + 2.0 * y + s["h"] * BF16)
    nbytes += rows * (wire["id_bytes"] + wire["value_bytes"]
                      + wire["score_bytes"])
    return flops, nbytes
