"""AFMoE stream scorer, per scored row (one token) on this chip's share,
from the configuration's ``model`` block (published keys):

  attention   2 x H x (2 q + 2 kv) + 2 x q x H        q, k, v, gate, o
              + 4 x q x positions attended              scores, weighted sum
  D layer     6 x H x dense_width                       gate | up, down
  E layer     2 x H x experts (router) + 6 x H x width  the shared expert
              + top_k x held / experts x 6 x H x width  the held pairs
  head        2 x H x vocab

Bytes: a flush reads, once, the weights every row shares (attention, the
dense MLPs, routers, shared experts, the head — the embedding is one row a
token) and the weights of the DISTINCT held experts its rows hit; a row
reads the keys and values it attends (2 x kv x head_dim x 2 bytes a
position a layer) and writes one position a layer. ``step_cost`` sees
rows and flushes only, so it takes the expected distinct experts under
uniform routing and every attention layer at the window's positions;
the readers that have the step's own counters give ``moe_cost`` and
``attn_cost`` those instead.
"""

from __future__ import annotations

BF16 = 2


def _sizes(model: dict) -> dict:
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    lo, hi = model["experts_held"]
    return {
        "h": model["hidden_size"], "q": q, "kv": kv, "held": hi - lo,
        "n_d": model["pattern"].count("D"), "n_e": model["pattern"].count("E"),
        "n_a": len(model["attention"]), "position": 2 * kv * BF16,
    }


def expert_params(model: dict) -> int:
    """Parameters of ONE routed (or the shared) expert: gate, up, down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def attention_params(model: dict) -> int:
    s = _sizes(model)
    return s["h"] * (3 * s["q"] + 2 * s["kv"])


def attn_cost(model: dict, positions: float, token_layers: float) -> tuple:
    """(FLOPs, bytes) of the attention kernels: ``positions`` attended
    (summed over tokens and layers, the step's own counters) and
    ``token_layers`` queries (tokens x attention layers): each position's
    key and value read, each query and its output."""
    s = _sizes(model)
    flops = 4.0 * s["q"] * positions
    nbytes = positions * s["position"] + token_layers * 2 * s["q"] * BF16
    return flops, nbytes


def moe_cost(model: dict, pairs_held: float, experts_hit: float) -> tuple:
    """(FLOPs, bytes) of the grouped products over the held experts:
    ``pairs_held`` (row, expert) pairs fell on held experts and
    ``experts_hit`` distinct experts were hit (both summed over layers
    and steps, from the step's own counters). A second pair on an expert
    reads no more weights; a pair's activations are its row in, gate and
    up out (f32), their product in and the row out."""
    h, width = model["hidden_size"], model["moe_intermediate_size"]
    flops = pairs_held * 2.0 * expert_params(model)
    nbytes = experts_hit * expert_params(model) * BF16
    nbytes += pairs_held * (h * BF16 + 2 * width * 4 + width * BF16 + h * 4)
    return flops, nbytes


def expected_experts_hit(model: dict, rows_per_step: float) -> float:
    """Distinct held experts a layer's step hits with that many rows, if
    routing is uniform."""
    s = _sizes(model)
    miss = 1.0 - model["num_experts_per_tok"] / model["num_experts_published"]
    return s["held"] * (1.0 - miss ** rows_per_step)


def step_cost(model: dict, wire: dict, valid_rows: float, flushes: float,
              slots_used_per_flush: float) -> tuple:
    s = _sizes(model)
    rows, steps = valid_rows, flushes
    h = s["h"]
    held_share = s["held"] / model["num_experts_published"]
    pairs_held = rows * s["n_e"] * model["num_experts_per_tok"] * held_share
    hit = (steps * s["n_e"] * expected_experts_hit(model, rows / steps)
           if steps else 0.0)
    moe_f, moe_b = moe_cost(model, pairs_held, hit)
    window = model["sliding_window"]
    attn_f, attn_b = attn_cost(model, rows * s["n_a"] * window,
                               rows * s["n_a"])
    attn_w = attention_params(model)
    attn_f += rows * s["n_a"] * 2.0 * attn_w
    dense = 3 * h * model["intermediate_size"]
    shared = expert_params(model) + h * model["num_experts_published"]
    head = h * model["vocab_size"]
    flops = (moe_f + attn_f + rows * 2.0 * (
        s["n_d"] * dense + s["n_e"] * shared + head))
    nbytes = moe_b + attn_b
    nbytes += steps * BF16 * (s["n_a"] * attn_w + s["n_d"] * dense
                              + s["n_e"] * expert_params(model) + head)
    nbytes += steps * 4 * s["n_e"] * h * model["num_experts_published"]
    nbytes += rows * (s["n_a"] * s["position"] + 2 * 4 * (h + 1) + h * BF16)
    nbytes += rows * (wire["id_bytes"] + wire["value_bytes"]
                      + wire["score_bytes"])
    return flops, nbytes
