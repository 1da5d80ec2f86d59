"""LFM2-MoE stream scorer, per scored row (one token) on this chip's
share, from the configuration's ``model`` block (published keys):

  C layer     2 x H x 3 H + 2 x H x H                   W_in, W_out
              + (2 x taps + 3) x H                      gates and taps
  A layer     2 x H x (2 q + 2 kv) + 4 x q x positions  projections; scores
                                                        and weighted sums
  D layer     6 x H x dense_width                       gate | up, down
  E layer     2 x H x experts (router)
              + top_k x held / experts x 6 x H x width  the held pairs
  head        2 x H x vocab                             the tied embedding

Bytes: a flush reads, once, the weights every row shares (mixers, dense
MLPs, routers, the head — the embedding, read whole as the head) and the
weights of the DISTINCT held experts its rows hit; a row reads and writes
its convolution tails, reads the keys and values it attends (2 x kv x
head_dim x 2 bytes a position a layer) and writes one position a layer.
``step_cost`` sees rows and flushes only, so it takes the expected
distinct experts under uniform routing and leaves out the positions
attended (at this family's cell under 1% of a step's bytes); the readers
that have the step's own counters give ``moe_cost``, ``attn_cost`` and
``shortconv_cost`` those instead.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def _sizes(model: dict) -> dict:
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    lo, hi = model["experts_held"]
    return {
        "h": model["hidden_size"], "q": q, "kv": kv, "held": hi - lo,
        "n_c": model["mixers"].count("C"), "n_a": model["mixers"].count("A"),
        "n_d": model["pattern"].count("D"), "n_e": model["pattern"].count("E"),
        "position": 2 * kv * BF16, "taps": model["conv_L_cache"],
    }


def expert_params(model: dict) -> int:
    """Parameters of ONE routed expert: gate, up, down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def conv_params(model: dict) -> int:
    """Parameters of one gated short-convolution layer's projections."""
    return 4 * model["hidden_size"] ** 2


def attention_params(model: dict) -> int:
    s = _sizes(model)
    return s["h"] * (2 * s["q"] + 2 * s["kv"])


def shortconv_cost(model: dict, rows: float, steps: float,
                   tail_bytes: float) -> tuple:
    """(FLOPs, bytes) of the conv layers over ``steps`` calls of ``rows``
    real rows in all: the projections' weights and the taps once a call,
    the tails the calls read and wrote (the step's own counter), and a
    row's activations a layer — its normed input (bf16) in, the
    projection's three slices (f32) and its output (f32) out."""
    s = _sizes(model)
    h = s["h"]
    flops = s["n_c"] * rows * (2.0 * conv_params(model)
                               + (2 * s["taps"] + 3) * h)
    nbytes = steps * s["n_c"] * (conv_params(model) * BF16
                                 + s["taps"] * h * F32)
    nbytes += tail_bytes + s["n_c"] * rows * h * (BF16 + 3 * F32 + F32)
    return flops, nbytes


def attn_cost(model: dict, positions: float, token_layers: float) -> tuple:
    """(FLOPs, bytes) of the attention kernels: ``positions`` attended
    (summed over tokens and layers, the step's own counter) and
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
    and steps). A second pair on an expert reads no more weights; a
    pair's activations are its row in, gate and up out (f32), their
    product in and the row out."""
    h, width = model["hidden_size"], model["moe_intermediate_size"]
    flops = pairs_held * 2.0 * expert_params(model)
    nbytes = experts_hit * expert_params(model) * BF16
    nbytes += pairs_held * (h * BF16 + 2 * width * F32 + width * BF16
                            + h * F32)
    return flops, nbytes


def expected_experts_hit(model: dict, rows_per_step: float) -> float:
    """Distinct held experts a layer's step hits with that many rows, if
    routing is uniform."""
    s = _sizes(model)
    miss = 1.0 - model["num_experts_per_tok"] / model["num_experts"]
    return s["held"] * (1.0 - miss ** rows_per_step)


def step_cost(model: dict, wire: dict, valid_rows: float, flushes: float,
              slots_used_per_flush: float) -> tuple:
    s = _sizes(model)
    rows, steps = valid_rows, flushes
    h = s["h"]
    held_share = s["held"] / model["num_experts"]
    pairs_held = rows * s["n_e"] * model["num_experts_per_tok"] * held_share
    hit = (steps * s["n_e"] * expected_experts_hit(model, rows / steps)
           if steps else 0.0)
    moe_f, moe_b = moe_cost(model, pairs_held, hit)
    tails = rows * 2 * s["n_c"] * (s["taps"] - 1) * h * F32
    conv_f, conv_b = shortconv_cost(model, rows, steps, tails)
    attn_w = attention_params(model)
    dense = 3 * h * model["intermediate_size"]
    router = h * model["num_experts"]
    head = h * model["vocab_size"]
    flops = moe_f + conv_f + rows * 2.0 * (
        s["n_a"] * attn_w + s["n_d"] * dense + s["n_e"] * router + head)
    nbytes = moe_b + conv_b
    nbytes += steps * (BF16 * (s["n_a"] * attn_w + s["n_d"] * dense + head)
                       + F32 * s["n_e"] * router)
    nbytes += rows * (s["n_a"] * s["position"] + 2 * F32 * (h + 1) + h * BF16)
    nbytes += rows * (wire["id_bytes"] + wire["value_bytes"]
                      + wire["score_bytes"])
    return flops, nbytes
