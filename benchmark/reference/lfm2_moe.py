"""Plain reference for the LFM2-MoE stream-scoring configurations: seeded
weights and the f32 forward pass over a stream's WHOLE series — the
short convolution over every gated input of the series, plain causal
attention over every earlier key, every held expert multiplied densely —
with no pages, no tails, no chunks and no batching.

It imports nothing of ``sitewhere_tpu`` and takes nothing the program has
made; what it shares with ``reference/afmoe.py`` (the arithmetic helpers,
the SwiGLU, the router, the dense and expert layers, RoPE, the head) is
that file's plain code. ``jax.numpy`` in float32 with every product at
``HIGHEST`` precision (on a TPU a float32 product otherwise runs in bf16
passes), on whatever device JAX gives it; a tiny size runs on the CPU in
tier-1.

Equations (LFM2-24B-A2B ``config.json``, ``model_type`` lfm2_moe, and
LiquidAI's LFM2 description). Layer i has a mixer (``C`` gated short
convolution / ``A`` attention) and an MLP (``D`` dense / ``E``
experts); RMSNorm eps 1e-5:

- input ``x = E[id]`` (no scale);
- ``h = x + Mixer(RMSNorm_op(x))``, ``x' = h + MLP(RMSNorm_ffn(h))``;
- ``C``: ``[B | C | x~] = W_in u``; ``z = B * x~``; ``y_t = sum_j w[j]
  z_{t-taps+1+j}`` (zeros before the series); ``W_out (C * y)``; no bias;
- ``A``: ``q, k, v = W_q u, W_k u, W_v u``; per-head RMSNorm of q and k
  (learned, over head_dim); rotate-half RoPE (theta, all dims) at the
  absolute position; softmax at 1/sqrt(d) over every earlier token;
  ``W_o o``;
- ``D``: ``W_down(silu(W_g u) * W_u u)``;
- ``E``: ``s = sigmoid(W_r u)``; top-k of ``s + bias``; gates ``s[top] /
  sum s[top] * scale``; SwiGLU experts; no shared expert. Only experts in
  ``held`` add anything: the share one chip of the deployment computes;
- a final RMSNorm and the head, which is the embedding (tied).

Departures from the published modelling: the head is tied to the
embedding (the config gives no ``tie_word_embeddings``; the LFM2 family
ties it: the configuration's ``assumed``); if the modelling code adds a
small epsilon to the gates' sum before it divides, it is left out here,
as in the program (four sigmoid scores sum to far more than any such
epsilon). No dropout, no padding mask: a series is one stream's readings.

The score of reading t is its surprisal in nats under the prediction made
BEFORE it: ``logsumexp(W_head y_{t-1}) - (W_head y_{t-1})[id_t]``,
``y_{-1} = 0`` (a first reading scores ``ln vocab``).

Blocks: the series is taken ``BLOCK`` readings at a time — each block's
queries over every earlier key and value, each block's convolution over
every earlier gated input, all kept in plain arrays of the series'
capacity. That is the same sum in another order of blocks; there is no
cache of the program's kind.

Weights: every matrix is drawn as int8 multiples of a power of two —
exact in bf16 and in f32 alike, so the program (bf16 weights) and this
reference (f32) multiply the SAME numbers and differ by the arithmetic
alone. ``[gate | up]`` of every SwiGLU is one matrix, the gate's columns
first. ``control=True`` computes the same in float8 e4m3 (weights and
every intermediate rounded) — one precision below the configuration's
bf16.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import afmoe as plain

BLOCK = 256  # readings of a series a pass


def dims(model: dict) -> dict:
    """Sizes by the names used here, from a configuration's ``model``
    block (published keys)."""
    return {
        "mixers": model["mixers"],
        "mlp": model["pattern"],
        "hidden": model["hidden_size"],
        "vocab": model["vocab_size"],
        "dense": model["intermediate_size"],
        "experts": model["num_experts"],
        "held": tuple(model["experts_held"]),
        "top_k": model["num_experts_per_tok"],
        "scale": model["routed_scaling_factor"],
        "width_e": model["moe_intermediate_size"],
        "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"],
        "head": model["head_dim"],
        "taps": model["conv_L_cache"],
        "theta": model["rope_theta"],
        "eps": model["norm_eps"],
    }


# ----------------------------------------------------------------- weights
def make_weights(seed: int, d: dict) -> dict:
    """Seeded weights: matrices as ``(int8 q, f32 scale)`` with value
    ``q * scale``, vectors f32. Expert matrices hold the HELD experts
    only (the chip's share), drawn per expert so that expert e's weights
    do not depend on which share holds it."""
    h, std, hd = d["hidden"], 0.02, d["head"]
    lo, hi = d["held"]
    jobs, out = [], {"layers": []}

    def rng(*tag):
        return np.random.default_rng([int(seed), 0x1F2, *tag])

    def matrix(where, key, shape, sigma, *tag):
        def draw():
            q = rng(*tag).integers(-127, 128, size=shape, dtype=np.int8)
            where[key] = (q, np.float32(plain._pow2_scale(sigma)))
        jobs.append(draw)

    def experts(where, key, shape, layer, tag):
        def draw():
            q = np.empty((hi - lo,) + shape, np.int8)
            for e in range(lo, hi):
                q[e - lo] = rng(layer, tag, e).integers(
                    -127, 128, size=shape, dtype=np.int8)
            where[key] = (q, np.float32(plain._pow2_scale(std)))
        jobs.append(draw)

    def vector(shape, sigma, mean, *tag):
        return (mean + sigma * rng(*tag).standard_normal(shape)).astype(
            np.float32)

    q, kv = d["heads"] * hd, d["kv"] * hd
    for i, (mixer, mlp) in enumerate(zip(d["mixers"], d["mlp"])):
        lw = {"op_norm": vector((h,), 0.1, 1.0, i, 0),
              "ffn_norm": vector((h,), 0.1, 1.0, i, 1)}
        if mixer == "C":
            matrix(lw, "w_in", (h, 3 * h), std, i, 2)
            lw["conv_w"] = vector((d["taps"], h), 0.5, 0.0, i, 3)
            matrix(lw, "w_out", (h, h), std, i, 4)
        else:
            matrix(lw, "wq", (h, q), std, i, 5)
            matrix(lw, "wk", (h, kv), std, i, 6)
            matrix(lw, "wv", (h, kv), std, i, 7)
            matrix(lw, "wo", (q, h), std, i, 8)
            lw["q_norm"] = vector((hd,), 0.1, 1.0, i, 9)
            lw["k_norm"] = vector((hd,), 0.1, 1.0, i, 10)
        if mlp == "D":
            matrix(lw, "w_gu", (h, 2 * d["dense"]), std, i, 11)
            matrix(lw, "w_down", (d["dense"], h), std, i, 12)
        else:
            matrix(lw, "router", (h, d["experts"]), std, i, 13)
            lw["e_bias"] = vector((d["experts"],), 0.02, 0.0, i, 14)
            experts(lw, "gu", (h, 2 * d["width_e"]), i, 15)
            experts(lw, "down", (d["width_e"], h), i, 16)
        out["layers"].append(lw)
    matrix(out, "embed", (d["vocab"], h), std, 100)
    out["norm_f"] = vector((h,), 0.1, 1.0, 102)
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(lambda job: job(), jobs))
    return out


# ----------------------------------------------------------------- forward
def short_conv_block(u, lw, d, inputs, start, control=False):
    """One block of rows u [B, H] at positions start.. of a C layer over
    the series' gated inputs [taps - 1 + C, H] (the first taps - 1 rows
    zeros; this block's written first). Returns (out [B, H], inputs')."""
    jax, jnp, r, wide, mm = plain._tools(control)
    b, h, taps = u.shape[0], d["hidden"], d["taps"]
    bcx = mm(u, wide(lw["w_in"]))
    z = r(bcx[:, :h] * bcx[:, 2 * h:])
    inputs = jax.lax.dynamic_update_slice(inputs, z, (start + taps - 1, 0))
    seen = jax.lax.dynamic_slice(inputs, (start, 0), (b + taps - 1, h))
    w = jnp.asarray(lw["conv_w"])
    y = sum(w[j][None, :] * seen[j:j + b] for j in range(taps))
    return mm(r(bcx[:, h:2 * h] * r(y)), wide(lw["w_out"])), inputs


def attention_block(u, lw, d, keys, values, start, control=False):
    """One block of queries u [B, H] at positions start.. over the
    series' keys and values [C, kv, d] (this block's written first).
    Returns (out [B, H], keys', values')."""
    jax, jnp, r, wide, mm = plain._tools(control)
    b = u.shape[0]
    nq, nkv, hd = d["heads"], d["kv"], d["head"]
    pos = start + jnp.arange(b)
    q = plain._rms(jnp, mm(u, wide(lw["wq"])).reshape(b, nq, hd),
                   jnp.asarray(lw["q_norm"]), d["eps"])
    k = plain._rms(jnp, mm(u, wide(lw["wk"])).reshape(b, nkv, hd),
                   jnp.asarray(lw["k_norm"]), d["eps"])
    v = mm(u, wide(lw["wv"])).reshape(b, nkv, hd)
    q = r(plain.rope(q, pos, d["theta"]))
    k = r(plain.rope(k, pos, d["theta"]))
    keys = jax.lax.dynamic_update_slice(keys, k, (start, 0, 0))
    values = jax.lax.dynamic_update_slice(values, v, (start, 0, 0))
    hi = jax.lax.Precision.HIGHEST
    qg = q.reshape(b, nkv, nq // nkv, hd)
    s = jnp.einsum("tgqd,sgd->gqts", qg, keys, precision=hi) / math.sqrt(hd)
    seen = jnp.arange(keys.shape[0])[None, :] <= pos[:, None]
    w = r(jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1))
    o = r(jnp.einsum("gqts,sgd->tgqd", w, values, precision=hi))
    return mm(o.reshape(b, nq * hd), wide(lw["wo"])), keys, values


_COMPILED: dict = {}


def _block_fn(d, capacity, control):
    """The compiled pass over one block: (weights, caches, ids [B],
    start) -> (caches', final-norm hidden [B, H])."""
    key = (tuple(sorted((k, v) for k, v in d.items())), capacity, control)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn
    jax, jnp, r, wide, mm = plain._tools(control)

    def block(weights, caches, toks, start):
        q, scale = weights["embed"]
        x = r(jnp.asarray(q)[toks].astype(jnp.float32) * scale)
        new = []
        for mixer, mlp, lw, cache in zip(
                d["mixers"], d["mlp"], weights["layers"], caches):
            u = r(plain._rms(jnp, x, jnp.asarray(lw["op_norm"]), d["eps"]))
            if mixer == "C":
                a, cache = short_conv_block(u, lw, d, cache, start, control)
            else:
                a, keys, values = attention_block(
                    u, lw, d, *cache, start, control)
                cache = (keys, values)
            new.append(cache)
            x = r(x + a)
            u = r(plain._rms(jnp, x, jnp.asarray(lw["ffn_norm"]), d["eps"]))
            m = (plain.dense_layer(u, lw, d, control) if mlp == "D"
                 else plain.moe_layer(u, lw, d, control, shared=False))
            x = r(x + m)
        return new, r(plain._rms(jnp, x, jnp.asarray(weights["norm_f"]),
                                 d["eps"]))

    fn = _COMPILED[key] = jax.jit(block, donate_argnums=1)
    return fn


def surprisal(weights, d, toks, control=False, capacity=None):
    """f32[T]: the score of every reading of one series (int ids), taken
    ``BLOCK`` readings a pass. ``capacity`` (a multiple of ``BLOCK`` no
    shorter than the series) fixes the shapes: one compile serves every
    series of a check."""
    import jax.numpy as jnp

    toks = np.asarray(toks, np.int32)
    t = len(toks)
    if capacity is None:
        block = min(BLOCK, -(-t // 8) * 8)
        capacity = -(-t // block) * block
    else:
        block = min(BLOCK, capacity)
    assert capacity % block == 0 and capacity >= t, (capacity, t)
    run = _block_fn({**d, "block": block}, capacity, control)
    shape = (capacity, d["kv"], d["head"])
    caches = [
        jnp.zeros((d["taps"] - 1 + capacity, d["hidden"]), jnp.float32)
        if mixer == "C" else
        (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
        for mixer in d["mixers"]]
    padded = np.zeros(-(-t // block) * block, np.int32)
    padded[:t] = toks
    ys = []
    for start in range(0, len(padded), block):
        caches, y = run(weights, caches,
                        jnp.asarray(padded[start:start + block]),
                        jnp.int32(start))
        ys.append(y)
    y = jnp.concatenate(ys)[:t]
    before = jnp.concatenate([jnp.zeros_like(y[:1]), y[:-1]])
    q, scale = weights["embed"]
    return np.asarray(plain._head_fn(d, control)(
        (jnp.asarray(q).T, scale), before, jnp.asarray(toks)))
