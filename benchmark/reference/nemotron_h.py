"""Plain reference for the Nemotron-H stream-scoring configurations:
seeded weights and the f32 forward pass over a stream's WHOLE series —
a sequential state-space recurrence, full causal attention, every expert
multiplied densely — with no cache, no chunks and no batching.

It imports nothing of ``sitewhere_tpu`` and takes nothing the program has
made. ``jax.numpy`` in float32 with every product at ``HIGHEST``
precision (on a TPU a float32 product otherwise runs in bf16 passes), on
whatever device JAX gives it; a tiny size runs on the CPU in tier-1.

Equations (NVIDIA-Nemotron-3-Nano-30B-A3B ``config.json``; Nemotron-H,
arXiv:2504.03624; Mamba-2, arXiv:2405.21060), block by pattern letter,
each ``x <- x + Mixer(RMSNorm(x))`` with eps 1e-5, then a final RMSNorm:

- ``M``: ``[z | xBC | dt] = W_in u``; ``xBC_t = silu(sum_k w[k] xBC_{t-3+k}
  + b)`` (zeros before the series); split x (heads x head_dim), B, C
  (groups x state; head h reads group h // (heads / groups));
  ``D = softplus(dt + dt_bias)``; ``S <- exp(-D e^{A_log}) S + D x (x) B``;
  ``y = S C + Dskip x``; ``y <- RMSNorm_group(y silu(z)) w``; ``W_out y``.
- ``E``: ``s = sigmoid(W_r u)``; top-k of ``s + bias``; gates ``s[top] /
  sum s[top] * scale``; expert ``W_down relu(W_up u)^2``; plus one shared
  expert of the same form. Only experts in ``held`` add anything: the
  share one chip of the deployment computes.
- ``*``: grouped-query attention, no bias, no positional embedding,
  causal softmax at 1/sqrt(head_dim) over the last ``context`` positions.

The score of reading t is its surprisal in nats under the prediction
made BEFORE it: ``logsumexp(W_head y_{t-1}) - (W_head y_{t-1})[id_t]``,
``y_{-1} = 0`` (so a first reading scores ``ln vocab``).

Weights: every matrix is drawn as int8 multiples of a power of two —
exact in bf16 and in f32 alike, so the program (bf16 weights) and this
reference (f32) multiply the SAME numbers and differ by the arithmetic
alone; they are kept as drawn (int8 + scale) and widened where used.
Vectors are f32. ``time_step_min/max/floor`` shape ``dt_bias`` only.

``control=True`` computes the same in float8 e4m3 (weights and every
intermediate rounded) — one precision below the configuration's bf16.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def dims(model: dict) -> dict:
    """Sizes by the names used here, from a configuration's ``model``
    block (published keys)."""
    return {
        "pattern": model["pattern"],
        "hidden": model["hidden_size"],
        "vocab": model["vocab_size"],
        "heads_m": model["mamba_num_heads"],
        "head_m": model["mamba_head_dim"],
        "groups": model["n_groups"],
        "state": model["ssm_state_size"],
        "taps": model["conv_kernel"],
        "experts": model["n_routed_experts_published"],
        "held": tuple(model["experts_held"]),
        "top_k": model["num_experts_per_tok"],
        "scale": model["routed_scaling_factor"],
        "width_e": model["moe_intermediate_size"],
        "width_s": model["moe_shared_expert_intermediate_size"],
        "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"],
        "head": model["head_dim"],
        "context": model["context_positions"],
        "eps": model["norm_eps"],
        "dt_min": model["time_step_min"],
        "dt_max": model["time_step_max"],
        "dt_floor": model["time_step_floor"],
    }


# ----------------------------------------------------------------- weights
def _pow2_scale(std: float) -> float:
    """The power of two that gives uniform int8 draws about ``std``."""
    return 2.0 ** round(math.log2(std / 73.3))


def make_weights(seed: int, d: dict) -> dict:
    """Seeded weights: matrices as ``(int8 q, f32 scale)`` with value
    ``q * scale``, vectors f32. Expert matrices hold the HELD experts
    only (the chip's share), drawn per expert so that expert e's weights
    do not depend on which share holds it."""
    h, std = d["hidden"], 0.02
    d_in = d["heads_m"] * d["head_m"]
    conv_dim = d_in + 2 * d["groups"] * d["state"]
    lo, hi = d["held"]
    jobs, out = [], {"layers": []}

    def rng(*tag):
        return np.random.default_rng([int(seed), 0x4E48, *tag])

    def matrix(where, key, shape, sigma, *tag):
        def draw():
            q = rng(*tag).integers(-127, 128, size=shape, dtype=np.int8)
            where[key] = (q, np.float32(_pow2_scale(sigma)))
        jobs.append(draw)

    def experts(where, key, shape, layer, tag):
        def draw():
            q = np.empty((hi - lo,) + shape, np.int8)
            for e in range(lo, hi):
                q[e - lo] = rng(layer, tag, e).integers(
                    -127, 128, size=shape, dtype=np.int8)
            where[key] = (q, np.float32(_pow2_scale(std)))
        jobs.append(draw)

    def vector(shape, sigma, mean, *tag):
        return (mean + sigma * rng(*tag).standard_normal(shape)).astype(
            np.float32)

    for i, kind in enumerate(d["pattern"]):
        lw = {"norm": vector((h,), 0.1, 1.0, i, 0)}
        if kind == "M":
            nh = d["heads_m"]
            step = np.exp(rng(i, 1).uniform(
                math.log(d["dt_min"]), math.log(d["dt_max"]), nh))
            step = np.maximum(step, d["dt_floor"])
            matrix(lw, "w_in", (h, d_in + conv_dim + nh), std, i, 2)
            lw["conv_w"] = vector((d["taps"], conv_dim), 0.5, 0.0, i, 3)
            lw["conv_b"] = vector((conv_dim,), 0.1, 0.0, i, 4)
            lw["dt_bias"] = (step + np.log(-np.expm1(-step))).astype(np.float32)
            lw["a_log"] = np.log(rng(i, 5).uniform(1.0, 16.0, nh)).astype(
                np.float32)
            lw["d"] = vector((nh,), 0.1, 1.0, i, 6)
            lw["norm_g"] = vector((d_in,), 0.1, 1.0, i, 7)
            matrix(lw, "w_out", (d_in, h), std, i, 8)
        elif kind == "E":
            matrix(lw, "router", (h, d["experts"]), std, i, 1)
            lw["e_bias"] = vector((d["experts"],), 0.02, 0.0, i, 2)
            experts(lw, "up", (h, d["width_e"]), i, 3)
            experts(lw, "down", (d["width_e"], h), i, 4)
            matrix(lw, "s_up", (h, d["width_s"]), std, i, 5)
            matrix(lw, "s_down", (d["width_s"], h), std, i, 6)
        else:
            q, kv = d["heads"] * d["head"], d["kv"] * d["head"]
            matrix(lw, "wq", (h, q), std, i, 1)
            matrix(lw, "wk", (h, kv), std, i, 2)
            matrix(lw, "wv", (h, kv), std, i, 3)
            matrix(lw, "wo", (q, h), std, i, 4)
        out["layers"].append(lw)
    matrix(out, "embed", (d["vocab"], h), 1.0, 100)
    matrix(out, "head", (h, d["vocab"]), std, 101)
    out["norm_f"] = vector((h,), 0.1, 1.0, 102)
    with ThreadPoolExecutor(8) as pool:
        list(pool.map(lambda job: job(), jobs))
    return out


# ----------------------------------------------------------------- forward
def _tools(control: bool):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if control:
        def r(a):
            return jnp.clip(a, -448.0, 448.0).astype(
                jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        def r(a):
            return a

    def wide(w):
        """A matrix as drawn -> f32 (rounded, in the control)."""
        q, scale = w
        return r(jnp.asarray(q).astype(jnp.float32) * scale)

    def mm(a, b):
        return r(jnp.matmul(a, b, precision=hi))

    return jax, jnp, r, wide, mm


def _rms(jnp, x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba_layer(u, lw, d, control=False):
    """u f32[T, H] -> the mixer's output f32[T, H]; the recurrence one
    token at a time from a zero state."""
    jax, jnp, r, wide, mm = _tools(control)
    nh, hd, g, n = d["heads_m"], d["head_m"], d["groups"], d["state"]
    d_in, t_len = nh * hd, u.shape[0]
    conv_dim = d_in + 2 * g * n
    zxbcdt = mm(u, wide(lw["w_in"]))
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + conv_dim],
                  zxbcdt[:, d_in + conv_dim:])
    taps = d["taps"]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, conv_dim), jnp.float32), xbc])
    acc = jnp.asarray(lw["conv_b"])[None, :]
    for k in range(taps):
        acc = acc + jnp.asarray(lw["conv_w"])[k][None, :] * padded[k:k + t_len]
    xbc = r(jax.nn.silu(acc))
    x = xbc[:, :d_in].reshape(t_len, nh, hd)
    b = jnp.repeat(xbc[:, d_in:d_in + g * n].reshape(t_len, g, n),
                   nh // g, axis=1)
    c = jnp.repeat(xbc[:, d_in + g * n:].reshape(t_len, g, n),
                   nh // g, axis=1)
    delta = jax.nn.softplus(dt + jnp.asarray(lw["dt_bias"]))
    decay = jnp.exp(-delta * jnp.exp(jnp.asarray(lw["a_log"])))

    def one(s, inp):
        x_t, b_t, c_t, delta_t, decay_t = inp
        s = r(decay_t[:, None, None] * s
              + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _s, y = jax.lax.scan(
        one, jnp.zeros((nh, hd, n), jnp.float32), (x, b, c, delta, decay))
    y = r(y + jnp.asarray(lw["d"])[None, :, None] * x).reshape(t_len, d_in)
    gated = (y * jax.nn.silu(z)).reshape(t_len, g, d_in // g)
    gated = gated / jnp.sqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + d["eps"])
    gated = r(gated.reshape(t_len, d_in) * jnp.asarray(lw["norm_g"]))
    return mm(gated, wide(lw["w_out"]))


def route(u, lw, d, control=False):
    """(idx int[T, k] over ALL experts, gates f32[T, k])."""
    jax, jnp, r, wide, mm = _tools(control)
    s = jax.nn.sigmoid(mm(u, wide(lw["router"])))
    _, idx = jax.lax.top_k(s + jnp.asarray(lw["e_bias"]), d["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * d["scale"]


def moe_layer(u, lw, d, control=False, shared=True):
    """The layer's output for the share ``d['held']``: every held expert
    multiplied densely over all tokens and weighted by its gate (0 where
    the token did not pick it); ``lw['up']`` / ``['down']`` hold exactly
    the held experts."""
    jax, jnp, r, wide, mm = _tools(control)
    lo, hi = d["held"]
    idx, gates = route(u, lw, d, control)
    up_q, up_s = lw["up"]
    down_q, down_s = lw["down"]

    def one(acc, e):
        g = jnp.sum(jnp.where(idx == lo + e, gates, 0.0), axis=-1)
        hid = mm(u, wide((up_q[e], up_s)))
        y = mm(r(jnp.square(jax.nn.relu(hid))), wide((down_q[e], down_s)))
        return acc + g[:, None] * y, None

    up_q, down_q = jnp.asarray(up_q), jnp.asarray(down_q)
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(hi - lo))
    if shared:
        hid = mm(u, wide(lw["s_up"]))
        out = out + mm(r(jnp.square(jax.nn.relu(hid))), wide(lw["s_down"]))
    return r(out)


def attention_layer(u, lw, d, control=False):
    jax, jnp, r, wide, mm = _tools(control)
    t_len = u.shape[0]
    nq, nkv, hd = d["heads"], d["kv"], d["head"]
    q = mm(u, wide(lw["wq"])).reshape(t_len, nkv, nq // nkv, hd)
    k = mm(u, wide(lw["wk"])).reshape(t_len, nkv, hd)
    v = mm(u, wide(lw["wv"])).reshape(t_len, nkv, hd)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("tgqd,sgd->gqts", q, k, precision=hi) / math.sqrt(hd)
    t = jnp.arange(t_len)
    seen = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < d["context"])
    w = r(jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1))
    o = r(jnp.einsum("gqts,sgd->tgqd", w, v, precision=hi))
    return mm(o.reshape(t_len, nq * hd), wide(lw["wo"]))


def hidden_states(weights, d, toks, control=False):
    """Final-norm hidden vectors f32[T, H] of one series."""
    jax, jnp, r, wide, mm = _tools(control)
    q, scale = weights["embed"]
    x = r(jnp.asarray(q)[toks].astype(jnp.float32) * scale)
    layer = {"M": mamba_layer, "E": moe_layer, "*": attention_layer}
    for kind, lw in zip(d["pattern"], weights["layers"]):
        u = r(_rms(jnp, x, jnp.asarray(lw["norm"]), d["eps"]))
        x = x + layer[kind](u, lw, d, control)
    return r(_rms(jnp, x, jnp.asarray(weights["norm_f"]), d["eps"]))


_COMPILED: dict = {}


def surprisal(weights, d, toks, control=False):
    """f32[T]: the score of every reading of one series (int ids). The
    whole forward pass is one compiled program a (sizes, length,
    precision) — the weights its arguments, so every series of a check
    reuses it."""
    jax, jnp, r, wide, mm = _tools(control)
    key = (tuple(sorted((k, v) for k, v in d.items())), len(toks), control)
    run = _COMPILED.get(key)
    if run is None:
        def forward(weights, toks):
            y = hidden_states(weights, d, toks, control)
            before = jnp.concatenate([jnp.zeros_like(y[:1]), y[:-1]])
            logits = mm(before, wide(weights["head"]))
            own = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - own

        run = _COMPILED[key] = jax.jit(forward)
    return np.asarray(run(weights, jnp.asarray(toks, jnp.int32)))
