"""Plain reference for the AFMoE stream-scoring configurations: seeded
weights and the f32 forward pass over a stream's WHOLE series — plain
causal attention (a window mask on ``W`` layers, none on ``F`` layers),
every held expert multiplied densely — with no pages, no chunks and no
batching.

It imports nothing of ``sitewhere_tpu`` and takes nothing the program has
made. ``jax.numpy`` in float32 with every product at ``HIGHEST``
precision (on a TPU a float32 product otherwise runs in bf16 passes), on
whatever device JAX gives it; a tiny size runs on the CPU in tier-1.

Equations (Trinity-Mini ``config.json``, ``model_type`` afmoe; the
readings the config leaves open are those of the public ``afmoe``
modelling code in transformers: RoPE on sliding-window layers only, and
``mup_enabled`` as the input scaled by sqrt(hidden)). Layer i has an
attention kind (``W`` window / ``F`` full) and an MLP kind (``D`` dense /
``E`` experts); RMSNorm eps 1e-5:

- input ``x = E[id] * sqrt(H)``;
- ``x <- x + RMSNorm_pa(Attn(RMSNorm_in(x)))``,
  ``x <- x + RMSNorm_pm(MLP(RMSNorm_m(x)))``;
- Attn: ``q, k, v = W_q u, W_k u, W_v u``; per-head RMSNorm of q and k
  (learned, over head_dim); ``W``: rotate-half RoPE (theta, all dims) at
  the absolute position, softmax at 1/sqrt(d) over the token and the
  ``sliding_window - 1`` before it; ``F``: no position, every earlier
  token; ``o = heads(.) * sigmoid(W_gate u)``; ``W_o o``;
- ``D``: ``W_down(silu(W_g u) * W_u u)``;
- ``E``: ``s = sigmoid(W_r u)``; top-k of ``s + bias``; gates ``s[top] /
  sum s[top] * scale``; experts and one shared expert SwiGLU. Only experts
  in ``held`` add anything: the share one chip of the deployment computes;
- a final RMSNorm and the head.

The score of reading t is its surprisal in nats under the prediction made
BEFORE it: ``logsumexp(W_head y_{t-1}) - (W_head y_{t-1})[id_t]``,
``y_{-1} = 0`` (a first reading scores ``ln vocab``).

Blocks: a 16k-reading series does not fit a T x T score matrix, so the
series is taken ``BLOCK`` readings at a time — each block's queries over
every earlier key and value of the series, kept in plain arrays of the
series' capacity. That is the same sum in another order of blocks; there
is no cache of the program's kind.

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

BLOCK = 256  # readings of a series a pass


def dims(model: dict) -> dict:
    """Sizes by the names used here, from a configuration's ``model``
    block (published keys)."""
    return {
        "attention": model["attention"],
        "mlp": model["pattern"],
        "hidden": model["hidden_size"],
        "vocab": model["vocab_size"],
        "dense": model["intermediate_size"],
        "experts": model["num_experts_published"],
        "held": tuple(model["experts_held"]),
        "top_k": model["num_experts_per_tok"],
        "scale": model["route_scale"],
        "width_e": model["moe_intermediate_size"],
        "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"],
        "head": model["head_dim"],
        "window": model["sliding_window"],
        "theta": model["rope_theta"],
        "eps": model["rms_norm_eps"],
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
    h, std, hd = d["hidden"], 0.02, d["head"]
    lo, hi = d["held"]
    jobs, out = [], {"layers": []}

    def rng(*tag):
        return np.random.default_rng([int(seed), 0xAF0E, *tag])

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

    q, kv = d["heads"] * hd, d["kv"] * hd
    for i, mlp in enumerate(d["mlp"]):
        lw = {name: vector((h,), 0.1, 1.0, i, 0, j) for j, name in enumerate(
            ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"))}
        matrix(lw, "wq", (h, q), std, i, 1)
        matrix(lw, "wk", (h, kv), std, i, 2)
        matrix(lw, "wv", (h, kv), std, i, 3)
        matrix(lw, "wg", (h, q), std, i, 4)
        matrix(lw, "wo", (q, h), std, i, 5)
        lw["q_norm"] = vector((hd,), 0.1, 1.0, i, 6)
        lw["k_norm"] = vector((hd,), 0.1, 1.0, i, 7)
        if mlp == "D":
            matrix(lw, "w_gu", (h, 2 * d["dense"]), std, i, 8)
            matrix(lw, "w_down", (d["dense"], h), std, i, 9)
        else:
            matrix(lw, "router", (h, d["experts"]), std, i, 10)
            lw["e_bias"] = vector((d["experts"],), 0.02, 0.0, i, 11)
            experts(lw, "gu", (h, 2 * d["width_e"]), i, 12)
            experts(lw, "down", (d["width_e"], h), i, 13)
            matrix(lw, "s_gu", (h, 2 * d["width_e"]), std, i, 14)
            matrix(lw, "s_down", (d["width_e"], h), std, i, 15)
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


def swiglu(u, gu, width, control=False):
    """W_down-less half of a SwiGLU: silu(u W_g) * (u W_u)."""
    jax, jnp, r, wide, mm = _tools(control)
    h = mm(u, wide(gu))
    return r(jax.nn.silu(h[:, :width]) * h[:, width:])


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
    the token did not pick it)."""
    jax, jnp, r, wide, mm = _tools(control)
    lo, hi = d["held"]
    width = d["width_e"]
    idx, gates = route(u, lw, d, control)
    gu_q, gu_s = lw["gu"]
    down_q, down_s = lw["down"]

    def one(acc, e):
        g = jnp.sum(jnp.where(idx == lo + e, gates, 0.0), axis=-1)
        y = mm(swiglu(u, (gu_q[e], gu_s), width, control),
               wide((down_q[e], down_s)))
        return acc + g[:, None] * y, None

    gu_q, down_q = jnp.asarray(gu_q), jnp.asarray(down_q)
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(hi - lo))
    if shared:
        out = out + mm(swiglu(u, lw["s_gu"], width, control),
                       wide(lw["s_down"]))
    return r(out)


def dense_layer(u, lw, d, control=False):
    jax, jnp, r, wide, mm = _tools(control)
    return mm(swiglu(u, lw["w_gu"], d["dense"], control), wide(lw["w_down"]))


def rope(x, pos, theta):
    """Rotate-half RoPE of x [T, heads, D] at positions pos [T]."""
    import jax.numpy as jnp

    dd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dd, 2, dtype=jnp.float32) / dd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., dd // 2:], x[..., :dd // 2]], -1)
    return x * cos + rot * sin


def attention_block(u, lw, d, kind, keys, values, start, control=False):
    """One block of queries u [B, H] at positions start.. over the
    series' keys and values [C, kv, d] (this block's written first).
    Returns (out [B, H], keys', values')."""
    jax, jnp, r, wide, mm = _tools(control)
    b = u.shape[0]
    nq, nkv, hd = d["heads"], d["kv"], d["head"]
    pos = start + jnp.arange(b)
    q = _rms(jnp, mm(u, wide(lw["wq"])).reshape(b, nq, hd),
             jnp.asarray(lw["q_norm"]), d["eps"])
    k = _rms(jnp, mm(u, wide(lw["wk"])).reshape(b, nkv, hd),
             jnp.asarray(lw["k_norm"]), d["eps"])
    v = mm(u, wide(lw["wv"])).reshape(b, nkv, hd)
    if kind == "W":
        q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
    q, k = r(q), r(k)
    keys = jax.lax.dynamic_update_slice(keys, k, (start, 0, 0))
    values = jax.lax.dynamic_update_slice(values, v, (start, 0, 0))
    hi = jax.lax.Precision.HIGHEST
    qg = q.reshape(b, nkv, nq // nkv, hd)
    s = jnp.einsum("tgqd,sgd->gqts", qg, keys, precision=hi) / math.sqrt(hd)
    col = jnp.arange(keys.shape[0])
    seen = col[None, :] <= pos[:, None]
    if kind == "W":
        seen &= pos[:, None] - col[None, :] < d["window"]
    w = r(jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1))
    o = r(jnp.einsum("gqts,sgd->tgqd", w, values, precision=hi))
    gate = jax.nn.sigmoid(mm(u, wide(lw["wg"])))
    o = r(o.reshape(b, nq * hd) * gate)
    return mm(o, wide(lw["wo"])), keys, values


_COMPILED: dict = {}


def _block_fn(d, capacity, control):
    """The compiled pass over one block: (weights, caches, ids [B],
    start) -> (caches', final-norm hidden [B, H])."""
    key = (tuple(sorted((k, v) for k, v in d.items())), capacity, control)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn
    jax, jnp, r, wide, mm = _tools(control)

    def block(weights, caches, toks, start):
        q, scale = weights["embed"]
        x = r(jnp.asarray(q)[toks].astype(jnp.float32) * scale
              * math.sqrt(d["hidden"]))
        new = []
        for kind, mlp, lw, (keys, values) in zip(
                d["attention"], d["mlp"], weights["layers"], caches):
            u = r(_rms(jnp, x, jnp.asarray(lw["attn_norm"]), d["eps"]))
            a, keys, values = attention_block(
                u, lw, d, kind, keys, values, start, control)
            new.append((keys, values))
            x = x + r(_rms(jnp, a, jnp.asarray(lw["attn_post_norm"]),
                           d["eps"]))
            u = r(_rms(jnp, x, jnp.asarray(lw["mlp_norm"]), d["eps"]))
            m = (dense_layer(u, lw, d, control) if mlp == "D"
                 else moe_layer(u, lw, d, control))
            x = x + r(_rms(jnp, m, jnp.asarray(lw["mlp_post_norm"]),
                           d["eps"]))
        return new, r(_rms(jnp, x, jnp.asarray(weights["norm_f"]), d["eps"]))

    fn = _COMPILED[key] = jax.jit(block, donate_argnums=1)
    return fn


def _head_fn(d, control):
    key = ("head", d["vocab"], d["hidden"], control)
    fn = _COMPILED.get(key)
    if fn is None:
        jax, jnp, r, wide, mm = _tools(control)

        def head(w_head, before, toks):
            logits = mm(before, wide(w_head))
            own = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - own

        fn = _COMPILED[key] = jax.jit(head)
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
    caches = [(jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
              for _ in d["mlp"]]
    padded = np.zeros(-(-t // block) * block, np.int32)
    padded[:t] = toks
    ys = []
    for start in range(0, len(padded), block):
        caches, y = run(weights, caches, jnp.asarray(padded[start:start + block]),
                        jnp.int32(start))
        ys.append(y)
    y = jnp.concatenate(ys)[:t]
    before = jnp.concatenate([jnp.zeros_like(y[:1]), y[:-1]])
    return np.asarray(_head_fn(d, control)(
        weights["head"], before, jnp.asarray(toks)))
