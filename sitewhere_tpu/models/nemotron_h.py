"""Nemotron-H (hybrid Mamba-2 / routed-expert / attention) as a per-stream
event scorer — the first family whose stream state is not a window of raw
values.

Architecture (NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type`` nemotron_h):
blocks named by a pattern string, each ``x <- x + Mixer(RMSNorm(x))``, a
final RMSNorm and an untied head.

- ``M`` Mamba-2 (``ops/ssd.py``): heads x head_dim inner, grouped B / C,
  4-tap depthwise convolution with bias, gated grouped RMSNorm.
- ``E`` routed + shared experts (``ops/moe.py``): sigmoid router over ALL
  experts, top-k of (score + correction bias), gates normalised and
  scaled; experts ``W_down relu(W_up u)^2``; this chip computes the
  experts in ``experts_held`` and the shared expert.
- ``*`` attention: grouped-query, no bias, NO positional embedding (the
  state-space layers carry position), causal softmax at 1/sqrt(head_dim)
  over the stream's last ``context_positions`` tokens.

How an event meets it: a reading's value IS its token id (a raw sensor
count inside the vocabulary slice held here). The score of a reading is
its surprisal in nats under the prediction made from the stream's history
BEFORE it — ``logsumexp(W_head y) - (W_head y)[id]`` with ``y`` the
stream's final-norm hidden vector after its previous reading (zeros for a
stream's first reading: ``ln vocab``) — and then the reading advances the
stream's state: per ``M`` layer the state-space state and the last three
convolution inputs, per ``*`` layer a ring of keys and values, the
position, and ``y``.

Two programs share ``advance``: the ONE-STEP program (one token a row,
rows are distinct streams) and the CHUNKED program (a run of up to
``chunk_size`` tokens a row, the SSD form), which give the same values.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from sitewhere_tpu.models.common import Params
from sitewhere_tpu.ops import moe, ssd

SCORE_RANGE = (1.0, 64.0)  # nats: ln(vocab) is 11.1 at 65,536 ids


@dataclass(frozen=True)
class NemotronHConfig:
    window: int = 1                     # unused: the state is not a window
    pattern: str = "EMEMEMEM*"
    hidden: int = 2688
    vocab: int = 65536                  # the slice of the vocabulary held
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_experts: int = 128                # the router's width: ALL experts
    experts_held_lo: int = 0            # this chip's experts: [lo, hi)
    experts_held_hi: int = 64
    top_k: int = 6
    routed_scale: float = 2.5
    expert_width: int = 1856
    shared_width: int = 3712
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    context_positions: int = 2048
    eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def mamba_dims(self) -> Tuple[int, int, int, int]:
        return (self.mamba_heads, self.mamba_head_dim, self.n_groups,
                self.ssm_state)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def n_held(self) -> int:
        return self.experts_held_hi - self.experts_held_lo


# ------------------------------------------------------------------ params
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init(key, cfg: NemotronHConfig) -> Params:
    """Seeded weights in the layout ``advance`` reads: matrices in the
    compute dtype, vectors and the router in f32."""
    dt = cfg.compute_dtype
    h, std = cfg.hidden, 0.02
    keys = iter(jax.random.split(key, 8 * len(cfg.pattern) + 4))
    layers = []
    for kind in cfg.pattern:
        lp = {"norm": jnp.ones((h,), jnp.float32)}
        if kind == "M":
            d_in, heads = cfg.d_inner, cfg.mamba_heads
            step = jnp.exp(jax.random.uniform(
                next(keys), (heads,), jnp.float32,
                math.log(1e-3), math.log(1e-1)))
            lp.update(
                w_in=_normal(next(keys), (h, d_in + cfg.conv_dim + heads),
                             std, dt),
                conv_w=_normal(next(keys), (cfg.conv_kernel, cfg.conv_dim),
                               0.5, jnp.float32),
                conv_b=_normal(next(keys), (cfg.conv_dim,), 0.1, jnp.float32),
                # softplus(dt_bias) = a step drawn log-uniform
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                a_log=jnp.log(jax.random.uniform(
                    next(keys), (heads,), jnp.float32, 1.0, 16.0)),
                d=jnp.ones((heads,), jnp.float32),
                norm_g=jnp.ones((d_in,), jnp.float32),
                w_out=_normal(next(keys), (d_in, h), std, dt),
            )
        elif kind == "E":
            e, i, s = cfg.n_held, cfg.expert_width, cfg.shared_width
            lp.update(
                router=_normal(next(keys), (h, cfg.n_experts), std,
                               jnp.float32),
                e_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
                # stored with the width padded to whole 128-lane tiles
                # (zero columns of ``up``, zero rows of ``down``:
                # relu(0)^2 adds nothing): the device keeps an array
                # whose minor dimension is not a multiple of 128
                # minor-on-H, and the grouped product would copy all of
                # it back every step; and its tiles are whole
                up=jnp.pad(_normal(next(keys), (e, h, i), std, dt),
                           ((0, 0), (0, 0), (0, -i % 128))),
                down=jnp.pad(_normal(next(keys), (e, i, h), std, dt),
                             ((0, 0), (0, -i % 128), (0, 0))),
                s_up=_normal(next(keys), (h, s), std, dt),
                s_down=_normal(next(keys), (s, h), std, dt),
            )
        elif kind == "*":
            q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
            lp.update(
                wq=_normal(next(keys), (h, q), std, dt),
                wk=_normal(next(keys), (h, kv), std, dt),
                wv=_normal(next(keys), (h, kv), std, dt),
                wo=_normal(next(keys), (q, h), std, dt),
            )
        else:
            raise ValueError(f"unknown block kind {kind!r} in pattern")
        layers.append(lp)
    return {
        "embed": _normal(next(keys), (cfg.vocab, h), 1.0, dt),
        "head": _normal(next(keys), (h, cfg.vocab), std, dt),
        "norm_f": jnp.ones((h,), jnp.float32),
        "layers": layers,
    }


# ------------------------------------------------------------------- state
def init_state(cfg: NemotronHConfig, max_streams: int) -> dict:
    """One slot's empty stream state, provisioned for ``max_streams``:
    a leaf a layer (so each is gathered from and scattered into once a
    step), every leaf led by the stream axis."""
    s = max_streams
    heads, head_dim, _groups, n_state = cfg.mamba_dims
    layers = []
    for kind in cfg.pattern:
        if kind == "M":
            layers.append({
                "ssm": jnp.zeros((s, heads, head_dim, n_state), jnp.float32),
                # (taps - 1) x conv_dim, flat: a 3-row minor tile would
                # be relaid whole, in and out, every step
                "conv": jnp.zeros(
                    (s, (cfg.conv_kernel - 1) * cfg.conv_dim), jnp.float32),
            })
        elif kind == "*":
            # a ring position is one lane row of kv_heads x head_dim
            # values, held in 32-bit words (``_pack`` / ``_unpack``): a
            # store of 16-bit elements is gathered on a TPU by first
            # copying both halves of ALL of it (described-v5e compile)
            width = cfg.kv_heads * cfg.head_dim * cfg.compute_dtype.itemsize
            kv = (s, cfg.context_positions, width // 4)
            layers.append({"k": jnp.zeros(kv, jnp.uint32),
                           "v": jnp.zeros(kv, jnp.uint32)})
        else:
            layers.append({})
    return {
        "layers": layers,
        "pos": jnp.zeros((s,), jnp.int32),
        "y": jnp.zeros((s, cfg.hidden), jnp.float32),
    }


def state_bytes_per_stream(cfg: NemotronHConfig) -> int:
    """Bytes of state one stream holds (and one step reads and writes)."""
    shapes = jax.eval_shape(lambda: init_state(cfg, 1))
    return int(sum(
        math.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(shapes)
    ))


def state_traffic(cfg: NemotronHConfig, streams: int, tokens: int):
    """(bytes read, bytes written) of stream state by calls that advance
    ``streams`` streams by ``tokens`` tokens in all: every leaf of the
    stream is read; every leaf but the rings is written whole, the rings
    one position a token."""
    per_stream = state_bytes_per_stream(cfg)
    n_attn = cfg.pattern.count("*")
    position = 2 * cfg.kv_heads * cfg.head_dim * cfg.compute_dtype.itemsize
    rings = n_attn * cfg.context_positions * position
    return (streams * per_stream,
            streams * (per_stream - rings) + tokens * n_attn * position)


# ----------------------------------------------------------------- forward
def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _pack(x):
    """Keys or values [..., kv_heads, d] -> the ring's 32-bit words
    [..., words]: an f32 value is its own word; two bf16 values — the
    same lane of a PAIR of heads — share one, the even head in the low
    half, so packing and unpacking are shifts and masks with no lane
    moved."""
    lead = x.shape[:-2]
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(
            lead + (-1,))
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    words = bits[..., 0::2, :] | (bits[..., 1::2, :] << 16)
    return words.reshape(lead + (-1,))


def _unpack(words, dtype, kv_heads: int, d: int):
    """The inverse of ``_pack``: [..., words] -> [..., kv_heads, d]. A
    bf16 value is the top half of the f32 with the same bits."""
    lead = words.shape[:-1]
    if jnp.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(words, dtype).reshape(
            lead + (kv_heads, d))
    words = words.reshape(lead + (kv_heads // 2, 1, d))
    both = jnp.concatenate(
        [words << 16, words & jnp.uint32(0xFFFF0000)], axis=-2)
    return jax.lax.bitcast_convert_type(both, jnp.float32).astype(
        dtype).reshape(lead + (kv_heads, d))


def _attention(u, lp, k_cache, v_cache, pos0, lens, cfg: NemotronHConfig):
    """u [N, L, H]; the rows' rings [N, ctx, kv, d]; ``pos0`` tokens each
    stream has seen. Every new token attends the ring's entries still
    inside its window and the run's earlier tokens. Returns (out, k, v)
    with k, v the run's own entries for the ring."""
    n, length, _ = u.shape
    g, d, ctx = cfg.kv_heads, cfg.head_dim, cfg.context_positions
    per = cfg.heads // g
    dt = cfg.compute_dtype
    ub = u.astype(dt)
    q = jnp.dot(ub, lp["wq"], preferred_element_type=jnp.float32)
    k = jnp.dot(ub, lp["wk"], preferred_element_type=jnp.float32)
    v = jnp.dot(ub, lp["wv"], preferred_element_type=jnp.float32)
    q = q.reshape(n, length, g, per, d).astype(dt)
    k = k.reshape(n, length, g, d).astype(dt)
    v = v.reshape(n, length, g, d).astype(dt)
    scale = 1.0 / math.sqrt(d)
    # ring slot j holds the stream's latest position p_j = j (mod ctx)
    # before this run; token at position t sees it while t - p_j < ctx
    j = jnp.arange(ctx, dtype=jnp.int32)[None, :]
    last = (pos0 - 1)[:, None]
    p_j = last - jnp.mod(last - j, ctx)                      # [N, ctx]
    t = pos0[:, None] + jnp.arange(length, dtype=jnp.int32)[None, :]
    see_ring = (p_j[:, None, :] >= 0) & (t[:, :, None] - p_j[:, None, :] < ctx)
    s_ring = jnp.einsum("nlgqd,ncgd->ngqlc", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    s_ring = jnp.where(see_ring[:, None, None], s_ring, -jnp.inf)
    li = jnp.arange(length, dtype=jnp.int32)
    see_run = (li[None, :] <= li[:, None])[None] & (
        (li[None, None, :] < lens[:, None, None])
        | (li[None, :] == li[:, None])[None]   # a padding token sees itself
    )
    s_run = jnp.einsum("nlgqd,nmgd->ngqlm", q, k,
                       preferred_element_type=jnp.float32) * scale
    s_run = jnp.where(see_run[:, None, None], s_run, -jnp.inf)
    w = jax.nn.softmax(jnp.concatenate([s_ring, s_run], axis=-1), axis=-1)
    o = jnp.einsum("ngqlc,ncgd->nlgqd", w[..., :ctx].astype(dt), v_cache,
                   preferred_element_type=jnp.float32)
    o = o + jnp.einsum("ngqlm,nmgd->nlgqd", w[..., ctx:].astype(dt), v,
                       preferred_element_type=jnp.float32)
    out = jnp.dot(o.reshape(n, length, cfg.heads * d).astype(dt), lp["wo"],
                  preferred_element_type=jnp.float32)
    return out, k, v


def advance(
    params: Params,          # ONE slot's weights
    cfg: NemotronHConfig,
    state: dict,             # ONE slot's stream state (``init_state``)
    ids: jnp.ndarray,        # i32[N] stream of each row; rows are distinct
    toks: jnp.ndarray,       # i32[N, L] token ids, oldest first
    lens: jnp.ndarray,       # i32[N] real tokens a row (0: a padding row)
    one_step: bool,
) -> Tuple[dict, jnp.ndarray, jnp.ndarray]:
    """Score each token under the prediction made before it, then advance
    the rows' streams by their tokens. ``one_step`` (L == 1) takes the
    recurrent form of the state-space mixer, otherwise the chunked form.
    Returns (state', scores f32[N, L] — 0 at padding —, counters i32[3]:
    routed (row, expert) pairs, pairs on held experts, held experts hit
    summed over the expert layers)."""
    n, length = toks.shape
    cap = state["pos"].shape[0]
    live = lens > 0
    at = jnp.minimum(ids, cap - 1)                 # gather: always in range
    to = jnp.where(live, ids, cap)                 # scatter: padding dropped
    real = jnp.arange(length, dtype=jnp.int32)[None, :] < lens[:, None]
    pos0 = state["pos"][at]
    y_before = state["y"][at]
    eps = cfg.eps
    x = params["embed"][toks].astype(jnp.float32)  # [N, L, H]
    stats = jnp.zeros((3,), jnp.int32)
    layers = []
    for kind, lp, ls in zip(cfg.pattern, params["layers"], state["layers"]):
        u = _rmsnorm(x, lp["norm"], eps)
        if kind == "M":
            with jax.named_scope("sw/ssm"):
                ssm_s = ls["ssm"][at]
                conv = ls["conv"][at].reshape(
                    n, cfg.conv_kernel - 1, cfg.conv_dim)
                if one_step:
                    out, conv, ssm_s = ssd.step(
                        u[:, 0], lp, conv, ssm_s, cfg.mamba_dims, eps)
                    out = out[:, None]
                else:
                    out, conv, ssm_s = ssd.chunk(
                        u, lp, conv, ssm_s, lens, cfg.mamba_dims, eps)
                ls = {"conv": ls["conv"].at[to].set(
                          conv.reshape(n, -1), mode="drop"),
                      "ssm": ls["ssm"].at[to].set(ssm_s, mode="drop")}
        elif kind == "E":
            with jax.named_scope("sw/moe"):
                flat = u.reshape(n * length, cfg.hidden)
                idx, gates = moe.route(
                    flat, lp["router"], lp["e_bias"], cfg.top_k,
                    cfg.routed_scale)
                share, st = moe.held_experts(
                    flat, idx, gates, real.reshape(-1), lp["up"], lp["down"],
                    cfg.experts_held_lo)
                out = share + moe.dense_relu2(flat, lp["s_up"], lp["s_down"])
                out = out.reshape(n, length, cfg.hidden)
                stats = stats + st
        else:
            with jax.named_scope("sw/attn"):
                ring = (cfg.kv_heads, cfg.head_dim)
                out, k, v = _attention(
                    u, lp,
                    _unpack(ls["k"][at], cfg.compute_dtype, *ring),
                    _unpack(ls["v"][at], cfg.compute_dtype, *ring),
                    pos0, lens, cfg)
                k, v = _pack(k), _pack(v)
                slot = jnp.mod(
                    pos0[:, None] + jnp.arange(length, dtype=jnp.int32),
                    cfg.context_positions)
                row = jnp.where(real, to[:, None], cap)
                ls = {"k": ls["k"].at[row, slot].set(k, mode="drop"),
                      "v": ls["v"].at[row, slot].set(v, mode="drop")}
        layers.append(ls)
        x = x + out
    with jax.named_scope("sw/head"):
        y = _rmsnorm(x, params["norm_f"], eps)
        before = jnp.concatenate([y_before[:, None], y[:, :-1]], axis=1)
        logits = jnp.dot(before.astype(cfg.compute_dtype), params["head"],
                         preferred_element_type=jnp.float32)
        own = jnp.take_along_axis(logits, toks[..., None], axis=-1)[..., 0]
        scores = jax.nn.logsumexp(logits, axis=-1) - own
        scores = jnp.where(real, scores, 0.0)
    newest = jnp.take_along_axis(
        y, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)[:, 0]
    state = {
        "layers": layers,
        "pos": state["pos"].at[to].add(lens, mode="drop"),
        "y": state["y"].at[to].set(newest, mode="drop"),
    }
    return state, scores, stats


# -------------------------------------------------------------- accounting
def flops_per_row(cfg: NemotronHConfig, window: int = 1, **_kw) -> float:
    """Matmul FLOPs one token needs on this chip's share: every mixer's
    projections, the shared expert, top_k x held share of the routed
    experts, the head over the vocabulary slice."""
    h = cfg.hidden
    held = cfg.top_k * cfg.n_held / cfg.n_experts
    total = 2.0 * h * cfg.vocab
    for kind in cfg.pattern:
        if kind == "M":
            total += 2.0 * h * (cfg.d_inner + cfg.conv_dim + cfg.mamba_heads)
            total += 2.0 * cfg.d_inner * h
        elif kind == "E":
            total += 2.0 * h * cfg.n_experts
            total += 4.0 * h * (cfg.shared_width + held * cfg.expert_width)
        else:
            q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
            total += 2.0 * h * (2 * q + 2 * kv)
    return total
