"""LFM2-MoE (gated short convolutions beside grouped-query attention,
routed SwiGLU experts) as a per-stream event scorer: the stateful family
with two kinds of state in one stream — convolution tails led by the
stream axis, keys and values in a pool of pages.

Architecture (LFM2-24B-A2B, ``model_type`` lfm2_moe), one letter pair a
layer — mixer ``C`` (gated short convolution) or ``A`` (attention), MLP
``D`` (dense) or ``E`` (experts) — each ``h = x + Mixer(RMSNorm(x))``,
then ``x' = h + MLP(RMSNorm(h))``; the input is ``E[id]``, then a final
RMSNorm and the head, which is the embedding (tied).

- ``C``: ``ops/shortconv.py`` — ``[B | C | x~] = W_in u``, ``z = B x~``, a
  causal depthwise convolution of ``conv_taps`` taps over z, ``W_out (C y)``.
- ``A``: grouped-query, no bias; a per-head RMSNorm of queries and keys,
  then RoPE (rotate-half, all head dims) at the token's absolute position
  in its stream; softmax at 1/sqrt(head_dim) over EVERY earlier position.
- ``E``: sigmoid router over ALL experts, top-k of score + bias, gates
  normalised and scaled (``ops/moe.py`` ``route``); this chip computes the
  experts in ``experts_held``, SwiGLU, and there is no shared expert.
- ``D``: one SwiGLU MLP of ``dense_width``.

How an event meets it is Nemotron-H's (``models/nemotron_h.py``): a
reading IS its token id, its score is its surprisal under the prediction
made from the stream's history before it, and then it advances the
stream's state.

The state. Per ``C`` layer the stream's last ``conv_taps - 1`` gated
inputs (f32, flat a stream: the convolution's left context), read and
written for the call's rows. Per ``A`` layer the stream's keys and values
in the slot's POOL of pages, granted by the host (``PagePool``) and named
in the call's page tables, laid out with kv heads paired into the lanes
(``ops/paged_attention.py`` ``paired``): at a head_dim of 64 a position
of a layer takes 2 x kv_heads x 64 x 2 bytes on the chip, not twice that.
Keys are normed and rotated before they are written; the call writes its
own positions first and attention then reads every page in place. The
position and the last hidden vector. One code path serves the one-step
program (L = 1) and the chunked one: the tails carry a run's left
context either way.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from sitewhere_tpu.models.afmoe import rmsnorm, rope
from sitewhere_tpu.models.common import Params
from sitewhere_tpu.ops import moe, paged_attention, shortconv
from sitewhere_tpu.parallel.streamstate import PageLayout

SCORE_RANGE = (1.0, 64.0)  # nats: ln(vocab) is 11.1 at 65,536 ids


@dataclass(frozen=True)
class Lfm2MoeConfig:
    mixers: str = "CCACCCACCC"          # a letter a layer: conv / attention
    mlp: str = "DDEEEEEEEE"             # a letter a layer: dense / experts
    hidden: int = 2048
    vocab: int = 65536
    dense_width: int = 11776
    chunk_size: int = 128
    n_experts: int = 64                 # the router's width: ALL experts
    experts_held_lo: int = 0            # this chip's experts: [lo, hi)
    experts_held_hi: int = 64
    top_k: int = 4
    routed_scale: float = 1.0
    expert_width: int = 1536
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    conv_taps: int = 3
    rope_theta: float = 1000000.0
    page_size: int = 256
    pool_pages: int = 3328              # the A layers' pool, a slot
    full_context_positions: int = 32768  # the most a stream's table holds
    eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_held(self) -> int:
        return self.experts_held_hi - self.experts_held_lo

    @property
    def table_pages(self) -> int:
        """Entries of a stream's page table."""
        return self.full_context_positions // self.page_size

    @property
    def tail(self) -> int:
        """Values of one conv layer's tail a stream (flat)."""
        return (self.conv_taps - 1) * self.hidden


# ------------------------------------------------------------------ params
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init(key, cfg: Lfm2MoeConfig) -> Params:
    """Seeded weights in the layout ``advance`` reads: matrices in the
    compute dtype, norms, taps and the router in f32; every gated MLP
    keeps ``[gate | up]`` as one matrix; the head is the embedding."""
    dt = cfg.compute_dtype
    h, std, d = cfg.hidden, 0.02, cfg.head_dim
    q, kv = cfg.heads * d, cfg.kv_heads * d
    keys = iter(jax.random.split(key, 8 * len(cfg.mlp) + 2))
    layers = []
    for mixer, mlp in zip(cfg.mixers, cfg.mlp):
        lp = {"op_norm": jnp.ones((h,), jnp.float32),
              "ffn_norm": jnp.ones((h,), jnp.float32)}
        if mixer == "C":
            lp.update(
                w_in=_normal(next(keys), (h, 3 * h), std, dt),
                conv_w=_normal(next(keys), (cfg.conv_taps, h), 0.5,
                               jnp.float32),
                w_out=_normal(next(keys), (h, h), std, dt))
        elif mixer == "A":
            lp.update(
                wq=_normal(next(keys), (h, q), std, dt),
                wk=_normal(next(keys), (h, kv), std, dt),
                wv=_normal(next(keys), (h, kv), std, dt),
                wo=_normal(next(keys), (q, h), std, dt),
                q_norm=jnp.ones((d,), jnp.float32),
                k_norm=jnp.ones((d,), jnp.float32))
        else:
            raise ValueError(f"unknown mixer kind {mixer!r}")
        if mlp == "D":
            lp.update(
                w_gu=_normal(next(keys), (h, 2 * cfg.dense_width), std, dt),
                w_down=_normal(next(keys), (cfg.dense_width, h), std, dt))
        elif mlp == "E":
            e, i = cfg.n_held, cfg.expert_width
            lp.update(
                router=_normal(next(keys), (h, cfg.n_experts), std,
                               jnp.float32),
                e_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
                gu=_normal(next(keys), (e, h, 2 * i), std, dt),
                down=_normal(next(keys), (e, i, h), std, dt))
        else:
            raise ValueError(f"unknown MLP kind {mlp!r}")
        layers.append(lp)
    return {
        "embed": _normal(next(keys), (cfg.vocab, h), std, dt),
        "norm_f": jnp.ones((h,), jnp.float32),
        "layers": layers,
    }


# ------------------------------------------------------------------- state
def init_state(cfg: Lfm2MoeConfig, max_streams: int) -> dict:
    """One slot's empty store: per C layer a tail a stream (led by the
    stream axis, flat: a 2-row minor tile would be relaid whole), per A
    layer the slot's pool of pages in the paired layout (led by the page
    axis: ``page_pool``), the position and the last hidden vector."""
    s = max_streams
    page = (cfg.page_size, cfg.kv_heads, 2 * cfg.head_dim)
    return {
        "conv": [jnp.zeros((s, cfg.tail), jnp.float32)
                 for m in cfg.mixers if m == "C"],
        "full": [jnp.zeros((cfg.pool_pages,) + page, cfg.compute_dtype)
                 for m in cfg.mixers if m == "A"],
        "pos": jnp.zeros((s,), jnp.int32),
        "y": jnp.zeros((s, cfg.hidden), jnp.float32),
    }


def page_pool(cfg: Lfm2MoeConfig) -> PageLayout:
    """The A layers' pool as the host sees it; no window layers."""
    tails = 4 * cfg.tail * cfg.mixers.count("C")
    return PageLayout(
        pool=cfg.pool_pages, page=cfg.page_size, table=cfg.table_pages,
        window=0, n_window=0, n_full=cfg.mixers.count("A"),
        position_bytes=2 * cfg.kv_heads * cfg.head_dim
        * cfg.compute_dtype.itemsize,
        row_bytes=4 * (cfg.hidden + 1) + tails, tail_bytes=tails)


# ----------------------------------------------------------------- forward
def _attention(u, lp, store, where, table, kv_len, lens, t_abs,
               cfg: Lfm2MoeConfig):
    """One attention layer: project, norm and rotate queries and keys,
    write the run's keys and values at ``where`` (page, offset: i32[N, L]
    each, dropped past the store), then attend through ``table``. Returns
    (out [N, L, H] f32, store')."""
    n, length, _ = u.shape
    g, d = cfg.kv_heads, cfg.head_dim
    dt = cfg.compute_dtype
    ub = u.astype(dt)
    q = jnp.dot(ub, lp["wq"], preferred_element_type=jnp.float32)
    k = jnp.dot(ub, lp["wk"], preferred_element_type=jnp.float32)
    v = jnp.dot(ub, lp["wv"], preferred_element_type=jnp.float32)
    q = rope(rmsnorm(q.reshape(n, length, cfg.heads, d), lp["q_norm"],
                     cfg.eps), t_abs, cfg.rope_theta)
    k = rope(rmsnorm(k.reshape(n, length, g, d), lp["k_norm"], cfg.eps),
             t_abs, cfg.rope_theta)
    kv = paged_attention.paired(k, v.reshape(n, length, g, d))
    page, off = where
    store = store.at[page, off].set(kv.astype(store.dtype), mode="drop")
    o = paged_attention.attend_paired(
        q.astype(dt), store, table, kv_len, lens, 1.0 / math.sqrt(d))
    out = jnp.dot(o.reshape(n, length, cfg.heads * d).astype(dt), lp["wo"],
                  preferred_element_type=jnp.float32)
    return out, store


def advance(
    params: Params,          # ONE slot's weights
    cfg: Lfm2MoeConfig,
    state: dict,             # ONE slot's store (``init_state``)
    ids: jnp.ndarray,        # i32[N] stream of each row; rows are distinct
    toks: jnp.ndarray,       # i32[N, L] token ids, oldest first
    lens: jnp.ndarray,       # i32[N] real tokens a row (0: a padding row)
    one_step: bool,
    pages: jnp.ndarray = None,  # i32[N, 1 + table]: first page, the table
) -> Tuple[dict, jnp.ndarray, jnp.ndarray]:
    """Score each token under the prediction made before it, then advance
    the rows' streams by their tokens. ``pages`` is each row's page table
    from the host: the logical page its first entry holds, then the
    physical pages of the pool in order, granted through the row's last
    token. Returns (state', scores f32[N, L] — 0 at padding —, counters
    i32[3]: routed (row, expert) pairs, pairs on held experts, held
    experts hit, summed over the E layers)."""
    del one_step
    n, length = toks.shape
    cap = state["pos"].shape[0]
    pg, taps = cfg.page_size, cfg.conv_taps
    live = lens > 0
    at = jnp.minimum(ids, cap - 1)                 # gather: always in range
    to = jnp.where(live, ids, cap)                 # scatter: padding dropped
    real = jnp.arange(length, dtype=jnp.int32)[None, :] < lens[:, None]
    pos0 = state["pos"][at]
    y_before = state["y"][at]
    t_abs = pos0[:, None] + jnp.arange(length, dtype=jnp.int32)[None, :]
    first, table = pages[:, 0], pages[:, 1:]
    kv_len = jnp.where(live, pos0 + lens - first * pg, 0)
    slot = jnp.clip(t_abs // pg - first[:, None], 0, table.shape[1] - 1)
    n_pages = state["full"][0].shape[0] if state["full"] else 0
    write = (jnp.where(real, jnp.take_along_axis(table, slot, axis=1),
                       n_pages), t_abs % pg)       # padding: dropped
    eps = cfg.eps
    x = params["embed"][toks].astype(jnp.float32)
    stats = jnp.zeros((3,), jnp.int32)
    convs, pools = list(state["conv"]), list(state["full"])
    n_c = n_a = 0
    for mixer, mlp, lp in zip(cfg.mixers, cfg.mlp, params["layers"]):
        u = rmsnorm(x, lp["op_norm"], eps)
        if mixer == "C":
            with jax.named_scope("sw/shortconv"):
                tail = convs[n_c][at].reshape(n, taps - 1, cfg.hidden)
                out, tail = shortconv.mixer(u, lp, tail, lens)
                convs[n_c] = convs[n_c].at[to].set(
                    tail.reshape(n, -1), mode="drop")
                n_c += 1
        else:
            with jax.named_scope("sw/full_attn"):
                out, pools[n_a] = _attention(
                    u, lp, pools[n_a], write, table, kv_len, lens, t_abs,
                    cfg)
                n_a += 1
        x = x + out
        flat = rmsnorm(x, lp["ffn_norm"], eps).reshape(n * length, -1)
        if mlp == "D":
            with jax.named_scope("sw/dense_mlp"):
                out = moe.dense_swiglu(flat, lp["w_gu"], lp["w_down"])
        else:
            with jax.named_scope("sw/gated_moe"):
                idx, gates = moe.route(flat, lp["router"], lp["e_bias"],
                                       cfg.top_k, cfg.routed_scale)
                out, st = moe.held_experts(
                    flat, idx, gates, real.reshape(-1), lp["gu"], lp["down"],
                    cfg.experts_held_lo, gated=True)
                stats = stats + st
        x = x + out.reshape(n, length, cfg.hidden)
    with jax.named_scope("sw/head"):
        y = rmsnorm(x, params["norm_f"], eps)
        before = jnp.concatenate([y_before[:, None], y[:, :-1]], axis=1)
        # the tied head: the embedding's rows are the vocabulary's
        logits = jnp.einsum(
            "nlh,vh->nlv", before.astype(cfg.compute_dtype),
            params["embed"], preferred_element_type=jnp.float32)
        own = jnp.take_along_axis(logits, toks[..., None], axis=-1)[..., 0]
        scores = jnp.where(real, jax.nn.logsumexp(logits, axis=-1) - own, 0.0)
    newest = jnp.take_along_axis(
        y, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)[:, 0]
    state = {
        "conv": convs,
        "full": pools,
        "pos": state["pos"].at[to].add(lens, mode="drop"),
        "y": state["y"].at[to].set(newest, mode="drop"),
    }
    return state, scores, stats


# -------------------------------------------------------------- accounting
def flops_per_row(cfg: Lfm2MoeConfig, window: int = 1, **_kw) -> float:
    """Matmul FLOPs one token needs on this chip's share, attention's
    scores and sums at ``window`` positions: every mixer's projections,
    the dense MLPs, top_k x held share of the routed experts, the
    routers, the tied head over the vocabulary."""
    h, d = cfg.hidden, cfg.head_dim
    q, kv = cfg.heads * d, cfg.kv_heads * d
    held = cfg.top_k * cfg.n_held / cfg.n_experts
    total = 2.0 * h * cfg.vocab
    for mixer, mlp in zip(cfg.mixers, cfg.mlp):
        if mixer == "C":
            total += 2.0 * h * 4 * h
        else:
            total += 2.0 * h * (2 * q + 2 * kv) + 4.0 * q * window
        if mlp == "D":
            total += 6.0 * h * cfg.dense_width
        else:
            total += 2.0 * h * cfg.n_experts
            total += 6.0 * h * cfg.expert_width * held
    return total
