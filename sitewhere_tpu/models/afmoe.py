"""AFMoE (window / full attention mixed, routed + shared SwiGLU experts)
as a per-stream event scorer: the stateful family whose keys and values
live in pages (``ops/paged_attention.py``).

Architecture (Trinity-Mini, ``model_type`` afmoe), one letter pair a
layer — attention ``W`` (window) or ``F`` (full), MLP ``D`` (dense) or
``E`` (experts) — each layer with four RMSNorms:
``x <- x + RMSNorm_post(Attn(RMSNorm_in(x)))``, then
``x <- x + RMSNorm_post_mlp(MLP(RMSNorm_pre_mlp(x)))``; the input is
``E[id] * sqrt(hidden)``, then a final RMSNorm and an untied head.

- Attention: grouped-query, no bias; a per-head RMSNorm of queries and
  keys; on ``W`` layers RoPE (rotate-half, all head dims) at the token's
  absolute position in its stream and a window of ``sliding_window``
  positions (the token and those before it), on ``F`` layers no position
  and every earlier position; the heads' output gated by
  ``sigmoid(W_gate u)`` before ``W_o``.
- ``E``: sigmoid router over ALL experts, top-k of score + bias, gates
  normalised and scaled (``ops/moe.py`` ``route``); this chip computes the
  experts in ``experts_held`` and the shared expert, all SwiGLU.
- ``D``: one SwiGLU MLP of ``dense_width``.

How an event meets it is Nemotron-H's (``models/nemotron_h.py``): a
reading IS its token id, its score is its surprisal under the prediction
made from the stream's history before it, and then it advances the
stream's state.

The state. A position of an attention layer is K and V of every kv head.
``W`` layers keep a ring of ``ring_pages`` pages a stream — the window
plus the longest run a call writes, so that a run's writes leave alone
the positions its earlier tokens still see. ``F`` layers keep their
positions in a POOL of pages shared by the slot's streams: a stream holds
the pages its history has reached, granted by the host (the scorer's
``PagePool``) before a call and named in the call's page tables. Keys
are normed (and rotated) before they are written; the call writes its
own positions first and attention then reads every page in place.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from sitewhere_tpu.models.common import Params
from sitewhere_tpu.ops import moe, paged_attention
from sitewhere_tpu.parallel.streamstate import PageLayout

SCORE_RANGE = (1.0, 64.0)  # nats: ln(vocab) is 11.1 at 65,536 ids


@dataclass(frozen=True)
class AfmoeConfig:
    attention: str = "WWWFWWWF"         # a letter a layer: window / full
    mlp: str = "DDEEEEEE"               # a letter a layer: dense / experts
    hidden: int = 2048
    vocab: int = 65536                  # the slice of the vocabulary held
    dense_width: int = 6144
    chunk_size: int = 128
    n_experts: int = 128                # the router's width: ALL experts
    experts_held_lo: int = 0            # this chip's experts: [lo, hi)
    experts_held_hi: int = 64
    top_k: int = 8
    routed_scale: float = 2.826
    expert_width: int = 1024
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    page_size: int = 256
    pool_pages: int = 3072              # the F layers' pool, a slot
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
    def ring_pages(self) -> int:
        """Pages of a W layer's ring: the window and a chunk's run."""
        return -(-(self.sliding_window - 1 + self.chunk_size)
                 // self.page_size)

    @property
    def window_table(self) -> int:
        """Pages a W layer's row reads: the window and the run may
        straddle one page more than the ring holds."""
        return self.ring_pages + 1

    @property
    def table_pages(self) -> int:
        """Entries of a stream's F-layer page table."""
        return self.full_context_positions // self.page_size


# ------------------------------------------------------------------ params
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init(key, cfg: AfmoeConfig) -> Params:
    """Seeded weights in the layout ``advance`` reads: matrices in the
    compute dtype, norms and the router in f32; every gated MLP keeps
    ``[gate | up]`` as one matrix."""
    dt = cfg.compute_dtype
    h, std, d = cfg.hidden, 0.02, cfg.head_dim
    q, kv = cfg.heads * d, cfg.kv_heads * d
    keys = iter(jax.random.split(key, 10 * len(cfg.mlp) + 4))
    layers = []
    for mlp in cfg.mlp:
        lp = {name: jnp.ones((h,), jnp.float32) for name in (
            "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")}
        lp.update(
            wq=_normal(next(keys), (h, q), std, dt),
            wk=_normal(next(keys), (h, kv), std, dt),
            wv=_normal(next(keys), (h, kv), std, dt),
            wg=_normal(next(keys), (h, q), std, dt),
            wo=_normal(next(keys), (q, h), std, dt),
            q_norm=jnp.ones((d,), jnp.float32),
            k_norm=jnp.ones((d,), jnp.float32),
        )
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
                down=_normal(next(keys), (e, i, h), std, dt),
                s_gu=_normal(next(keys), (h, 2 * i), std, dt),
                s_down=_normal(next(keys), (i, h), std, dt),
            )
        else:
            raise ValueError(f"unknown MLP kind {mlp!r}")
        layers.append(lp)
    return {
        "embed": _normal(next(keys), (cfg.vocab, h), 1.0, dt),
        "head": _normal(next(keys), (h, cfg.vocab), std, dt),
        "norm_f": jnp.ones((h,), jnp.float32),
        "layers": layers,
    }


# ------------------------------------------------------------------- state
def _page_shape(cfg: AfmoeConfig) -> tuple:
    return (cfg.page_size, 2 * cfg.kv_heads, cfg.head_dim)


def init_state(cfg: AfmoeConfig, max_streams: int) -> dict:
    """One slot's empty store: per W layer a ring of pages a stream (led
    by the stream axis), per F layer the slot's pool of pages (led by the
    page axis: ``page_pool``), the position and the last hidden vector."""
    s, page = max_streams, _page_shape(cfg)
    dt = cfg.compute_dtype
    return {
        "window": [jnp.zeros((s, cfg.ring_pages) + page, dt)
                   for a in cfg.attention if a == "W"],
        "full": [jnp.zeros((cfg.pool_pages,) + page, dt)
                 for a in cfg.attention if a == "F"],
        "pos": jnp.zeros((s,), jnp.int32),
        "y": jnp.zeros((s, cfg.hidden), jnp.float32),
    }


def page_pool(cfg: AfmoeConfig) -> PageLayout:
    """The F layers' pool and the W layers' rings as the host sees them."""
    return PageLayout(
        pool=cfg.pool_pages, page=cfg.page_size, table=cfg.table_pages,
        window=cfg.sliding_window, n_window=cfg.attention.count("W"),
        n_full=cfg.attention.count("F"),
        position_bytes=2 * cfg.kv_heads * cfg.head_dim
        * cfg.compute_dtype.itemsize,
        row_bytes=4 * (cfg.hidden + 1))


# ----------------------------------------------------------------- forward
def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta: float):
    """Rotate-half RoPE over the last axis of x [N, L, heads, D] at the
    absolute positions ``pos`` i32[N, L] (f32)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [N, L, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, :, None]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _attention(u, lp, store, where, table, kv_len, lens, t_abs, full: bool,
               cfg: AfmoeConfig):
    """One attention layer: project, norm (and rotate) queries and keys,
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
    q = rmsnorm(q.reshape(n, length, cfg.heads, d), lp["q_norm"], cfg.eps)
    k = rmsnorm(k.reshape(n, length, g, d), lp["k_norm"], cfg.eps)
    if not full:
        q = rope(q, t_abs, cfg.rope_theta)
        k = rope(k, t_abs, cfg.rope_theta)
    kv = jnp.stack([k, v.reshape(n, length, g, d)], axis=3).reshape(
        n, length, 2 * g, d).astype(store.dtype)
    page, off = where
    store = store.at[page, off].set(kv, mode="drop")
    o = paged_attention.attend(
        q.astype(dt), store, table, kv_len, lens, 1.0 / math.sqrt(d),
        window=None if full else cfg.sliding_window)
    gate = jax.nn.sigmoid(jnp.dot(ub, lp["wg"],
                                  preferred_element_type=jnp.float32))
    o = (o.reshape(n, length, cfg.heads * d).astype(jnp.float32) * gate)
    out = jnp.dot(o.astype(dt), lp["wo"], preferred_element_type=jnp.float32)
    return out, store


def advance(
    params: Params,          # ONE slot's weights
    cfg: AfmoeConfig,
    state: dict,             # ONE slot's store (``init_state``)
    ids: jnp.ndarray,        # i32[N] stream of each row; rows are distinct
    toks: jnp.ndarray,       # i32[N, L] token ids, oldest first
    lens: jnp.ndarray,       # i32[N] real tokens a row (0: a padding row)
    one_step: bool,
    pages: jnp.ndarray = None,  # i32[N, 1 + table]: first page, the table
) -> Tuple[dict, jnp.ndarray, jnp.ndarray]:
    """Score each token under the prediction made before it, then advance
    the rows' streams by their tokens. ``pages`` is each row's F-layer
    page table from the host: the logical page its first entry holds,
    then the physical pages of the pool in order, granted through the
    row's last token. Returns (state', scores f32[N, L] — 0 at padding —,
    counters i32[3]: routed (row, expert) pairs, pairs on held experts,
    held experts hit, summed over the E layers). One code path serves
    both programs: nothing here is recurrent."""
    del one_step
    n, length = toks.shape
    cap = state["pos"].shape[0]
    pg, ring = cfg.page_size, cfg.ring_pages
    live = lens > 0
    at = jnp.minimum(ids, cap - 1)                 # gather: always in range
    real = jnp.arange(length, dtype=jnp.int32)[None, :] < lens[:, None]
    pos0 = state["pos"][at]
    y_before = state["y"][at]
    t_abs = pos0[:, None] + jnp.arange(length, dtype=jnp.int32)[None, :]
    end = pos0 + lens
    off = t_abs % pg
    # W layers: the ring's pages from the first the window reaches
    first_w = jnp.maximum(pos0 - (cfg.sliding_window - 1), 0) // pg
    logical = first_w[:, None] + jnp.arange(cfg.window_table)[None, :]
    table_w = at[:, None] * ring + logical % ring
    kv_len_w = jnp.where(live, end - first_w * pg, 0)
    write_w = jnp.where(real, at[:, None] * ring + (t_abs // pg) % ring,
                        cap * ring)
    # F layers: the host's table, from the first page the stream holds
    first_f, table_f = pages[:, 0], pages[:, 1:]
    kv_len_f = jnp.where(live, end - first_f * pg, 0)
    slot = jnp.clip(t_abs // pg - first_f[:, None], 0, table_f.shape[1] - 1)
    n_pages = state["full"][0].shape[0] if state["full"] else 0
    write_f = jnp.where(real, jnp.take_along_axis(table_f, slot, axis=1),
                        n_pages)                   # padding: dropped
    eps, dt = cfg.eps, cfg.compute_dtype
    x = params["embed"][toks].astype(jnp.float32) * math.sqrt(cfg.hidden)
    stats = jnp.zeros((3,), jnp.int32)
    rings, pools = list(state["window"]), list(state["full"])
    n_w = n_f = 0
    for a, m, lp in zip(cfg.attention, cfg.mlp, params["layers"]):
        u = rmsnorm(x, lp["attn_norm"], eps)
        if a == "W":
            with jax.named_scope("sw/swa"):
                flat = rings[n_w].reshape((-1,) + rings[n_w].shape[2:])
                out, flat = _attention(
                    u, lp, flat, (write_w, off), table_w, kv_len_w, lens,
                    t_abs, False, cfg)
                rings[n_w] = flat.reshape(rings[n_w].shape)
                n_w += 1
        else:
            with jax.named_scope("sw/full_attn"):
                out, pools[n_f] = _attention(
                    u, lp, pools[n_f], (write_f, off), table_f, kv_len_f,
                    lens, t_abs, True, cfg)
                n_f += 1
        x = x + rmsnorm(out, lp["attn_post_norm"], eps)
        flat = rmsnorm(x, lp["mlp_norm"], eps).reshape(n * length, -1)
        if m == "D":
            with jax.named_scope("sw/dense_mlp"):
                out = moe.dense_swiglu(flat, lp["w_gu"], lp["w_down"])
        else:
            with jax.named_scope("sw/gated_moe"):
                idx, gates = moe.route(flat, lp["router"], lp["e_bias"],
                                       cfg.top_k, cfg.routed_scale)
                share, st = moe.held_experts(
                    flat, idx, gates, real.reshape(-1), lp["gu"], lp["down"],
                    cfg.experts_held_lo, gated=True)
                out = share + moe.dense_swiglu(flat, lp["s_gu"],
                                               lp["s_down"])
                stats = stats + st
        out = out.reshape(n, length, cfg.hidden)
        x = x + rmsnorm(out, lp["mlp_post_norm"], eps)
    with jax.named_scope("sw/head"):
        y = rmsnorm(x, params["norm_f"], eps)
        before = jnp.concatenate([y_before[:, None], y[:, :-1]], axis=1)
        logits = jnp.dot(before.astype(dt), params["head"],
                         preferred_element_type=jnp.float32)
        own = jnp.take_along_axis(logits, toks[..., None], axis=-1)[..., 0]
        scores = jnp.where(real, jax.nn.logsumexp(logits, axis=-1) - own, 0.0)
    newest = jnp.take_along_axis(
        y, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)[:, 0]
    to = jnp.where(live, ids, cap)                 # scatter: padding dropped
    state = {
        "window": rings,
        "full": pools,
        "pos": state["pos"].at[to].add(lens, mode="drop"),
        "y": state["y"].at[to].set(newest, mode="drop"),
    }
    return state, scores, stats


# -------------------------------------------------------------- accounting
def flops_per_row(cfg: AfmoeConfig, window: int = 1, **_kw) -> float:
    """Matmul FLOPs one token needs on this chip's share, attention's
    scores and sums at a full window: every attention projection, the
    dense MLPs, the shared expert, top_k x held share of the routed
    experts, the router, the head over the vocabulary slice."""
    h, d = cfg.hidden, cfg.head_dim
    q, kv = cfg.heads * d, cfg.kv_heads * d
    held = cfg.top_k * cfg.n_held / cfg.n_experts
    total = 2.0 * h * cfg.vocab
    for m in cfg.mlp:
        total += 2.0 * h * (3 * q + 2 * kv) + 4.0 * q * cfg.sliding_window
        if m == "D":
            total += 6.0 * h * cfg.dense_width
        else:
            total += 2.0 * h * cfg.n_experts
            total += 6.0 * h * cfg.expert_width * (1 + held)
    return total
