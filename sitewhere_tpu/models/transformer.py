"""Decoder-only transformer forecaster for multi-sensor telemetry.

North-star model #2b (BASELINE.json:9 — "Transformer/DeepAR forecaster");
the transformer variant handles long telemetry histories. For histories
that exceed one chip's appetite, the attention call routes through
``parallel.ring.ring_attention`` (sequence-parallel shard_map) — see
SURVEY.md §5 "long-context".

TPU notes: tokens are (value, Δt-bucket) pairs embedded to ``dim``; all
attention/MLP matmuls are bf16 einsums on the MXU; generation is a
``lax.scan`` re-encoding the (short) context per step — O(H·T²) but T here
is telemetry-scale (≤512), not LLM-scale.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from sitewhere_tpu.models.common import (
    Params,
    clamp_fuse_k,
    dense,
    dense_init,
    dense_stacked,
    kstep_mask,
    layernorm,
    layernorm_init,
    layernorm_stacked,
    normalize_windows,
    transformer_block,
    transformer_block_init,
    transformer_block_stacked,
)


@dataclass(frozen=True)
class TransformerForecasterConfig:
    context: int = 256
    horizon: int = 24
    dim: int = 128
    depth: int = 4
    heads: int = 4
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def init(key, cfg: TransformerForecasterConfig) -> Params:
    keys = jax.random.split(key, cfg.depth + 3)
    return {
        "embed": dense_init(keys[0], 1, cfg.dim),
        "pos": jax.random.normal(keys[1], (cfg.context, cfg.dim), jnp.float32) * 0.02,
        "blocks": [
            transformer_block_init(keys[2 + i], cfg.dim, cfg.heads)
            for i in range(cfg.depth)
        ],
        "ln_f": layernorm_init(cfg.dim),
        "head": dense_init(keys[-1], cfg.dim, 2),  # (mu, raw_sigma)
    }


def _backbone(params: Params, normed: jnp.ndarray, cfg) -> jnp.ndarray:
    """normed: f32[B, T] → features [B, T, D]. T must be ≤ cfg.context."""
    dtype = cfg.compute_dtype
    t = normed.shape[1]
    x = dense(params["embed"], normed[..., None].astype(dtype), dtype)
    x = x + params["pos"][:t].astype(dtype)[None]
    for blk in params["blocks"]:
        x = transformer_block(blk, x, cfg.heads, causal=True, dtype=dtype)
    return layernorm(params["ln_f"], x)


# -- sequence-parallel long-context path ----------------------------------

def _backbone_local(params: Params, normed_local, cfg, axis_name: str):
    """Per-device body of the sequence-sharded backbone: token-local ops
    (embed/LN/MLP/projections) run on the local block; only attention
    mixes across devices, via ring attention (``ops.ring_attention``)."""
    from jax import lax

    from sitewhere_tpu.models.common import dense, layernorm, mlp
    from sitewhere_tpu.ops.ring_attention import ring_attention_local

    dtype = cfg.compute_dtype
    tl = normed_local.shape[1]
    idx = lax.axis_index(axis_name)
    x = dense(params["embed"], normed_local[..., None].astype(dtype), dtype)
    pos = lax.dynamic_slice_in_dim(params["pos"], idx * tl, tl, 0)
    x = x + pos.astype(dtype)[None]
    heads = cfg.heads
    for blk in params["blocks"]:
        h = layernorm(blk["ln1"], x)
        d = h.shape[-1]
        hd = d // heads

        def split(a):
            return a.reshape(*a.shape[:-1], heads, hd)

        ap = blk["attn"]
        q = split(dense(ap["wq"], h, dtype)).astype(jnp.float32)
        k = split(dense(ap["wk"], h, dtype)).astype(jnp.float32)
        v = split(dense(ap["wv"], h, dtype)).astype(jnp.float32)
        attn = ring_attention_local(q, k, v, axis_name, causal=True)
        attn = attn.reshape(*attn.shape[:-2], d).astype(dtype)
        x = x + dense(ap["wo"], attn, dtype)
        x = x + mlp(blk["mlp"], layernorm(blk["ln2"], x), dtype=dtype)
    return layernorm(params["ln_f"], x)


def backbone_sharded(
    params: Params,
    cfg: TransformerForecasterConfig,
    normed: jnp.ndarray,   # f32[B, T] — T divisible by the axis size
    mesh,
    axis_name: str = "data",
) -> jnp.ndarray:
    """Sequence-parallel backbone: the context shards over ``axis_name``
    (each device holds T/n tokens + the full params), attention runs as a
    ring, and features come back sharded the same way. Numerically
    identical to ``_backbone`` — the long-context escape hatch when a
    history exceeds one chip (SURVEY.md §5)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    t = normed.shape[1]
    n = mesh.shape[axis_name]
    if t > cfg.context:
        # fail loudly: dynamic_slice would silently CLAMP the positional
        # slice for trailing shards (wrong features, no error)
        raise ValueError(
            f"context {t} exceeds cfg.context {cfg.context}; truncate first"
        )
    if t % n:
        raise ValueError(
            f"context {t} must divide across {n} '{axis_name}' shards"
        )

    # jitted: an eager shard_map call is interpreted op by op (minutes on
    # the 8-virtual-device CPU rig under jax 0.9); one compile is seconds
    fn = jax.jit(shard_map(
        partial(_backbone_local, cfg=cfg, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(), P(None, axis_name)),
        out_specs=P(None, axis_name, None),
    ))
    return fn(params, normed)


def forecast_seed_sharded(
    params: Params,
    cfg: TransformerForecasterConfig,
    windows: jnp.ndarray,   # f32[B, T] raw history (long)
    mesh,
    axis_name: str = "data",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(mu, sigma) for the NEXT step after a long sharded context, in
    RAW units — the forecast seed distribution computed without ever
    materializing the full context on one device."""
    windows = windows[:, -cfg.context:]  # same guard as forecast()
    normed, mu_n, sigma_n = normalize_windows(windows)
    feats = backbone_sharded(params, cfg, normed, mesh, axis_name)
    mu, sigma = _emit(params, feats[:, -1:], cfg)
    # back to raw units (the model works in normalized space);
    # normalize_windows returns [B, 1] stats
    return (
        mu[:, 0] * sigma_n[:, 0] + mu_n[:, 0],
        sigma[:, 0] * sigma_n[:, 0],
    )


def _emit(params: Params, feats: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    out = dense(params["head"], feats, cfg.compute_dtype).astype(jnp.float32)
    mu = out[..., 0]
    sigma = jax.nn.softplus(out[..., 1]) + 1e-4
    return mu, sigma


def loss(params: Params, cfg: TransformerForecasterConfig, windows: jnp.ndarray):
    """Causal next-step Gaussian NLL over the window."""
    normed, _, _ = normalize_windows(windows)
    feats = _backbone(params, normed[:, :-1], cfg)
    mu, sigma = _emit(params, feats, cfg)
    target = normed[:, 1:]
    nll = 0.5 * jnp.log(2 * jnp.pi * sigma**2) + (target - mu) ** 2 / (2 * sigma**2)
    return nll.mean()


def forecast(
    params: Params,
    cfg: TransformerForecasterConfig,
    windows: jnp.ndarray,   # f32[B, T] raw history
    key: jax.Array,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Autoregressive mean forecast (+1 sampled path) over the horizon.

    Keeps a fixed-size rolling context (static shapes for XLA): each step
    shifts the context left and appends the new sample.
    Returns (samples f32[B, H], means f32[B, H]) in raw units.
    """
    normed, mu_n, sigma_n = normalize_windows(windows)
    ctx = normed[:, -cfg.context :]
    if ctx.shape[1] < cfg.context:
        pad = cfg.context - ctx.shape[1]
        ctx = jnp.concatenate([jnp.repeat(ctx[:, :1], pad, axis=1), ctx], axis=1)

    def step(carry, k):
        c = carry
        feats = _backbone(params, c, cfg)
        mu, sigma = _emit(params, feats, cfg)
        mu_t, sigma_t = mu[:, -1], sigma[:, -1]
        x_next = mu_t + sigma_t * jax.random.normal(k, mu_t.shape)
        c = jnp.concatenate([c[:, 1:], x_next[:, None]], axis=1)
        return c, (x_next, mu_t)

    keys = jax.random.split(key, cfg.horizon)
    _, (samples, means) = jax.lax.scan(step, ctx, keys)
    samples = samples.T * sigma_n + mu_n   # [B, H] raw
    means = means.T * sigma_n + mu_n
    return samples.astype(jnp.float32), means.astype(jnp.float32)


def _backbone_stacked(params: Params, normed: jnp.ndarray, cfg) -> jnp.ndarray:
    """normed: f32[S, B, T] → features [S, B, T, D] with weight-stacked
    params (leading S on every leaf). Same math as ``_backbone``; every
    projection is one einsum over the whole stacked plane."""
    dtype = cfg.compute_dtype
    t = normed.shape[-1]
    x = dense_stacked(params["embed"], normed[..., None].astype(dtype), dtype)
    # pos is a raw [S, context, D] table (no dense dict — never quantized)
    x = x + params["pos"][:, :t].astype(dtype)[:, None]
    for blk in params["blocks"]:
        x = transformer_block_stacked(blk, x, cfg.heads, causal=True, dtype=dtype)
    return layernorm_stacked(params["ln_f"], x)


def score_stacked(
    params: Params,
    cfg: TransformerForecasterConfig,
    windows: jnp.ndarray,   # f32[S, B, W]
    n_valid: jnp.ndarray,   # i32[S, B]
    k: int = 1,
) -> jnp.ndarray:
    """Fused megabatch scoring (``score_stacked`` contract): last-K-step
    Gaussian NLL per row, f32[S, B, K] — j = K-1 matches the legacy
    ``score``. The causal backbone computes features for every position
    anyway; K-step scoring reads K head outputs from one forward pass."""
    dtype = cfg.compute_dtype
    k = clamp_fuse_k(k, windows.shape[-1])
    normed, _, _ = normalize_windows(windows)
    feats = _backbone_stacked(params, normed[..., :-1], cfg)   # [S,B,T,D]
    out = dense_stacked(params["head"], feats[..., -k:, :], dtype).astype(
        jnp.float32
    )                                                          # [S,B,K,2]
    mu = out[..., 0]
    sigma = jax.nn.softplus(out[..., 1]) + 1e-4
    target = normed[..., -k:]
    nll = 0.5 * jnp.log(2 * jnp.pi * sigma**2) + (
        target - mu
    ) ** 2 / (2 * sigma**2)
    return jnp.where(
        kstep_mask(n_valid, k), nll, 0.0
    ).astype(jnp.float32)


def loss_stacked(
    params: Params,
    cfg: TransformerForecasterConfig,
    windows: jnp.ndarray,   # f32[S, B, W]
) -> jnp.ndarray:
    """Per-row causal next-step Gaussian NLL over the stacked tenant
    plane (``loss_stacked`` contract): f32[S, B] — the scalar ``loss``'s
    per-row mean, with every projection (forward and backward) lowered
    as one weight-stacked einsum over [S·B]."""
    dtype = cfg.compute_dtype
    normed, _, _ = normalize_windows(windows)
    feats = _backbone_stacked(params, normed[..., :-1], cfg)   # [S,B,T,D]
    out = dense_stacked(params["head"], feats, dtype).astype(
        jnp.float32
    )                                                          # [S,B,T,2]
    mu = out[..., 0]
    sigma = jax.nn.softplus(out[..., 1]) + 1e-4
    target = normed[..., 1:]
    nll = 0.5 * jnp.log(2 * jnp.pi * sigma**2) + (
        target - mu
    ) ** 2 / (2 * sigma**2)
    return nll.mean(axis=-1)                                   # [S, B]


def score(params, cfg: TransformerForecasterConfig, windows, n_valid):
    """Anomaly-score adapter: last-step NLL (same contract as lstm_ad.score)."""
    normed, _, _ = normalize_windows(windows)
    feats = _backbone(params, normed[:, :-1], cfg)
    mu, sigma = _emit(params, feats, cfg)
    target = normed[:, -1]
    nll = 0.5 * jnp.log(2 * jnp.pi * sigma[:, -1] ** 2) + (
        target - mu[:, -1]
    ) ** 2 / (2 * sigma[:, -1] ** 2)
    return jnp.where(n_valid >= 4, nll, 0.0).astype(jnp.float32)


def train_step(params, opt_state, windows, cfg, optimizer):
    l, grads = jax.value_and_grad(loss)(params, cfg, windows)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
    return params, opt_state, l
