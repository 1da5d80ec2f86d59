"""ViT-B/16 frame classifier for the streaming-media path.

North-star model #3 (BASELINE.json:11 "ViT-B/16 frame classification on
streaming-media camera feed"; the reference's streaming-media service only
stores/plays chunks — SURVEY.md §2.2 [U] — classification is rebuild-only).

Standard ViT (patch embed → [CLS] + learned pos → pre-LN transformer →
head), pure-JAX pytree params. TPU notes: patchify is a reshape+einsum (one
big MXU matmul, no conv needed for non-overlapping patches); everything runs
bf16; the default config is the real B/16 (86M params — fits a single v5e
chip in bf16 with room to spare); tests use a tiny config.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import shard_map

from sitewhere_tpu.models.common import (
    Params,
    dense,
    dense_init,
    layernorm,
    layernorm_init,
    transformer_block,
    transformer_block_init,
)


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    dim: int = 768
    depth: int = 12
    heads: int = 12
    num_classes: int = 1000
    channels: int = 3
    dtype: str = "bfloat16"

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


VIT_B16 = ViTConfig()
VIT_TINY_TEST = ViTConfig(image_size=32, patch_size=8, dim=64, depth=2, heads=2, num_classes=10)


def init(key, cfg: ViTConfig = VIT_B16) -> Params:
    keys = jax.random.split(key, cfg.depth + 4)
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.channels
    return {
        "patch": dense_init(keys[0], patch_dim, cfg.dim),
        "cls": jax.random.normal(keys[1], (1, 1, cfg.dim), jnp.float32) * 0.02,
        "pos": jax.random.normal(keys[2], (cfg.num_patches + 1, cfg.dim), jnp.float32)
        * 0.02,
        "blocks": [
            transformer_block_init(keys[3 + i], cfg.dim, cfg.heads)
            for i in range(cfg.depth)
        ],
        "ln_f": layernorm_init(cfg.dim),
        "head": dense_init(keys[-1], cfg.dim, cfg.num_classes),
    }


def patchify(images: jnp.ndarray, patch: int) -> jnp.ndarray:
    """[B, H, W, C] → [B, N, patch*patch*C] non-overlapping patches."""
    b, h, w, c = images.shape
    gh, gw = h // patch, w // patch
    x = images.reshape(b, gh, patch, gw, patch, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, gh * gw, patch * patch * c)


def apply(params: Params, cfg: ViTConfig, images: jnp.ndarray) -> jnp.ndarray:
    """images f32[B, H, W, C] (pre-normalized) → logits f32[B, classes]."""
    dtype = cfg.compute_dtype
    x = dense(params["patch"], patchify(images, cfg.patch_size).astype(dtype), dtype)
    b = x.shape[0]
    cls = jnp.broadcast_to(params["cls"].astype(dtype), (b, 1, cfg.dim))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"].astype(dtype)[None]
    for blk in params["blocks"]:
        x = transformer_block(blk, x, cfg.heads, causal=False, dtype=dtype)
    x = layernorm(params["ln_f"], x)
    return dense(params["head"], x[:, 0], dtype).astype(jnp.float32)


def apply_dct(
    params: Params,
    cfg: ViTConfig,
    y_z: jnp.ndarray,
    cb_z: jnp.ndarray,
    cr_z: jnp.ndarray,
    layout,
) -> jnp.ndarray:
    """Compressed-wire forward: truncated zigzag DCT coefficients →
    logits, decode fused INTO preprocessing (one XLA program).

    The media pipeline ships jpegwire's entropy-decoded coefficient
    planes instead of raw RGB (h2d payload ~5-20× smaller); the
    embarrassingly parallel reconstruction — dezigzag, IDCT, chroma
    upsample, YCbCr→RGB, normalization — runs here as einsums feeding
    straight into patchify, so no intermediate frame buffer ever
    materializes on host OR in HBM. ``layout`` is a static
    ``ops.dct.FrameLayout`` (part of the jit cache key)."""
    from sitewhere_tpu.ops.dct import decode_frames

    rgb = decode_frames(y_z, cb_z, cr_z, layout)   # f32 0..255
    images = (rgb / 255.0 - 0.5) / 0.5             # the u8 wire's norm
    return apply(params, cfg, images)


def loss(params: Params, cfg: ViTConfig, images: jnp.ndarray, labels: jnp.ndarray):
    logits = apply(params, cfg, images)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


def train_step(params, opt_state, batch, cfg: ViTConfig, optimizer):
    images, labels = batch
    l, grads = jax.value_and_grad(loss)(params, cfg, images, labels)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
    return params, opt_state, l


# -- tensor-parallel inference (mesh 'model' axis) -------------------------

def shard_params_tp(params: Params, n: int):
    """Pre-slice the blocks for n TP ranks → (blocks_stacked, rest).

    ``blocks_stacked``: per-rank block slices stacked on a leading rank
    dim (shard over the model axis with P(axis)); ``rest``: the
    replicated leaves (patch/cls/pos/ln_f/head)."""
    from sitewhere_tpu.models.common import shard_block_params_tp

    per_rank = [
        [shard_block_params_tp(b, n, i) for b in params["blocks"]]
        for i in range(n)
    ]
    blocks_stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *per_rank
    )
    rest = {k: params[k] for k in ("patch", "cls", "pos", "ln_f", "head")}
    return blocks_stacked, rest


def apply_tp(
    blocks_stacked,
    rest: Params,
    cfg: ViTConfig,
    images: jnp.ndarray,
    mesh,
    axis_name: str = "model",
) -> jnp.ndarray:
    """Tensor-parallel forward: each device holds 1/n of every block's
    heads + MLP hidden (Megatron-style column/row split, two psums per
    block); activations and the non-block leaves stay replicated. For
    models whose weights outgrow one chip's HBM (SURVEY.md §2
    parallelism census)."""
    from jax.sharding import PartitionSpec as P

    from sitewhere_tpu.models.common import transformer_block_tp

    n_ranks = jax.tree_util.tree_leaves(blocks_stacked)[0].shape[0]
    n = mesh.shape[axis_name]
    if n_ranks != n:
        # a mismatch would SILENTLY drop ranks (each psum would cover a
        # fraction of the heads/MLP hidden)
        raise ValueError(
            f"params sliced for {n_ranks} TP ranks but '{axis_name}' has "
            f"{n} devices"
        )

    def body(blocks_local, rest_p, imgs):
        # shard_map leaves a leading rank dim of size 1 on the stacked tree
        blocks = jax.tree_util.tree_map(lambda a: a[0], blocks_local)
        dtype = cfg.compute_dtype
        x = dense(rest_p["patch"], patchify(imgs, cfg.patch_size).astype(dtype), dtype)
        b = x.shape[0]
        cls = jnp.broadcast_to(rest_p["cls"].astype(dtype), (b, 1, cfg.dim))
        x = jnp.concatenate([cls, x], axis=1) + rest_p["pos"].astype(dtype)[None]
        for blk in blocks:
            x = transformer_block_tp(
                blk, x, cfg.heads, axis_name, causal=False, dtype=dtype
            )
        x = layernorm(rest_p["ln_f"], x)
        return dense(rest_p["head"], x[:, 0], dtype).astype(jnp.float32)

    # jitted: an eager shard_map call is interpreted op by op (minutes on
    # the 8-virtual-device CPU rig under jax 0.9); one compile is seconds
    fn = jax.jit(shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P(), P()),
        out_specs=P(),
    ))
    return fn(blocks_stacked, rest, images)
