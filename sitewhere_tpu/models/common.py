"""Shared pure-JAX building blocks for the model zoo.

Models are plain pytrees (nested dicts of arrays) + pure ``init``/``apply``
functions — no framework class hierarchy, so stacking per-tenant parameters
along a leading tenant axis (``parallel.sharded``) and checkpointing
(``runtime.checkpoint``) are trivial tree ops.

TPU notes: params are stored float32, compute defaults to bfloat16 (MXU
native); all matmuls are batched ``einsum``s so XLA tiles them onto the MXU.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def dense_init(key, in_dim: int, out_dim: int, scale: Optional[float] = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return {
        "w": jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale,
        "b": jnp.zeros((out_dim,), jnp.float32),
    }


def dense(p: Params, x: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return jnp.einsum("...i,io->...o", x.astype(dtype), p["w"].astype(dtype)) + p[
        "b"
    ].astype(dtype)


def layernorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    # LN in float32 for numerical stability, cast back after
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def mha_init(key, dim: int, heads: int) -> Params:
    del heads  # head count is config, not a parameter (keeps pytrees array-only)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, dim, dim),
        "wk": dense_init(k2, dim, dim),
        "wv": dense_init(k3, dim, dim),
        "wo": dense_init(k4, dim, dim),
    }


def attn_core(
    q: jnp.ndarray,   # [..., T, H, hd]
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool,
    dtype,
) -> jnp.ndarray:
    """THE attention math (scaled QK^T, optional causal mask, f32
    softmax, AV) — shared by the single-device and tensor-parallel
    blocks so their numerics can't diverge. Returns [..., T, H*hd]."""
    t, hd = q.shape[-3], q.shape[-1]
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        logits = jnp.where(mask, logits, -1e30)
    attn = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("...hqk,...khd->...qhd", attn, v)
    return out.reshape(*out.shape[:-2], out.shape[-2] * out.shape[-1])


def mha(
    p: Params,
    x: jnp.ndarray,                      # [..., T, D]
    heads: int,
    causal: bool = False,
    dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Multi-head self-attention. Softmax in f32; QK^T/AV are MXU matmuls."""
    d = x.shape[-1]
    hd = d // heads

    def split(a):
        return a.reshape(*a.shape[:-1], heads, hd)

    q = split(dense(p["wq"], x, dtype))
    k = split(dense(p["wk"], x, dtype))
    v = split(dense(p["wv"], x, dtype))
    return dense(p["wo"], attn_core(q, k, v, causal, dtype), dtype)


def mlp_init(key, dim: int, hidden: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {"fc1": dense_init(k1, dim, hidden), "fc2": dense_init(k2, hidden, dim)}


def mlp(p: Params, x: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return dense(p["fc2"], jax.nn.gelu(dense(p["fc1"], x, dtype)), dtype)


def transformer_block_init(key, dim: int, heads: int, mlp_ratio: int = 4) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": layernorm_init(dim),
        "attn": mha_init(k1, dim, heads),
        "ln2": layernorm_init(dim),
        "mlp": mlp_init(k2, dim, dim * mlp_ratio),
    }


def transformer_block(
    p: Params, x: jnp.ndarray, heads: int, causal: bool = False, dtype=jnp.bfloat16
) -> jnp.ndarray:
    x = x + mha(p["attn"], layernorm(p["ln1"], x), heads, causal=causal, dtype=dtype)
    x = x + mlp(p["mlp"], layernorm(p["ln2"], x), dtype=dtype)
    return x


def carry_zeros(shape, like: jnp.ndarray, dtype) -> jnp.ndarray:
    """Zero scan-carry that inherits ``like``'s varying-axis (vma) type.

    Under ``shard_map`` with the varying-axis checker on, a plain
    ``jnp.zeros`` carry is 'unvarying' and ``lax.scan`` rejects it against
    a data-derived carry output. Adding ``0 * like[..0..]`` transfers the
    data's vma without naming mesh axes, so models stay mesh-agnostic and
    also run outside shard_map. ``like``'s leading dim must match
    ``shape[0]`` (the batch dim)."""
    z = (like.reshape(like.shape[0], -1)[:, :1] * 0).astype(dtype)
    return jnp.zeros(shape, dtype) + z


def normalize_windows(windows: jnp.ndarray, eps: float = 1e-6):
    """Per-row standardization of [..., W] windows → (normed, mu, sigma).

    Models score/forecast in normalized space; callers un-normalize with the
    returned (mu, sigma). Keeps params scale-free across heterogeneous
    sensors (°C vs kPa vs rpm).
    """
    wf = windows.astype(jnp.float32)
    mu = wf.mean(-1, keepdims=True)
    sigma = wf.std(-1, keepdims=True) + eps
    return (wf - mu) / sigma, mu, sigma


def param_count(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


# -- fused megabatch (weight-stacked) scoring ------------------------------
#
# The stacked scoring contract (``parallel.sharded`` fused step;
# docs/PERFORMANCE.md "Fused tenant kernels"): each scorer family exposes
#
#     spec.score_stacked(stacked_params, cfg, windows[S, B, W],
#                        n_valid[S, B], k=K) -> f32[S, B, K]
#
# where every param leaf carries a leading stacked-slot dim ``S`` and each
# time-step contraction runs as ONE wide einsum over the whole [S·B]
# tenant plane (``sbh,sho->sbo`` — a single batched MXU dot) instead of S
# independent [B, H] matmuls. ``scores[..., j]`` is the score at window
# position ``W-K+j`` (j = K-1 ⇔ the newest position == the legacy
# single-step score). tools/check_fusion.py lints that these entry points
# actually lower to ≤2 dot_generals per scan step.

# The stacked TRAINING contract (``parallel.sharded`` fused train step;
# docs/PERFORMANCE.md "Continual learning lane") is the gradient twin of
# ``score_stacked``: each trainable family also exposes
#
#     spec.loss_stacked(stacked_params, cfg, windows[S, B, W]) -> f32[S, B]
#
# the PER-ROW teacher-forced loss (mean over the window's W-1 next-step
# predictions — exactly what vmapping the scalar ``spec.loss`` over
# single-row windows computes), built from the same weight-stacked
# einsums as scoring. Differentiating its masked per-slot mean therefore
# runs the backward pass as wide stacked dots too — one dot_general
# chain per scan step over the whole [S·B] tenant plane, slot-count-
# invariant (tools/check_fusion.py lints the grad jaxpr the same way it
# lints score_stacked). Slot s's loss depends only on slot s's param
# slices, so the stacked gradient IS the per-slot gradients, bit-packed.

PARAM_DTYPES = ("f32", "bf16", "int8")

# Real MAC width of quantized weight matmuls against the bf16 peak the
# MFU denominator uses (runtime.metrics.PEAK_FLOPS_BF16): the MXU retires
# int8 MACs at ~2× the bf16 rate, so an int8 MAC counts as HALF a
# bf16-equivalent FLOP pair — counting it full-width would flatter
# tpu_mfu_pct{family} for quantized stacks. Activation·activation matmuls
# (attention QK^T/AV) never quantize and always count full width.
QUANT_MAC_WIDTH = {"f32": 1.0, "bf16": 1.0, "int8": 0.5}


def quant_mac_width(param_dtype: Optional[str]) -> float:
    return QUANT_MAC_WIDTH.get(param_dtype or "f32", 1.0)


def quantize_dense(p: Params, param_dtype: str) -> Params:
    """One dense param dict → its kernel-side representation.

    - ``f32``: unchanged (the master params serve directly);
    - ``bf16``: weight cast once at derive time;
    - ``int8``: symmetric per-output-channel scales over the contraction
      dim (axis -2) — for stacked ``[S, I, O]`` weights that is per-slot
      AND per-channel, so one tenant's weight range never clips another's.
    Biases stay f32 (they add once per row — no MAC savings to chase).
    """
    if param_dtype == "f32":
        return p
    if param_dtype == "bf16":
        return {"w": p["w"].astype(jnp.bfloat16), "b": p["b"]}
    if param_dtype != "int8":
        raise ValueError(f"param_dtype must be one of {PARAM_DTYPES}")
    w = p["w"]
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    scale = jnp.maximum(scale, jnp.asarray(1e-12, w.dtype))
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return {"qw": q, "scale": scale.astype(jnp.float32), "b": p["b"]}


def quantize_params(params: Params, param_dtype: str) -> Params:
    """Derive the kernel-side param tree: every dense ``{"w", "b"}`` node
    whose weight has a contraction dim (ndim ≥ 2) re-represents per
    ``quantize_dense``; everything else (layernorm scales, positional
    embeddings) passes through. Structure-compatible with the master
    tree, so model code reads weights through ``kernel_weight`` and never
    branches on the storage format."""
    if param_dtype == "f32":
        return params

    def walk(node):
        if isinstance(node, dict):
            w = node.get("w")
            if (
                w is not None
                and "b" in node
                and getattr(w, "ndim", 0) >= 2
            ):
                return quantize_dense(node, param_dtype)
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(params)


def kernel_shape(p: Params) -> tuple:
    """Shape of a dense node's kernel, whatever its storage form
    (``w`` master / ``qw`` int8)."""
    arr = p.get("qw")
    if arr is None:
        arr = p["w"]
    return arr.shape


def kernel_weight(p: Params, dtype) -> jnp.ndarray:
    """Read a (possibly quantized) dense kernel at compute dtype. For
    int8 storage this IS the dequant — an elementwise
    ``qw.astype(dtype) * scale`` the fused scan steps inline so XLA fuses
    it against the wide dot (weights live in HBM at 1 byte/element; the
    dequant rides the VPU while the MXU does the matmul)."""
    qw = p.get("qw")
    if qw is not None:
        return qw.astype(dtype) * p["scale"].astype(dtype)
    return p["w"].astype(dtype)


def stacked_bias(p: Params, x_ndim: int, dtype) -> jnp.ndarray:
    """Bias ``[S, O]`` broadcast-shaped against a stacked activation of
    ``x_ndim`` dims (``[S, ..., O]``)."""
    b = p["b"].astype(dtype)
    return b.reshape(b.shape[0], *([1] * (x_ndim - 2)), b.shape[-1])


def dense_stacked(p: Params, x: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Weight-stacked dense: x [S, ..., I] × w [S, I, O] → [S, ..., O] as
    ONE einsum over the whole stacked plane (the megabatch analog of
    ``dense``)."""
    w = kernel_weight(p, dtype)
    return (
        jnp.einsum("s...i,sio->s...o", x.astype(dtype), w)
        + stacked_bias(p, x.ndim, dtype)
    )


def layernorm_stacked(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Per-row LN with stacked [S, D] scale/bias — same math (f32
    reduction over the last dim) as ``layernorm``."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    return (
        y * p["scale"].reshape(shape) + p["bias"].reshape(shape)
    ).astype(x.dtype)


def mha_stacked(
    p: Params,
    x: jnp.ndarray,          # [S, ..., T, D]
    heads: int,
    causal: bool = False,
    dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Weight-stacked multi-head attention — ``attn_core`` already
    batches over arbitrary leading dims, so only the projections change."""
    d = x.shape[-1]
    hd = d // heads

    def split(a):
        return a.reshape(*a.shape[:-1], heads, hd)

    q = split(dense_stacked(p["wq"], x, dtype))
    k = split(dense_stacked(p["wk"], x, dtype))
    v = split(dense_stacked(p["wv"], x, dtype))
    return dense_stacked(p["wo"], attn_core(q, k, v, causal, dtype), dtype)


def transformer_block_stacked(
    p: Params, x: jnp.ndarray, heads: int, causal: bool = False,
    dtype=jnp.bfloat16,
) -> jnp.ndarray:
    x = x + mha_stacked(
        p["attn"], layernorm_stacked(p["ln1"], x), heads, causal=causal,
        dtype=dtype,
    )
    h = layernorm_stacked(p["ln2"], x)
    return x + dense_stacked(
        p["mlp"]["fc2"],
        jax.nn.gelu(dense_stacked(p["mlp"]["fc1"], h, dtype)),
        dtype,
    )


def kstep_mask(n_valid: jnp.ndarray, k: int) -> jnp.ndarray:
    """Cold-start mask per K-step score column: position W-K+j had seen
    ``n_valid - (K-1-j)`` samples when it was the newest — rows below 4
    samples AT THAT TIME score 0 (same gate the legacy single-step path
    applies to its one position). Returns bool[..., K] for n_valid[...]."""
    ages = jnp.arange(k, dtype=jnp.int32)            # j = 0 .. K-1
    return (n_valid[..., None] - (k - 1 - ages)) >= 4


def clamp_fuse_k(k: int, window: int) -> int:
    """K is bounded by the predictable positions: a length-W window has
    W-1 one-step-ahead predictions."""
    return max(1, min(int(k), int(window) - 1))


# -- device-side score sketches (score-quality observability) --------------
#
# Each scoring flush emits a fixed-bin score histogram per stacked tenant
# slot, accumulated ON DEVICE inside the jitted step (parallel.sharded →
# ``sketch_histogram``: each score compared with the edges, counts reduced
# over the lane axis — no gather, no scatter) and ridden home on the
# existing async d2h reaper path. Bin edges are log-spaced over the
# family's declared score range (``ModelSpec.score_range``): anomaly
# scores are sigma-ish units spanning decades, so log bins keep both the
# nominal bulk (~0.1–1) and the anomaly tail (10–100+) resolvable with 64
# bins. ``runtime.scorehealth`` merges these sketches into per-tenant
# drift statistics (PSI/KS vs a frozen reference) and quantile gauges.

SKETCH_NBINS = 64

# default per-family score range (lo, hi) for the log-spaced sketch edges;
# scores below lo land in bin 0, above hi in the top bin. The window-scan
# scorers all emit |error|-in-sigma-style scores, so one default covers
# the zoo; a family with different score units overrides on its ModelSpec.
DEFAULT_SCORE_RANGE = (1e-3, 1e2)


def sketch_edges(
    lo: float = DEFAULT_SCORE_RANGE[0],
    hi: float = DEFAULT_SCORE_RANGE[1],
    nbins: int = SKETCH_NBINS,
):
    """The ``nbins - 1`` interior bin edges, log-spaced over (lo, hi):
    bin 0 is [0, lo), bin nbins-1 is [hi', inf) — np.histogram semantics
    (left-closed bins; ``sketch_histogram`` matches them exactly).
    Returns float32 numpy; the jitted step closes over it as a
    constant."""
    import numpy as np

    return np.logspace(
        math.log10(lo), math.log10(hi), nbins - 1, dtype=np.float32
    )


def sketch_histogram(scores, valid, edges):
    """Per-row histogram of ``scores`` [T, B] (any float dtype; binned in
    f32) where ``valid`` [T, B] and the score is not NaN, over the bins
    ``[-inf, *edges, inf]`` with np.histogram's left-closed bins:
    int32 [T, len(edges) + 1].

    A score's bin is the number of edges at or below it, so the count of
    bins >= k is the count of valid scores >= ``edges[k-1]``: one compare
    of each score with each edge, summed over the lane axis (the compare
    fuses into the reduction), and adjacent differences of those counts.
    No gather, no scatter, no loop."""
    s = scores.astype(jnp.float32)
    ok = valid & ~jnp.isnan(s)
    e = jnp.asarray(edges, jnp.float32)
    ge = jnp.sum(ok[:, None, :] & (s[:, None, :] >= e[None, :, None]),
                 axis=-1, dtype=jnp.int32)               # [T, E]
    n = jnp.sum(ok, axis=-1, dtype=jnp.int32)[:, None]  # [T, 1]
    at_least = jnp.concatenate([n, ge, jnp.zeros_like(n)], axis=1)
    return at_least[:, :-1] - at_least[:, 1:]


# -- analytic FLOP accounting (device-time / MFU attribution) --------------
#
# Each model family declares ``flops_per_row(cfg, window)``: the matmul
# FLOPs (2 × MACs — the MFU convention; elementwise/nonlinearity ops are
# excluded) the device executes to score ONE row with a length-``window``
# series window. The scoring hot path multiplies by the flushed PLANE
# (every padded lane row executes, valid or not) to feed the live
# ``tpu_flops_total{family}`` / ``tpu_mfu_pct{family}`` accounting.
#
# Why analytic instead of XLA's cost analysis: XLA's ``cost_analysis()``
# counts a ``lax.scan`` BODY once, not per trip — for the window-scan
# models here that under-reports FLOPs by ~(window-1)× (see
# docs/PERFORMANCE.md "MFU accounting").

def dense_flops(in_dim: int, out_dim: int) -> float:
    """Matmul FLOPs for one row through a dense layer (2 per MAC)."""
    return 2.0 * in_dim * out_dim


def lstm_scan_flops(hidden: int, steps: int, in_dim: int = 1) -> float:
    """One row through an LSTM scan: fused 4-gate input + recurrent
    matmuls per step."""
    per_step = dense_flops(in_dim, 4 * hidden) + dense_flops(hidden, 4 * hidden)
    return per_step * steps


def gru_scan_flops(hidden: int, steps: int, in_dim: int = 1) -> float:
    """One row through a GRU scan: fused 3-gate input + recurrent
    matmuls per step."""
    per_step = dense_flops(in_dim, 3 * hidden) + dense_flops(hidden, 3 * hidden)
    return per_step * steps


def transformer_block_flops(dim: int, seq: int, mlp_ratio: int = 4) -> float:
    """One transformer block over a length-``seq`` sequence (all rows):
    QKV+output projections, the two attention matmuls, and the MLP."""
    proj = 4 * dense_flops(dim, dim) * seq              # wq/wk/wv/wo
    attn = 2 * (2.0 * seq * seq * dim)                  # QK^T and AV
    mlp = (dense_flops(dim, mlp_ratio * dim)
           + dense_flops(mlp_ratio * dim, dim)) * seq
    return proj + attn + mlp


# The ``k``/``param_dtype`` kwargs describe the FUSED megabatch variant
# (parallel.sharded fused step): ``k=None`` means the legacy vmap path —
# per-step head over every position, full-width master weights — so the
# default call is numerically identical to the pre-fusion accounting.
# With ``k`` set, the fused kernel runs the same scan but applies its
# heads only to the last K positions, and quantized weight matmuls count
# at their real MAC width (``QUANT_MAC_WIDTH`` — int8 at 0.5× against
# the bf16 peak). This is what keeps ``tpu_flops_total{family}`` /
# ``tpu_mfu_pct{family}`` honest for K-step and quantized stacks.

def lstm_ad_flops_per_row(
    cfg, window: int, k: Optional[int] = None, param_dtype: str = "f32",
) -> float:
    """lstm_ad.score: LSTM over window-1 steps + head (per-step on the
    legacy path; last-K-only on the fused path)."""
    t = max(1, int(window) - 1)
    wq = quant_mac_width(param_dtype) if k is not None else 1.0
    head_steps = t if k is None else max(1, min(int(k), t))
    return (
        lstm_scan_flops(cfg.hidden, t)
        + dense_flops(cfg.hidden, 1) * head_steps
    ) * wq


def deepar_flops_per_row(
    cfg, window: int, k: Optional[int] = None, param_dtype: str = "f32",
) -> float:
    """deepar.score: GRU encode over window-1 steps + (mu, sigma) heads
    (per-step legacy; last-K-only fused)."""
    t = max(1, int(window) - 1)
    wq = quant_mac_width(param_dtype) if k is not None else 1.0
    head_steps = t if k is None else max(1, min(int(k), t))
    return (
        gru_scan_flops(cfg.hidden, t)
        + 2 * dense_flops(cfg.hidden, 1) * head_steps
    ) * wq


def transformer_flops_per_row(
    cfg, window: int, k: Optional[int] = None, param_dtype: str = "f32",
) -> float:
    """transformer.score: embed + causal backbone over window-1 tokens +
    the (mu, raw_sigma) head. Quantization scales only the WEIGHT
    matmuls — the attention QK^T/AV products are activation·activation
    and run full width regardless of param_dtype."""
    t = max(1, int(window) - 1)
    wq = quant_mac_width(param_dtype) if k is not None else 1.0
    head_steps = t if k is None else max(1, min(int(k), t))
    attn = cfg.depth * 2 * (2.0 * t * t * cfg.dim)        # QK^T and AV
    mlp_ratio = 4
    weight_mm = (
        dense_flops(1, cfg.dim) * t                        # embed
        + cfg.depth * (
            4 * dense_flops(cfg.dim, cfg.dim) * t          # wq/wk/wv/wo
            + (dense_flops(cfg.dim, mlp_ratio * cfg.dim)
               + dense_flops(mlp_ratio * cfg.dim, cfg.dim)) * t
        )
        + dense_flops(cfg.dim, 2) * head_steps             # (mu, sigma)
    )
    return weight_mm * wq + attn


def vit_flops_per_image(cfg, window: int = 0) -> float:
    """vit.apply: patch embed + backbone over N+1 tokens + CLS head.
    ``window`` is ignored (frames carry no series window) — the arg keeps
    the ``flops_per_row`` contract uniform across the registry."""
    del window
    n = cfg.num_patches
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.channels
    return (
        dense_flops(patch_dim, cfg.dim) * n
        + cfg.depth * transformer_block_flops(cfg.dim, n + 1)
        + dense_flops(cfg.dim, cfg.num_classes)
    )


# -- tensor parallelism (Megatron-style, over the mesh 'model' axis) -------
#
# Column-parallel Q/K/V and fc1 (each device owns heads/n heads and
# hidden/n MLP units), row-parallel wo and fc2 with ONE psum each — two
# collectives per block, the standard TP recipe, expressed with shard_map
# over a named axis so it composes with the tenant/data axes
# (SURVEY.md §2 parallelism census: "pjit/shard_map for intra-model
# parallelism of the larger models").

def shard_block_params_tp(blk: Params, n: int, idx: int) -> Params:
    """Slice one transformer block's params for TP rank ``idx`` of ``n``.

    Column-parallel weights split on the OUTPUT dim (wq/wk/wv, fc1 — and
    their biases); row-parallel weights split on the INPUT dim (wo, fc2 —
    bias kept whole, added once after the psum on rank 0's addend).

    Every split dimension must divide by ``n`` — silent truncation would
    be silently-wrong outputs."""
    dim = blk["attn"]["wq"]["w"].shape[1]
    hidden = blk["mlp"]["fc1"]["w"].shape[1]
    if dim % n or hidden % n:
        raise ValueError(
            f"TP degree {n} must divide model dim {dim} and MLP hidden "
            f"{hidden}"
        )

    def col(p):
        w, b = p["w"], p["b"]
        o = w.shape[1] // n
        return {"w": w[:, idx * o:(idx + 1) * o], "b": b[idx * o:(idx + 1) * o]}

    def row(p):
        w, b = p["w"], p["b"]
        i = w.shape[0] // n
        # bias must be added exactly once across the psum: only rank 0
        # carries it (idx is a trace-time Python int)
        bias = b if idx == 0 else jnp.zeros_like(b)
        return {"w": w[idx * i:(idx + 1) * i], "b": bias}

    return {
        "ln1": blk["ln1"],
        "ln2": blk["ln2"],
        "attn": {
            "wq": col(blk["attn"]["wq"]),
            "wk": col(blk["attn"]["wk"]),
            "wv": col(blk["attn"]["wv"]),
            "wo": row(blk["attn"]["wo"]),
        },
        "mlp": {
            "fc1": col(blk["mlp"]["fc1"]),
            "fc2": row(blk["mlp"]["fc2"]),
        },
    }


def transformer_block_tp(
    p: Params,
    x: jnp.ndarray,          # [..., T, D] REPLICATED activations
    heads: int,              # GLOBAL head count (local = heads / n)
    axis_name: str,
    causal: bool = False,
    dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Tensor-parallel transformer block body (run under shard_map with
    the block params pre-sliced by ``shard_block_params_tp``). Activations
    stay replicated; each device computes its head/hidden slice; the two
    row-parallel projections psum partial results."""
    import jax.lax as lax

    n = lax.psum(1, axis_name)
    if heads % n:
        raise ValueError(f"TP degree {n} must divide head count {heads}")
    local_heads = heads // n
    h = layernorm(p["ln1"], x)
    ap = p["attn"]
    hd = ap["wq"]["w"].shape[1] // local_heads

    def split(a):
        return a.reshape(*a.shape[:-1], local_heads, hd)

    q = split(dense(ap["wq"], h, dtype))
    k = split(dense(ap["wk"], h, dtype))
    v = split(dense(ap["wv"], h, dtype))
    out = attn_core(q, k, v, causal, dtype)
    x = x + lax.psum(dense(ap["wo"], out, dtype), axis_name)   # collective 1
    h2 = layernorm(p["ln2"], x)
    part = dense(p["mlp"]["fc2"], jax.nn.gelu(dense(p["mlp"]["fc1"], h2, dtype)), dtype)
    x = x + lax.psum(part, axis_name)                          # collective 2
    return x
