"""A routed expert layer that is told which experts it holds.

One chip of a deployment that splits each layer's experts over several
holds a contiguous range of them (``experts_held``). The layer routes
over ALL experts at the published router width, computes the part of the
result its own experts give for the (row, expert) pairs that fall on
them, and adds nothing for pairs on absent experts — no code stands in
for the other chips, and the partial sum is what goes on to the next
layer (model-configs guide, section 4).

Mechanism: the held pairs of a flush are sorted by expert and multiplied
group by group (``grouped_product``), so a step reads the weights of the
experts it hit and no others: on a TPU the Pallas grouped matmul
(``megablox.gmm``: a grid over the (row tile, expert) pairs that hold
rows) with weight tiles of about a megabyte; elsewhere
``jax.lax.ragged_dot``, which tier-1 holds to the plain reference. (On
the TPU ``ragged_dot`` lowers to a grouped kernel too, but with 128 x 128
weight tiles: a step that hits 35 experts a layer with one or two rows
each spent 25 of its 30 ms in 11,000 32 KB tile fetches a product — my
chip run, PR 39.) Router scores and gates are f32; the expert products
are bf16 with f32 accumulation.

Two expert forms: ``W_down relu(W_up u)^2`` (Nemotron-H) and the gated
``W_down (silu(W_gate u) * W_up u)`` (SwiGLU; afmoe), whose gate and up
matrices are one stored matrix ``[gate | up]`` so that a step multiplies
each held expert once on the way up.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def route(
    u: jnp.ndarray, w_router: jnp.ndarray, bias: jnp.ndarray,
    top_k: int, scale: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid router over every expert (f32): pick ``top_k`` of
    ``s + bias``, gate with ``s[top] / sum(s[top]) * scale`` — the bias
    steers the choice and never the weight. u [N, H] → (idx i32[N, k],
    gates f32[N, k])."""
    logits = jnp.dot(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=HIGHEST,
    )
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    gates = w / jnp.sum(w, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), gates


def _on_tpu() -> bool:
    """Which grouped product ``grouped_product`` takes: the platform's.
    (A compile for a DESCRIBED chip steers this in the test.)"""
    return jax.default_backend() == "tpu"


def _tile(dim: int, want: int) -> int:
    """The largest multiple of 128 up to ``want`` that divides ``dim``,
    or the whole dimension."""
    for t in range(want - want % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def grouped_product(
    xs: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray
) -> jnp.ndarray:
    """Rows of ``xs`` [P, K], sorted by group, each times its group's
    matrix of ``w`` [G, K, N] -> f32[P, N]; ``sizes`` i32[G] rows a
    group, empty groups read nothing. Rows past the last group are left
    as the kernel left them: the caller drops them."""
    rows = xs.shape[0]
    tm = next((t for t in (128, 64, 32, 16, 8) if rows % t == 0
               and (t < 128 or rows >= 1024)), None)
    if not _on_tpu() or tm is None:
        return jax.lax.ragged_dot(
            xs, w, sizes, preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(
        xs, w, sizes, preferred_element_type=jnp.float32,
        tiling=(tm, _tile(w.shape[1], 896), _tile(w.shape[2], 896)),
    )


def relu2(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(jax.nn.relu(x))


def swiglu(h: jnp.ndarray, width: int) -> jnp.ndarray:
    """``silu(gate) * up`` of a product with ``[gate | up]``: the first
    ``width`` columns are the gate's, the next ``width`` the up's."""
    return jax.nn.silu(h[..., :width]) * h[..., width:2 * width]


def held_experts(
    u: jnp.ndarray,            # [N, H] rows (normed hidden)
    idx: jnp.ndarray,          # i32[N, k] expert picked, over ALL experts
    gates: jnp.ndarray,        # f32[N, k]
    valid: jnp.ndarray,        # bool[N] padding rows route nowhere
    w_up: jnp.ndarray,         # [E_held, H, I (+ zero columns to a tile)]
    w_down: jnp.ndarray,       # [E_held, I (+ zero rows alike), H]
    held_lo: int,
    gated: bool = False,       # w_up is [E_held, H, 2 I]: [gate | up]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' share ``sum_e g_e E_e(u)`` f32[N, H], and the
    step's counters i32[3]: (row, expert) pairs routed, pairs that fell
    on held experts, distinct held experts hit."""
    n, k = idx.shape
    e_held = w_up.shape[0]
    local = idx - held_lo
    here = (local >= 0) & (local < e_held) & valid[:, None]
    # absent and padding pairs sort behind every group and ride no group
    group = jnp.where(here, local, e_held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    row = (order // k).astype(jnp.int32)
    sizes = jnp.zeros((e_held + 1,), jnp.int32).at[group].add(1)[:e_held]
    xs = u[row].astype(w_up.dtype)
    h = grouped_product(xs, w_up, sizes)
    width = w_down.shape[1]
    act = swiglu(h, width) if gated else relu2(h[:, :width])
    y = grouped_product(act.astype(w_down.dtype), w_down, sizes)
    g = jnp.where(here, gates, 0.0).reshape(-1)[order]
    # rows past the last group belong to no expert: whatever the grouped
    # product left there is dropped, not scaled
    y = jnp.where(g[:, None] != 0.0, y * g[:, None], 0.0)
    out = jnp.zeros((n, u.shape[-1]), jnp.float32).at[row].add(y)
    stats = jnp.stack([
        k * jnp.sum(valid.astype(jnp.int32)),
        jnp.sum(here.astype(jnp.int32)),
        jnp.sum((sizes > 0).astype(jnp.int32)),
    ])
    return out, stats


def dense_relu2(
    u: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray
) -> jnp.ndarray:
    """A shared (always-on) expert of the same form: W_down relu(W_up u)^2."""
    h = jnp.dot(u.astype(w_up.dtype), w_up,
                preferred_element_type=jnp.float32)
    return jnp.dot(relu2(h).astype(w_down.dtype), w_down,
                   preferred_element_type=jnp.float32)


def dense_swiglu(
    u: jnp.ndarray, w_gate_up: jnp.ndarray, w_down: jnp.ndarray
) -> jnp.ndarray:
    """A shared expert or a dense MLP of the gated form:
    W_down (silu(W_gate u) * W_up u), ``w_gate_up`` = [gate | up]."""
    h = jnp.dot(u.astype(w_gate_up.dtype), w_gate_up,
                preferred_element_type=jnp.float32)
    return jnp.dot(swiglu(h, w_down.shape[0]).astype(w_down.dtype), w_down,
                   preferred_element_type=jnp.float32)
