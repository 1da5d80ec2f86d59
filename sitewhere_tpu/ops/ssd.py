"""Mamba-2 state-space mixer over a per-stream state that lives from event
to event: the one-token step and the chunked (SSD) scan of a run.

Published equations (Mamba-2, Dao & Gu 2024; the Nemotron-H block):

    [z | xBC | dt] = W_in u
    xBC_t = silu(sum_k w[k] * xBC_{t-3+k} + b)        depthwise, 4 taps
    x (heads x head_dim), B, C (groups x state)  = split(xBC_t)
    D_h = softplus(dt_h + dt_bias_h)                  the step size
    S_h <- exp(-D_h e^{A_log_h}) S_h + D_h x_h (x) B_g   head h reads group g
    y_h = S_h C_g + Dskip_h x_h
    y <- RMSNorm_per-group(y * silu(z)) * w ;  out = W_out y

``step`` applies them to one token a row, the rows' states in hand;
``step_in_store`` does the same with each live row's state read from and
written back into the store that holds every stream's — the one-step
program's form, which moves no state for a padding row. ``chunk``
applies them to a run of up to L tokens a row in the chunked form —
inside the run the decays are a lower-triangular matrix, the state
before the run enters through ``exp(cumsum) C . S0`` and the state after
it is accumulated in one product — which gives the same values as L
single steps.

Precision: the two projections are bf16 products with f32 accumulation;
the step size, the decay, the convolution, the state update and every
product with the f32 state are f32 (``HIGHEST``), in both forms — so the
two forms differ by the order of f32 sums only.

State a stream and layer: ``ssm`` f32[heads, head_dim, state] and
``conv`` f32[taps-1, conv_dim] (the last three convolution inputs).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _project_in(u, p, dims):
    heads, head_dim, groups, n_state = dims
    d_in = heads * head_dim
    conv_dim = d_in + 2 * groups * n_state
    zxbcdt = jnp.dot(
        u.astype(p["w_in"].dtype), p["w_in"],
        preferred_element_type=jnp.float32,
    )
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in : d_in + conv_dim]
    dt = zxbcdt[..., d_in + conv_dim :]
    return z, xbc, dt


def _split_xbc(xbc, dims):
    heads, head_dim, groups, n_state = dims
    d_in = heads * head_dim
    lead = xbc.shape[:-1]
    x = xbc[..., :d_in].reshape(lead + (heads, head_dim))
    b = xbc[..., d_in : d_in + groups * n_state].reshape(
        lead + (groups, n_state)
    )
    c = xbc[..., d_in + groups * n_state :].reshape(lead + (groups, n_state))
    # head h reads group h // (heads / groups)
    rep = heads // groups
    return x, jnp.repeat(b, rep, axis=-2), jnp.repeat(c, rep, axis=-2)


def _gate_norm_out(y, z, p, groups: int, eps: float):
    """RMSNorm over each of ``groups`` slices of (y * silu(z)), the norm
    weight, then the output projection."""
    lead = y.shape[:-1]
    g = (y * jax.nn.silu(z)).reshape(lead + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    g = g.reshape(lead + (-1,)) * p["norm_g"]
    return jnp.dot(
        g.astype(p["w_out"].dtype), p["w_out"],
        preferred_element_type=jnp.float32,
    )


def _step_inputs(u, p, conv, dims):
    """What one token a row brings to the state, batched over the rows:
    (z, conv', x [N, heads, P], b, c [N, heads, state], delta [N, heads])."""
    z, xbc, dt = _project_in(u, p, dims)
    hist = jnp.concatenate([conv, xbc[:, None, :]], axis=1)
    xbc = jax.nn.silu(jnp.sum(hist * p["conv_w"][None], axis=1) + p["conv_b"])
    x, b, c = _split_xbc(xbc, dims)
    delta = jax.nn.softplus(dt + p["dt_bias"])               # [N, heads]
    return z, hist[:, 1:], x, b, c, delta


def _recur(ssm, x, b, c, delta, p):
    """The state update and its read-out for rows ``[n, heads, ...]``:
    (y [n, heads, P], ssm')."""
    decay = jnp.exp(-delta * jnp.exp(p["a_log"]))
    ssm = (
        decay[:, :, None, None] * ssm
        + (delta[:, :, None] * x)[..., None] * b[:, :, None, :]
    )
    y = jnp.sum(ssm * c[:, :, None, :], axis=-1) + p["d"][None, :, None] * x
    return y, ssm


def step(
    u: jnp.ndarray,        # [N, H] normed hidden, one token a row
    p: dict,
    conv: jnp.ndarray,     # f32[N, taps-1, conv_dim]
    ssm: jnp.ndarray,      # f32[N, heads, head_dim, state]
    dims: Tuple[int, int, int, int],
    eps: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token a row, every row's state in hand: (out f32[N, H], conv',
    ssm'). The plain batched form that ``step_in_store`` is held to."""
    heads, head_dim, groups, _ = dims
    z, conv, x, b, c, delta = _step_inputs(u, p, conv, dims)
    y, ssm = _recur(ssm, x, b, c, delta, p)
    y = y.reshape(y.shape[0], heads * head_dim)
    return _gate_norm_out(y, z, p, groups, eps), conv, ssm


def step_in_store(
    u: jnp.ndarray,        # [N, H] normed hidden, one token a row
    p: dict,
    conv: jnp.ndarray,     # f32[N, taps-1, conv_dim]
    store: jnp.ndarray,    # f32[S, heads, head_dim, state], every stream's
    rows: jnp.ndarray,     # i32[N] each row's stream in ``store``
    live: jnp.ndarray,     # bool[N] rows that carry a token
    dims: Tuple[int, int, int, int],
    eps: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``step`` with the state read from and written back into the whole
    store, one live row a turn: (out f32[N, H], conv', store'). Nothing
    of the state is moved for a padding row — its ``out`` is 0 — and the
    store is updated in place where the caller donates it; the rows'
    inputs (KB a row) are computed batched, as in ``step``."""
    heads, head_dim, groups, _ = dims
    z, conv, x, b, c, delta = _step_inputs(u, p, conv, dims)
    # the live rows first, turn k takes the k-th (a sort: a compaction by
    # ``flatnonzero`` is a scatter)
    order = jnp.argsort(~live, stable=True)
    one = (1,) + store.shape[1:]

    def turn(k, carry):
        store, y = carry
        i = order[k]
        at = (rows[i], 0, 0, 0)
        y_i, s = _recur(
            jax.lax.dynamic_slice(store, at, one),
            *(jax.lax.dynamic_slice_in_dim(v, i, 1) for v in (x, b, c, delta)),
            p)
        return (jax.lax.dynamic_update_slice(store, s, at),
                jax.lax.dynamic_update_slice_in_dim(y, y_i, i, 0))

    store, y = jax.lax.fori_loop(
        0, live.sum(), turn, (store, jnp.zeros_like(x)))
    y = y.reshape(y.shape[0], heads * head_dim)
    return _gate_norm_out(y, z, p, groups, eps), conv, store


def chunk(
    u: jnp.ndarray,        # [N, L, H] normed hidden, a run a row
    p: dict,
    conv: jnp.ndarray,     # f32[N, taps-1, conv_dim]
    ssm: jnp.ndarray,      # f32[N, heads, head_dim, state]
    lens: jnp.ndarray,     # i32[N] tokens of each run that are real
    dims: Tuple[int, int, int, int],
    eps: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A run of up to L tokens a row, chunked form: (out f32[N, L, H],
    conv', ssm'). Tokens past ``lens`` leave the state as it was."""
    heads, head_dim, groups, _ = dims
    n, length = u.shape[0], u.shape[1]
    taps = conv.shape[1] + 1
    z, xbc, dt = _project_in(u, p, dims)
    full = jnp.concatenate([conv, xbc], axis=1)              # [N, L+3, C]
    acc = p["conv_b"][None, None, :]
    for k in range(taps):
        acc = acc + p["conv_w"][k][None, None, :] * full[:, k : k + length]
    xbc = jax.nn.silu(acc)
    # the last three real inputs: rows lens .. lens+2 of ``full``
    at = lens[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
    conv = jnp.take_along_axis(full, at[:, :, None], axis=1)
    x, b, c = _split_xbc(xbc, dims)                          # [N, L, heads, .]
    real = jnp.arange(length, dtype=jnp.int32)[None, :] < lens[:, None]
    delta = jax.nn.softplus(dt + p["dt_bias"]) * real[:, :, None]
    cum = jnp.cumsum(-delta * jnp.exp(p["a_log"]), axis=1)   # [N, L, heads]
    # inside the run: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) D_j x_j
    cb = jnp.einsum("nihk,njhk->nhij", c, b, precision=HIGHEST)
    cum_h = jnp.moveaxis(cum, 1, 2)                          # [N, heads, L]
    diff = cum_h[:, :, :, None] - cum_h[:, :, None, :]
    causal = jnp.tril(jnp.ones((length, length), bool))
    decay = jnp.where(causal[None, None], jnp.exp(jnp.where(
        causal[None, None], diff, 0.0)), 0.0)
    dx = delta[..., None] * x                                # [N, L, heads, P]
    y = jnp.einsum("nhij,njhp->nihp", cb * decay, dx, precision=HIGHEST)
    # the state before the run, decayed to each token
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "nihk,nhpk->nihp", c, ssm, precision=HIGHEST)
    y = y + p["d"][None, None, :, None] * x
    # the state after the run
    total = cum[:, -1]                                       # [N, heads]
    to_end = jnp.exp(total[:, None, :] - cum)                # [N, L, heads]
    ssm = jnp.exp(total)[:, :, None, None] * ssm + jnp.einsum(
        "njhp,njhk->nhpk", dx * to_end[..., None], b, precision=HIGHEST)
    y = y.reshape(n, length, heads * head_dim)
    return _gate_norm_out(y, z, p, groups, eps), conv, ssm
