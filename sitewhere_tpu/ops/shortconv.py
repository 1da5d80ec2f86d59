"""The gated short convolution of LFM2 over a per-stream state that lives
from event to event: a run of tokens a row, its left context the last
``taps - 1`` gated inputs the stream saw before it.

Published equations (LFM2; ``conv_L_cache`` taps, no bias):

    [B | C | x~] = W_in u                    three slices of the hidden width
    z_t = B_t * x~_t                         the input gate
    y_t = sum_{j=0..taps-1} w[j] * z_{t-taps+1+j}   depthwise, causal
    out_t = W_out (C_t * y_t)                the output gate

``mixer`` applies them to a run of up to L tokens a row: its convolution
reads the stream's tail (``taps - 1`` rows of z, zeros before a stream's
first token) and then the run's own z, and returns the new tail — the
last ``taps - 1`` rows of z through each row's last real token (tokens
past ``lens`` leave the tail as it was). One token a row (L = 1) is the
one-step form; a longer run the chunked form; both read and write the
same tail, so they give the same values.

Precision: the two projections are bf16 products with f32 accumulation;
the gates, the taps and the tail are f32.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def conv(z: jnp.ndarray, tail: jnp.ndarray, w: jnp.ndarray,
         lens: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The causal depthwise convolution of a run with its left context.
    z f32[N, L, C], tail f32[N, taps-1, C], w f32[taps, C], lens i32[N]
    -> (y f32[N, L, C], tail' f32[N, taps-1, C])."""
    taps, length = w.shape[0], z.shape[1]
    full = jnp.concatenate([tail, z], axis=1)             # [N, L+taps-1, C]
    y = w[0][None, None, :] * full[:, :length]
    for j in range(1, taps):
        y = y + w[j][None, None, :] * full[:, j:j + length]
    # the last taps-1 real inputs: rows lens .. lens+taps-2 of ``full``
    at = lens[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
    return y, jnp.take_along_axis(full, at[:, :, None], axis=1)


def mixer(u: jnp.ndarray, p: dict, tail: jnp.ndarray,
          lens: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One gated short-convolution layer over a run a row: u [N, L, H]
    normed hidden, ``p`` its ``w_in`` [H, 3 H] ([B | C | x~]), ``conv_w``
    f32[taps, H] and ``w_out`` [H, H] -> (out f32[N, L, H], tail')."""
    h = p["w_out"].shape[0]
    bcx = jnp.dot(u.astype(p["w_in"].dtype), p["w_in"],
                  preferred_element_type=jnp.float32)
    z = bcx[..., :h] * bcx[..., 2 * h:]
    y, tail = conv(z, tail, p["conv_w"], lens)
    out = jnp.dot((bcx[..., h:2 * h] * y).astype(p["w_out"].dtype),
                  p["w_out"], preferred_element_type=jnp.float32)
    return out, tail
