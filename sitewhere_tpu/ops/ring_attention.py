"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context telemetry is first-class (SURVEY.md §5 long-context): when
a forecasting context exceeds one chip's HBM, the SEQUENCE axis shards
across the mesh and attention runs as a ring — each device holds one
query block resident, while K/V blocks rotate around the ring via
``lax.ppermute`` (ICI neighbor exchange, the cheapest collective
pattern), combining partial attention with running log-sum-exp
rescaling. Exact (not approximate) attention; communication overlaps
block compute; peak memory per device is O(T/n) instead of O(T).

The reference has no analog (no ML); this implements the technique from
Liu et al., "Ring Attention with Blockwise Transformers" (public
method), TPU-idiomatically: static shapes, `lax.fori_loop`, collectives
over a named mesh axis.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One (Q-block × K-block) partial attention.

    q [B, Tq, H, D], k/v [B, Tk, H, D], mask [Tq, Tk] (True = attend) →
    (scores-max m [B, H, Tq], partial denom l, partial numerator acc).
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # [B, H, Tq]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[None, None], p, 0.0)      # fully-masked rows: p=0
    l = jnp.sum(p, axis=-1)                      # [B, H, Tq]
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, v)    # [B, H, Tq, D]
    return m, l, acc


def ring_attention_local(q, k, v, axis_name: str, causal: bool = True):
    """The per-device body (call under ``shard_map`` with the sequence
    dim sharded over ``axis_name``). q/k/v: [B, T_local, H, D] local
    blocks; returns [B, T_local, H, D] — exact attention over the FULL
    sequence."""
    n = lax.psum(1, axis_name)                   # static: the axis size
    my = lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    q_pos = my * tl + jnp.arange(tl)             # global query positions

    m = jnp.full((b, h, tl), NEG_INF, q.dtype)
    l = jnp.zeros((b, h, tl), q.dtype)
    acc = jnp.zeros((b, h, tl, d), q.dtype)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # the axis size is static, so the ring unrolls as a Python loop — the
    # ppermute for the NEXT block overlaps this block's compute under
    # XLA's async collectives, and the final (discarded) rotation is
    # simply not emitted
    k_cur, v_cur = k, v
    for step in range(n):
        # the block arriving at step s originated s hops "behind" us
        src = (my - step) % n
        k_pos = src * tl + jnp.arange(tl)
        mask = (
            q_pos[:, None] >= k_pos[None, :]
            if causal
            else jnp.ones((tl, tl), bool)
        )
        bm, bl, bacc = _block_attn(q, k_cur, v_cur, mask)
        # running log-sum-exp combine
        m_new = jnp.maximum(m, bm)
        r_old = jnp.exp(m - m_new)
        r_blk = jnp.exp(bm - m_new)
        l = l * r_old + bl * r_blk
        acc = acc * r_old[..., None] + bacc * r_blk[..., None]
        m = m_new
        if step < n - 1:  # rotate K/V to the next device (ICI neighbors)
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    # causal first rows always attend to themselves → l > 0; guard anyway
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3))     # [B, Tl, H, D]


def ring_attention(q, k, v, mesh, axis_name: str, causal: bool = True):
    """Convenience wrapper: shard q/k/v's sequence dim over
    ``axis_name`` of ``mesh`` and run the ring. q/k/v: [B, T, H, D]
    global arrays (T divisible by the axis size)."""
    spec = P(None, axis_name, None, None)
    # jitted: an eager shard_map call is interpreted op by op (minutes on
    # the 8-virtual-device CPU rig under jax 0.9); one compile is seconds
    fn = jax.jit(shard_map(
        partial(ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    ))
    return fn(q, k, v)


def full_attention_reference(q, k, v, causal: bool = True):
    """Single-device exact attention — the numerics oracle for tests."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v)
    return jnp.transpose(out, (0, 2, 1, 3))
