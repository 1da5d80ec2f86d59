"""Attention that reads each row's keys and values where they live: in
pages of a store shared by many streams, through the row's page table.

The store is ``[pages, page_size, 2 G, D]`` with a position's key of
head g at ``2 g`` and its value at ``2 g + 1`` of the third axis (the
layout of the Pallas kernel below). A row's table lists, in order, the
physical pages that hold its positions from the table's first page on;
``kv_len`` counts those positions up to and including the row's newest
token (the caller has written the row's own keys and values first), and
the row's ``lens`` newest positions are its queries. A query at table
position ``p`` sees table positions ``c <= p`` — and, given ``window``,
only ``p - c < window``. Padding rows (``lens`` 0) come after the live
ones and read nothing.

Mechanism: on a TPU the Pallas kernel
``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` (queries of
all rows packed back to back, per-row lengths, the tables, a dynamic
count of live rows); elsewhere a loop over the tables' columns with an
online softmax, one page of every row a pass — what tier-1 holds to the
plain reference. Neither makes a temporary of rows x table: the loop's
is rows x one page. Scores and the softmax are f32, the products take
the store's dtype with f32 accumulation.

Heads of 64 lanes (``attend_paired``). A TPU lays the store's minor axis
over 128 lanes, so a ``[pages, page, 2 G, 64]`` store would hold as many
bytes of padding as of keys and values, and the kernel would fetch them.
The paired store puts kv heads 2 i and 2 i + 1 side by side in one row of
128 lanes — ``[pages, page, G, 128]``, combined head 2 i the keys of the
pair, 2 i + 1 its values — and a query of 64 lanes meets it with zeros in
the other half: its product with the pair's keys is its product with its
own head's keys, to the bit, and of the 128 lanes of output the half of
its own head is kept. The kernel computes twice the scores' products a
position and reads no byte more than the keys and values themselves.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# the Pallas kernel's blocks: 4 pages (1,024 positions at 256 a page) of
# every kv head a step, 32 queries a block — a chunk of 1,024 queries at
# 128 queries a block asks for 34 MB of the 16 MB of scoped fast memory
# (described-v5e compile)
KV_PAGES_PER_BLOCK = 4
QUERIES_PER_BLOCK = 32
NEG = -1e30  # a masked score: exp(NEG - m) is 0 for any live m


def _on_tpu() -> bool:
    """Which form ``attend`` takes: the platform's. (A compile for a
    DESCRIBED chip steers this in the test.)"""
    return jax.default_backend() == "tpu"


def attend(
    q: jnp.ndarray,        # [N, L, Hq, D] queries, row-major, oldest first
    pages: jnp.ndarray,    # [P, page, 2 G, D] the store
    table: jnp.ndarray,    # i32[N, M] the rows' physical pages in order
    kv_len: jnp.ndarray,   # i32[N] positions through the newest query
    lens: jnp.ndarray,     # i32[N] queries a row (0: a padding row)
    scale: float,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Softmax attention of each row's ``lens`` queries over its table's
    positions. Returns [N, L, Hq, D] in ``q``'s dtype (0 at padding)."""
    real = jnp.arange(q.shape[1])[None, :] < lens[:, None]
    if _on_tpu():
        out = _pallas(q, pages, table, kv_len, lens, scale, window)
    else:
        out = _gather(q, pages, table, kv_len, lens, scale, window)
    return jnp.where(real[:, :, None, None], out, 0).astype(q.dtype)


def _pallas(q, pages, table, kv_len, lens, scale, window):
    """The rows' queries packed back to back (row i's at ``cu[i]``), the
    kernel, and its output unpacked to rows."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention,
    )

    n, length, hq, d = q.shape
    # the kernel waits for the first block it fetches only inside a live
    # row: a call of padding alone (the prewarm's) would leave that fetch
    # pending and halt the chip, so row 0 then reads one position
    none = jnp.all(lens == 0)
    lens = lens.at[0].set(jnp.where(none, 1, lens[0]))
    kv_len = kv_len.at[0].set(jnp.where(none, 1, kv_len[0]))
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(lens).astype(jnp.int32)])
    slot = cu[:-1, None] + jnp.arange(length, dtype=jnp.int32)[None, :]
    real = jnp.arange(length)[None, :] < lens[:, None]
    slot = jnp.where(real, slot, n * length)          # padding: dropped
    packed = jnp.zeros((n * length, hq, d), q.dtype).at[slot].set(
        q, mode="drop")
    out = ragged_paged_attention(
        packed, pages, kv_len.astype(jnp.int32), table.astype(jnp.int32), cu,
        jnp.sum(lens > 0, dtype=jnp.int32)[None],
        sm_scale=scale, sliding_window=window,
        num_kv_pages_per_block=min(KV_PAGES_PER_BLOCK, table.shape[1]),
        num_queries_per_block=min(QUERIES_PER_BLOCK, n * length),
    )
    return out[jnp.minimum(slot, n * length - 1)]


def _gather(q, pages, table, kv_len, lens, scale, window):
    """One column of every row's table a pass, an online softmax over the
    passes; as many passes as the longest row needs."""
    n, length, hq, d = q.shape
    page = pages.shape[1]
    g = pages.shape[2] // 2
    qg = q.reshape(n, length, g, hq // g, d)
    # a query's position in its row's table; padding queries see nothing
    row = (kv_len - lens)[:, None] + jnp.arange(length)[None, :]
    real = jnp.arange(length)[None, :] < lens[:, None]
    row = jnp.where(real, row, -1)
    n_pages = jnp.where(lens > 0, -(-kv_len // page), 0)

    def body(carry):
        j, m, l, acc = carry
        kv = pages[table[:, jnp.minimum(j, table.shape[1] - 1)]]
        k, v = kv[:, :, 0::2], kv[:, :, 1::2]           # [N, page, G, D]
        col = j * page + jnp.arange(page)
        seen = (col[None, None, :] <= row[:, :, None]) & (
            j < n_pages)[:, None, None]
        if window is not None:
            seen &= row[:, :, None] - col[None, None, :] < window
        s = jnp.einsum("nlgqd,npgd->nlgqp", qg, k.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen[:, :, None, None], s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * seen[:, :, None, None]
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "nlgqp,npgd->nlgqd", p.astype(q.dtype), v.astype(q.dtype),
            preferred_element_type=jnp.float32)
        return j + 1, m_new, l * fix + p.sum(axis=-1), acc

    shape = (n, length, g, hq // g)
    _, _m, l, acc = jax.lax.while_loop(
        lambda c: c[0] < jnp.max(n_pages), body,
        (jnp.int32(0), jnp.full(shape, NEG, jnp.float32),
         jnp.zeros(shape, jnp.float32), jnp.zeros(shape + (d,), jnp.float32)))
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.reshape(n, length, hq, d)


def paired(k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Keys and values [..., G, D] of every kv head as a position of the
    paired store: [..., G, 2 D], row 2 i the keys of heads 2 i and 2 i + 1
    side by side, row 2 i + 1 their values."""
    lead, (g, d) = k.shape[:-2], k.shape[-2:]
    pair = lead + (g // 2, 2 * d)
    return jnp.stack([k.reshape(pair), v.reshape(pair)],
                     axis=-2).reshape(lead + (g, 2 * d))


def attend_paired(
    q: jnp.ndarray,        # [N, L, Hq, D] queries of D lanes
    pages: jnp.ndarray,    # [P, page, G, 2 D] the paired store
    table: jnp.ndarray,
    kv_len: jnp.ndarray,
    lens: jnp.ndarray,
    scale: float,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """``attend`` over a store whose kv heads are paired into the lanes
    (``paired``): each query widened to 2 D with zeros in the half its
    head's pair does not hold, and the output's other half dropped.
    Returns [N, L, Hq, D]."""
    d, hq = q.shape[-1], q.shape[2]
    per = hq // pages.shape[2]          # query heads a kv head
    second = ((jnp.arange(hq) // per) % 2 == 1)[:, None]
    zero = jnp.zeros_like(q)
    wide = jnp.where(second, jnp.concatenate([zero, q], axis=-1),
                     jnp.concatenate([q, zero], axis=-1))
    out = attend(wide, pages, table, kv_len, lens, scale, window)
    return jnp.where(second, out[..., d:], out[..., :d])
