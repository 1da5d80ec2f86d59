"""On-device per-stream ring-buffer windows — the scatter/gather core.

The reference has no analog (its CEP sliding windows live in Siddhi on the
JVM — SURVEY.md §5 "long-context" [U]; reference mount empty, see provenance
banner). This module is the TPU-native replacement: every (device,
measurement-name) series gets a fixed-length ring buffer that lives in device
memory, so the steady-state hot loop never ships history back and forth —
only the new micro-batch crosses host→device each step.

Design constraints (why it looks the way it does):

- **Static shapes.** A fixed stream capacity ``S`` and window ``W``;
  micro-batches are padded to bucketed sizes. XLA compiles each bucket once.
- **Lane-dense ring store.** The rings are logically ``[S, W]`` but live as
  ``[ceil(S*W/128), 128]``: ring slot ``k`` of stream ``s`` is flat
  position ``f = s*W + k``, stored at ``(f // 128, f % 128)``. A 128-wide
  minor dimension is the one TPU tiling (8 x 128) that neither pads the
  array nor needs a relayout to scatter into: the compiled step touches the
  rows of the batch and nothing else, where XLA copies a ``[S, W]`` store
  with W < 128 whole, lane-padded, to and from the flat form its scatter
  wants — four passes over the state a step. ``init_window_state``,
  ``_apply_update``, ``_read_windows`` and ``ring_values`` are the only
  places that know the physical shape.
- **Windows without an element gather.** Where W divides 128 a ring never
  straddles a row, so ``gather_windows`` fetches each row's 128-lane store
  row (one row gather) and takes the window out of it with elementwise
  work only: selects pick the ring's W lanes, log2(W) static rolls, each
  under one bit of ``pos``, turn it into time order, one more select
  left-pads a short history. A TPU gathers single elements one at a time
  — the ``take_along_axis`` this replaced was 10.7 ms of a 27 ms step for
  a 32 x 1,024 x 32 plane (PERF.md section 6, PR 37). Any other W keeps
  the element gather.
- **Duplicate streams per batch.** One micro-batch routinely carries several
  samples of the same series. A plain scatter would be order-ambiguous, so
  we compute each row's *rank among same-stream rows* (sort + segment rank,
  all O(B log B) inside jit) and write to ``(pos[s] + rank) % W``.
- **Counters written where a batch writes.** ``pos`` and ``count`` are
  read once at the batch's ids, moved on per row, and written back by
  each stream's last row alone — in place, one stream at a time, since
  a TPU scatter into the tiled ``[T, S]`` counters relays them out
  whole; a batch that writes more streams than the counters' size makes
  worth it adds to them whole instead (``_write_counters``).
- **Branchless padding.** Invalid rows get an out-of-range scatter index and
  are dropped by XLA's scatter ``mode='drop'`` — no ``cond`` in the hot loop.
- **Functional state.** ``WindowState`` is a pytree; update returns a new
  state (donate the old one under jit for in-place HBM reuse).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

LANES = 128  # minor dimension of the ring store: one TPU vector row


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("values", "pos", "count"),
    meta_fields=("window",),
)
@dataclasses.dataclass(frozen=True)
class WindowState:
    """Per-stream ring buffers. All array leaves live on device.

    values: f32[R, 128] ring storage (raw measurement values), lane-dense:
                        slot k of stream s at flat position s*W + k;
                        ``ring_values`` gives the logical [S, W] view
    pos:    i32[S]      next write slot per stream
    count:  i32[S]      total samples ever written per stream (saturating add
                        not needed: int32 @ 1M ev/s/stream ≈ 35 min to wrap is
                        fine because only ``min(count, W)`` is ever used)
    window: W           static (the store's shape does not carry it)
    """

    values: jnp.ndarray
    pos: jnp.ndarray
    count: jnp.ndarray
    window: int

    @property
    def capacity(self) -> int:
        return self.pos.shape[-1]


def init_window_state(
    max_streams: int, window: int, dtype=jnp.float32, shards: int = 1
) -> WindowState:
    """Empty rings. ``shards`` > 1 lays the store out for a stream axis
    split that many ways (``shard_map`` over the data axis): every shard
    owns whole 128-lane rows, padded where ``S/shards * W`` is no
    multiple of 128, and sees its part as a ``shards=1`` state."""
    shard_rows = -(-(max_streams // shards * window) // LANES)  # ceil
    return WindowState(
        values=jnp.zeros((shards * shard_rows, LANES), dtype),
        pos=jnp.zeros((max_streams,), jnp.int32),
        count=jnp.zeros((max_streams,), jnp.int32),
        window=window,
    )


def ring_values(state: WindowState, shards: int = 1) -> jnp.ndarray:
    """The logical rings ``[..., S, W]`` in ring order (slot ``pos`` is
    the oldest once a ring is full) — for tests, smokes and debugging;
    the hot path never materialises it."""
    s, w = state.capacity, state.window
    lead = state.values.shape[:-2]
    per_shard = state.values.reshape(lead + (shards, -1))
    return per_shard[..., : s // shards * w].reshape(lead + (s, w))


def _segment_ranks(stream_ids: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rank of each row among rows sharing its stream id, plus per-row
    total count of rows with that id. Works on padded ids too.

    Returns (ranks i32[B], totals i32[B]) in the *original* row order.
    """
    b = stream_ids.shape[0]
    order = jnp.argsort(stream_ids, stable=True)
    sorted_ids = stream_ids[order]
    idx = jnp.arange(b, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    is_end = jnp.concatenate(
        [sorted_ids[1:] != sorted_ids[:-1], jnp.ones((1,), bool)]
    )
    # each run's first and last index, broadcast along the run: forwards
    # from its start, backwards from its end (a reversed cummax of ranks
    # would carry a later, longer run's total into a shorter one)
    start_idx = jax.lax.cummax(jnp.where(is_start, idx, -1))
    end_idx = jax.lax.cummin(jnp.where(is_end, idx, b), reverse=True)
    ranks_sorted = idx - start_idx
    totals_sorted = end_idx - start_idx + 1
    inv = jnp.argsort(order, stable=True)
    return ranks_sorted[inv].astype(jnp.int32), totals_sorted[inv].astype(jnp.int32)


def _apply_update(
    state: WindowState,
    stream_ids: jnp.ndarray,
    values: jnp.ndarray,
    valid: jnp.ndarray,
    ranks: jnp.ndarray,
    totals: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scatter one slot's ranked micro-batch into its rings. Returns the
    new store and each row's post-batch ``pos`` and ``count``, read once
    at the batch's ids (a padding row's are its id's as they were); the
    counters themselves are written by ``_write_counters``, over every
    slot at once."""
    w = state.window
    pos0 = state.pos[stream_ids]
    count0 = state.count[stream_ids]
    write_slot = (pos0 + ranks) % w
    flat_idx = stream_ids * w + write_slot
    # invalid rows → out-of-range row → dropped by scatter mode='drop'.
    # Bursts of > W same-stream rows in one batch: only the newest W rows
    # write (older ones would be overwritten in sequential order anyway;
    # without this, duplicate scatter indices pick an unspecified winner).
    newest_w = ranks >= (totals - w)
    row = jnp.where(
        valid & newest_w, flat_idx // LANES, state.values.shape[0]
    )
    new_values = state.values.at[row, flat_idx % LANES].set(
        values.astype(state.values.dtype), mode="drop"
    )
    added = jnp.where(valid, totals, 0)
    return new_values, (pos0 + added) % w, count0 + added


# One stream's counters written in place cost as much as this many
# counters written whole: ~7.3 us a stream against ~0.19 ns a counter on
# the v5e (PERF.md section 6, PR 40; ``tools/counter_write_bench.py``).
COUNTERS_PER_WRITE = 39_000


def _write_counters(
    state: WindowState,
    stream_ids: jnp.ndarray,  # i32[..., B]
    valid: jnp.ndarray,       # bool[..., B]
    ranks: jnp.ndarray,
    totals: jnp.ndarray,
    pos: jnp.ndarray,         # i32[..., B] post-batch, per row
    count: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``pos`` and ``count`` of ``state`` (any leading slot axes, matching
    the rows') with the batch's streams moved on. A TPU scatter writes a
    linear array: into the ``[T, S]`` counters, whose (8, 128) tiles are
    not linear, XLA would relay the whole array out and back — 5 ms a
    step for the 32 x 1,171,875 counters (PERF.md section 5). So each
    stream's last row writes its counters in place, one update at a time,
    where that is cheaper than the whole arrays: while the batch writes
    at most ``size // COUNTERS_PER_WRITE`` streams, a bound the counters'
    shape fixes. A batch that writes more adds its per-stream counts to
    the arrays whole; where the bound is 0, or every row of the batch
    fits under it, only one of the two ways is built."""
    w, s = state.window, state.capacity
    writer = valid & (ranks == totals - 1)  # one row a stream
    most = min(state.count.size // COUNTERS_PER_WRITE, writer.size)
    n = writer.sum()

    def one_by_one(pos0, count0):
        # the writers' rows first, turn k writes the k-th (a sort: the
        # compaction ``flatnonzero`` does is a scatter, 0.25 ms on the v5e)
        order = jnp.argsort(~writer.reshape(-1), stable=True)
        ids, new = stream_ids.reshape(-1), (pos.reshape(-1), count.reshape(-1))
        one = (1,) * writer.ndim

        def write(k, counters):
            row = order[k]
            at = (*jnp.unravel_index(row, writer.shape)[:-1], ids[row])
            return tuple(
                jax.lax.dynamic_update_slice(c, v[row].reshape(one), at)
                for c, v in zip(counters, new))

        return jax.lax.fori_loop(0, n, write, (pos0, count0))

    def whole(pos0, count0):
        lead = jnp.indices(stream_ids.shape, sparse=True)[:-1]
        per_stream = jnp.zeros_like(count0).at[
            (*lead, jnp.where(valid, stream_ids, s))
        ].add(valid.astype(jnp.int32), mode="drop")
        return (pos0 + per_stream) % w, count0 + per_stream

    if most == 0:
        return whole(state.pos, state.count)
    if most == writer.size:
        return one_by_one(state.pos, state.count)
    return jax.lax.cond(
        n <= most, one_by_one, whole, state.pos, state.count)


def _update(state, stream_ids, values, valid):
    """The batch written, over any leading slot axes the rows carry: per
    slot the ranks and the rings, then every slot's counters together.
    Returns (new state, per-row post-batch pos and count, ranks, totals)."""
    def per_slot(st, ids, vals, ok):
        ranks, totals = _segment_ranks(jnp.where(ok, ids, -1))
        return (*_apply_update(st, ids, vals, ok, ranks, totals),
                ranks, totals)

    for _ in range(stream_ids.ndim - 1):
        per_slot = jax.vmap(per_slot)
    store, pos, count, ranks, totals = per_slot(
        state, stream_ids, values, valid)
    new_pos, new_count = _write_counters(
        state, stream_ids, valid, ranks, totals, pos, count)
    new_state = WindowState(store, new_pos, new_count, state.window)
    return new_state, pos, count, ranks, totals


def update_windows(
    state: WindowState,
    stream_ids: jnp.ndarray,  # i32[..., B]
    values: jnp.ndarray,      # f32[..., B]
    valid: jnp.ndarray,       # bool[..., B]
) -> WindowState:
    """Append a micro-batch into the ring buffers (order-preserving within
    a stream). Pure, jit-friendly, static-shaped. Leading axes of the
    rows are slots of a stacked state."""
    return _update(state, stream_ids, values, valid)[0]


def gather_windows(
    state: WindowState,
    stream_ids: jnp.ndarray,  # i32[B]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize time-ordered windows for each requested stream.

    Returns (windows f32[B, W] oldest→newest, n_valid i32[B] clamped to W).
    Streams with fewer than W samples are left-padded with their oldest
    value (constant padding keeps models shift-robust without NaNs).
    """
    return _read_windows(
        state.values, stream_ids, state.pos[stream_ids],
        state.count[stream_ids], w=state.window,
    )


def _read_windows(
    store: jnp.ndarray,       # one slot's lane-dense ring store
    stream_ids: jnp.ndarray,  # i32[B]
    pos: jnp.ndarray,         # i32[B] each row's stream's next write slot
    count: jnp.ndarray,       # i32[B] each row's stream's samples written
    *,
    w: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``gather_windows`` for counters already in hand: the update knows
    each row's post-batch ``pos`` and ``count`` and does not read them
    again."""
    n = jnp.minimum(count, w)  # [B]
    # window column c reads ring slot (pos + c) % W — slot ``pos`` is the
    # oldest entry — and a column left of the first valid one (W - n)
    # reads that one instead: the roll and the left-pad are one index
    col = jnp.arange(w, dtype=jnp.int32)[None, :]
    first_valid_col = jnp.minimum(w - n, w - 1)[:, None]
    slot = (pos[:, None] + jnp.maximum(col, first_valid_col)) % w  # [B, W]
    base = stream_ids * w                     # [B] flat position of slot 0
    if LANES % w == 0:
        # a ring never straddles a row: fetch its row, then pick its
        # lanes with selects and static rolls — a TPU gathers single
        # elements one at a time (10 ns each on the v5e), and the row is
        # already on chip. Selects pass bits through; the rolls and the
        # pad run on the bit patterns, so whatever the store holds (-0.0,
        # a NaN's payload, Inf) comes out as it went in
        rows = store[base // LANES]           # [B, 128]
        parts = rows.reshape(rows.shape[0], LANES // w, w)
        part = (base % LANES) // w            # which W lanes of the row
        ring = parts[:, 0]
        for k in range(1, LANES // w):
            ring = jnp.where((part == k)[:, None], parts[:, k], ring)
        ring = jax.lax.bitcast_convert_type(
            ring, jnp.dtype(f"uint{ring.dtype.itemsize * 8}")
        )
        shift = 1
        while shift < w:  # rotate left by pos, one bit of pos a stage
            ring = jnp.where(
                ((pos & shift) != 0)[:, None],
                jnp.roll(ring, -shift, axis=1), ring,
            )
            shift <<= 1
        # left-pad: exactly one column is the first valid one, so the
        # masked max over the columns IS that column
        first = jnp.max(
            jnp.where(col == first_valid_col, ring, 0), axis=1, keepdims=True
        )
        windows = jax.lax.bitcast_convert_type(
            jnp.where(col >= first_valid_col, ring, first), rows.dtype
        )
    else:
        flat = base[:, None] + slot
        windows = store[flat // LANES, flat % LANES]
    return windows, n


def update_and_gather(
    state: WindowState,
    stream_ids: jnp.ndarray,
    values: jnp.ndarray,
    valid: jnp.ndarray,
) -> Tuple[WindowState, jnp.ndarray, jnp.ndarray]:
    """Fused hot-path step: append batch, then gather each row's window
    *including* the row itself as the newest element."""
    new_state, windows, n, _ = update_gather_ranked(
        state, stream_ids, values, valid)
    return new_state, windows, n


def update_gather_ranked(
    state: WindowState,
    stream_ids: jnp.ndarray,
    values: jnp.ndarray,
    valid: jnp.ndarray,
) -> Tuple[WindowState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``update_and_gather`` plus per-row recency: also returns ``later``
    i32[..., B] — how many valid same-stream rows come AFTER row b in
    this batch (0 = the stream's newest sample). A K-step fused scorer
    uses it to resolve each row at its OWN window position: a row with
    ``later = j`` sits at position W-1-j of the post-batch window, so it
    takes the K-step score at index K-1-j instead of the newest one.
    One ``_segment_ranks`` sort serves the ring scatter, the counters
    and this; the window read takes the counters the update computed."""
    new_state, pos, count, ranks, totals = _update(
        state, stream_ids, values, valid)
    read = partial(_read_windows, w=state.window)
    for _ in range(stream_ids.ndim - 1):
        read = jax.vmap(read)
    windows, n = read(new_state.values, stream_ids, pos, count)
    later = jnp.where(valid, totals - 1 - ranks, 0).astype(jnp.int32)
    return new_state, windows, n, later
