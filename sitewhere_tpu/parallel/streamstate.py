"""The second kind of per-stream state behind ``ShardedScorer``: a
recurrent / cached state a family advances by one step an event
(``ModelSpec.init_state`` / ``ModelSpec.advance``), where ``WindowState``
re-scans a window of raw values every flush.

``ShardedScorer`` owns the store like it owns the rings — allocated for
``max_streams`` at tenant start, donated through every program, cleared
by ``reset_slot`` — and hands each flush to ``StreamPrograms.run``, which
splits it between the family's two compiled programs:

- the ONE-STEP program: one token a row, the rows distinct streams —
  what live traffic rides;
- the CHUNKED program: a run of up to ``chunk_size`` tokens of ONE
  stream a row — a bulk message, the pre-fill, replay.

A recurrent step cannot apply two events of one stream in parallel, so
the split is made on the host from the flush's own ids (``plan``): a
stream that rides a flush once goes through the one-step program; one
that rides it a few times (``SHORT_RUN``) through that many PASSES of
the one-step program, its j-th row in pass j; a longer run through the
chunked program, ``chunk_size`` tokens a pass — always IN ORDER, the
state passing from pass to pass through the store. No row waits a
flush; ``stats['rows_same_stream']`` counts the rows that shared a flush
with another row of their stream.

Every program has a fixed shape (``ONE_STEP_ROWS``, ``CHUNK_RUNS`` x
chunk_size) whatever the bucket; a tiny ``place`` program a call drops
its scores into the bucket's score plane, so ``gather_rows`` and the
reaper see what the window families give them.

A family may also keep leaves led by a PAGE axis (``ModelSpec.page_pool``):
a pool of pages the slot's streams share, so that what a stream holds
follows its own history and not streams x the longest one. The host
hands the pages out (``PagePool``): before a call it grants each row the
pages its run reaches and writes the row's page table into the call's
packed array; ``reset_slot`` returns a slot's pages.
"""

from __future__ import annotations

import collections
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ONE_STEP_ROWS = (32, 128)   # row capacities of the one-step program
CHUNK_RUNS = 8              # runs (distinct streams) a chunked call takes
# A stream that rides a flush at most this many times takes that many
# passes of the one-step program (pass j: every such stream's j-th row,
# distinct streams by construction); a longer run takes the chunked
# program. A backlog of a few seconds puts every stream of a fleet into a
# flush two or three times: through the chunked program, CHUNK_RUNS
# streams a 25 ms call, that flush took longer than the backlog it
# carried and the slice never recovered (my chip run, PR 39).
SHORT_RUN = 8

# the step's counters, in the order ``run`` returns them: the first
# three come from the device (summed over the expert layers of every
# call of the flush), the rest from the host's plan
DEVICE_STATS = ("pairs_routed", "pairs_held", "experts_hit")
HOST_STATS = ("rows_one_step", "rows_chunked", "rows_same_stream",
              "calls_one_step", "calls_chunked", "streams_advanced",
              "rows_padding")
# a paged family's counters, from its ``PagePool`` (the last a level)
PAGE_STATS = ("kv_pages_granted", "kv_pages_freed", "kv_pages_recycled",
              "positions_swa", "positions_full", "state_read_bytes",
              "state_written_bytes")
PAGE_GAUGES = ("kv_pages_in_use",)


@dataclass(frozen=True)
class PageLayout:
    """A paged family's store as the host sees it (``page_pool(cfg)``)."""

    pool: int            # pages in a slot's pool
    page: int            # positions a page
    table: int           # entries of a stream's page table
    window: int          # positions a window layer's token attends
    n_window: int        # layers that keep a ring of pages a stream
    n_full: int          # layers that keep their positions in the pool
    position_bytes: int  # K and V of one position of one layer
    row_bytes: int       # the rest of a stream's state (position, hidden,
    #                      and the convolution tails)
    tail_bytes: int = 0  # a stream's convolution tails (in row_bytes too)


@dataclass
class Call:
    """One dispatch of one program, as ONE int32 array ``packed`` [R, 2 +
    2 L (+ extra)] (a flush crosses host->device once a call): a row is a
    stream's id, its real tokens, its L token ids, where each token's
    score goes in the flat score plane (the plane's size = dropped), and
    — for a paged family — its page table (``extra`` columns: ``grants``)."""

    one_step: bool
    slot: int
    packed: np.ndarray
    extra: int = 0

    @classmethod
    def empty(cls, one_step: bool, slot: int, rows: int, length: int,
              cap: int, plane: int, extra: int = 0) -> "Call":
        packed = np.zeros((rows, 2 + 2 * length + extra), np.int32)
        packed[:, 0] = cap
        packed[:, 2 + length:2 + 2 * length] = plane
        return cls(one_step, slot, packed, extra)

    @property
    def ids(self) -> np.ndarray:
        return unpack(self.packed)[0]

    @property
    def lens(self) -> np.ndarray:
        return unpack(self.packed)[1]

    @property
    def toks(self) -> np.ndarray:
        return unpack(self.packed)[2]

    @property
    def cols(self) -> np.ndarray:
        return unpack(self.packed, self.extra)[3]

    @property
    def grants(self) -> np.ndarray:
        return grants(self.packed, self.extra)


def unpack(packed, extra: int = 0):
    """(ids [R], lens [R], toks [R, L], cols [R, L]) of a call's array —
    views, on the host and under ``jit`` alike."""
    length = (packed.shape[1] - 2 - extra) // 2
    return (packed[:, 0], packed[:, 1], packed[:, 2:2 + length],
            packed[:, 2 + length:2 + 2 * length])


def grants(packed, extra: int):
    """The rows' page tables [R, extra] of a paged family's call."""
    return packed[:, packed.shape[1] - extra:]


def plan(
    ids: np.ndarray,       # [T, B] local stream ids, front-contiguous
    toks: np.ndarray,      # i32[T, B] token ids
    counts: np.ndarray,    # i32[T] valid rows a slot
    chunk: int,
    cap: int,
    extra: int = 0,        # page-table columns a row (a paged family)
) -> Tuple[List[Call], Dict[str, int]]:
    """Split a flush into calls (see the module docstring). Pure numpy,
    O(rows log rows); the common flush — a few distinct streams — costs
    one ``np.unique``."""
    t_n, b = ids.shape
    plane = t_n * b
    calls: List[Call] = []
    stats = dict.fromkeys(HOST_STATS, 0)
    for slot in range(t_n):
        c = int(counts[slot])
        if c == 0:
            continue
        row_ids = ids[slot, :c].astype(np.int32)
        row_toks = toks[slot, :c]
        base = slot * b
        uniq, inverse, reps = np.unique(
            row_ids, return_inverse=True, return_counts=True)
        if len(uniq) == c:
            passes, long_streams = [np.arange(c)], ()
        else:
            stats["rows_same_stream"] += int((reps[inverse] > 1).sum())
            # rank of each row among its stream's rows, in flush order
            order = np.argsort(inverse, kind="stable")
            starts = np.concatenate([[0], np.cumsum(reps)])
            rank = np.empty(c, np.int64)
            rank[order] = np.arange(c) - np.repeat(starts[:-1], reps)
            short = reps[inverse] <= SHORT_RUN
            passes = [np.flatnonzero(short & (rank == j))
                      for j in range(int(min(reps.max(), SHORT_RUN)))]
            long_streams = np.flatnonzero(reps > SHORT_RUN)
        # -- one row a stream a pass: the one-step program
        for single in passes:
            a = 0
            while a < len(single):
                left = len(single) - a
                r = next((x for x in ONE_STEP_ROWS if left <= x),
                         ONE_STEP_ROWS[-1])
                take = single[a:a + r]
                k = len(take)
                call = Call.empty(True, slot, r, 1, cap, plane, extra)
                call.ids[:k] = row_ids[take]
                call.lens[:k] = 1
                call.toks[:k, 0] = row_toks[take]
                call.cols[:k, 0] = base + take
                calls.append(call)
                stats["rows_one_step"] += k
                stats["rows_padding"] += r - k
                stats["streams_advanced"] += k
                stats["calls_one_step"] += 1
                a += k
        # -- long runs: the chunked program, chunk tokens a pass
        if len(long_streams):
            runs = [order[starts[u]:starts[u + 1]] for u in long_streams]
            longest = max(len(r) for r in runs)
            for p in range(-(-longest // chunk)):
                part = [r[p * chunk:(p + 1) * chunk] for r in runs
                        if len(r) > p * chunk]
                for a in range(0, len(part), CHUNK_RUNS):
                    group = part[a:a + CHUNK_RUNS]
                    call = Call.empty(False, slot, CHUNK_RUNS, chunk, cap,
                                      plane, extra)
                    for i, rows in enumerate(group):
                        k = len(rows)
                        call.ids[i] = row_ids[rows[0]]
                        call.lens[i] = k
                        call.toks[i, :k] = row_toks[rows]
                        call.cols[i, :k] = base + rows
                        stats["rows_chunked"] += k
                    calls.append(call)
                    stats["streams_advanced"] += len(group)
                    stats["calls_chunked"] += 1
    return calls, stats


class StreamPrograms:
    """The compiled programs of one stateful family on one device, and
    the loop that runs a flush's plan through them."""

    def __init__(self, spec, cfg, n_slots: int, max_streams: int,
                 score_dtype, edges: np.ndarray) -> None:
        self.spec, self.cfg = spec, cfg
        self.n_slots, self.max_streams = n_slots, max_streams
        # a paged family's pool (pages a slot) and the columns a row's
        # page table takes in a call: its first logical page, the table
        layout = spec.page_pool(cfg) if spec.page_pool is not None else None
        self.pool_pages = layout.pool if layout else 0
        self.extra = 1 + layout.table if layout else 0
        self.chunk = int(cfg.chunk_size)
        self.vocab = int(cfg.vocab)
        self.score_dtype = score_dtype
        self.nbins = len(edges) + 1
        self._edges = jnp.asarray(edges)
        self._advance: Dict[Tuple[bool, int], Callable] = {}
        self._place = jax.jit(self._place_fn, donate_argnums=(0, 1, 2))
        self._place_first = jax.jit(self._place_first_fn, static_argnums=3)

    # -- programs --------------------------------------------------------
    def program(self, one_step: bool, slot: int) -> Callable:
        """The one-step or the chunked program for a slot. The slot is
        static: its weights are a static slice of the stack (a view where
        the stack holds one slot, as a chip-filling model's does)."""
        fn = self._advance.get((one_step, slot))
        if fn is None:
            spec, cfg = self.spec, self.cfg
            name = "stream_one_step" if one_step else "stream_chunked"

            cap, extra = self.max_streams, self.extra

            def stream_step(params, state, packed):
                ids, lens, toks, _cols = unpack(packed, extra)
                kw = {}
                p = jax.tree_util.tree_map(lambda x: x[slot], params)
                # the slots' stores side by side on one stream axis (a
                # reshape, no copy): slot t's stream s is row t * cap + s,
                # and its page q (a pool's) is page t * pool + q
                flat = jax.tree_util.tree_map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), state)
                if extra:
                    g = grants(packed, extra)
                    kw["pages"] = g.at[:, 1:].add(slot * self.pool_pages)
                with jax.named_scope("sw/step"):
                    flat, scores, stats = spec.advance(
                        p, cfg, flat, ids + slot * cap, toks, lens,
                        one_step=one_step, **kw)
                state = jax.tree_util.tree_map(
                    lambda whole, mine: mine.reshape(whole.shape),
                    state, flat)
                return state, scores.reshape(-1), stats

            stream_step.__name__ = name
            fn = self._advance[(one_step, slot)] = jax.jit(
                stream_step, donate_argnums=(1,))
        return fn

    def _place_fn(self, plane, hist, stats, scores, packed, new_stats):
        """Drop one call's scores into the flat plane (padding falls
        off its end) and fold the call's sketch and counters in."""
        cols = unpack(packed, self.extra)[3].reshape(-1)
        flat = plane.reshape(-1)
        real = cols < flat.shape[0]
        flat = flat.at[cols].set(scores.astype(plane.dtype), mode="drop")
        # left-closed bins, as ``np.histogram`` and the window families'
        # searchsorted(side="right") have them; no gather
        bins = jnp.sum(scores[:, None] >= self._edges[None, :], axis=-1)
        bins = jnp.where(real & ~jnp.isnan(scores), bins, self.nbins)
        hist = hist.at[cols // plane.shape[1], 0, bins].add(1, mode="drop")
        return flat.reshape(plane.shape), hist, stats + new_stats

    def _empty(self, b_plane: int):
        """An empty score plane, sketch and counters."""
        return (jnp.zeros((self.n_slots, b_plane), self.score_dtype),
                jnp.zeros((self.n_slots, 1, self.nbins), jnp.int32),
                jnp.zeros((len(DEVICE_STATS),), jnp.int32))

    def _place_first_fn(self, scores, packed, new_stats, b_plane: int):
        """``_place_fn`` into an empty plane made where it is filled: a
        flush's first call (most flushes' only one) sends no zeros."""
        return self._place_fn(*self._empty(b_plane), scores, packed,
                              new_stats)

    # -- a flush -----------------------------------------------------------
    def tokens(self, vals: np.ndarray) -> np.ndarray:
        """The wire's values as token ids: a reading IS its id."""
        return np.clip(
            np.rint(np.asarray(vals, np.float32)), 0, self.vocab - 1
        ).astype(np.int32)

    def run(self, params, state, calls: List[Call], b_plane: int):
        """Dispatch a flush's calls in order. Returns (state', score
        plane [T, B], sketch i32[T, 1, NBINS], device counters i32[3])."""
        plane = hist = stats = None
        for c in calls:
            state, scores, st = self.program(c.one_step, c.slot)(
                params, state, c.packed)
            if plane is None:
                plane, hist, stats = self._place_first(
                    scores, c.packed, st, b_plane)
            else:
                plane, hist, stats = self._place(
                    plane, hist, stats, scores, c.packed, st)
        if plane is None:  # an empty flush (prewarm's zero-count step)
            plane, hist, stats = self._empty(b_plane)
        return state, plane, hist, stats

    def warm_calls(self, slot: int) -> List[Call]:
        """One padding-only call of every program shape: compiles them
        and leaves the state as it was."""
        # their scores fall off the plane's end whatever its size
        never = np.iinfo(np.int32).max
        return [
            Call.empty(True, slot, r, 1, self.max_streams, never, self.extra)
            for r in ONE_STEP_ROWS
        ] + [Call.empty(False, slot, CHUNK_RUNS, self.chunk,
                        self.max_streams, never, self.extra)]


class PoolExhausted(RuntimeError):
    """A stream needs a page, the pool has none free, and the stream holds
    none it could give up (what one stream would take from another is
    eviction, not built)."""


class PagePool:
    """The host's side of a paged family's pool: which pages each
    stream holds, in order, and how many tokens it has seen.

    A stream holds the pages from its ``first`` logical page on. Before a
    call, ``grant`` gives each live row the pages its run reaches: a free
    page of the slot's pool, or — when the pool is empty or the stream's
    table is full — the stream's OWN oldest page (counted as recycled;
    the stream then attends the positions it still holds). ``release``
    returns a slot's pages. ``grant`` also counts what the call's
    attention reads and writes (``PAGE_STATS``) and, for a family that
    keeps convolution tails, the tails its rows read and write
    (``conv_tail_bytes``).

    A grant is all or nothing: where a row finds no page
    (``PoolExhausted``) the pool is put back as it was before the grant,
    and ``undo`` does the same for a grant whose calls never ran — the
    host's token counts stay those the device's positions reached."""

    def __init__(self, n_slots: int, max_streams: int,
                 layout: PageLayout) -> None:
        self.layout = layout
        self.pool, self.page, self.width = layout.pool, layout.page, layout.table
        self.tokens = np.zeros((n_slots, max_streams), np.int64)
        self.first = np.zeros((n_slots, max_streams), np.int64)
        self.held: Dict[Tuple[int, int], collections.deque] = {}
        self.free = [list(range(self.pool - 1, -1, -1))
                     for _ in range(n_slots)]
        self.totals = dict.fromkeys(("granted", "freed", "recycled"), 0)
        self._reported = dict(self.totals)
        # what the last grant changed, for ``undo``: each stream's
        # (tokens, first, held pages) before it, and the free pages it
        # took, in order
        self._before: Dict[Tuple[int, int], tuple] = {}
        self._taken: List[Tuple[int, int]] = []
        self._counts_before = (dict(self.totals), dict(self._reported))

    @property
    def in_use(self) -> int:
        return sum(len(h) for h in self.held.values())

    def _take(self, slot: int, stream: int, keep_from: int) -> int:
        """One page for a stream: a free one, or its own oldest when the
        pool is empty or its table is full (never one this call writes:
        ``keep_from`` is the first logical page the call writes)."""
        held = self.held.setdefault((slot, stream), collections.deque())
        if self.free[slot] and len(held) < self.width:
            self.totals["granted"] += 1
            page = self.free[slot].pop()
            self._taken.append((slot, page))
            return page
        if not held or self.first[slot, stream] >= keep_from:
            raise PoolExhausted(
                f"slot {slot} stream {stream}: no free page of "
                f"{self.pool} and none of its own to give up")
        self.first[slot, stream] += 1
        self.totals["recycled"] += 1
        return held.popleft()

    def grant(self, calls: List[Call]) -> Dict[str, int]:
        """Give every live row of every call the pages its run reaches,
        write its table into the call, advance its token count, and
        return the calls' counters — or raise ``PoolExhausted`` with the
        pool as it was."""
        self._before, self._taken = {}, []
        self._counts_before = (dict(self.totals), dict(self._reported))
        try:
            return self._grant(calls)
        except PoolExhausted:
            self.undo()
            raise

    def undo(self) -> None:
        """Put the pool back as it was before the last ``grant``."""
        for slot, page in reversed(self._taken):
            self.free[slot].append(page)
        for (slot, s), (tokens, first, pages) in self._before.items():
            self.tokens[slot, s], self.first[slot, s] = tokens, first
            self.held[(slot, s)] = collections.deque(pages)
        self.totals, self._reported = (
            dict(self._counts_before[0]), dict(self._counts_before[1]))
        self._before, self._taken = {}, []

    def _grant(self, calls: List[Call]) -> Dict[str, int]:
        stats = dict.fromkeys(PAGE_STATS, 0)
        lay, page = self.layout, self.page
        pb, window = lay.position_bytes, lay.window
        for c in calls:
            table = c.grants
            for i in np.flatnonzero(c.lens > 0):
                s, n = int(c.ids[i]), int(c.lens[i])
                pos0 = int(self.tokens[c.slot, s])
                end = pos0 + n
                held = self.held.setdefault((c.slot, s), collections.deque())
                if (c.slot, s) not in self._before:
                    self._before[(c.slot, s)] = (
                        pos0, int(self.first[c.slot, s]), tuple(held))
                while self.first[c.slot, s] + len(held) <= (end - 1) // page:
                    held.append(self._take(c.slot, s, pos0 // page))
                first = int(self.first[c.slot, s])
                table[i, 0] = first
                table[i, 1:1 + len(held)] = held
                self.tokens[c.slot, s] = end
                # what attention reads: the window's positions and the
                # held history a token each (one-step calls: what the
                # traced steps read); the pages a row's kernel walks;
                # what it writes: a position a token a layer
                if c.one_step:
                    stats["positions_swa"] += lay.n_window * min(end, window)
                    stats["positions_full"] += lay.n_full * (
                        end - first * page)
                w_first = max(pos0 - window + 1, 0) // page
                w_pages = -(-(end - w_first * page) // page)
                f_pages = -(-(end - first * page) // page)
                stats["state_read_bytes"] += pb * page * (
                    lay.n_window * w_pages + lay.n_full * f_pages
                ) + lay.row_bytes
                stats["state_written_bytes"] += (
                    pb * n * (lay.n_window + lay.n_full) + lay.row_bytes)
                if lay.tail_bytes:
                    # a row's tails, read and written whole a call
                    stats["conv_tail_bytes"] = stats.get(
                        "conv_tail_bytes", 0) + 2 * lay.tail_bytes
        for key, total in self.totals.items():
            stats[f"kv_pages_{key}"] = total - self._reported[key]
        self._reported = dict(self.totals)
        stats["kv_pages_in_use"] = self.in_use
        return stats

    def release(self, slot: int) -> None:
        """A slot's streams start again: every page goes back."""
        for key in [k for k in self.held if k[0] == slot]:
            pages = self.held.pop(key)
            self.totals["freed"] += len(pages)
            self.free[slot].extend(reversed(pages))
        self.tokens[slot] = 0
        self.first[slot] = 0
