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
"""

from __future__ import annotations

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
              "calls_one_step", "calls_chunked", "streams_advanced")


@dataclass
class Call:
    """One dispatch of one program, as ONE int32 array ``packed`` [R, 2 +
    2 L] (a flush crosses host->device once a call): a row is a stream's
    id, its real tokens, its L token ids, and where each token's score
    goes in the flat score plane (the plane's size = dropped)."""

    one_step: bool
    slot: int
    packed: np.ndarray

    @classmethod
    def empty(cls, one_step: bool, slot: int, rows: int, length: int,
              cap: int, plane: int) -> "Call":
        packed = np.zeros((rows, 2 + 2 * length), np.int32)
        packed[:, 0] = cap
        packed[:, 2 + length:] = plane
        return cls(one_step, slot, packed)

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
        return unpack(self.packed)[3]


def unpack(packed):
    """(ids [R], lens [R], toks [R, L], cols [R, L]) of a call's array —
    views, on the host and under ``jit`` alike."""
    length = (packed.shape[1] - 2) // 2
    return (packed[:, 0], packed[:, 1], packed[:, 2:2 + length],
            packed[:, 2 + length:])


def plan(
    ids: np.ndarray,       # [T, B] local stream ids, front-contiguous
    toks: np.ndarray,      # i32[T, B] token ids
    counts: np.ndarray,    # i32[T] valid rows a slot
    chunk: int,
    cap: int,
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
                call = Call.empty(True, slot, r, 1, cap, plane)
                call.ids[:k] = row_ids[take]
                call.lens[:k] = 1
                call.toks[:k, 0] = row_toks[take]
                call.cols[:k, 0] = base + take
                calls.append(call)
                stats["rows_one_step"] += k
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
                                      plane)
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

            cap = self.max_streams

            def stream_step(params, state, packed):
                ids, lens, toks, _cols = unpack(packed)
                p = jax.tree_util.tree_map(lambda x: x[slot], params)
                # the slots' stores side by side on one stream axis (a
                # reshape, no copy): slot t's stream s is row t * cap + s
                flat = jax.tree_util.tree_map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), state)
                with jax.named_scope("sw/step"):
                    flat, scores, stats = spec.advance(
                        p, cfg, flat, ids + slot * cap, toks, lens,
                        one_step=one_step)
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
        cols = unpack(packed)[3].reshape(-1)
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
            Call.empty(True, slot, r, 1, self.max_streams, never)
            for r in ONE_STEP_ROWS
        ] + [Call.empty(False, slot, CHUNK_RUNS, self.chunk,
                        self.max_streams, never)]
