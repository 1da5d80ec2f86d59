"""What one (family, mesh slice) holds, when it is born and when it dies.

The serving pipeline of ``pipeline/inference.py`` is instantiated once
per (family, mesh slice). ``SliceRuntime`` is that instance: one object,
built in ``scorer_for_slice`` and dropped in ``on_stop``, holding as
plain attributes everything keyed by (family, slice), with the rules
that read nothing else — when a flush is due, when a due flush waits
(``held``), what counts as in flight, which bucket and staging set a
flush takes, what deadline it gets, what quarantine and re-admission
reset. Beside it, the data structures a slice is made of. The service
keeps what spans slices: engines, bus, batch registry, reaper, scoring
loop, a moving tenant's fences, a family's parked state.
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from sitewhere_tpu.runtime.config import FaultTolerancePolicy
from sitewhere_tpu.runtime.metrics import RollingQuantile


class _LaneRing:
    """Pending rows for one (slot, data_shard): a preallocated numpy ring.

    Rows are written into fixed-dtype ring segments at enqueue time
    (``push`` — slice assignment, no per-row Python, no per-enqueue
    allocation) and leave either straight into a flush's reusable staging
    buffers (``pop_into``) or as fresh arrays on the cold paths (``pop``:
    drain / park / breaker / failover). Capacity doubles when an intake
    burst overshoots — the per-tenant lane watermark bounds steady-state
    depth, so growth is rare and amortized.
    """

    COLS = ("ids", "vals", "seqs", "rows")
    __slots__ = COLS + ("head", "count")

    def __init__(self, capacity: int = 4096) -> None:
        cap = max(64, int(capacity))
        self.ids = np.empty((cap,), np.int32)   # local stream ids
        self.vals = np.empty((cap,), np.float32)
        self.seqs = np.empty((cap,), np.int64)  # batch sequence numbers
        self.rows = np.empty((cap,), np.int32)  # row index inside the batch
        self.head = 0
        self.count = 0

    @property
    def capacity(self) -> int:
        return len(self.ids)

    def _grow(self, need: int) -> None:
        cap = self.capacity
        new_cap = cap
        while new_cap < need:
            new_cap *= 2
        k = self.count
        first = min(k, cap - self.head)
        for name in self.COLS:
            old = getattr(self, name)
            new = np.empty((new_cap,), old.dtype)
            new[:first] = old[self.head : self.head + first]
            new[first:k] = old[: k - first]
            setattr(self, name, new)
        self.head = 0

    def push(self, ids, vals, seq, rows) -> None:
        """Append rows. ``seq`` may be a scalar (the per-enqueue common
        case — broadcast into the ring, no per-batch full() array)."""
        n = len(ids)
        if self.count + n > self.capacity:
            self._grow(self.count + n)
        cap = self.capacity
        tail = (self.head + self.count) % cap
        first = min(n, cap - tail)
        second = n - first
        self.ids[tail : tail + first] = ids[:first]
        self.vals[tail : tail + first] = vals[:first]
        self.rows[tail : tail + first] = rows[:first]
        if np.ndim(seq):
            self.seqs[tail : tail + first] = seq[:first]
        else:
            self.seqs[tail : tail + first] = seq
        if second:
            self.ids[:second] = ids[first:]
            self.vals[:second] = vals[first:]
            self.rows[:second] = rows[first:]
            self.seqs[:second] = seq[first:] if np.ndim(seq) else seq
        self.count += n

    def pop_into(
        self, k: int, ids_row, vals_row, col0: int, seqs_out, rows_out, off: int
    ) -> None:
        """Move k rows FIFO off the front, straight into one slot's
        staging views (``ids_row``/``vals_row`` at column ``col0`` — the
        dtype cast to the scorer's wire happens inside the slice write)
        and the flush's bookkeeping arrays at offset ``off``. At most two
        slice copies per column; zero intermediate arrays."""
        h, cap = self.head, self.capacity
        first = min(k, cap - h)
        second = k - first
        ids_row[col0 : col0 + first] = self.ids[h : h + first]
        vals_row[col0 : col0 + first] = self.vals[h : h + first]
        seqs_out[off : off + first] = self.seqs[h : h + first]
        rows_out[off : off + first] = self.rows[h : h + first]
        if second:
            ids_row[col0 + first : col0 + k] = self.ids[:second]
            vals_row[col0 + first : col0 + k] = self.vals[:second]
            seqs_out[off + first : off + k] = self.seqs[:second]
            rows_out[off + first : off + k] = self.rows[:second]
        self.head = (h + k) % cap
        self.count -= k

    def pop(self, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Take up to n rows off the front as fresh arrays (cold paths)."""
        k = min(int(n), self.count)
        h, cap = self.head, self.capacity
        first = min(k, cap - h)
        out = []
        for name in self.COLS:
            a = getattr(self, name)
            dst = np.empty((k,), a.dtype)
            dst[:first] = a[h : h + first]
            if k > first:
                dst[first:] = a[: k - first]
            out.append(dst)
        self.head = (h + k) % cap
        self.count -= k
        return tuple(out)


class _TrainLaneRing(_LaneRing):
    """Replay-fed train-lane ring: pending TRAINING rows for one
    (slot, data-shard), consumed from the tenant's ``replay-train-feed``
    topic and packed into train microbatches through the same staging →
    h2d wire as scoring flushes. Bounded by the train watermark
    (2 × ``replay_microbatch``): past it the feed consumer stops pulling
    (``tpu_inference.train_feed_backpressure``) and the backlog stays in
    the bus topic, where retention bounds it and the replay pump's own
    overload arbitration already parks the producer. Depth is the
    ``tpu_inference_train_rows{family}`` gauge (tools/check_queues.py).
    Same columnar ring mechanics as the serve lanes — distinct type so
    the bounded-queue lint tracks the train lane as its own queue."""

    __slots__ = ()


def _empty_taken():
    """A train-lane pending entry's ``taken`` placeholder: zero rows, so
    every row-oriented resolve/teardown path (``_resolve_rows`` on the
    seqs/rows columns) is a structural no-op without branching."""
    return (None, None, np.empty((0,), np.int64), np.empty((0,), np.int32))


class _StagingSet:
    """One reusable flush staging set: ids/vals ``[T, D*B]`` in the
    scorer's wire dtypes, lane counts ``[T, D]``, and a cached column
    arange. A flush packs lanes into these buffers in place (no fresh
    ``np.zeros`` per flush) and ``jax.device_put``s them; ``staged``
    pins the device arrays from this set's LAST put — the async h2d copy
    reads the host buffers, so reuse must wait on it (two sets rotating
    per (family, bucket) normally hides that wait entirely)."""

    __slots__ = ("ids", "vals", "counts", "arange", "staged")

    def __init__(self, scorer, b_lane: int) -> None:
        t, d = scorer.n_slots, scorer.mm.n_data_shards
        self.ids = np.zeros((t, d * b_lane), scorer.ids_np_dtype)
        self.vals = np.zeros((t, d * b_lane), scorer.vals_np_dtype)
        self.counts = np.zeros((t, d), np.int32)
        self.arange = np.arange(d * b_lane, dtype=np.int32)
        self.staged = None

    def ensure_reusable(self, metrics) -> None:
        """Block until this set's previous device copy finished (counted;
        with overlap working the transfer is long done by recycle time)."""
        staged = self.staged
        if staged is None:
            return
        self.staged = None
        try:
            if all(a.is_ready() for a in staged):
                return
            metrics.counter("tpu_inference.stage_reuse_waits").inc()
            for a in staged:
                a.block_until_ready()
        except Exception:  # noqa: BLE001 - non-jax arrays (tests) or a
            # dead device buffer (failover mid-rotation): treat as free
            pass


class _PendingFlush:
    """One dispatched flush awaiting its device→host score transfer —
    and the span record of that flush: ``flush_id`` names it (each batch
    it completes stamps the id on its inference span), ``rec`` is the
    record dict the flight recorder's ring and ``flush_records`` share,
    which carries the batch ``seqs`` and the contiguous
    ``time.perf_counter()`` stamps ``t_oldest → t_asked → t_got →
    t_assembled → t_staged → t_dispatched → t_landed → t_resolved`` (the
    first six written by ``_flush_slice``, the last two by
    ``_resolve_flush``).

    ``scores`` is either the device-gathered row vector (``gathered``
    True — slice ``[:moved]`` is the picks, already in pack order) or
    the full score plane (fallback for scorers without ``gather_rows``,
    e.g. monkeypatched test doubles — the host then picks
    ``scores[slots, cols]``). The d2h copy was started at dispatch
    (``copy_to_host_async``); outputs that can't copy asynchronously
    get an eager executor materialization instead (``host_future``), so
    fallback flushes still overlap each other like the old per-flush
    deliver tasks did."""

    __slots__ = (
        "family", "sl", "scores", "taken", "moved", "gathered",
        "t_dispatch", "nbytes", "plane_nbytes", "host_future", "t_wait",
        "poisoned", "flops", "rec", "sketch", "shadow", "slot_override",
        "resolved", "lane", "deadline", "retried", "retry_rows",
        "retry_from", "owns_permit", "flush_id", "stream_stats",
        "t_landed",
    )

    def __init__(
        self, family: str, scores, taken, moved: int, gathered: bool,
        nbytes: int, plane_nbytes: int, poisoned: bool = False,
        flops: float = 0.0, rec: Optional[dict] = None,
        sketch=None, shadow=None, sl: int = 0, lane: str = "serve",
        flush_id: int = -1, t_dispatch: Optional[float] = None,
    ) -> None:
        self.family = family
        self.flush_id = flush_id
        # the mesh slice that ran this flush: reap queues, overlap
        # probes, and device-labeled attribution are all keyed
        # (family, slice) on a multi-chip mesh
        self.sl = sl
        # set when the flush's resolution finished (either way) — the
        # slice-move fence waits on this, never on queue identity
        self.resolved = False
        self.scores = scores
        self.taken = taken
        self.moved = moved
        self.gathered = gathered
        # when the dispatch call returned (perf_counter): the start of
        # the in-flight interval and of the supervisor's deadline
        self.t_dispatch = (
            time.perf_counter() if t_dispatch is None else t_dispatch
        )
        self.nbytes = nbytes
        self.plane_nbytes = plane_nbytes
        self.host_future = None
        self.t_wait = None  # when the reaper first started waiting on us
        # the landing stamp (perf_counter): the worker thread's where
        # ``_materialize`` returned, or the first ``landed()`` that found
        # the output ready, whichever came first — the moment the slice's
        # device went free of this flush, as the host can tell it
        self.t_landed: Optional[float] = None
        # a flush whose DISPATCH failed (no scores, no transfer): it
        # rides the FIFO so its unscored resolution can't overtake an
        # earlier in-flight flush of the same family
        self.poisoned = poisoned
        # device-time attribution: FLOPs this flush's padded plane
        # executes (scorer.flops_per_flush) and the flight-recorder
        # record completed in place when the flush resolves
        self.flops = flops
        self.rec = rec
        # score-quality payloads riding the same reaper slot: the step's
        # per-slot score sketch (i32[T, D, NBINS] — runtime.scorehealth)
        # and the canary's shadow-scored row vector (previous-variant
        # divergence). Their async host copies start at dispatch like the
        # scores'; by the time the scores land these few-KB transfers
        # have long since followed — no extra round-trip.
        self.sketch = sketch
        self.shadow = shadow
        # a stateful family's step counters (``ShardedScorer.last_stats``:
        # device i32 vector, host dict) — out of the same programs as the
        # scores, so landed when they have
        self.stream_stats = None
        # the single-used-slot fallback slice zeroes the pack-order slot
        # indices (rows then index row 0 of the slice); this remembers
        # the real slot so NaN attribution survives that path
        self.slot_override: Optional[int] = None
        # which lane dispatched this entry: "serve" (a scoring flush —
        # everything above applies) or "train" (a continual-learning
        # train step riding the same per-slice in-flight window and
        # reaper: ``scores`` holds the per-slot loss vector, ``taken``
        # is empty, and resolution records training metrics instead of
        # publishing batches). One FIFO per (family, slice) keeps the
        # permit accounting and teardown drain uniform across lanes.
        self.lane = lane
        # flush supervision (docs/ROBUSTNESS.md "Device fault domains"):
        # the absolute perf_counter() moment by which this flush's
        # transfer must have landed — past it the reaper force-resolves
        # the rows unscored in this FIFO slot and quarantines the slice.
        # None = unsupervised (flush_deadline_ms knob off, or poisoned
        # entries that land immediately by construction).
        self.deadline: Optional[float] = None
        # poison-batch ejection: this pf IS the one-shot retry of a
        # faulted flush's rows (``retry_from`` = the slice the FIRST
        # failure happened on) — a second failure on a DIFFERENT slice
        # attributes the fault to the data and ships the batches to the
        # scorer-poison DLQ; a second failure on the SAME chip stays a
        # chip signal (unscored resolve + breaker/failover pacing)
        self.retried = False
        self.retry_from: Optional[int] = None
        # host copies of the staged (ids, vals, dshards) rows, kept so a
        # TIMED-OUT flush can retry with the same bytes (the staging set
        # recycles long before a deadline expires); populated only while
        # the family's poison_retry knob is on
        self.retry_rows: Optional[tuple] = None
        # False for ORDERED host-only entries enqueued from inside a
        # resolve task (per-tenant FIFO fallbacks of the poison-retry
        # path): acquiring a permit there can deadlock against the very
        # head whose resolution is enqueueing them, and a host-only
        # poisoned entry holds no device resources for the in-flight
        # window to meter — the resolve/teardown release sites skip it
        self.owns_permit = True

    @property
    def key(self) -> Tuple[str, int]:
        return (self.family, self.sl)

    def overdue(self, now: Optional[float] = None) -> bool:
        """Deadline passed without resolution — the supervisor's
        force-resolve trigger (poisoned entries land instantly and are
        never overdue)."""
        if self.deadline is None or self.poisoned:
            return False
        return (time.perf_counter() if now is None else now) > self.deadline

    def _materialize(self):
        """Worker-thread materialization of every device output riding
        this flush — one executor hop for scores + sketch + shadow."""
        out = (
            np.asarray(self.scores),
            None if self.sketch is None else np.asarray(self.sketch),
            None if self.shadow is None else np.asarray(self.shadow),
        )
        if self.t_landed is None:
            self.t_landed = time.perf_counter()
        return out

    def landed(self) -> bool:
        """Probably-complete signal used to PRIORITIZE heads: a finished
        executor materialization, or (for jax arrays) ``is_ready`` —
        which only proves the device COMPUTE finished, not that the
        async host copy crossed the link. Honest overlap accounting is
        therefore measured at materialize time (see ``_resolve_flush``),
        never inferred from this."""
        if self.poisoned:
            return True  # nothing to wait for — resolvable immediately
        if self.host_future is not None:
            return self.host_future.done()
        try:
            ready = bool(self.scores.is_ready())
        except Exception:  # noqa: BLE001 - non-jax doubles: never "landed"
            return False
        if ready and self.t_landed is None:
            self.t_landed = time.perf_counter()  # async: ok(either side's stamp is a landing; one float store)
        return ready

    def ensure_host_future(self, loop, pool):
        """Lazily start (and cache) an executor materialization — used
        when the reaper must wait on several families' heads at once.
        Resolves to the (scores, sketch, shadow) host triple."""
        if self.host_future is None:
            self.host_future = loop.run_in_executor(
                pool, self._materialize
            )
        return self.host_future


class _ReapQueue(list):
    """Per-(family, mesh-slice) FIFO of in-flight flush completions —
    the PER-DEVICE drain queues of the multi-chip result path. Depth is
    bounded by the ``max_inflight`` semaphore (acquired before rows are
    popped from lanes) and reaches it only where flushes pipeline — a
    lane at the smallest bucket or over; smaller flushes wait for the
    one in flight (``SliceRuntime.held``) and the queue stands one deep. It is
    observable via the
    ``tpu_inference_deliver_inflight`` gauge (+ per-family and
    per-device labeled variants) and the
    ``tpu_inference.deliver_backpressure`` counter
    (tools/check_queues.py registry). FIFO per (family, slice) is what
    gives per-tenant in-order delivery: a tenant lives on exactly one
    slice of one family, the reaper never resolves past an unfinished
    head, and a slice MOVE (failover/rebalance) holds the tenant's rows
    behind a ``_SliceFence`` until the old slice's in-flight flushes
    resolve — so one slow chip's transfers never head-of-line block
    another slice's deliveries, and ordering still survives the move."""

    __slots__ = ()

    def popleft(self) -> _PendingFlush:
        return self.pop(0)


class SliceRuntime:
    """One (family, mesh slice) of the serving pipeline: its scorer and
    everything the service keeps per slice.

    Born whole in ``TpuInferenceService.scorer_for_slice`` — scorer,
    breaker, permits, reap queue and deadline history exist from the
    first moment; only what traffic sizes appears later, inside the
    slice: a lane ring per (slot, data shard) at its first row, a
    staging rotation per bucket at its first flush. It dies with the
    service. The service holds one table of these, keyed (family,
    slice); ``scorers``, ``breakers``, ``last_train_losses`` view it."""

    __slots__ = (
        "family", "sl", "scorer", "breaker", "metrics", "mfu", "lanes",
        "train_lanes", "staging", "staging_slots", "seen_shapes", "permits",
        "reap", "resolving", "first_pending_ts", "last_scores", "last_landed",
        "landed_at", "due_at",
        "flush_p99", "consec_errors", "quarantine", "probing", "train_ticks",
        "lane_swap", "lane_last_source", "last_train_losses",
    )

    def __init__(
        self, family: str, sl: int, scorer, breaker, metrics,
        max_inflight: int, staging_slots: int, mfu=None,
    ) -> None:
        self.family = family
        self.sl = sl
        self.scorer = scorer
        # breaker scope matches failure scope: one sick chip's open
        # breaker must not short-circuit the family's healthy slices
        self.breaker = breaker
        self.metrics = metrics
        # the device-labelled MfuAccount beside the family aggregate;
        # None on a one-device mesh, where the family's says it all
        self.mfu = mfu
        # pending rows per (slot, data shard): to score, and to train on
        self.lanes: Dict[Tuple[int, int], _LaneRing] = {}
        self.train_lanes: Dict[Tuple[int, int], _TrainLaneRing] = {}
        # reusable flush staging, bucket → [next_idx, sets]: the sets
        # rotate PER SLICE, so a slice packs host buffers while its own
        # previous flush's async h2d copy is still in flight
        self.staging: Dict[int, list] = {}
        self.staging_slots = staging_slots
        # shapes compiled so far (bucket sizes, and "train"): the first
        # flush at a new one IS an XLA compile
        self.seen_shapes: set = set()
        # in-flight budget: it bounds the d2h round trips on ONE device
        # queue, so a saturated slice exhausts only ITS OWN permits
        self.permits = asyncio.Semaphore(max_inflight)
        self.reap = _ReapQueue()
        # the one resolve task in flight: ≤ 1 keeps the per-tenant FIFO
        self.resolving: Optional[asyncio.Task] = None
        # perf_counter() when the oldest row now on a lane arrived
        self.first_pending_ts: Optional[float] = None
        # last dispatch output, the overlap probe (the next flush's
        # staging "overlapped" ⇔ this is still computing): the GATHERED
        # rows, dropped when the reap queue drains — an idle slice pins
        # nothing
        self.last_scores = None
        # perf_counter() of the newest landing: the device queue is
        # FIFO, so a flush's non-overlapping service time runs from the
        # later of its own dispatch and this
        self.last_landed = 0.0
        # the host's gap between a landing and the next dispatch, split
        # (``idle_split``): the newest serve flush's landing stamp
        # (``_PendingFlush.t_landed``) once it has left the reap queue,
        # and when a flush first became due since the last dispatch
        # (None: not due) — both perf_counter()
        self.landed_at = 0.0
        self.due_at: Optional[float] = None
        # dispatch→landed history, the flush deadline's source
        self.flush_p99 = RollingQuantile()
        # consecutive scorer errors: failover pacing, chip-local
        self.consec_errors = 0
        # None, or the SUSPECT record {reason, since_ms, ok_probes,
        # next_probe}; while set the router routes around the slice, its
        # lanes drain unscored and probes (≤ 1 in flight) re-admit it
        self.quarantine: Optional[dict] = None
        self.probing: Optional[asyncio.Task] = None
        # live-training cadence {slot: flush-tick}. A LANE slot's tick
        # only accumulates (maturity is checked — and reset — at lane
        # dispatch, so a throttled slot keeps its mature tick until
        # admitted); inline slots check and reset per flush
        self.train_ticks: Dict[int, int] = {}
        # lane steps since the last weight commit; the last lane source
        # ("replay" | "resident": alternated when both are pending); the
        # newest train step's per-slot losses
        self.lane_swap = 0
        self.lane_last_source: Optional[str] = None
        self.last_train_losses = None

    # -- lanes -------------------------------------------------------------
    def lane(self, slot: int, dshard: int, capacity: int) -> _LaneRing:
        """The (slot, data shard) lane ring, made at its first row."""
        ring = self.lanes.get((slot, dshard))
        if ring is None:
            ring = self.lanes[(slot, dshard)] = _LaneRing(capacity)
        return ring

    def train_lane(self, slot: int, dshard: int) -> _TrainLaneRing:
        ring = self.train_lanes.get((slot, dshard))
        if ring is None:
            ring = self.train_lanes[(slot, dshard)] = _TrainLaneRing(4096)
        return ring

    def mark_pending(self) -> None:
        """Rows landed on a lane: the collect deadline starts with the oldest."""
        if self.first_pending_ts is None:
            self.first_pending_ts = time.perf_counter()

    def drain_lanes(self, slot: Optional[int] = None) -> Iterator[tuple]:
        """Pop every lane (of one slot, if given) whole, one at a time —
        the cold paths: a tenant stopping, moving or paged out, the
        family parked, the slice quarantined, the breaker open,
        teardown. Yields each non-empty lane's (dshard, ids, vals, seqs,
        rows), for the caller to park or to resolve unscored."""
        for key in [k for k in self.lanes if slot is None or k[0] == slot]:
            lane = self.lanes.pop(key)
            if lane.count:
                yield (key[1], *lane.pop(lane.count))

    def forget_slot_training(self, slot: int) -> int:
        """A tenant left ``slot`` (stop, move, page-out): its pending
        TRAIN rows go — droppable history the store still holds, which a
        later replay train job re-feeds — and its cadence tick, so the
        slot's next tenant neither trains on this one's data nor
        inherits a mature tick. Returns the rows dropped."""
        dropped = 0
        for key in [k for k in self.train_lanes if k[0] == slot]:
            dropped += self.train_lanes.pop(key).count
        self.train_ticks.pop(slot, None)
        return dropped

    # -- the flush policy ----------------------------------------------------
    def due(self, mb) -> bool:
        """THE flush policy's first half: a flush is DUE when a lane is
        full or the oldest pending row has waited the collect deadline.
        The first True since the last dispatch stamps ``due_at``: the
        deadline's own moment, or now for a full lane (the scoring pass
        that filled it asks at once)."""
        first = self.first_pending_ts
        full = any(l.count >= mb.max_batch for l in self.lanes.values())
        if first is None and not full:
            self.due_at = None
            return False
        now = time.perf_counter()
        at = now if first is None else first + mb.deadline_ms / 1000.0
        if full:
            at = min(at, now)
        elif at > now:
            self.due_at = None
            return False
        if self.due_at is None:
            self.due_at = at
        return True

    def idle_split(self, t_asked: float) -> Tuple[float, float]:
        """(unfed, turn): the host's side of the device's idle before a
        serve flush that asked for its permit at ``t_asked`` — unfed from
        the previous serve landing to the moment a flush first became
        due (no work to give the chip), turn from the later of the two
        to ``t_asked`` (the loop's turn). Both 0 while a serve flush of
        this slice is still in flight, and before its first landing."""
        landed = self.landed_at
        for p in self.reap:
            if p.lane != "serve" or p.poisoned:
                continue
            if not p.resolved and not p.landed():
                return 0.0, 0.0
            if p.t_landed is not None and p.t_landed > landed:
                landed = p.t_landed
        if not landed:
            return 0.0, 0.0
        due = t_asked if self.due_at is None else self.due_at
        return max(0.0, due - landed), max(0.0, t_asked - max(landed, due))

    def in_flight(self) -> List[_PendingFlush]:
        """This slice's entries that are dispatched and have not landed:
        what its device is still working on. A landed head that only
        waits for its resolve to publish is not among them — the device
        is free from the landing on — nor is a poisoned (host-only)
        entry, which lands by construction."""
        return [p for p in self.reap if not p.resolved and not p.landed()]

    def held(self, mb, parked: bool) -> bool:
        """THE flush policy's second half (the first is ``due``). A due
        flush waits for the one in flight unless some lane already holds
        the smallest compiled bucket:

          hold ⇔ due ∧ a serve flush of this slice is in flight
                     ∧ every lane's count < buckets[0]

        (a train-lane step in flight holds nothing: serving has the
        right of way, and the lane only ever enters an empty window).

        A held flush never runs a program larger than the smallest
        bucket's (a window family's narrow plane while every lane fits
        it, ``ShardedScorer.planes``, else that bucket's), and holding
        saves a whole step of device queue: the rows stay on the lanes
        (counted by the lane watermark as ever) and ride out together
        when the in-flight flush lands. From the smallest bucket up,
        coalescing further would move to a larger program, and pipelining up to
        ``max_inflight`` deep — which hides h2d and assembly under
        compute for full flushes — applies unchanged.

        Evaluated afresh every pass from the reap queue, per (family,
        slice): whatever takes the in-flight flush out of the queue
        (landing, the supervisor's force-resolve, teardown) lifts the
        hold, and where ``_flush_slice`` would not dispatch at all (the
        family parked — the one thing here that is not the slice's, so
        the service passes it — the slice quarantined, the breaker open:
        rows pass through unscored) there is nothing to wait for."""
        if not any(p.lane == "serve" for p in self.in_flight()):
            return False
        if parked or self.quarantine is not None:
            return False
        if self.breaker.state == "open":
            return False
        smallest = min(mb.buckets[0], mb.max_batch)
        return all(l.count < smallest for l in self.lanes.values())

    @staticmethod
    def pick_bucket(need: int, buckets: Tuple[int, ...], max_batch: int) -> int:
        for b in buckets:
            if need <= b:
                return min(b, max_batch)
        return max_batch

    def staging_set(self, b_lane: int) -> _StagingSet:
        """Next rotating staging set for the bucket — created once,
        reused for the lifetime of the shape. Per-slice pools are what
        let slices pack+stage concurrently instead of funneling through
        one rotation."""
        rot = self.staging.get(b_lane)
        if rot is None:
            n = self.staging_slots
            rot = self.staging[b_lane] = [
                0, [_StagingSet(self.scorer, b_lane) for _ in range(n)],
            ]
            # bounded-pool observability (check_queues): total resident
            # staging sets across every (family, slice, bucket) rotation
            self.metrics.gauge("tpu_inference_staging_sets").inc(n)
        idx, sets = rot
        rot[0] = (idx + 1) % len(sets)
        st = sets[idx]
        st.ensure_reusable(self.metrics)
        return st

    # -- flush supervision ---------------------------------------------------
    def flush_deadline_s(self, ft: FaultTolerancePolicy) -> Optional[float]:
        """Seconds a newly dispatched flush gets before the supervisor
        force-resolves it, under the family's policy ``ft``: max(floor,
        x × this slice's observed dispatch→landed p99). None =
        supervision off for the family (``flush_deadline_ms = 0`` — the
        rollback knob)."""
        floor = ft.flush_deadline_ms / 1000.0
        if floor <= 0:
            return None
        p99 = self.flush_p99.quantile()
        if p99 is None:
            return floor
        return max(floor, ft.flush_deadline_x * p99)

    def note_device_s(self, device_s: float) -> None:
        self.flush_p99.add(device_s)
        p99 = self.flush_p99.quantile()
        if p99 is not None:
            # the deadline source, surfaced live: the latency waterfall
            # and history sampler read this gauge
            self.metrics.gauge(
                "tpu_flush_latency_p99_ms",
                family=self.family, slice=str(self.sl),
            ).set(round(p99 * 1000.0, 3))

    # -- quarantine & probation ------------------------------------------------
    def enter_quarantine(self, reason: str, probe_interval_s: float) -> bool:
        """Mark the slice SUSPECT; False if it already was (idempotent)."""
        if self.quarantine is not None:
            return False
        self.quarantine = {
            "reason": reason,
            "since_ms": time.time() * 1000.0,
            "ok_probes": 0,
            "next_probe": time.monotonic() + probe_interval_s,
        }
        return True

    def clear_quarantine(self) -> bool:
        """The operator's re-admission (engine (re)start), without
        probation: the record goes and the probe in flight with it (the
        breaker is the caller's to reset). False if not quarantined."""
        if self.quarantine is None:
            return False
        self.quarantine = None
        if self.probing is not None:
            self.probing.cancel()
            self.probing = None
        return True

    def readmit(self) -> None:
        """Probation passed — called from the probe task itself, which
        therefore is not cancelled: the record, the error count and the
        breaker's history clear together."""
        self.quarantine = None
        self.consec_errors = 0
        self.breaker.reset()
