"""Columnar event batches — the hot-path representation.

TPU-first design decision (SURVEY.md §7 step 1): the ingest→score path moves
structs-of-arrays, not lists of objects. A ``MeasurementBatch`` holds device
measurements as parallel numpy arrays (stream id, value, timestamps) so that:

- the micro-batcher can concatenate/pad/bucket without Python loops,
- host→TPU transfer is a handful of contiguous arrays,
- the windowed scoring step is a single gather/scatter + model apply
  under ``jit`` (see ``pipeline.inference``).

``stream_id`` identifies a (device, measurement-name) series — assigned by
the device registry at inbound-processing time — and indexes directly into
the on-device window state (``ops.windows``). Object-shaped events
(``core.events.DeviceMeasurement``) are materialized only at the edges
(REST, outbound connectors, event store rows).
"""

from __future__ import annotations

import struct
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

from sitewhere_tpu.core.events import DeviceMeasurement

# grow-on-demand pool of row-index suffix strings: `prefix + pool[:n]`
# (object-array broadcast add) is ~5x cheaper than np.char.add + astype —
# id generation sits on the persistence path at full ingest rate
_ID_SUFFIXES = np.zeros((0,), object)
# growth guard: persistence materializes ids on executor threads, so two
# threads can race the grow-and-publish. Growth happens under the lock
# (monotonic — a later, smaller grow can never shrink the published pool)
# and readers slice a LOCAL reference: re-reading the global after the
# length check could observe a concurrent swap and hand back fewer than
# n ids, silently breaking the column-length invariant downstream.
_ID_LOCK = threading.Lock()


def make_event_ids(prefix: str, n: int) -> np.ndarray:
    """object[n] ids '{prefix}{row}' — the one vectorized id generator.

    Thread-safe: safe to call from executor threads at full ingest rate."""
    global _ID_SUFFIXES
    pool = _ID_SUFFIXES
    if len(pool) < n:
        with _ID_LOCK:
            pool = _ID_SUFFIXES
            if len(pool) < n:
                pool = np.arange(
                    max(n, 2 * len(pool), 4096)
                ).astype("U8").astype(object)
                _ID_SUFFIXES = pool
    return prefix + pool[:n]


@dataclass(slots=True)
class MeasurementBatch:
    """A columnar batch of device measurements for one tenant.

    Invariant: all arrays share length ``n``. ``pad_to`` produces bucketed
    static shapes for XLA (padding rows carry ``valid == False``).
    """

    tenant: str
    stream_ids: np.ndarray      # int32 [n]  (device,measurement) series index
    values: np.ndarray          # float32 [n]
    event_ts: np.ndarray        # float64 [n] epoch ms (device time)
    received_ts: np.ndarray     # float64 [n] epoch ms (ingest time)
    valid: np.ndarray           # bool [n]  False on padding rows
    # edge-materialization support: original event ids / tokens (object dtype
    # kept host-side only; never shipped to device)
    event_ids: Optional[np.ndarray] = None     # object [n]
    device_tokens: Optional[np.ndarray] = None  # object [n]
    names: Optional[np.ndarray] = None          # object [n]
    # enrichment columns (inbound-processing) + scoring output
    assignment_tokens: Optional[np.ndarray] = None  # object [n]
    area_tokens: Optional[np.ndarray] = None        # object [n]
    scores: Optional[np.ndarray] = None             # float32 [n], NaN=unscored
    # lazy-id contract: ids are '{id_prefix}{row}'. The prefix pins the
    # identity at first need so the store's lazily-persisted ids and any
    # later edge materialization of the SAME batch agree (row subsets get
    # fresh prefixes — their row numbering diverges from the parent's)
    id_prefix: Optional[str] = None
    # batch-level trace marks (stage → epoch ms) — the columnar analog of
    # DeviceEvent.trace for p99 accounting
    trace: Dict[str, float] = field(default_factory=dict)
    # end-to-end trace context (core.trace.TraceContext | None), minted at
    # the ingest edge when the tenant has tracing enabled; one trace per
    # batch — the columnar unit of tracing (per-row spans would put a
    # Python loop back on the hot path)
    trace_ctx: Optional[object] = None
    # admission deadline (absolute epoch ms | None), stamped at the
    # ingest edge from the tenant's OverloadPolicy; stages consult the
    # remaining budget before doing work (runtime.overload.DeadlineGate)
    # — one deadline per batch, like the trace context
    deadline_ms: Optional[float] = None
    # cached group indices: (uniq object[], inverse int32[]) for the token /
    # name columns. np.unique over object arrays is a string argsort — the
    # single biggest per-batch host cost when every stage re-derives it —
    # so it's computed at most once per batch (or inherited for free from
    # the bulk wire's chunk structure) and shared by inbound, the stream
    # registry, and device-state
    tok_index: Optional[tuple] = None
    name_index: Optional[tuple] = None
    # time.perf_counter() stamps of this process (0.0 = not stamped;
    # never on the wire — another process's clock means nothing here):
    # broker delivery to the event source, lane enqueue, publish on
    # scored-events. ``pipeline.intake`` / ``tpu_inference.lane_wait`` /
    # ``pipeline.egress`` are the intervals between them and their
    # neighbours (docs/OBSERVABILITY.md "Latency attribution").
    t_intake: float = 0.0
    t_lane: float = 0.0
    t_scored: float = 0.0

    def token_index(self) -> tuple:
        if self.tok_index is None:
            u, inv = np.unique(self.device_tokens, return_inverse=True)
            self.tok_index = (u, inv.astype(np.int32))
        return self.tok_index

    def names_index(self) -> tuple:
        if self.name_index is None:
            u, inv = np.unique(self.names, return_inverse=True)
            self.name_index = (u, inv.astype(np.int32))
        return self.name_index

    def pair_codes(self) -> np.ndarray:
        """int64[n] code per (device_token, name) pair — the single
        audited combination of the two cached group indices (token code ×
        name-vocab + name code). Equal codes ⇔ equal (token, name)."""
        _, ti = self.token_index()
        un, ni = self.names_index()
        return ti.astype(np.int64) * len(un) + ni

    def mark(self, stage: str) -> None:
        self.trace[stage] = time.time() * 1000.0

    @property
    def n(self) -> int:
        return int(self.stream_ids.shape[0])

    @property
    def n_valid(self) -> int:
        return int(self.valid.sum())

    OBJ_COLS = ("event_ids", "device_tokens", "names",
                "assignment_tokens", "area_tokens")

    @staticmethod
    def empty(tenant: str = "default") -> "MeasurementBatch":
        return MeasurementBatch(
            tenant=tenant,
            stream_ids=np.zeros((0,), np.int32),
            values=np.zeros((0,), np.float32),
            event_ts=np.zeros((0,), np.float64),
            received_ts=np.zeros((0,), np.float64),
            valid=np.zeros((0,), bool),
        )

    @staticmethod
    def from_requests(
        tenant: str,
        reqs: Sequence[dict],
    ) -> "MeasurementBatch":
        """Build from decoded measurement request dicts (the event-source
        fast path). Event ids are batch-prefixed sequences — one uuid per
        BATCH, not per row (uuid4 per row would dominate the decode loop)."""
        n = len(reqs)
        prefix = uuid.uuid4().hex[:16]
        now = time.time() * 1000.0
        # ONE pass over the dicts (not one per column) — this runs at the
        # full ingest rate
        values = np.empty((n,), np.float32)
        event_ts = np.empty((n,), np.float64)
        received_ts = np.empty((n,), np.float64)
        event_ids = np.empty((n,), object)
        device_tokens = np.empty((n,), object)
        names = np.empty((n,), object)
        for i, r in enumerate(reqs):
            get = r.get
            values[i] = get("value", 0.0)
            event_ts[i] = get("event_ts", now)
            received_ts[i] = get("received_ts", now)
            event_ids[i] = get("id") or f"{prefix}-{i:06d}"
            device_tokens[i] = get("device_token", "")
            names[i] = get("name", "")
        return MeasurementBatch(
            tenant=tenant,
            stream_ids=np.zeros((n,), np.int32),  # assigned by tpu-inference
            values=values,
            event_ts=event_ts,
            received_ts=received_ts,
            valid=np.ones((n,), bool),
            event_ids=event_ids,
            device_tokens=device_tokens,
            names=names,
        )

    @staticmethod
    def from_columns(
        tenant: str,
        device_tokens: list,
        names: list,
        values: list,
        event_ts: list,
        received_ms: Optional[float] = None,
    ) -> "MeasurementBatch":
        """Build straight from decoder column lists — the zero-dict ingest
        path. ``event_ts`` entries of 0 mean 'now'."""
        n = len(values)
        now = received_ms if received_ms is not None else time.time() * 1000.0
        ets = np.asarray(event_ts, np.float64)
        if (ets == 0).any():
            ets = np.where(ets == 0, now, ets)
        return MeasurementBatch(
            tenant=tenant,
            stream_ids=np.zeros((n,), np.int32),
            values=np.asarray(values, np.float32),
            event_ts=ets,
            received_ts=np.full((n,), now, np.float64),
            valid=np.ones((n,), bool),
            event_ids=None,  # lazily generated at the edges (ensure_event_ids)
            device_tokens=np.asarray(device_tokens, object),
            names=np.asarray(names, object),
        )

    @staticmethod
    def from_column_chunks(
        tenant: str,
        chunks: Sequence[tuple],
        received_ms: Optional[float] = None,
    ) -> "MeasurementBatch":
        """Build from decoder chunk tuples ``(device_token, name,
        values f32[k], event_ts f64[k])`` — the bulk-binary-wire ingest
        path. Zero per-row Python: token/name columns are C-level
        ``np.full`` fills, numeric columns concatenate."""
        now = received_ms if received_ms is not None else time.time() * 1000.0

        def cat(parts, dtype):
            return (
                np.asarray(parts[0], dtype)
                if len(parts) == 1
                else np.concatenate([np.asarray(p, dtype) for p in parts])
            )

        values = cat([c[2] for c in chunks], np.float32)
        ets = cat([c[3] for c in chunks], np.float64)
        if (ets == 0).any():
            ets = np.where(ets == 0, now, ets)
        n = int(values.shape[0])
        # ONE np.repeat per object column (C-level pointer fan-out) — a
        # per-chunk np.full here costs ~0.4 µs/event at ingest rate
        lens = [len(c[2]) for c in chunks]
        toks = np.repeat(np.asarray([c[0] for c in chunks], object), lens)
        names = np.repeat(np.asarray([c[1] for c in chunks], object), lens)
        # group indices come FREE from the chunk structure (one (device,
        # name) per chunk) — O(chunks), no string sort ever
        tok_map: dict = {}
        name_map: dict = {}
        tok_codes = [tok_map.setdefault(c[0], len(tok_map)) for c in chunks]
        name_codes = [name_map.setdefault(c[1], len(name_map)) for c in chunks]
        return MeasurementBatch(
            tenant=tenant,
            stream_ids=np.zeros((n,), np.int32),
            values=values,
            event_ts=ets,
            received_ts=np.full((n,), now, np.float64),
            valid=np.ones((n,), bool),
            event_ids=None,
            device_tokens=toks,
            names=names,
            tok_index=(
                np.asarray(list(tok_map), object),
                np.repeat(np.asarray(tok_codes, np.int32), lens),
            ),
            name_index=(
                np.asarray(list(name_map), object),
                np.repeat(np.asarray(name_codes, np.int32), lens),
            ),
        )

    def ensure_event_ids(self) -> np.ndarray:
        """Materialize per-row event ids on demand. Generated vectorized
        (batch-unique prefix + row index) only where an edge actually needs
        ids (event store seal, REST/object materialization) — the scoring
        hot path never pays for them."""
        if self.event_ids is None:
            if self.id_prefix is None:
                self.id_prefix = uuid.uuid4().hex[:16] + "-"
            self.event_ids = make_event_ids(self.id_prefix, self.n)
        return self.event_ids

    def select(self, idx: np.ndarray) -> "MeasurementBatch":
        """Row subset (fancy index or bool mask) carrying every column.

        Id identity: if this batch's lazy id prefix is already pinned
        (e.g. the store persisted it lazily), the subset's ids are DERIVED
        from the parent's prefix + original row numbers — a rule alert's
        ``origin_event`` must reference the id the store actually holds.
        Unpinned parents pass laziness through (fresh prefix on demand)."""
        def cut(a):
            return None if a is None else a[idx]

        sel_ids = cut(self.event_ids)
        if sel_ids is None and self.id_prefix is not None:
            rows = np.arange(self.n)[idx]
            sel_ids = np.asarray(
                [f"{self.id_prefix}{r}" for r in rows.tolist()], object
            )
        return MeasurementBatch(
            tenant=self.tenant,
            stream_ids=self.stream_ids[idx],
            values=self.values[idx],
            event_ts=self.event_ts[idx],
            received_ts=self.received_ts[idx],
            valid=self.valid[idx],
            event_ids=sel_ids,
            device_tokens=cut(self.device_tokens),
            names=cut(self.names),
            assignment_tokens=cut(self.assignment_tokens),
            area_tokens=cut(self.area_tokens),
            scores=cut(self.scores),
            trace=dict(self.trace),
            trace_ctx=self.trace_ctx,
            deadline_ms=self.deadline_ms,
            t_intake=self.t_intake,
            t_lane=self.t_lane,
            t_scored=self.t_scored,
        )

    def to_events(self) -> List[DeviceMeasurement]:
        """Materialize rows as edge objects (REST/conn/rules slow path)."""
        out: List[DeviceMeasurement] = []
        ids = self.ensure_event_ids() if self.n else self.event_ids
        toks = self.device_tokens
        names = self.names
        asg = self.assignment_tokens
        areas = self.area_tokens
        sc = self.scores
        for i in range(self.n):
            if not self.valid[i]:
                continue
            score = None
            if sc is not None and not np.isnan(sc[i]):
                score = float(sc[i])
            out.append(DeviceMeasurement(
                id=str(ids[i]) if ids is not None else "",
                device_token=str(toks[i]) if toks is not None else "",
                assignment_token=str(asg[i]) if asg is not None else "",
                area_token=str(areas[i]) if areas is not None else "",
                tenant=self.tenant,
                name=str(names[i]) if names is not None else "",
                value=float(self.values[i]),
                score=score,
                event_ts=int(self.event_ts[i]),
                received_ts=int(self.received_ts[i]),
            ))
        return out

    @staticmethod
    def from_arrays(
        tenant: str,
        stream_ids: np.ndarray,
        values: np.ndarray,
        event_ts: Optional[np.ndarray] = None,
        received_ts: Optional[np.ndarray] = None,
    ) -> "MeasurementBatch":
        n = int(np.asarray(stream_ids).shape[0])
        ts = np.full((n,), time.time() * 1000.0, np.float64)
        return MeasurementBatch(
            tenant=tenant,
            stream_ids=np.asarray(stream_ids, np.int32),
            values=np.asarray(values, np.float32),
            event_ts=ts if event_ts is None else np.asarray(event_ts, np.float64),
            received_ts=ts if received_ts is None else np.asarray(received_ts, np.float64),
            valid=np.ones((n,), bool),
        )

    @staticmethod
    def from_events(
        events: Sequence[DeviceMeasurement],
        stream_ids: Sequence[int],
        tenant: str = "default",
    ) -> "MeasurementBatch":
        n = len(events)
        return MeasurementBatch(
            tenant=tenant,
            stream_ids=np.asarray(stream_ids, np.int32),
            values=np.asarray([e.value for e in events], np.float32),
            event_ts=np.asarray([e.event_ts for e in events], np.float64),
            received_ts=np.asarray([e.received_ts for e in events], np.float64),
            valid=np.ones((n,), bool),
            event_ids=np.asarray([e.id for e in events], object),
            device_tokens=np.asarray([e.device_token for e in events], object),
            names=np.asarray([e.name for e in events], object),
        )

    @staticmethod
    def concat(batches: Iterable["MeasurementBatch"]) -> "MeasurementBatch":
        bs: List[MeasurementBatch] = [b for b in batches if b.n]
        if not bs:
            return MeasurementBatch.empty()
        if any(b.event_ids is not None for b in bs):
            # mixed lazy/materialized ids: materialize the lazy sides now —
            # the ""-fill below would otherwise permanently block
            # ensure_event_ids on the combined batch
            for b in bs:
                b.ensure_event_ids()

        def _cat_opt(col: str, fill, dtype) -> Optional[np.ndarray]:
            # preserve optional columns row-aligned even when some inputs
            # lack them (those rows get the fill), rather than dropping them
            if not any(getattr(b, col) is not None for b in bs):
                return None
            parts = []
            for b in bs:
                a = getattr(b, col)
                parts.append(a if a is not None else np.full((b.n,), fill, dtype))
            return np.concatenate(parts)

        return MeasurementBatch(
            tenant=bs[0].tenant,
            stream_ids=np.concatenate([b.stream_ids for b in bs]),
            values=np.concatenate([b.values for b in bs]),
            event_ts=np.concatenate([b.event_ts for b in bs]),
            received_ts=np.concatenate([b.received_ts for b in bs]),
            valid=np.concatenate([b.valid for b in bs]),
            scores=_cat_opt("scores", np.nan, np.float32),
            # a combined batch keeps the FIRST input's trace identity (one
            # trace per batch; the others' traces decide at idle timeout)
            trace_ctx=next(
                (b.trace_ctx for b in bs if b.trace_ctx is not None), None
            ),
            # the combined batch honors the TIGHTEST constituent deadline
            # (late rows must not inherit a fresher batch's slack)
            deadline_ms=min(
                (b.deadline_ms for b in bs if b.deadline_ms is not None),
                default=None,
            ),
            **{c: _cat_opt(c, "", object) for c in MeasurementBatch.OBJ_COLS},
        )

    def pad_to(self, size: int) -> "MeasurementBatch":
        """Pad (with invalid rows) to a bucketed static shape for XLA.

        Padding rows point at stream 0 with value 0; they still flow through
        the jitted step (branchless) but their window-state writes are masked
        and their scores discarded (``valid`` mask).
        """
        n = self.n
        if n == size:
            return self
        if n > size:
            raise ValueError(f"batch of {n} cannot pad to {size}")
        pad = size - n

        def _pad(a: np.ndarray, fill: float = 0.0) -> np.ndarray:
            return np.concatenate([a, np.full((pad,), fill, a.dtype)])

        def _pad_opt(a: Optional[np.ndarray], fill, dtype) -> Optional[np.ndarray]:
            if a is None:
                return None
            return np.concatenate([a, np.full((pad,), fill, dtype)])

        return MeasurementBatch(
            tenant=self.tenant,
            stream_ids=_pad(self.stream_ids),
            values=_pad(self.values),
            event_ts=_pad(self.event_ts),
            received_ts=_pad(self.received_ts),
            valid=np.concatenate([self.valid, np.zeros((pad,), bool)]),
            scores=_pad_opt(self.scores, np.nan, np.float32),
            trace=dict(self.trace),
            trace_ctx=self.trace_ctx,
            deadline_ms=self.deadline_ms,
            **{
                c: _pad_opt(getattr(self, c), "", object)
                for c in self.OBJ_COLS
            },
        )

    def take(self, n: int) -> "tuple[MeasurementBatch, MeasurementBatch]":
        """Split into (first n rows, rest) — used by the micro-batcher."""
        return self.select(np.s_[:n]), self.select(np.s_[n:])

    def __reduce__(self):
        # every pickle of a batch (netbus frames, dlog WAL appends,
        # checkpoint snapshots, DLQ payloads) rides the raw-buffer wire
        # codec below: numeric columns ship as dtype-tagged raw buffers
        # instead of per-element pickle ops, object token columns ship as
        # (unique vocab, int32 inverse) when their group index is cheap —
        # which also hands the CONSUMER the cached index for free
        if not WIRE_CODEC_ENABLED:
            # kill switch: a PLAIN class-construction pickle that builds
            # without _batch_from_wire being allowlisted — the escape
            # hatch for feeding frames to consumers that predate the
            # codec (see the version notes below)
            return (
                MeasurementBatch,
                (self.tenant, self.stream_ids, self.values,
                 self.event_ts, self.received_ts, self.valid),
                (None, {
                    "event_ids": self.event_ids,
                    "device_tokens": self.device_tokens,
                    "names": self.names,
                    "assignment_tokens": self.assignment_tokens,
                    "area_tokens": self.area_tokens,
                    "scores": self.scores,
                    "id_prefix": self.id_prefix,
                    "trace": self.trace,
                    "trace_ctx": self.trace_ctx,
                    "deadline_ms": self.deadline_ms,
                }),
            )
        return (_batch_from_wire, (encode_batch_wire(self),))


# ----------------------------------------------------------------------
# Raw-buffer wire codec (the MeasurementBatch serialization hot path)
# ----------------------------------------------------------------------
# Frame layout (version 1):
#   b"SWB" | version u8 | meta_len u32 | meta | raw segments
# ``meta`` is a restricted-pickle blob (runtime.safepickle) holding the
# scalar fields, the object-column vocabularies, and the segment table
# [(field, nbytes), ...]; the raw segments are the numeric columns'
# ``tobytes()`` concatenated in table order. Decode copies the segment
# region ONCE into a bytearray and hands out writable zero-copy
# ``np.frombuffer`` views — no per-row work on either side.
#
# Version 0 is the odd-shape fallback: the same envelope around a
# restricted-pickle blob of the raw field dict. Encoders drop to it when
# a column is out of the wire contract (wrong dtype, or a batch
# violating its own length invariant — which must ship decodably, never
# as a torn v1 frame that drops the peer's connection); decoders accept
# both versions.
#
# Version compatibility: codec-aware consumers decode frames from OLDER
# producers (plain class pickles) and both envelope versions. The
# reverse — feeding a codec frame to a consumer that predates
# ``_batch_from_wire`` on the safepickle allowlist — does NOT work;
# for that rollback/mixed-fleet window set ``WIRE_CODEC_ENABLED=False``
# on the producer, which switches ``__reduce__`` to a plain
# class-construction pickle any build can load.

WIRE_CODEC_ENABLED = True
_WIRE_MAGIC = b"SWB"
_WIRE_META = struct.Struct(">I")

# field → required dtype for the raw segments (anything else falls back
# to version 0 — the decoder REFUSES unexpected dtypes/fields outright,
# so a tampered frame cannot smuggle object buffers through the raw path)
_WIRE_NUMERIC = {
    "stream_ids": np.dtype(np.int32),
    "values": np.dtype(np.float32),
    "event_ts": np.dtype(np.float64),
    "received_ts": np.dtype(np.float64),
    "valid": np.dtype(bool),
    "scores": np.dtype(np.float32),
    "tok_inverse": np.dtype(np.int32),
    "name_inverse": np.dtype(np.int32),
}


class WireCodecError(ValueError):
    """A torn, truncated, or out-of-contract wire frame."""


def _wire_safepickle():
    from sitewhere_tpu.runtime import safepickle  # lazy: no import cycle

    return safepickle


def _encode_fallback(batch: "MeasurementBatch") -> bytes:
    fields = {
        "tenant": batch.tenant,
        "stream_ids": batch.stream_ids,
        "values": batch.values,
        "event_ts": batch.event_ts,
        "received_ts": batch.received_ts,
        "valid": batch.valid,
        "event_ids": batch.event_ids,
        "device_tokens": batch.device_tokens,
        "names": batch.names,
        "assignment_tokens": batch.assignment_tokens,
        "area_tokens": batch.area_tokens,
        "scores": batch.scores,
        "id_prefix": batch.id_prefix,
        "trace": batch.trace,
        "trace_ctx": batch.trace_ctx,
        "deadline_ms": batch.deadline_ms,
    }
    import pickle as _pickle

    return _WIRE_MAGIC + b"\x00" + _pickle.dumps(
        fields, protocol=_pickle.HIGHEST_PROTOCOL
    )


def encode_batch_wire(batch: "MeasurementBatch") -> bytes:
    """Serialize a batch as the columnar raw-buffer frame (version 1),
    falling back to the safepickle envelope (version 0) for batches whose
    columns don't match the wire contract."""
    import pickle as _pickle

    if not WIRE_CODEC_ENABLED:
        return _encode_fallback(batch)
    numeric = [
        ("stream_ids", batch.stream_ids),
        ("values", batch.values),
        ("event_ts", batch.event_ts),
        ("received_ts", batch.received_ts),
        ("valid", batch.valid),
    ]
    if batch.scores is not None:
        numeric.append(("scores", batch.scores))
    n = batch.n
    for f, a in numeric:
        # shape check included: a batch violating its own column-length
        # invariant must ship via the fallback envelope, NOT become an
        # undecodable frame that drops the peer's whole connection
        if not isinstance(a, np.ndarray) or a.dtype != _WIRE_NUMERIC[f] \
                or a.shape != (n,):
            return _encode_fallback(batch)
    meta: Dict[str, object] = {
        "tenant": batch.tenant,
        "n": batch.n,
        "id_prefix": batch.id_prefix,
        "trace": batch.trace,
        "trace_ctx": batch.trace_ctx,
        "deadline_ms": batch.deadline_ms,
    }
    # token/name columns ride as (vocab, int32 inverse): computing the
    # group index here (cached on the batch — token_index memoizes) is a
    # one-time cost the producer's own later stages reuse, and the
    # consumer inherits the index without ever paying the string sort
    if batch.device_tokens is not None:
        u, inv = batch.token_index()
        if inv.shape != (n,):
            return _encode_fallback(batch)
        meta["tok_uniq"] = u.tolist()
        numeric.append(("tok_inverse", inv))
    if batch.names is not None:
        u, inv = batch.names_index()
        if inv.shape != (n,):
            return _encode_fallback(batch)
        meta["name_uniq"] = u.tolist()
        numeric.append(("name_inverse", inv))
    # low-volume object columns (usually None on the scoring path)
    obj: Dict[str, list] = {}
    for col in ("event_ids", "assignment_tokens", "area_tokens"):
        a = getattr(batch, col)
        if a is not None:
            if len(a) != n:
                return _encode_fallback(batch)
            obj[col] = a.tolist()
    if obj:
        meta["obj"] = obj
    meta["segs"] = [(f, int(a.nbytes)) for f, a in numeric]
    blob = _pickle.dumps(meta, protocol=_pickle.HIGHEST_PROTOCOL)
    parts = [_WIRE_MAGIC, b"\x01", _WIRE_META.pack(len(blob)), blob]
    parts.extend(
        a.tobytes() if not a.flags.c_contiguous else a.data.cast("B")
        for _f, a in numeric
    )
    return b"".join(parts)


def _batch_from_wire(data: bytes) -> "MeasurementBatch":
    """Decode one wire frame. Registered on the safepickle allowlist so
    frames decode through the SAME restricted path as everything else;
    every malformed shape raises (never returns a short batch)."""
    sp = _wire_safepickle()
    if len(data) < 4 or data[:3] != _WIRE_MAGIC:
        raise WireCodecError("not a MeasurementBatch wire frame (bad magic)")
    version = data[3]
    if version == 0:
        fields = sp.loads(data[4:])
        if not isinstance(fields, dict) or "tenant" not in fields:
            raise WireCodecError("malformed fallback frame")
        return MeasurementBatch(**fields)
    if version != 1:
        raise WireCodecError(
            f"unknown wire codec version {version} (this build speaks "
            "0-1; producer must fall back to the safepickle envelope)"
        )
    if len(data) < 4 + _WIRE_META.size:
        raise WireCodecError("torn frame: truncated meta header")
    (meta_len,) = _WIRE_META.unpack_from(data, 4)
    seg0 = 4 + _WIRE_META.size + meta_len
    if seg0 > len(data):
        raise WireCodecError("torn frame: meta overruns payload")
    meta = sp.loads(data[4 + _WIRE_META.size : seg0])
    if not isinstance(meta, dict):
        raise WireCodecError("malformed meta")
    try:
        n = int(meta["n"])
        segs = list(meta["segs"])
    except (KeyError, TypeError, ValueError) as exc:
        raise WireCodecError(f"malformed meta: {exc}") from None
    total = 0
    for f, nbytes in segs:
        dt = _WIRE_NUMERIC.get(f)
        if dt is None:
            raise WireCodecError(f"unexpected raw segment '{f}'")
        if int(nbytes) != n * dt.itemsize:
            raise WireCodecError(
                f"torn frame: segment '{f}' is {nbytes} bytes, "
                f"expected {n * dt.itemsize}"
            )
        total += int(nbytes)
    if seg0 + total != len(data):
        raise WireCodecError(
            f"torn frame: {len(data) - seg0} segment bytes, expected {total}"
        )
    # ONE copy of the segment region; every column is a writable
    # zero-copy view into it (scores are scatter-written downstream)
    buf = bytearray(data[seg0:])
    cols: Dict[str, np.ndarray] = {}
    off = 0
    for f, nbytes in segs:
        dt = _WIRE_NUMERIC[f]
        cols[f] = np.frombuffer(buf, dt, count=n, offset=off)
        off += int(nbytes)

    def vocab_col(inv_field: str, uniq_key: str) -> Optional[np.ndarray]:
        inv = cols.get(inv_field)
        if inv is None:
            return None
        uniq = meta.get(uniq_key)
        if not isinstance(uniq, list):
            raise WireCodecError(f"missing vocab for '{inv_field}'")
        u = np.asarray(uniq, object) if uniq else np.zeros((0,), object)
        if n and (inv.min() < 0 or inv.max() >= len(u)):
            raise WireCodecError(f"'{inv_field}' index out of vocab range")
        return u

    tok_u = vocab_col("tok_inverse", "tok_uniq")
    name_u = vocab_col("name_inverse", "name_uniq")
    obj = meta.get("obj") or {}

    def obj_col(name: str) -> Optional[np.ndarray]:
        lst = obj.get(name)
        if lst is None:
            return None
        if not isinstance(lst, list) or len(lst) != n:
            raise WireCodecError(f"object column '{name}' length mismatch")
        return np.asarray(lst, object) if n else np.zeros((0,), object)

    return MeasurementBatch(
        tenant=str(meta.get("tenant", "default")),
        stream_ids=cols["stream_ids"],
        values=cols["values"],
        event_ts=cols["event_ts"],
        received_ts=cols["received_ts"],
        valid=cols["valid"],
        event_ids=obj_col("event_ids"),
        device_tokens=None if tok_u is None else tok_u[cols["tok_inverse"]],
        names=None if name_u is None else name_u[cols["name_inverse"]],
        assignment_tokens=obj_col("assignment_tokens"),
        area_tokens=obj_col("area_tokens"),
        scores=cols.get("scores"),
        id_prefix=meta.get("id_prefix"),
        trace=dict(meta.get("trace") or {}),
        trace_ctx=meta.get("trace_ctx"),
        deadline_ms=meta.get("deadline_ms"),
        # the wire's chunk structure IS the group index — the consumer
        # never pays the object-string sort (round 5)
        tok_index=None if tok_u is None else (tok_u, cols["tok_inverse"]),
        name_index=None if name_u is None else (name_u, cols["name_inverse"]),
    )
