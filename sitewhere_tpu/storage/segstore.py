"""Columnar segment store: the event store's wire-speed persistence layer.

ROADMAP item 5: storage and replay must be a first-class scale axis — the
store and the DLQ/replay paths see the same traffic the scorer does, so
rows have to move the way the PR 4 feed path moves them: as dtype-tagged
raw column buffers, never as per-event Python objects.

Layout (one **segment** = one sealed, immutable row range)::

    b"SWS" | version u8 | meta_len u32 | meta (restricted pickle) | raw cols

``meta`` holds the scalar fields, the object-column vocabularies
(device/assignment/area/name columns ship as vocab + int32 inverse — the
same contract as ``MeasurementBatch.__reduce__``), the lazy event-id
prefix segments, the segment table ``[(field, nbytes), ...]``, and the
**zone map** (device-id set / hash bloom + event-time min/max + seq
range). The raw region is the numeric columns' buffers concatenated in
table order; decode hands out zero-copy ``np.frombuffer`` views — over an
``mmap`` of the file when the store is disk-backed, so a sealed-segment
scan never materializes a per-event object and never copies a column it
does not slice.

Durability (dir mode): a seal writes the segment file, fsyncs it, then
atomically replaces ``manifest.json`` — the **commit point**. Recovery
trusts only the manifest: a committed entry whose file is missing, short,
or undecodable is a torn tail — it (and everything after it) is dropped,
never half-read, and ``next_seq`` keeps the manifest's value so dropped
row seqs are never reused (replay cursors stay unambiguous).

Retention & compaction (``maintain``): segments wholly past the retention
horizon drop; runs of adjacent small segments (checkpoint tail
generations, low-rate tenants) merge into sealed full-size segments so
the zone-map index stays shallow, and segments carrying a score overlay
(write-back after rescore) re-encode so the overlay becomes durable.
``maintain`` runs off the ingest path — the instance history tick,
checkpoint/restore, and explicit calls drive it — so a seal stays
O(chunk) and generational tails don't pay quadratic re-encodes.

Seq contract: every appended row gets a monotonically increasing
store-global sequence number (implicit: a segment's rows are
``seq0 .. seq0+n-1`` in append order). ``plan``/``scan`` prune segments
by zone map and stream filtered column slices — the feed for
``pipeline/replay.py``'s replay-to-rescore engine.
"""

from __future__ import annotations

import json
import mmap
import os
import struct
import time
import uuid
import zlib
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from sitewhere_tpu.core.batch import make_event_ids
from sitewhere_tpu.runtime.loopledger import spanned

SEG_MAGIC = b"SWS"
SEG_VERSION = 1
SEG_SUFFIX = ".sws"
_SEG_META = struct.Struct(">I")

# field → required dtype for the raw column region (same discipline as
# core.batch._WIRE_NUMERIC: the decoder refuses anything else, so a
# tampered file can never smuggle object buffers through the raw path)
SEG_NUMERIC = {
    "value": np.dtype(np.float32),
    "score": np.dtype(np.float32),
    "event_ts": np.dtype(np.int64),
    "received_ts": np.dtype(np.int64),
    "tok_inverse": np.dtype(np.int32),
    "name_inverse": np.dtype(np.int32),
    "asg_inverse": np.dtype(np.int32),
    "area_inverse": np.dtype(np.int32),
}

# object column → (inverse raw field, vocab meta key)
OBJ_FIELDS = (
    ("device_token", "tok_inverse", "tok_uniq"),
    ("name", "name_inverse", "name_uniq"),
    ("assignment_token", "asg_inverse", "asg_uniq"),
    ("area_token", "area_inverse", "area_uniq"),
)

# zone map: store the exact device set up to this size, a 64-bit hash
# bloom above it (crc32 — stable across processes, unlike hash())
ZONE_DEVICE_LIST_MAX = 64


class SegmentFormatError(ValueError):
    """A torn, truncated, or out-of-contract segment file."""


def _safepickle():
    from sitewhere_tpu.runtime import safepickle  # lazy: no import cycle

    return safepickle


def _pin_prefix(b) -> str:
    """Pin (or reuse) a batch's lazy event-id prefix (see
    MeasurementBatch.id_prefix for the identity contract)."""
    if b.id_prefix is None:
        b.id_prefix = uuid.uuid4().hex[:16] + "-"
    return b.id_prefix


def _dev_bloom(vocab: Sequence[str]) -> int:
    """64-bit membership bloom over device tokens (1 bit per token)."""
    bits = 0
    for tok in vocab:
        bits |= 1 << (zlib.crc32(str(tok).encode()) & 63)
    return bits


def _zone_map(vocab: Sequence[str], event_ts: np.ndarray,
              seq0: int, n: int) -> dict:
    """The per-segment zone map: device set (exact up to
    ZONE_DEVICE_LIST_MAX, hash bloom always), event-time min/max, seq
    range — everything ``plan`` needs to prune without touching rows."""
    return {
        "ts_min": int(event_ts.min()) if n else 0,
        "ts_max": int(event_ts.max()) if n else 0,
        "seq_min": int(seq0),
        "seq_max": int(seq0 + n - 1) if n else int(seq0),
        "n_devices": len(vocab),
        "devices": (
            sorted(str(t) for t in vocab)
            if len(vocab) <= ZONE_DEVICE_LIST_MAX else None
        ),
        "dev_bloom": _dev_bloom(vocab),
    }


def _vocab_encode(col: Optional[np.ndarray], hint: Optional[tuple]):
    """(vocab list, int32 inverse) for one object column. The hint — a
    precomputed group index inherited from the batch wire (see
    ``SegmentColumns.append_batch``) — skips the object-string sort the
    hot path must never pay; ``np.unique`` is the cold fallback."""
    if hint is not None:
        return list(hint[0]), np.asarray(hint[1], np.int32)
    if col is None or len(col) == 0:
        return [], np.zeros((len(col) if col is not None else 0,), np.int32)
    u, inv = np.unique(col, return_inverse=True)
    return u.tolist(), inv.astype(np.int32)


def encode_segment(
    chunk: Dict[str, object],
    seq0: int,
    tenant: str = "default",
    vocab_hints: Optional[Dict[str, tuple]] = None,
) -> bytes:
    """Serialize one column chunk as a sealed segment.

    ``chunk`` is the store's legacy column-dict shape: numeric columns
    (``value``/``score``/``event_ts``/``received_ts``) plus the four
    object columns, plus either a materialized ``event_id`` array or the
    lazy markers (``_idsegs`` / ``_idp``) the event store's tail carries.
    ``vocab_hints`` maps object-column names to ``(vocab, inverse)``
    pairs computed upstream (the batch wire's free group index)."""
    n = int(len(chunk["value"]))
    hints = vocab_hints or {}
    numeric: List[Tuple[str, np.ndarray]] = []
    for f in ("value", "score", "event_ts", "received_ts"):
        a = np.ascontiguousarray(
            np.asarray(chunk[f]), dtype=SEG_NUMERIC[f]
        )
        if a.shape != (n,):
            raise SegmentFormatError(
                f"column '{f}' is {a.shape}, expected ({n},)"
            )
        numeric.append((f, a))
    meta: Dict[str, object] = {"n": n, "seq0": int(seq0), "tenant": tenant}
    for obj_field, inv_field, uniq_key in OBJ_FIELDS:
        vocab, inv = _vocab_encode(chunk.get(obj_field), hints.get(obj_field))
        if inv.shape != (n,):
            raise SegmentFormatError(
                f"inverse for '{obj_field}' is {inv.shape}, expected ({n},)"
            )
        meta[uniq_key] = vocab
        numeric.append((inv_field, np.ascontiguousarray(inv)))
    # event ids: lazy (prefix, count) spans when the store never had to
    # materialize them; explicit list otherwise (the low-volume path)
    ids = chunk.get("event_id")
    if ids is None:
        segs = chunk.get("_idsegs")
        if segs is None:
            segs = [(chunk["_idp"], n)]
        meta["idsegs"] = [(str(p), int(k)) for p, k in segs]
    else:
        meta["ids"] = [str(x) for x in ids]
    meta["zone"] = _zone_map(meta["tok_uniq"], numeric[2][1], seq0, n)
    meta["segs"] = [(f, int(a.nbytes)) for f, a in numeric]
    import pickle as _pickle

    blob = _pickle.dumps(meta, protocol=_pickle.HIGHEST_PROTOCOL)
    parts = [SEG_MAGIC, bytes([SEG_VERSION]), _SEG_META.pack(len(blob)), blob]
    parts.extend(a.tobytes() for _f, a in numeric)
    return b"".join(parts)


class Segment:
    """One sealed, immutable segment: zone map + zero-copy column views.

    Backed either by the encoded bytes (memory mode — the bytes double as
    the checkpoint payload) or by an ``mmap`` of the segment file (dir
    mode / restore): every numeric column is a ``np.frombuffer`` view
    into the backing buffer, token columns come back as (vocab object
    array, int32 inverse view), and object materialization is a single
    C-level fancy-index fan-out callers pay only when they ask."""

    __slots__ = (
        "n", "seq0", "tenant", "zone", "nbytes", "name", "path",
        "_buf", "_mm", "_meta", "_cols", "_vocab_obj", "_ids",
        "_score_overlay", "ckpt_name",
    )

    def __init__(self, buf, meta: dict, cols: Dict[str, np.ndarray],
                 mm=None, path: Optional[Path] = None,
                 name: str = "") -> None:
        self._buf = buf
        self._mm = mm
        self._meta = meta
        self._cols = cols
        self.path = path
        self.name = name or (path.name if path is not None else "")
        self.n = int(meta["n"])
        self.seq0 = int(meta["seq0"])
        self.tenant = str(meta.get("tenant", "default"))
        self.zone = dict(meta["zone"])
        self.nbytes = len(buf)
        self._vocab_obj: Dict[str, np.ndarray] = {}
        self._ids: Optional[np.ndarray] = None
        self._score_overlay: Optional[np.ndarray] = None
        # name of the committed CHECKPOINT file holding exactly these
        # bytes (set by checkpoint save/load) — the incremental-reuse
        # identity: a maintain() merge/rewrite yields a NEW Segment with
        # ckpt_name None, so the changed bytes re-checkpoint even when
        # row counts line up
        self.ckpt_name: Optional[str] = None

    # -- construction ------------------------------------------------------
    @classmethod
    def from_bytes(cls, data, mm=None, path: Optional[Path] = None,
                   name: str = "") -> "Segment":
        """Decode + validate one segment buffer. Every malformed shape
        raises ``SegmentFormatError`` — a segment is either fully intact
        or rejected whole (the manifest commit point decides which sealed
        files are even attempted)."""
        sp = _safepickle()
        if len(data) < 4 or bytes(data[:3]) != SEG_MAGIC:
            raise SegmentFormatError("not a segment file (bad magic)")
        version = data[3]
        if version != SEG_VERSION:
            raise SegmentFormatError(f"unknown segment version {version}")
        if len(data) < 4 + _SEG_META.size:
            raise SegmentFormatError("torn segment: truncated meta header")
        (meta_len,) = _SEG_META.unpack_from(data, 4)
        col0 = 4 + _SEG_META.size + meta_len
        if col0 > len(data):
            raise SegmentFormatError("torn segment: meta overruns payload")
        try:
            meta = sp.loads(bytes(data[4 + _SEG_META.size: col0]))
        except Exception as exc:  # noqa: BLE001 - safepickle surfaces
            # corrupt bytes as UnpicklingError (NOT ValueError); any meta
            # decode fault must read as a torn/undecodable segment so the
            # recovery contract ("dropped, never half-read") holds
            raise SegmentFormatError(
                f"undecodable segment meta: {exc!r}"
            ) from None
        if not isinstance(meta, dict):
            raise SegmentFormatError("malformed segment meta")
        try:
            n = int(meta["n"])
            segs = list(meta["segs"])
            zone = dict(meta["zone"])
        except (KeyError, TypeError, ValueError) as exc:
            raise SegmentFormatError(f"malformed meta: {exc}") from None
        del zone
        total = 0
        for f, nbytes in segs:
            dt = SEG_NUMERIC.get(f)
            if dt is None:
                raise SegmentFormatError(f"unexpected raw column '{f}'")
            if int(nbytes) != n * dt.itemsize:
                raise SegmentFormatError(
                    f"torn segment: column '{f}' is {nbytes} bytes, "
                    f"expected {n * dt.itemsize}"
                )
            total += int(nbytes)
        if col0 + total != len(data):
            raise SegmentFormatError(
                f"torn segment: {len(data) - col0} column bytes, "
                f"expected {total}"
            )
        cols: Dict[str, np.ndarray] = {}
        off = col0
        for f, nbytes in segs:
            cols[f] = np.frombuffer(data, SEG_NUMERIC[f], count=n, offset=off)
            off += int(nbytes)
        # vocab range validation (hostile index must not read off the end)
        for _obj, inv_field, uniq_key in OBJ_FIELDS:
            inv = cols.get(inv_field)
            uniq = meta.get(uniq_key)
            if inv is None or not isinstance(uniq, list):
                raise SegmentFormatError(f"missing vocab for '{inv_field}'")
            if n and len(inv) and (inv.min() < 0 or inv.max() >= max(len(uniq), 1)):
                raise SegmentFormatError(
                    f"'{inv_field}' index out of vocab range"
                )
        ids = meta.get("ids")
        idsegs = meta.get("idsegs")
        if ids is not None:
            if not isinstance(ids, list) or len(ids) != n:
                raise SegmentFormatError("event-id list length mismatch")
        elif idsegs is not None:
            if sum(int(k) for _p, k in idsegs) != n:
                raise SegmentFormatError("event-id spans do not cover rows")
        elif n:
            raise SegmentFormatError("segment carries no event-id source")
        return cls(data, meta, cols, mm=mm, path=path, name=name)

    @classmethod
    def open(cls, path: str | Path) -> "Segment":
        """mmap a sealed segment file: columns become zero-copy views over
        the mapped region — opening a 1 GB store touches no row bytes."""
        path = Path(path)
        with open(path, "rb") as fh:
            mm = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
        return cls.from_bytes(mm, mm=mm, path=path, name=path.name)

    # -- column access -----------------------------------------------------
    @property
    def encoded(self) -> bytes:
        """The raw segment bytes (checkpoint payload). Memory segments
        return their backing buffer; mmap segments copy (cold path —
        incremental checkpoints never re-encode committed segments)."""
        return self._buf if isinstance(self._buf, bytes) else bytes(self._buf)

    def numeric(self, field: str) -> np.ndarray:
        if field == "score" and self._score_overlay is not None:
            return self._score_overlay
        return self._cols[field]

    @property
    def is_dirty(self) -> bool:
        """True when a score overlay shadows the wire bytes — compaction
        re-encodes dirty segments so the write-back becomes durable."""
        return self._score_overlay is not None

    def writable_scores(self) -> np.ndarray:
        """A mutable copy-on-write score column over the immutable
        segment buffer — the replay write-back target. Readers
        (``numeric``/``scan``/``to_chunk``/compaction) see the overlay;
        the raw wire bytes stay untouched, so ``encoded`` (the
        checkpoint payload) keeps its encode-once identity and the
        overlay becomes durable when compaction re-encodes the segment
        (see docs/STORAGE.md "Score write-back")."""
        if self._score_overlay is None:
            self._score_overlay = np.array(self._cols["score"])
        return self._score_overlay

    def vocab(self, obj_field: str) -> Tuple[np.ndarray, np.ndarray]:
        """(vocab object array, int32 inverse view) for one token column —
        the same shape the batch wire hands consumers, so replay batches
        inherit their group index without a string sort."""
        for of, inv_field, uniq_key in OBJ_FIELDS:
            if of == obj_field:
                u = self._vocab_obj.get(obj_field)
                if u is None:
                    u = self._vocab_obj[obj_field] = np.asarray(
                        self._meta[uniq_key], object
                    )
                return u, self._cols[inv_field]
        raise KeyError(obj_field)

    def obj_column(self, obj_field: str) -> np.ndarray:
        """Materialize one object column (vocab fan-out: one C-level
        fancy-index, no per-row Python)."""
        u, inv = self.vocab(obj_field)
        if len(u) == 0:
            return np.full((self.n,), "", object)
        return u[inv]

    def event_ids(self) -> np.ndarray:
        """Materialize (and cache) the per-row event ids."""
        if self._ids is None:
            ids = self._meta.get("ids")
            if ids is not None:
                self._ids = np.asarray(ids, object)
            else:
                parts = [
                    make_event_ids(p, k) for p, k in self._meta["idsegs"]
                ]
                self._ids = (
                    parts[0] if len(parts) == 1 else np.concatenate(parts)
                )
        return self._ids

    def id_entries(self) -> Tuple[Optional[list], Optional[list]]:
        """(explicit ids | None, idsegs | None) for the O(1) id index."""
        return self._meta.get("ids"), self._meta.get("idsegs")

    def to_chunk(self) -> Dict[str, np.ndarray]:
        """The legacy column-dict view (parquet export, sealed-cache
        concat): numeric views + object fan-outs + materialized ids."""
        out = {"event_id": self.event_ids()}
        for obj_field, _inv, _uk in OBJ_FIELDS:
            out[obj_field] = self.obj_column(obj_field)
        for f in ("value", "score", "event_ts", "received_ts"):
            out[f] = self.numeric(f)  # score reads through the overlay
        return out

    # -- zone pruning ------------------------------------------------------
    def matches(
        self,
        ts0: int = 0,
        ts1: int = 0,
        seq_lo: int = 0,
        seq_hi: Optional[int] = None,
        device: str = "",
    ) -> bool:
        """Zone-map test: can this segment contain a matching row?"""
        z = self.zone
        if self.n == 0:
            return False
        if ts0 and z["ts_max"] < ts0:
            return False
        if ts1 and z["ts_min"] > ts1:
            return False
        if seq_lo and z["seq_max"] < seq_lo:
            return False
        if seq_hi is not None and z["seq_min"] > seq_hi:
            return False
        if device:
            devs = z.get("devices")
            if devs is not None:
                return device in devs
            return bool(z["dev_bloom"] & (1 << (zlib.crc32(device.encode()) & 63)))
        return True

    def close(self) -> None:
        if self._mm is not None:
            # drop the views first? numpy views keep the mmap buffer
            # alive; the map closes when the last view dies. Explicit
            # close is only safe once callers dropped their views — the
            # store calls this on segments it is unlinking.
            try:
                self._mm.close()
            except (BufferError, ValueError):
                pass  # live views: the map dies with them
            self._mm = None


class ScanSlice:
    """One filtered row window of a planned segment: absolute row indices
    (``sel``), the dedupe-skip count inside the raw window, and
    ``seq_end`` — the last RAW seq the window covered, which is what a
    replay cursor commits (resume re-scans nothing before it, re-counts
    nothing after it). Per-row seqs are implicit: ``seg.seq0 + sel``."""

    __slots__ = ("seg", "sel", "skipped", "seq_end")

    def __init__(self, seg: Segment, sel: np.ndarray,
                 skipped: int, seq_end: int) -> None:
        self.seg = seg
        self.sel = sel
        self.skipped = skipped
        self.seq_end = seq_end

    @property
    def n(self) -> int:
        return int(len(self.sel))


def slice_columns(sl: ScanSlice) -> Dict[str, object]:
    """Materialize one scan slice's columns for batch building: numeric
    picks (one fancy-index per column), token columns as (vocab, picked
    inverse) — consumers inherit the group index, never a string sort —
    and the slice's event ids. No per-row Python anywhere."""
    seg, sel = sl.seg, sl.sel
    tok_u, tok_inv = seg.vocab("device_token")
    name_u, name_inv = seg.vocab("name")
    asg_u, asg_inv = seg.vocab("assignment_token")
    area_u, area_inv = seg.vocab("area_token")
    ids = seg.event_ids()
    return {
        "values": seg.numeric("value")[sel],
        "scores": seg.numeric("score")[sel],
        "event_ts": seg.numeric("event_ts")[sel],
        "received_ts": seg.numeric("received_ts")[sel],
        "tok": (tok_u, tok_inv[sel]),
        "name": (name_u, name_inv[sel]),
        "asg": (asg_u, asg_inv[sel]) if len(asg_u) else None,
        "area": (area_u, area_inv[sel]) if len(area_u) else None,
        "event_ids": ids[sel],
    }


class SegmentColumns:
    """Append-only columnar measurement store over sealed segments.

    The drop-in successor to the event store's chunk store: same append
    surface (per-event ``append``, columnar ``append_batch`` parking the
    batch's arrays as one pending chunk — O(1) per batch), same two-level
    read cache (``columns``), but seals produce :class:`Segment` objects
    — zone-mapped, wire-encoded once, durable at seal time when the store
    has a ``directory`` — and reads/replay go through ``plan``/``scan``
    instead of full materialization.
    """

    CHUNK = 65536  # default rows per sealed segment

    def __init__(
        self,
        tenant: str = "default",
        directory: Optional[str | Path] = None,
        rows_per_segment: int = CHUNK,
        retention_ms: float = 0.0,
        lineage: Optional[str] = None,
    ) -> None:
        self.tenant = tenant
        self.rows_per_segment = int(rows_per_segment)
        self.retention_ms = float(retention_ms)
        # lineage id: identifies THIS store's data history across
        # checkpoint/restore cycles — a data dir written by a different
        # lineage must never be incrementally extended
        self.lineage = lineage or uuid.uuid4().hex
        self.directory = Path(directory) if directory is not None else None
        self.segments: List[Segment] = []
        self._cur: Dict[str, list] = self._fresh()
        self._pending: List[Dict[str, object]] = []
        self._pending_rows = 0
        self._materialized: Optional[Dict[str, np.ndarray]] = None
        self._sealed_cache: Optional[Dict[str, np.ndarray]] = None
        self._next_seq = 0
        self._gen = 0
        # O(1) event-id index (activated on first find_row, maintained at
        # seal time): explicit ids → (seg_idx, row); lazy prefixes →
        # (seg_idx, base_row, count). Explicit-id segments queue in
        # _stale_index at seal and build on the next LOOKUP — the per-row
        # dict build must never run on the ingest seal path.
        self._id_map: Optional[Dict[str, Tuple[int, int]]] = None
        self._prefix_map: Optional[Dict[str, Tuple[int, int, int]]] = None
        self._stale_index: List[int] = []
        # maintenance accounting (surfaced via describe / REST)
        self.compactions = 0
        self.compacted_segments = 0
        self.dropped_segments = 0
        self.dropped_rows = 0
        self.torn_dropped = 0
        if self.directory is not None:
            self.directory.mkdir(parents=True, exist_ok=True)
            self._recover()

    # -- append (the persistence hot path) --------------------------------
    @staticmethod
    def _fresh() -> Dict[str, list]:
        return {
            "event_id": [], "device_token": [], "assignment_token": [],
            "area_token": [], "name": [], "value": [], "score": [],
            "event_ts": [], "received_ts": [],
        }

    def append(self, e) -> None:
        c = self._cur
        c["event_id"].append(e.id)
        c["device_token"].append(e.device_token)
        c["assignment_token"].append(e.assignment_token)
        c["area_token"].append(e.area_token)
        c["name"].append(e.name)
        c["value"].append(e.value)
        c["score"].append(e.score if e.score is not None else np.nan)
        c["event_ts"].append(e.event_ts)
        c["received_ts"].append(e.received_ts)
        self._next_seq += 1
        self._materialized = None  # invalidate read cache (tail changed)
        if len(c["value"]) >= self.rows_per_segment:
            self._seal()

    def append_batch(self, b) -> None:
        """Columnar bulk append from a MeasurementBatch: the batch's
        arrays are parked as one pending chunk — O(1) per batch, no
        per-row work on the ingest hot path. The batch's cached group
        indexes (free from the wire codec) ride along as vocab hints so
        the seal never pays an object-string sort for them."""
        n = b.n
        if n == 0:
            return

        def col(a):
            return a if a is not None else np.full((n,), "", object)

        hints: Dict[str, tuple] = {}
        if b.tok_index is not None and b.device_tokens is not None:
            u, inv = b.tok_index
            hints["device_token"] = (u.tolist(), inv)
        if b.name_index is not None and b.names is not None:
            u, inv = b.name_index
            hints["name"] = (u.tolist(), inv)
        self._pending.append(
            {
                # ids stay LAZY (None + the BATCH's pinned prefix) until a
                # seal or read forces them — sharing the batch's prefix
                # keeps the persisted ids identical to any later edge
                # materialization of the same batch (to_events, WS feed)
                "event_id": b.event_ids,
                "_idp": None if b.event_ids is not None else _pin_prefix(b),
                "_vocabs": hints,
                "device_token": col(b.device_tokens),
                "assignment_token": col(b.assignment_tokens),
                "area_token": col(b.area_tokens),
                "name": col(b.names),
                "value": b.values,
                "score": (
                    b.scores
                    if b.scores is not None
                    else np.full((n,), np.nan, np.float32)
                ),
                "event_ts": b.event_ts.astype(np.int64),
                "received_ts": b.received_ts.astype(np.int64),
            }
        )
        self._pending_rows += n
        self._next_seq += n
        self._materialized = None
        if self._pending_rows + len(self._cur["value"]) >= self.rows_per_segment:
            self._seal()

    # -- sealing -----------------------------------------------------------
    OBJ = ("event_id", "device_token", "assignment_token", "area_token", "name")
    DTYPES = {"value": np.float32, "score": np.float32,
              "event_ts": np.int64, "received_ts": np.int64}

    def _cur_arrays(self) -> Dict[str, np.ndarray]:
        """Live per-row tail → typed arrays (the one _cur→array mapping)."""
        return {
            k: np.asarray(v, object if k in self.OBJ else self.DTYPES[k])
            for k, v in self._cur.items()
        }

    @staticmethod
    def _ensure_ids(chunk: Dict[str, object]) -> Dict[str, object]:
        """Materialize a chunk's lazy event ids in place (idempotent)."""
        if chunk.get("event_id") is not None:
            chunk.pop("_idp", None)
            chunk.pop("_idsegs", None)
            return chunk
        segs = chunk.pop("_idsegs", None)
        if segs is None:
            segs = [(chunk.pop("_idp"), len(chunk["value"]))]
        else:
            chunk.pop("_idp", None)
        parts = [make_event_ids(p, k) for p, k in segs]
        chunk["event_id"] = (
            parts[0] if len(parts) == 1 else np.concatenate(parts)
        )
        return chunk

    @staticmethod
    def _merge_vocab_hints(parts: List[Dict[str, object]], field: str):
        """Merge per-chunk (vocab, inverse) hints into one chunk-spanning
        hint — dict merges over vocabs (O(unique)) + one int32 remap per
        part, never a string sort over rows. None when any part lacks the
        hint (the seal then falls back to np.unique)."""
        hints = []
        for p in parts:
            h = (p.get("_vocabs") or {}).get(field)
            if h is None:
                return None
            hints.append(h)
        vocab_map: Dict[str, int] = {}
        remapped = []
        for vocab, inv in hints:
            codes = np.asarray(
                [vocab_map.setdefault(t, len(vocab_map)) for t in vocab],
                np.int32,
            )
            remapped.append(codes[np.asarray(inv, np.int32)])
        merged_inv = (
            remapped[0] if len(remapped) == 1 else np.concatenate(remapped)
        )
        return list(vocab_map), merged_inv

    @spanned("seal")
    def _seal(self) -> None:
        """Seal the tail (pending chunks + live rows) into one Segment:
        encode the wire layout once, compute the zone map, write + fsync
        the file and commit the manifest when disk-backed."""
        if not self._cur["value"] and not self._pending:
            return
        parts: List[Dict[str, object]] = list(self._pending)
        if self._cur["value"]:
            parts.append(self._cur_arrays())
        n = sum(len(p["value"]) for p in parts)
        seq0 = self._next_seq - n
        # all-lazy parts seal LAZY: the (prefix, count) spans go into the
        # segment meta instead of paying id generation on the ingest path
        lazy = all(p.get("event_id") is None for p in parts)
        if len(parts) == 1:
            chunk = dict(parts[0])
            hints = dict(chunk.pop("_vocabs", None) or {})
        else:
            if lazy:
                idsegs: List[tuple] = []
                for p in parts:
                    idsegs.extend(
                        p.get("_idsegs") or [(p["_idp"], len(p["value"]))]
                    )
            else:
                parts = [self._ensure_ids(p) for p in parts]
            chunk = {
                k: np.concatenate([np.asarray(p[k]) for p in parts])
                for k in ("device_token", "assignment_token", "area_token",
                          "name", "value", "score", "event_ts",
                          "received_ts")
            }
            hints = {}
            for field in ("device_token", "name"):
                merged = self._merge_vocab_hints(parts, field)
                if merged is not None:
                    hints[field] = merged
            if lazy:
                chunk["event_id"] = None
                chunk["_idsegs"] = idsegs
            else:
                chunk["event_id"] = np.concatenate(
                    [p["event_id"] for p in parts]
                )
        data = encode_segment(chunk, seq0, self.tenant, vocab_hints=hints)
        seg = Segment.from_bytes(data)
        if self.directory is not None:
            seg = self._write_segment(seg)
        self.segments.append(seg)
        self._note_segment(len(self.segments) - 1)
        self._pending = []
        self._pending_rows = 0
        self._cur = self._fresh()
        self._sealed_cache = None
        self._materialized = None
        if self.directory is not None:
            self._commit_manifest()

    def add_segment(self, seg: Segment) -> None:
        """Adopt a decoded segment (restore path): zero per-row work."""
        self.segments.append(seg)
        self._note_segment(len(self.segments) - 1)
        self._next_seq = max(self._next_seq, seg.seq0 + seg.n)
        self._sealed_cache = None
        self._materialized = None

    def add_sealed_chunk(self, chunk: Dict[str, np.ndarray]) -> None:
        """Adopt a pre-built legacy column chunk (parquet import path):
        encoded into a segment once, then immutable."""
        n = len(chunk["value"])
        if n == 0:
            return
        data = encode_segment(dict(chunk), self._next_seq, self.tenant)
        self._next_seq += n
        self.add_segment(Segment.from_bytes(data))

    def encode_tail(self) -> bytes:
        """The unsealed tail (pending + live rows) as segment bytes — the
        checkpoint's generational-tail payload. The tail is NOT sealed by
        this (the live store keeps appending to it)."""
        parts: List[Dict[str, object]] = [dict(p) for p in self._pending]
        if self._cur["value"]:
            parts.append(self._cur_arrays())
        n = sum(len(p["value"]) for p in parts)
        seq0 = self._next_seq - n
        if not parts:
            empty: Dict[str, object] = {
                k: np.zeros((0,), dt) for k, dt in self.DTYPES.items()
            }
            empty.update({k: np.zeros((0,), object) for k in self.OBJ})
            return encode_segment(empty, seq0, self.tenant)
        if len(parts) == 1:
            chunk = dict(parts[0])
            hints = dict(chunk.pop("_vocabs", None) or {})
            return encode_segment(chunk, seq0, self.tenant, vocab_hints=hints)
        parts = [self._ensure_ids(dict(p)) for p in parts]
        chunk = {
            k: np.concatenate([np.asarray(p[k]) for p in parts])
            for k in ("event_id", "device_token", "assignment_token",
                      "area_token", "name", "value", "score", "event_ts",
                      "received_ts")
        }
        return encode_segment(chunk, seq0, self.tenant)

    # -- durability (dir mode) ---------------------------------------------
    def _seg_filename(self, seq0: int) -> str:
        return f"seg-{seq0:012d}-g{self._gen:06d}{SEG_SUFFIX}"

    def _write_segment(self, seg: Segment) -> Segment:
        """Write + fsync one sealed segment, then reopen it mmap'd so the
        resident copy is the page cache, not a second heap buffer."""
        self._gen += 1
        path = self.directory / self._seg_filename(seg.seq0)
        with open(path, "wb") as fh:
            fh.write(seg.encoded)
            fh.flush()
            os.fsync(fh.fileno())
        return Segment.open(path)

    def _manifest_path(self) -> Path:
        return self.directory / "manifest.json"

    def _commit_manifest(self) -> None:
        """Atomic-replace the manifest — THE commit point. ``next_seq``
        is recorded so a torn-tail drop never reuses the dropped rows'
        seqs (replay cursors stay unambiguous across the repair)."""
        doc = {
            "version": 1,
            "lineage": self.lineage,
            "gen": self._gen,
            "next_seq": self._next_seq,
            "segments": [
                {"name": s.name, "n": s.n, "seq0": s.seq0,
                 "nbytes": s.nbytes, "zone": s.zone}
                for s in self.segments
            ],
        }
        path = self._manifest_path()
        tmp = path.with_suffix(".tmp")
        with open(tmp, "w") as fh:
            fh.write(json.dumps(doc))
            fh.flush()
            os.fsync(fh.fileno())
        tmp.replace(path)

    def _recover(self) -> None:
        """Open a store directory trusting ONLY the manifest: committed
        segments whose file is missing/short/undecodable are a torn tail
        — dropped (with everything after them), never half-read. Stray
        files the manifest does not name (a crash between file write and
        commit) are deleted."""
        path = self._manifest_path()
        doc: dict = {}
        if path.exists():
            try:
                doc = json.loads(path.read_text())
            except ValueError:
                doc = {}
        entries = list(doc.get("segments", []))
        self.lineage = doc.get("lineage", self.lineage)
        self._gen = int(doc.get("gen", 0))
        kept: List[Segment] = []
        dropped = 0
        for i, entry in enumerate(entries):
            p = self.directory / str(entry["name"])
            seg = None
            if p.exists() and p.stat().st_size == int(entry["nbytes"]):
                try:
                    seg = Segment.open(p)
                    if seg.n != int(entry["n"]):
                        seg = None
                except (SegmentFormatError, OSError, ValueError):
                    seg = None
            if seg is None:
                # torn tail: this and every later committed entry drop
                dropped = len(entries) - i
                break
            kept.append(seg)
        self.segments = kept
        self.torn_dropped += dropped
        # seqs of dropped rows are NEVER reused: next_seq keeps the
        # manifest's (pre-crash) value, falling back to the kept tail
        self._next_seq = int(doc.get(
            "next_seq",
            kept[-1].seq0 + kept[-1].n if kept else 0,
        ))
        for i in range(len(kept)):
            self._note_segment(i)
        named = {s.name for s in kept}
        for stray in self.directory.glob(f"seg-*{SEG_SUFFIX}"):
            if stray.name not in named:
                stray.unlink(missing_ok=True)
        if dropped:
            self._commit_manifest()  # commit the repair

    # -- retention + compaction --------------------------------------------
    def maintain(
        self,
        now_ms: Optional[float] = None,
        max_units: Optional[int] = None,
    ) -> Dict[str, int]:
        """One maintenance pass: drop segments wholly past the retention
        horizon, merge runs of adjacent small-or-dirty segments
        (generational checkpoint tails, low-rate stores, score
        write-backs) into sealed ones, and re-encode lone dirty segments
        so their overlays become durable. O(segments) when there is
        nothing to do — cheap enough for the instance's background tick;
        never called from the seal path (a hot tenant's ingest must not
        pay re-encodes). ``max_units`` caps RE-ENCODE units per pass
        (each unit is one merge/rewrite bounded at 2x the row budget) —
        the instance tick runs inline on the event loop, and a
        fully-rescored 1M-row store must not re-encode every segment in
        one synchronous pass; the remainder completes on later ticks.
        Retention drops are cheap and never capped."""
        actions = {"dropped": 0, "merged": 0, "rewritten": 0}
        changed = False
        # files to delete AFTER the new manifest commits: unlinking a
        # committed file first would, on a crash inside this pass, make
        # recovery read the OLD manifest, treat the missing file as a
        # torn tail, and drop every committed segment after it
        doomed: List[Path] = []
        if self.retention_ms > 0 and self.segments:
            horizon = (
                now_ms if now_ms is not None else time.time() * 1000.0
            ) - self.retention_ms
            keep: List[Segment] = []
            victims: List[Segment] = []
            for s in self.segments:
                if s.zone["ts_max"] < horizon:
                    victims.append(s)
                else:
                    keep.append(s)
            if victims:
                self.segments = keep
                for s in victims:
                    self.dropped_rows += s.n
                    if s.path is not None:
                        s.close()
                        # only a dir-mode store owns its files; a restored
                        # memory store's segments are mmap'd CHECKPOINT
                        # files (checkpoint.py names them in its seg meta)
                        # — deleting those outside the checkpoint commit
                        # protocol would lose committed rows on the next
                        # restore
                        if self.directory is not None:
                            doomed.append(s.path)
                self.dropped_segments += len(victims)
                actions["dropped"] = len(victims)
                changed = True
        small = max(1, self.rows_per_segment // 2)
        # merged output may exceed the seal budget (generational merge)
        # but never unboundedly: 2x caps the re-encode unit
        cap = 2 * self.rows_per_segment

        def _candidate(s: Segment) -> bool:
            return s.n < small or s.is_dirty

        units = 0
        i = 0
        while i < len(self.segments):
            if max_units is not None and units >= max_units:
                break  # re-encode budget spent; later ticks finish
            run = [self.segments[i]]
            j = i + 1
            while (
                j < len(self.segments)
                and _candidate(self.segments[j])
                and _candidate(run[-1])
                and self.segments[j].seq0 == run[-1].seq0 + run[-1].n
                and sum(s.n for s in run) + self.segments[j].n <= cap
            ):
                run.append(self.segments[j])
                j += 1
            if len(run) >= 2:
                merged = self._merge_run(run, doomed)
                self.segments[i:j] = [merged]
                self.compactions += 1
                self.compacted_segments += len(run)
                actions["merged"] += len(run)
                changed = True
                units += 1
            elif run[0].is_dirty:
                # no mergeable neighbor: re-encode in place so the score
                # overlay survives a restart (write-back durability)
                self.segments[i] = self._merge_run(run, doomed)
                actions["rewritten"] += 1
                changed = True
                units += 1
            i += 1
        if changed:
            self._sealed_cache = None
            self._materialized = None
            self._id_map = None
            self._prefix_map = None
            self._stale_index = []  # positions shifted; activation rebuilds
            if self.directory is not None:
                self._commit_manifest()  # ── commit, THEN delete ──
        for p in doomed:
            p.unlink(missing_ok=True)
        return actions

    def _merge_run(self, run: List[Segment],
                   doomed: List[Path]) -> Segment:
        """Merge adjacent segments into one (vocab dicts merge + one int32
        remap per part — the ``_merge_vocab_hints`` discipline; ids stay
        lazy when every part is lazy). Replaced files are queued on
        ``doomed`` for the caller to delete AFTER the manifest commit."""
        chunk: Dict[str, object] = {}
        for f in ("value", "score", "event_ts", "received_ts"):
            chunk[f] = np.concatenate([s.numeric(f) for s in run])
        hints: Dict[str, tuple] = {}
        for obj_field, _inv, uniq_key in OBJ_FIELDS:
            parts = [
                {"_vocabs": {obj_field: (s._meta[uniq_key],
                                         s._cols[_inv])}}
                for s in run
            ]
            merged = self._merge_vocab_hints(parts, obj_field)
            hints[obj_field] = merged
            chunk[obj_field] = None  # vocab hint carries the column
        idsegs: List[tuple] = []
        lazy = True
        for s in run:
            ids, spans = s.id_entries()
            if ids is not None:
                lazy = False
                break
            idsegs.extend(spans)
        if lazy:
            chunk["event_id"] = None
            chunk["_idsegs"] = idsegs
        else:
            chunk["event_id"] = np.concatenate([s.event_ids() for s in run])
        data = encode_segment(
            chunk, run[0].seq0, self.tenant, vocab_hints=hints
        )
        merged = Segment.from_bytes(data)
        if self.directory is not None:
            merged = self._write_segment(merged)
            for s in run:
                if s.path is not None:
                    s.close()
                    # deleted by maintain() only after the new manifest
                    # commits — until then the OLD manifest + files remain
                    # a complete recoverable set (a crash here leaves the
                    # merged file as a stray that recovery removes).
                    # Memory-mode stores never unlink: their mmap'd
                    # segments are checkpoint-owned files (see maintain()).
                    doomed.append(s.path)
        return merged

    # -- O(1) event-id index (maintained at seal time) ---------------------
    def _note_segment(self, seg_idx: int) -> None:
        """Seal/adopt-time index upkeep. Lazy-id segments index their
        (prefix, count) spans immediately — O(spans). Explicit-id
        segments would need a per-row Python dict build, so they queue
        for the next lookup (DLQ inspection, replay write-back — both
        off the ingest path) instead of stalling the seal."""
        if self._id_map is None:
            return  # index not activated yet (first find_row builds it)
        ids, _spans = self.segments[seg_idx].id_entries()
        if ids is None:
            self._index_segment(seg_idx)
        else:
            self._stale_index.append(seg_idx)

    def _drain_stale_index(self) -> None:
        if self._stale_index:
            for idx in self._stale_index:
                self._index_segment(idx)
            self._stale_index = []

    def _index_segment(self, seg_idx: int) -> None:
        if self._id_map is None:
            return  # index not activated yet (first find_row builds it)
        seg = self.segments[seg_idx]
        ids, idsegs = seg.id_entries()
        if ids is not None:
            for row, ev_id in enumerate(ids):
                self._id_map[ev_id] = (seg_idx, row)
        elif idsegs:
            base = 0
            for prefix, k in idsegs:
                self._prefix_map[prefix] = (seg_idx, base, int(k))
                base += int(k)

    def _activate_id_index(self) -> None:
        self._id_map = {}
        self._prefix_map = {}
        self._stale_index = []
        for i in range(len(self.segments)):
            self._index_segment(i)

    @staticmethod
    def _resolve_lazy(ev_id: str, pmap) -> Optional[Tuple[int, int]]:
        """Resolve a lazy ``'{hex16}-{row}'`` id against a prefix-span
        map ``{prefix: (slot, base, count)}`` → (slot, base+row) or
        None. The 17-char prefix contract is ``core.batch``'s
        ``make_event_ids`` format — THE one parser for it."""
        if len(ev_id) <= 17:
            return None
        span = pmap.get(ev_id[:17])
        if span is None:
            return None
        slot, base, count = span
        rest = ev_id[17:]
        if not rest.isdigit() or int(rest) >= count:
            return None
        return slot, base + int(rest)

    def find_row(self, event_id: str) -> Optional[Dict[str, object]]:
        """O(1) sealed lookup (id index) + bounded tail scan: the row's
        scalar fields, or None. The index activates lazily on first use
        and is maintained at seal time from then on — DLQ requeue
        inspection stays O(1) as the store grows."""
        if self._id_map is None:
            self._activate_id_index()
        self._drain_stale_index()
        hit = self._id_map.get(event_id)
        if hit is None:
            hit = self._resolve_lazy(event_id, self._prefix_map)
        if hit is not None:
            seg_idx, row = hit
            seg = self.segments[seg_idx]
            out = {
                f: seg.numeric(f)[row]
                for f in ("value", "score", "event_ts", "received_ts")
            }
            for obj_field, _inv, _uk in OBJ_FIELDS:
                u, inv = seg.vocab(obj_field)
                out[obj_field] = str(u[inv[row]]) if len(u) else ""
            out["event_id"] = event_id
            return out
        # live tail: bounded by rows_per_segment, so the scan stays O(1)
        # in store size
        tail = self._tail_arrays()
        idx = np.nonzero(tail["event_id"] == event_id)[0]
        if idx.size == 0:
            return None
        i = int(idx[0])
        return {k: tail[k][i] for k in tail}

    def write_back_scores(self, event_ids, scores) -> int:
        """Record freshly computed scores against store rows (the
        persistence stage calls this for replayed-rescore batches, so a
        LATER rescore job's ``only_unscored`` dedupe skips them — no
        re-publish of already-rescored history within a store lifetime).

        Sealed rows land in copy-on-write overlays per segment: the
        immutable wire bytes stay untouched; ``maintain`` re-encodes
        overlays durably. Rows still in the unsealed tail write into the
        pending chunks / live rows directly (the replay plan includes
        the tail, so its rescored rows must teach the dedupe too) and
        become durable at seal. Foreign ids are skipped. Not a hot path:
        replay is the low-priority lane, and the per-id lookups are O(1)
        each (tail resolution is bounded by ``rows_per_segment``)."""
        if self._id_map is None:
            self._activate_id_index()
        self._drain_stale_index()
        sc = np.asarray(scores, np.float32)
        written = 0
        misses: List[int] = []
        # resolve first, then ONE vectorized scatter per segment — not a
        # numpy scalar store per row (this runs in the persistence stage
        # for every replayed batch)
        per_seg: Dict[int, Tuple[List[int], List[int]]] = {}
        for i, ev_id in enumerate(event_ids):
            hit = self._id_map.get(ev_id)
            if hit is None:
                hit = self._resolve_lazy(ev_id, self._prefix_map)
            if hit is None:
                misses.append(i)
                continue
            rows, idxs = per_seg.setdefault(hit[0], ([], []))
            rows.append(hit[1])
            idxs.append(i)
        for seg_idx, (rows, idxs) in per_seg.items():
            self.segments[seg_idx].writable_scores()[
                np.asarray(rows, np.intp)
            ] = sc[np.asarray(idxs, np.intp)]
            written += len(rows)
        if misses and (self._pending or self._cur["value"]):
            written += self._write_back_tail(event_ids, sc, misses)
        if per_seg and self._sealed_cache is not None:
            # only the score column changed: rebuild it alone — dropping
            # the whole sealed cache would make every REST query during a
            # replay re-pay the object fan-outs + id materialization for
            # the full store
            self._sealed_cache["score"] = np.concatenate(
                [s.numeric("score") for s in self.segments]
            )
        if written:
            self._materialized = None
        return written

    def _write_back_tail(self, event_ids, sc: np.ndarray,
                         misses: List[int]) -> int:
        """Resolve id-index misses against the unsealed tail and write
        scores into the pending chunks / live rows (copy-on-write per
        chunk: a chunk's score array may still be the producer batch's
        own buffer)."""
        explicit: Dict[str, Tuple[int, int]] = {}
        prefixes: Dict[str, Tuple[int, int, int]] = {}
        for ci, p in enumerate(self._pending):
            ids = p.get("event_id")
            if ids is not None:
                for r, ev in enumerate(ids):
                    explicit[ev] = (ci, r)
            elif p.get("_idsegs") is not None:
                base = 0
                for prefix, k in p["_idsegs"]:
                    prefixes[prefix] = (ci, base, int(k))
                    base += int(k)
            else:
                prefixes[p["_idp"]] = (ci, 0, len(p["value"]))
        cur_pos = {
            ev: r for r, ev in enumerate(self._cur["event_id"])
        }
        per_chunk: Dict[int, Tuple[List[int], List[int]]] = {}
        written = 0
        for i in misses:
            ev_id = event_ids[i]
            hit = explicit.get(ev_id)
            if hit is None:
                hit = self._resolve_lazy(ev_id, prefixes)
            if hit is not None:
                rows, idxs = per_chunk.setdefault(hit[0], ([], []))
                rows.append(hit[1])
                idxs.append(i)
                written += 1
                continue
            r = cur_pos.get(ev_id)
            if r is not None:
                self._cur["score"][r] = float(sc[i])
                written += 1
        for ci, (rows, idxs) in per_chunk.items():
            p = self._pending[ci]
            # copy-on-write: the chunk may still hold the producer
            # batch's own score buffer
            p["score"] = np.array(p["score"], np.float32)
            p["score"][np.asarray(rows, np.intp)] = sc[
                np.asarray(idxs, np.intp)
            ]
        return written

    # -- reads -------------------------------------------------------------
    def _tail_arrays(self) -> Dict[str, np.ndarray]:
        cur = self._cur_arrays()
        if not self._pending:
            return cur
        # ids materialize on COPIES (like encode_tail): a REST read
        # racing ingest must not de-lazy the pending chunks in place, or
        # the next seal pays the per-row str() loop and ships the full
        # id list instead of (prefix, count) spans
        parts = [self._ensure_ids(dict(p)) for p in self._pending] + (
            [cur] if len(cur["value"]) else []
        )
        if len(parts) == 1:
            return {k: v for k, v in parts[0].items() if not k.startswith("_")}
        return {
            k: np.concatenate([np.asarray(p[k]) for p in parts])
            for k in cur
        }

    def columns(self) -> Dict[str, np.ndarray]:
        """Materialize all rows as one struct-of-arrays dict. Two-level
        cache: sealed segments concat once per seal (not per append), the
        live tail concats on top per read — a REST query racing live
        ingest pays O(tail), not O(total rows)."""
        if self._materialized is not None:
            return self._materialized
        if self._sealed_cache is None and self.segments:
            chunks = [s.to_chunk() for s in self.segments]
            self._sealed_cache = {
                k: np.concatenate([ch[k] for ch in chunks])
                for k in chunks[0]
            }
        tail = self._tail_arrays()
        if self._sealed_cache is None:
            out = tail
        elif len(tail["value"]) == 0:
            out = self._sealed_cache
        else:
            out = {
                k: np.concatenate([self._sealed_cache[k], tail[k]])
                for k in tail
            }
        self._materialized = out
        return out

    def sealed_chunks(self) -> List[Dict[str, np.ndarray]]:
        """Legacy chunk-dict views of the sealed segments (parquet export
        compatibility; checkpoints ride the segment bytes directly)."""
        return [s.to_chunk() for s in self.segments]

    def __len__(self) -> int:
        return (
            sum(s.n for s in self.segments)
            + self._pending_rows
            + len(self._cur["value"])
        )

    @property
    def next_seq(self) -> int:
        return self._next_seq

    # -- zone-planned scans (the replay feed) ------------------------------
    def tail_segment(self) -> Optional[Segment]:
        """The unsealed tail as an in-memory pseudo-segment (scan
        snapshot; rows appended after the call are not seen)."""
        n_tail = self._pending_rows + len(self._cur["value"])
        if n_tail == 0:
            return None
        return Segment.from_bytes(self.encode_tail(), name="<tail>")

    def plan(
        self,
        ts0: int = 0,
        ts1: int = 0,
        seq_lo: int = 0,
        seq_hi: Optional[int] = None,
        device: str = "",
        include_tail: bool = True,
    ) -> Tuple[List[Segment], int]:
        """Zone-map segment planning: (segments that may hold matching
        rows, count pruned without touching a row)."""
        segs = list(self.segments)
        if include_tail:
            tail = self.tail_segment()
            if tail is not None:
                segs.append(tail)
        selected = []
        pruned = 0
        for s in segs:
            if s.matches(ts0, ts1, seq_lo, seq_hi, device):
                selected.append(s)
            else:
                pruned += 1
        return selected, pruned

    def scan(
        self,
        ts0: int = 0,
        ts1: int = 0,
        seq_lo: int = 0,
        seq_hi: Optional[int] = None,
        device: str = "",
        only_unscored: bool = False,
        batch_rows: int = 8192,
        include_tail: bool = True,
        segments: Optional[List[Segment]] = None,
    ) -> Iterator[ScanSlice]:
        """Stream filtered row windows off the planned segments.

        Rows move as vectorized index picks over the zero-copy column
        views — no per-event objects, no list accumulators (registered in
        tools/check_hotpath.py). Windows chunk the RAW seq range, so a
        consumer that commits ``slice.seq_end`` after each window resumes
        exactly (``only_unscored`` dedupe skips are counted per window —
        replayed ∪ skipped accounting stays exact across a crash)."""
        if segments is None:
            segments, _ = self.plan(
                ts0, ts1, seq_lo, seq_hi, device, include_tail
            )
        for seg in segments:
            lo = max(0, int(seq_lo) - seg.seq0) if seq_lo else 0
            hi = seg.n
            if seq_hi is not None:
                hi = min(hi, int(seq_hi) - seg.seq0 + 1)
            ets = seg.numeric("event_ts")
            score = seg.numeric("score")
            tok_u, tok_inv = seg.vocab("device_token")
            dev_code = -1
            if device:
                match = np.nonzero(tok_u == device)[0]
                if match.size == 0:
                    continue  # bloom false positive: no rows here
                dev_code = int(match[0])
            off = lo
            while off < hi:
                end = min(off + int(batch_rows), hi)
                mask = np.ones((end - off,), bool)
                win_ts = ets[off:end]
                if ts0:
                    mask &= win_ts >= ts0
                if ts1:
                    mask &= win_ts <= ts1
                if dev_code >= 0:
                    mask &= tok_inv[off:end] == dev_code
                skipped = 0
                if only_unscored:
                    scored = ~np.isnan(score[off:end]) & mask
                    skipped = int(scored.sum())
                    mask &= ~scored
                sel = np.nonzero(mask)[0] + off
                yield ScanSlice(
                    seg, sel, skipped, seg.seq0 + end - 1,
                )
                off = end

    # -- introspection -----------------------------------------------------
    def describe(self) -> dict:
        return {
            "tenant": self.tenant,
            "segments": len(self.segments),
            "rows": len(self),
            "sealed_rows": sum(s.n for s in self.segments),
            "tail_rows": self._pending_rows + len(self._cur["value"]),
            "next_seq": self._next_seq,
            "disk_bytes": sum(
                s.nbytes for s in self.segments if s.path is not None
            ),
            "rows_per_segment": self.rows_per_segment,
            "retention_ms": self.retention_ms,
            "compactions": self.compactions,
            "compacted_segments": self.compacted_segments,
            "dropped_segments": self.dropped_segments,
            "dropped_rows": self.dropped_rows,
            "torn_dropped": self.torn_dropped,
            "directory": str(self.directory) if self.directory else None,
            "zone_maps": [
                {"name": s.name, "n": s.n, **{
                    k: s.zone[k] for k in
                    ("ts_min", "ts_max", "seq_min", "seq_max", "n_devices")
                }}
                for s in self.segments
            ],
        }
