"""Streaming-media classification pipeline: camera chunks → frame decode
→ micro-batched ViT classification → classification events on the bus.

Closes the north-star media loop (BASELINE.json:11; SURVEY.md §2.2
streaming-media [U]; reference mount empty, see provenance banner): the
reference's service only STORES stream chunks — the rebuild adds the TPU
leg, reusing the micro-batching playbook from ``pipeline.inference``
(bucketed static shapes, collect deadline, pipelined materialization off
the event loop).

Compressed media wire (docs/PERFORMANCE.md "Media wire & on-chip
decode"): by default, COMPRESSED bytes — not raw pixels — are the unit
that crosses every boundary from camera receiver to chip. Camera chunks
land in a preallocated variable-length byte arena (``_ByteRing``) at
submit time with zero host-side pixel materialization; at classify time
the SERIAL half of the decode (JPEG Huffman + dequant,
``native/jpegwire.py``) fans out over an executor thread pool into
int16 DCT coefficient buffers, and the embarrassingly parallel half
(dezigzag, IDCT, chroma upsample, YCbCr→RGB, normalize, patchify) runs
ON DEVICE fused into the ViT jit (``models.vit.apply_dct``). The h2d
payload is zigzag-truncated coefficients — typically 2-10× smaller than
raw RGB, and the ring holds 10-20×-smaller JPEG bytes, so ring capacity
bounds resident BYTES, not frame count. ``MEDIA_WIRE_COMPRESSED_ENABLED``
(captured at pipeline build, the FUSED_STEP_ENABLED pattern) restores
the raw-RGB path bitwise; a missing native build or any unsupported
stream degrades per batch to the PIL path — counted
(``media_native_decode_fallback_total``), never an error.

Zero-copy feed path (docs/PERFORMANCE.md): frames leave the ring as
contiguous span copies into pooled staging buffers, micro-batches ship
as ONE contiguous device put, and ``max_inflight`` pooled buffers
rotate through in-flight classifies so batch N+1's transfer overlaps
batch N's device compute — the same double-buffering scheme as the
scoring flush path.

Chunk kinds:
- ``raw-rgb8``: H*W*3 uint8 bytes (raw camera feed);
- ``jpeg``: compressed frames — native entropy decode + on-device IDCT
  on the compressed wire; PIL on the fallback/legacy paths;
- ``png``: lossless compressed — PIL-decoded (no native path), rides
  the byte ring so submit stays pixel-free either way.
"""

from __future__ import annotations

import asyncio
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from sitewhere_tpu.runtime.bus import EventBus
from sitewhere_tpu.runtime.lifecycle import (
    LifecycleComponent,
    LifecycleState,
    cancel_and_wait,
)
from sitewhere_tpu.runtime.metrics import D2H_OVERLAP_EPS_S, MetricsRegistry
from sitewhere_tpu.services.streaming_media import StreamingMedia

# Compressed-frame wire kill switch (mirrors FUSED_STEP_ENABLED /
# WIRE_CODEC_ENABLED): captured at PIPELINE BUILD time. False rebuilds
# the pre-compression pipeline exactly — decoded frames ring
# (``_FrameRing``), submit-time PIL decode, raw-pixel h2d — bit for bit
# (regression-tested in tests/test_media_wire.py). Flip process-wide
# BEFORE tenants start for a rollback/mixed-fleet window.
MEDIA_WIRE_COMPRESSED_ENABLED = True


def media_classifications_topic(bus: EventBus, tenant: str) -> str:
    return bus.naming.tenant_topic(tenant, "media-classifications")


class _FrameRing:
    """Preallocated decoded-frame ring for one media pipeline.

    Frames are written into a fixed ``uint8[cap, H, W, 3]`` buffer at
    submit time (``reserve``/``commit``); each micro-batch leaves as ONE
    contiguous slice copy into a pooled staging buffer (``pop_into``) —
    a single contiguous device put per flush, never ``np.stack`` over a
    Python list of frames. Live-video semantics: newest frame wins — a
    full ring sheds its OLDEST pending frame (``media_frames_shed_total``)
    instead of backpressuring the camera feed into the transport layer.
    Depth surfaces per tenant through the ``media_queue_depth`` gauge
    (collected in ``instance.py``; tools/check_queues.py registry).
    """

    __slots__ = ("frames", "meta", "head", "count", "data_event", "metrics")

    def __init__(self, capacity: int, size: int, metrics) -> None:
        self.frames = np.empty((capacity, size, size, 3), np.uint8)
        self.meta: List = [None] * capacity  # (stream_id, seq, t0, wire_nb)
        self.head = 0
        self.count = 0
        self.data_event = asyncio.Event()
        self.metrics = metrics

    @property
    def capacity(self) -> int:
        return len(self.meta)

    def qsize(self) -> int:
        return self.count

    def used_bytes(self) -> int:
        return self.count * int(self.frames[0].nbytes)

    def reserve(self) -> np.ndarray:
        """The next write slot's frame view — fill it, then ``commit``.
        A full ring sheds its oldest pending frame first (counted)."""
        if self.count >= self.capacity:
            self.head = (self.head + 1) % self.capacity
            self.count -= 1
            self.metrics.counter("media_frames_shed_total").inc()
        return self.frames[(self.head + self.count) % self.capacity]

    def commit(
        self, stream_id: str, seq: int, t0: float, wire_nb: int = 0
    ) -> None:
        # wire_nb: bytes the chunk ARRIVED as (flightrec wire_bytes must
        # report the camera wire, not the decoded pixels it became)
        self.meta[(self.head + self.count) % self.capacity] = (
            stream_id, seq, t0, wire_nb,
        )
        self.count += 1
        self.data_event.set()

    def pop_into(self, staging: np.ndarray, max_n: int) -> List[Tuple]:
        """Move up to ``max_n`` frames off the front into ``staging`` with
        one slice copy; returns their metas. Bounded by the contiguous
        span at the head — a wrap remainder rides the next batch (keeps
        every copy a single contiguous memcpy)."""
        k = min(self.count, max_n, self.capacity - self.head)
        if k <= 0:
            return []
        h = self.head
        staging[:k] = self.frames[h : h + k]
        metas = self.meta[h : h + k]
        self.head = (h + k) % self.capacity
        self.count -= k
        return metas


class _ByteRing:
    """Variable-length compressed-frame ring: one preallocated byte
    arena + a per-frame (offset, length, kind, meta) index ring.

    The compressed wire's holding pen — JPEG chunks are ~10-20× smaller
    than decoded frames, so ``arena_bytes`` bounds RESIDENT bytes per
    tenant where ``_FrameRing`` bounded frame count. Frames occupy
    contiguous arena spans in FIFO order; when the tail can't fit the
    next frame the writer wraps to offset 0 (the skipped tail is dead
    until the reader passes it). ``_FrameRing`` semantics preserved:
    newest frame wins — a full arena (or full index) sheds its OLDEST
    pending frame (``media_frames_shed_total``); depth rides the same
    ``media_queue_depth`` gauge plus ``media_ring_bytes`` for the byte
    watermark (tools/check_queues.py registry).
    """

    __slots__ = (
        "arena", "meta", "head", "count", "write_off", "used",
        "data_event", "metrics",
    )

    def __init__(self, index_capacity: int, arena_bytes: int, metrics) -> None:
        self.arena = np.empty((arena_bytes,), np.uint8)
        # (off, nbytes, kind, stream_id, seq, t0)
        self.meta: List = [None] * index_capacity
        self.head = 0
        self.count = 0
        self.write_off = 0
        self.used = 0          # pending payload bytes (excludes dead tail)
        self.data_event = asyncio.Event()
        self.metrics = metrics

    @property
    def capacity(self) -> int:
        return len(self.meta)

    @property
    def arena_bytes(self) -> int:
        return int(self.arena.shape[0])

    def qsize(self) -> int:
        return self.count

    def used_bytes(self) -> int:
        return self.used

    def _drop_oldest(self) -> None:
        self.meta[self.head] = None
        self.head = (self.head + 1) % self.capacity
        self.count -= 1
        if self.count == 0:
            self.write_off = 0
            self.used = 0

    def _shed_oldest(self) -> None:
        self.used -= self.meta[self.head][1]
        self._drop_oldest()
        self.metrics.counter("media_frames_shed_total").inc()

    def _fit(self, nb: int) -> int:
        """Arena offset where ``nb`` bytes fit RIGHT NOW, or -1."""
        if self.count == 0:
            return 0 if nb <= self.arena_bytes else -1
        head_off = self.meta[self.head][0]
        if self.write_off >= head_off:
            # data occupies [head_off, write_off)
            if nb <= self.arena_bytes - self.write_off:
                return self.write_off
            if nb < head_off:  # wrap (strict: write_off==head_off is full)
                return 0
            return -1
        # wrapped: data occupies [head_off, ...) ∪ [0, write_off).
        # STRICT: filling the gap exactly would make write_off==head_off,
        # which is indistinguishable from the unwrapped-empty-gap state
        if nb < head_off - self.write_off:
            return self.write_off
        return -1

    def append(
        self, data: bytes, kind: str, stream_id: str, seq: int, t0: float
    ) -> bool:
        """One compressed frame into the arena (one memcpy). Sheds
        oldest pending frames until it fits; returns False only for a
        frame larger than the whole arena (caller counts it shed)."""
        nb = len(data)
        if nb > self.arena_bytes:
            self.metrics.counter("media_frames_shed_total").inc()
            return False
        if self.count >= self.capacity:
            self._shed_oldest()
        off = self._fit(nb)
        while off < 0:
            self._shed_oldest()
            off = self._fit(nb)
        self.arena[off : off + nb] = np.frombuffer(data, np.uint8)
        self.meta[(self.head + self.count) % self.capacity] = (
            off, nb, kind, stream_id, seq, t0,
        )
        self.count += 1
        self.write_off = off + nb
        self.used += nb
        self.data_event.set()
        return True

    def peek_bytes(self, max_n: int) -> int:
        """Total payload bytes of the up-to-``max_n`` oldest frames
        (sizes the staging checkout before ``pop_into``)."""
        total = 0
        n = min(self.count, max_n)
        for i in range(n):
            total += self.meta[(self.head + i) % self.capacity][1]
        return total

    def pop_into(
        self,
        staging: np.ndarray,
        offs: np.ndarray,
        lens: np.ndarray,
        max_n: int,
    ) -> List[Tuple]:
        """Move up to ``max_n`` frames off the front into ``staging``
        (compacting: span copies land back to back), filling per-frame
        ``offs``/``lens``; returns their (kind, stream_id, seq, t0)
        metas. Frees ring space immediately — the staging buffer is the
        classify task's own, so a submit racing the decode can never
        overwrite bytes still being read."""
        pos = 0
        n = 0
        cap = int(staging.shape[0])
        metas: List[Tuple] = [None] * min(self.count, max_n)
        while n < max_n and self.count:
            off, nb, kind, stream_id, seq, t0 = self.meta[self.head]
            if pos + nb > cap:
                break
            staging[pos : pos + nb] = self.arena[off : off + nb]
            offs[n] = pos
            lens[n] = nb
            metas[n] = (kind, stream_id, seq, t0)
            pos += nb
            self.used -= nb
            self._drop_oldest()
            n += 1
        del metas[n:]
        return metas


class MediaClassificationPipeline(LifecycleComponent):
    """Per-tenant micro-batched frame classifier over the media service."""

    def __init__(
        self,
        tenant: str,
        bus: EventBus,
        media: StreamingMedia,
        metrics: Optional[MetricsRegistry] = None,
        max_batch: int = 16,
        deadline_ms: float = 30.0,
        top_k: int = 5,
        tiny: bool = False,          # tiny ViT for CI; B/16 in prod/bench
        max_inflight: int = 4,
        store_chunks: bool = True,
        # legacy (kill-switch) decoded-frame ring: 256 frames ≈ 38 MB at
        # 224×224×3 — the write cursor cycles the whole ring over time,
        # so capacity bounds RESIDENT memory per tenant, not just backlog
        ring_capacity: int = 256,
        # compressed wire: the byte arena bounds resident bytes instead.
        # None = a quarter of the legacy ring's resident bytes (~9.6 MB
        # at 224px, floor 4 MB): the full ring_capacity depth at ≥4×
        # compression AND ≥64 frames of raw-rgb8 burst (a raw feed
        # riding the byte ring must still fill a max_batch without
        # waiting out the collect deadline); raw-heavy tenants size it
        # explicitly
        ring_bytes: Optional[int] = None,
        decode_workers: int = 4,
        flightrec=None,
        # flush supervision (docs/ROBUSTNESS.md "Device fault domains"):
        # every classify readback is bounded by max(flush_deadline_ms,
        # flush_deadline_x × this tenant's observed dispatch→landed
        # p99); an overdue batch's frames drop (media is lossy by
        # design — shed-oldest already governs the intake side) and
        # tpu_flush_timeout_total counts it. 0 disables supervision.
        flush_deadline_ms: float = 5000.0,
        flush_deadline_x: float = 8.0,
    ) -> None:
        super().__init__(f"media-pipeline[{tenant}]")
        self.tenant = tenant
        self.bus = bus
        self.media = media
        self.metrics = metrics or MetricsRegistry()
        self.max_batch = max_batch
        self.deadline_ms = deadline_ms
        self.top_k = top_k
        self.tiny = tiny
        self.store_chunks = store_chunks
        self.max_inflight = max_inflight
        # kill switch captured at BUILD time (the FUSED_STEP_ENABLED
        # pattern): a pipeline is born compressed or legacy and never
        # changes mid-flight — rollback = flip the module flag and
        # rebuild the tenant
        self.compressed = bool(MEDIA_WIRE_COMPRESSED_ENABLED)
        if self.compressed:
            if ring_bytes is None:
                frame_nb = self.image_size * self.image_size * 3
                ring_bytes = max(4 << 20, ring_capacity * frame_nb // 4)
            self._ring = _ByteRing(ring_capacity, ring_bytes, self.metrics)
        else:
            self._ring = _FrameRing(ring_capacity, self.image_size, self.metrics)
        # pooled staging buffers: one per in-flight classify (+1 for the
        # batch being packed) so a buffer is never rewritten while its
        # classify still reads it; sized lazily to the CURRENT max_batch
        # (benches retune max_batch after construction)
        # pools are touched from the loop thread AND (in compressed
        # mode) up to max_inflight concurrent executor threads running
        # _decode_batch — every check-then-pop/append runs under this
        # lock (allocation of fresh buffers stays outside it)
        self._pool_lock = threading.Lock()
        self._staging_pool: deque = deque()
        self._byte_staging_pool: deque = deque()   # (buf, offs, lens)
        self._coef_pool: deque = deque()           # (y, cb, cr) full-64
        self._coef_sub = 2                         # cached subsampling mode
        # hysteresis against recurring wasted decodes: a 4:4:4 stream
        # whose payload keeps failing the oversize guard (full-precision
        # 4:4:4 coefficients exceed raw pixels) routes straight to the
        # PIL path after a couple of rejected attempts
        self._sub1_rejects = 0
        self._packed_pools: Dict[tuple, deque] = {}
        # (bucket, k) coefficient variants prewarm compiled: once
        # populated, _decode_batch only picks shapes from this set (a
        # cold variant would pay a 20-40 s XLA compile MID-TRAFFIC on a
        # real chip, holding the inflight semaphore while the live ring
        # sheds); empty (no prewarm — tests/drives) = no restriction
        self._warm_variants: set = set()
        self._task: Optional[asyncio.Task] = None
        self._inflight = asyncio.Semaphore(max_inflight)
        self._deliver_tasks: set = set()
        # native decode pool: the serial Huffman+dequant stage fans out
        # here as per-worker RANGE jobs (ctypes releases the GIL, so
        # frames genuinely decode in parallel); the gauge counts those
        # jobs — bounded by max_inflight × decode_workers — and
        # media.decode_backpressure counts fan-outs that queued behind
        # a pool already running another batch's ranges
        self._decode_workers = max(1, decode_workers)
        self._decode_pool = None
        self._decode_lock = threading.Lock()
        self._decode_inflight = 0
        self._native_ok = False
        self._native_resolved = True   # start() sets False if build pending
        self._native_warned = False
        self._prewarmed = False
        # flight-recorder + live MFU attribution for the ViT leg (wired
        # on start — the flops figure needs the classifier config)
        self.flightrec = flightrec
        self._mfu = None
        self._flops_per_frame = 0.0
        # flush supervision: injectable device faults (runtime.faultplan;
        # None in production) + the classify deadline's p99 history
        self.faultplan = None
        self.flush_deadline_ms = float(flush_deadline_ms)
        self.flush_deadline_x = float(flush_deadline_x)
        from sitewhere_tpu.runtime.metrics import RollingQuantile

        self._classify_p99 = RollingQuantile()

    def _classify_deadline_s(self) -> Optional[float]:
        """The current classify completion budget (None = supervision
        off): the media twin of SliceRuntime.flush_deadline_s."""
        floor = self.flush_deadline_ms / 1000.0
        if floor <= 0:
            return None
        p99 = self._classify_p99.quantile()
        if p99 is None:
            return floor
        return max(floor, self.flush_deadline_x * p99)

    def _warn_native_absent(self) -> None:
        if self._native_warned:
            return
        self._native_warned = True
        import logging

        logging.getLogger(__name__).warning(
            "media[%s]: native jpegwire unavailable — compressed "
            "frames decode via PIL (counted in "
            "media_native_decode_fallback_total)", self.tenant,
        )

    def refresh_mfu(self) -> None:
        """Decay this tenant's idle ``tpu_mfu_pct`` gauge from the
        sliding window (instance history tick / scrape — a stream that
        stopped must read 0, not its last busy value)."""
        if self._mfu is not None:
            self._mfu.refresh()

    def pending_frames(self) -> int:
        """Decoded frames awaiting classification (media_queue_depth)."""
        return self._ring.qsize()

    def pending_bytes(self) -> int:
        """Resident ring payload bytes (media_ring_bytes gauge — the
        byte watermark the compressed arena bounds)."""
        return self._ring.used_bytes()

    def _checkout_staging(self) -> np.ndarray:
        with self._pool_lock:
            while self._staging_pool:
                buf = self._staging_pool.popleft()
                if buf.shape[0] >= self.max_batch:
                    return buf
        size = self.image_size
        return np.empty((self.max_batch, size, size, 3), np.uint8)

    def _return_staging(self, buf: np.ndarray) -> None:
        with self._pool_lock:
            if len(self._staging_pool) <= self.max_inflight:
                self._staging_pool.append(buf)

    # -- compressed-wire staging pools ------------------------------------
    def _checkout_bytes(self, min_bytes: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pooled (byte buffer, per-frame offsets, lengths) for one
        popped batch; grows to the requested byte span."""
        with self._pool_lock:
            while self._byte_staging_pool:
                buf, offs, lens = self._byte_staging_pool.popleft()
                if buf.shape[0] >= min_bytes and offs.shape[0] >= self.max_batch:
                    return buf, offs, lens
        cap = max(64 << 10, 1 << (max(min_bytes, 1) - 1).bit_length())
        return (
            np.empty((cap,), np.uint8),
            np.empty((self.max_batch,), np.int64),
            np.empty((self.max_batch,), np.int64),
        )

    def _return_bytes(self, entry) -> None:
        with self._pool_lock:
            if len(self._byte_staging_pool) <= self.max_inflight:
                self._byte_staging_pool.append(entry)

    @property
    def _coef_cap_blocks(self) -> int:
        # padded MCU-aligned Y-plane worst case
        return (((self.image_size + 15) // 16) * 2) ** 2

    @property
    def _chroma_cap_blocks(self) -> int:
        """Chroma decode-buffer capacity: sized for the cached
        subsampling mode — 1/4 of the Y grid at 4:2:0 (the camera/PIL
        default; a full-grid chroma allocation would quadruple resident
        decode memory for nothing), the full Y grid once a 4:4:4 stream
        has been seen (``_decode_batch``'s SOF peek upgrades the cached
        mode before any entropy decode runs)."""
        cap = self._coef_cap_blocks
        return cap if self._coef_sub == 1 else max(cap // 4, 1)

    def _checkout_coefs(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pooled full-precision (64-coefficient) decode buffers the
        jpegwire pool writes into — one set per in-flight batch."""
        ccap = self._chroma_cap_blocks
        with self._pool_lock:
            while self._coef_pool:
                bufs = self._coef_pool.popleft()
                if bufs[0].shape[0] >= self.max_batch and bufs[1].shape[1] >= ccap:
                    return bufs
        cap = self._coef_cap_blocks
        return (
            np.zeros((self.max_batch, cap, 64), np.int16),
            np.zeros((self.max_batch, ccap, 64), np.int16),
            np.zeros((self.max_batch, ccap, 64), np.int16),
        )

    def _return_coefs(self, bufs) -> None:
        with self._pool_lock:
            # a set superseded by a chroma-mode upgrade drops, not pools
            if bufs[1].shape[1] < self._chroma_cap_blocks:
                return
            if len(self._coef_pool) <= self.max_inflight:
                self._coef_pool.append(bufs)

    def _checkout_packed(self, bucket: int, layout) -> Tuple[np.ndarray, ...]:
        """Pooled zigzag-truncated wire buffers for one (bucket, layout)
        — the contiguous arrays the device put ships. Unwritten rows
        past the live frames carry whatever the pool held (finite int16
        garbage; results sliced off, same contract as pixel staging)."""
        key = (bucket, layout.y_blocks, layout.c_blocks, layout.k)
        with self._pool_lock:
            pool = self._packed_pools.setdefault(key, deque())
            if pool:
                return pool.popleft()
        return (
            np.zeros((bucket, layout.y_blocks, layout.k), np.int16),
            np.zeros((bucket, layout.c_blocks, layout.k), np.int16),
            np.zeros((bucket, layout.c_blocks, layout.k), np.int16),
        )

    def _return_packed(self, bucket: int, layout, bufs) -> None:
        key = (bucket, layout.y_blocks, layout.c_blocks, layout.k)
        with self._pool_lock:
            pool = self._packed_pools.setdefault(key, deque())
            if len(pool) <= self.max_inflight:
                pool.append(bufs)

    # -- ingest -----------------------------------------------------------
    @property
    def image_size(self) -> int:
        from sitewhere_tpu.models.vit import VIT_B16, VIT_TINY_TEST

        return (VIT_TINY_TEST if self.tiny else VIT_B16).image_size

    async def submit_chunk(
        self,
        stream_id: str,
        seq: int,
        data: bytes,
        kind: str = "raw-rgb8",
    ) -> None:
        """One camera chunk: persisted to the stream store (playback
        parity) and — on the compressed wire — appended to the byte
        arena AS-IS (one memcpy, no pixel materialization; shed-oldest
        when full). Legacy path decodes straight into the frame ring.
        Malformed chunks are counted (``media_frames_bad_total``) and
        shed, never raised through the submit path."""
        if self.store_chunks:
            self.media.append_chunk(stream_id, seq, data)
        size = self.image_size
        if self.compressed:
            if kind == "raw-rgb8" and len(data) < size * size * 3:
                # torn/short raw chunk: drop at the edge — decode-stage
                # frombuffer would shear the whole batch
                self.metrics.counter("media_frames_bad_total").inc()
                return
            self._ring.append(data, kind, stream_id, seq, time.monotonic())
            self.metrics.counter(
                "media_wire_bytes_total", tenant=self.tenant
            ).inc(len(data))
            return
        # ---- legacy (kill-switch) path: decode at submit time ----
        if kind == "raw-rgb8":
            # validate BEFORE reserving a ring slot (a short chunk must
            # not consume/shear ring state)
            frame = self._decode_raw(data, size)
            if frame is None:
                return
        else:  # jpeg/png: PIL decode is CPU-bound — off the loop. u8 so
            # every frame shares the on-device normalization path
            try:
                frame = await asyncio.get_running_loop().run_in_executor(
                    None, self.media.decode_frame, data, size, "u8"
                )
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - torn/corrupt chunk: count,
                # shed, keep the submit path alive
                self.metrics.counter("media_frames_bad_total").inc()
                return
        self.metrics.counter(
            "media_wire_bytes_total", tenant=self.tenant
        ).inc(len(data))
        # reserve+commit run on the loop thread (no await between them)
        self._ring.reserve()[...] = frame
        self._ring.commit(stream_id, seq, time.monotonic(), len(data))

    def _decode_raw(self, data: bytes, size: int) -> Optional[np.ndarray]:
        n = size * size * 3
        if len(data) < n:
            # a torn/short chunk is counted and shed — the caller's bug
            # must not take the whole submit path (and pipeline) down
            self.metrics.counter("media_frames_bad_total").inc()
            return None
        # stays uint8: frames normalize ON DEVICE (classify_frames), so
        # host→device moves 1 byte/px instead of 4
        return np.frombuffer(data, np.uint8, n).reshape(size, size, 3)

    # -- lifecycle --------------------------------------------------------
    async def on_start(self) -> None:
        # classifier init (86M params for real B/16) runs OFF the loop —
        # a synchronous init would freeze every other tenant's pipeline
        # for its duration
        await asyncio.get_running_loop().run_in_executor(
            None, self.media._get_classifier, self.tiny
        )
        if self.compressed:
            from concurrent.futures import ThreadPoolExecutor

            from sitewhere_tpu.native import jpegwire as jw

            self._decode_pool = ThreadPoolExecutor(
                max_workers=self._decode_workers,
                thread_name_prefix=f"media-decode[{self.tenant}]",
            )
            # resolve the native build off the loop with a BOUNDED wait
            # (the common cold-cache cc run is a few hundred ms; a slow
            # or hung toolchain must not stall tenant start for the full
            # build timeout). An unresolved probe is not a verdict —
            # _decode_batch keeps re-probing nonblockingly and upgrades
            # when a late build lands; a DEFINITIVE failure stays PIL.
            self._native_ok = await asyncio.get_running_loop().run_in_executor(
                None, jw.jpegwire_lib, True, 10.0
            ) is not None
            self._native_resolved = jw.build_resolved()
            if self._native_resolved and not self._native_ok:
                self._warn_native_absent()
        # device-time/MFU attribution: per-frame analytic flops from the
        # classifier config (labeled per tenant — media pipelines are
        # per-tenant, and drop_labeled(tenant=...) reclaims the children)
        try:
            self._flops_per_frame = self.media.classifier_flops_per_frame(
                self.tiny
            )
        except Exception:  # noqa: BLE001 - attribution must not block start
            self._flops_per_frame = 0.0
        from sitewhere_tpu.runtime.metrics import MfuAccount

        self._mfu = MfuAccount(self.metrics, "vit_b16", tenant=self.tenant)
        self._task = asyncio.create_task(self._run(), name=self.name)

    async def on_stop(self) -> None:
        await cancel_and_wait(self._task)
        self._task = None
        if self._deliver_tasks:
            # bounded grace, then force-cancel: an in-flight publish
            # against a full topic whose consumer is already stopped
            # would otherwise hang the whole stop cascade
            _done, pending = await asyncio.wait(
                list(self._deliver_tasks), timeout=5.0
            )
            for t in pending:
                await cancel_and_wait(t)
        if self._decode_pool is not None:
            self._decode_pool.shutdown(wait=False, cancel_futures=True)
            self._decode_pool = None

    def _buckets(self) -> List[int]:
        """Static batch-shape ladder (XLA recompile avoidance, same
        playbook as the inference flush buckets): light traffic classifies
        at the smallest fitting shape instead of paying a full max_batch
        forward per frame."""
        out = [1]
        b = 4
        while b < self.max_batch:
            out.append(b)
            b *= 4
        out.append(self.max_batch)
        return out

    def _expected_layout(self, sub: int, k: int):
        """The coefficient layout one ``image_size`` frame decodes to at
        subsampling ``sub`` — prewarm compiles against it."""
        from sitewhere_tpu.ops.dct import layout_for

        return layout_for(self.image_size, self.image_size, sub, k)

    def prewarm(self) -> None:
        """Compile every bucket shape before timed traffic: the pixel
        ladder (raw chunks + PIL fallback) always; on the compressed
        wire also the coefficient variants — every batch bucket at full
        precision (k=64) plus the max-batch bucket across the truncation
        ladder (4:2:0, the camera default; an exotic subsampling pays
        one first-use compile instead)."""
        size = self.image_size
        for b in self._buckets():
            self.media.classify_frames(
                np.zeros((b, size, size, 3), np.uint8),
                top_k=self.top_k, tiny=self.tiny,
            )
        self._prewarmed = True
        if self.compressed and not self._native_ok and not self._native_resolved:
            # a prewarm invoked after the background build landed must
            # see it (start()'s bounded wait may have outrun cc)
            from sitewhere_tpu.native import jpegwire as jw

            if jw.build_resolved():
                self._native_resolved = True
                self._native_ok = jw.jpegwire_lib(wait=False) is not None
        if not (self.compressed and self._native_ok):
            return
        from sitewhere_tpu.ops.dct import COEF_BUCKETS

        variants = [(b, 64, 2) for b in self._buckets()]
        variants += [(self.max_batch, k, 2) for k in COEF_BUCKETS if k != 64]
        for b, k, sub in variants:
            lay = self._expected_layout(sub, k)
            y = np.zeros((b, lay.y_blocks, k), np.int16)
            c = np.zeros((b, lay.c_blocks, k), np.int16)
            self.media.topk_results(
                *self.media.classify_coeffs_dispatch(
                    y, c, c, lay, top_k=self.top_k, tiny=self.tiny
                )
            )
        # runtime shape-choice is pinned to this set, keyed (bucket, k,
        # SUBSAMPLING) — sub is part of the jit layout key too: partial
        # buckets ship full precision (k=64 — still the whole JPEG wire
        # win; the truncation diet engages at saturation, where batches
        # are max_batch) and a subsampling prewarm never compiled (4:4:4
        # on a prewarmed pipeline) rides the PIL path, instead of paying
        # a 20-40 s cold XLA compile mid-traffic
        self._warm_variants = set(variants)

    # -- batching loop ----------------------------------------------------
    async def _run(self) -> None:
        topic = media_classifications_topic(self.bus, self.tenant)
        frames_ctr = self.metrics.counter("media.frames_classified")
        lat = self.metrics.histogram("media.latency", unit="s")
        ring = self._ring
        while True:
            # wait for the first frame (clear-then-recheck: a commit
            # between the count check and the clear must not be missed)
            while ring.count == 0:
                ring.data_event.clear()
                if ring.count:
                    break
                await ring.data_event.wait()
            deadline = time.monotonic() + self.deadline_ms / 1000.0
            while ring.count < self.max_batch:
                timeout = deadline - time.monotonic()
                if timeout <= 0:
                    break
                ring.data_event.clear()
                if ring.count >= self.max_batch:
                    break
                try:
                    await asyncio.wait_for(ring.data_event.wait(), timeout)
                except asyncio.TimeoutError:
                    break
            await self._inflight.acquire()
            if self.compressed:
                entry = self._checkout_bytes(ring.peek_bytes(self.max_batch))
                buf, offs, lens = entry
                metas = ring.pop_into(buf, offs, lens, self.max_batch)
                if not metas:
                    self._inflight.release()
                    self._return_bytes(entry)
                    continue
                task = asyncio.create_task(
                    self._classify_compressed(
                        entry, metas, topic, frames_ctr, lat
                    )
                )
            else:
                # the batch leaves the ring as ONE contiguous slice copy
                # into a pooled staging buffer the classify task owns
                # until done
                staging = self._checkout_staging()
                metas = ring.pop_into(staging, self.max_batch)
                if not metas:
                    self._inflight.release()
                    self._return_staging(staging)
                    continue
                task = asyncio.create_task(
                    self._classify_and_publish(
                        staging, metas, topic, frames_ctr, lat
                    )
                )
            self._deliver_tasks.add(task)
            task.add_done_callback(self._deliver_tasks.discard)

    # -- compressed-wire decode + dispatch (executor side) ----------------
    def _pool_map(self, fn, jobs: list) -> list:
        """Fan decode jobs (contiguous per-worker frame RANGES, not one
        future per frame — future overhead at camera rate is real) over
        the decode pool and gather in order; tracks the in-flight gauge
        and counts submissions that queued behind a saturated pool
        (media.decode_backpressure)."""
        # local capture: on_stop may null the pool while a force-
        # cancelled classify's executor half is still running — abort
        # the batch instead of AttributeError into an unawaited future
        # (a shutdown pool's submit raises RuntimeError, same abort)
        pool = self._decode_pool
        if pool is None:
            raise RuntimeError("media decode pool stopped")
        with self._decode_lock:
            self._decode_inflight += len(jobs)
            if self._decode_inflight > self._decode_workers:
                self.metrics.counter("media.decode_backpressure").inc()
            self.metrics.gauge(
                "media_decode_inflight", tenant=self.tenant
            ).set(self._decode_inflight)
        try:
            futs = [pool.submit(fn, *j) for j in jobs]
            return [f.result() for f in futs]
        finally:
            with self._decode_lock:
                self._decode_inflight -= len(jobs)
                self.metrics.gauge(
                    "media_decode_inflight", tenant=self.tenant
                ).set(self._decode_inflight)

    def _ranges(self, n: int) -> List[Tuple[int, int]]:
        """Split ``n`` frames into up to ``decode_workers`` contiguous
        ranges (the decode pool's unit of work)."""
        w = min(self._decode_workers, n)
        step = (n + w - 1) // w
        return [(lo, min(lo + step, n)) for lo in range(0, n, step)]

    def _decode_batch(self, buf, offs, lens, metas):
        """Host decode stage for one popped batch (runs on an executor
        thread). Tries the native coefficient path first — ALL frames
        jpeg, native lib present, identical geometry at the classifier's
        frame size, and a coefficient payload no larger than raw pixels;
        otherwise decodes the whole batch to pixels (raw memcpy / PIL),
        counting native fallbacks and shedding malformed frames.

        Returns ``(mode, payload, keep_metas, codec)`` where mode is
        ``"coef"`` (payload = (packed y/cb/cr, layout, bucket)) or
        ``"pix"`` (payload = (staging, bucket))."""
        from sitewhere_tpu.native import jpegwire as jw
        from sitewhere_tpu.ops.dct import FrameLayout, coef_bucket

        n = len(metas)
        size = self.image_size
        kinds = [m[0] for m in metas]
        all_jpeg = all(k == "jpeg" for k in kinds)
        if not self._native_ok and not self._native_resolved:
            # start()'s bounded wait elapsed before the background build
            # finished — re-probe nonblockingly until the outcome is
            # definitive (a build landing late upgrades the pipeline)
            if jw.build_resolved():
                self._native_resolved = True
                self._native_ok = jw.jpegwire_lib(wait=False) is not None
                if not self._native_ok:
                    self._warn_native_absent()
        native_ok = self._native_ok and all_jpeg
        if native_ok and self._prewarmed and not self._warm_variants:
            # the pipeline prewarmed while native was absent, so NO
            # coefficient variant was ever compiled — a late-landing
            # build must not buy a 20-40 s cold XLA compile mid-traffic;
            # stay on PIL until an operator re-runs prewarm()
            native_ok = False
        if native_ok:
            # cheap SOF peek BEFORE committing to the coefficient path:
            # off-size/progressive/mixed-geometry streams must not pay a
            # full wasted entropy decode per batch just to discover the
            # mismatch and re-decode via PIL — and the subsampling mode
            # learned here sizes the chroma buffers correctly up front
            # (no misreading an oversized 4:2:0 as a 4:4:4 stream)
            peek0 = None
            for i in range(n):
                g = jw.peek_geometry(buf[offs[i] : offs[i] + lens[i]])
                if g is None or g[0] != size or g[1] != size or (
                    peek0 is not None and g != peek0
                ):
                    native_ok = False
                    break
                peek0 = g
            if native_ok and self._warm_variants and not any(
                v[2] == peek0[2] for v in self._warm_variants
            ):
                # prewarmed pipelines never compile a cold subsampling
                # mid-traffic (the jit layout key includes sub) — route
                # to the PIL path before paying the entropy decode
                native_ok = False
            if native_ok and peek0[2] == 1:
                if self._sub1_rejects >= 2:
                    # this 4:4:4 stream's payloads keep losing to raw —
                    # stop paying the entropy decode just to rediscover
                    # it (the PIL route below counts the fallback)
                    native_ok = False
                elif self._coef_sub == 2:
                    # first 4:4:4 stream: upgrade the cached mode so
                    # this batch already decodes into full-grid chroma
                    with self._pool_lock:
                        self._coef_sub = 1
                        self._coef_pool.clear()
        if native_ok:
            coefs = self._checkout_coefs()
            try:
                y, cb, cr = coefs
                infos: List = [None] * n

                def _entropy_range(lo: int, hi: int) -> None:
                    for i in range(lo, hi):
                        infos[i] = jw.decode_into(
                            buf[offs[i] : offs[i] + lens[i]],
                            y[i], cb[i], cr[i],
                        )

                self._pool_map(_entropy_range, self._ranges(n))
                geo = None
                kmax = 0
                ok = True
                for info in infos:
                    if info is None:
                        ok = False
                        break
                    g = (info.width, info.height, info.y_gw, info.y_gh,
                         info.c_gw, info.c_gh, info.sub)
                    if geo is None:
                        geo = g
                    elif g != geo:
                        ok = False
                        break
                    kmax = max(kmax, info.y_k, info.c_k)
                if ok and geo is not None and geo[0] == size and geo[1] == size:
                    k = coef_bucket(kmax)
                    bucket_n = next(b for b in self._buckets() if b >= n)

                    def _warm(kk: int) -> bool:
                        # shape pinning: the jit layout key includes k
                        # AND subsampling — a cold variant would compile
                        # 20-40 s mid-traffic (empty set = no prewarm =
                        # no restriction)
                        return not self._warm_variants or (
                            (bucket_n, kk, geo[6]) in self._warm_variants
                        )

                    if not _warm(k):
                        k = 64
                    layout = FrameLayout(*geo, k=k)
                    if _warm(k) and layout.wire_bytes(1) <= size * size * 3:
                        if geo[6] == 1:
                            self._sub1_rejects = 0
                        bucket = bucket_n
                        packed = self._checkout_packed(bucket, layout)
                        py, pcb, pcr = packed
                        np.copyto(py[:n], y[:n, : layout.y_blocks, :k])
                        np.copyto(pcb[:n], cb[:n, : layout.c_blocks, :k])
                        np.copyto(pcr[:n], cr[:n, : layout.c_blocks, :k])
                        return (
                            "coef", (packed, layout, bucket), metas,
                            f"dct{k}",
                        )
                    if geo[6] == 1:
                        # a 4:4:4 batch that lost the size guard (or has
                        # no warm shape): feed the hysteresis so the
                        # peek stage stops re-trying this stream
                        self._sub1_rejects += 1
            finally:
                self._return_coefs(coefs)
        # ---- pixel fallback: raw memcpy or PIL decode per frame ----
        pix = self._checkout_staging()
        keep = np.zeros(n, bool)
        n_fallback = 0
        pil_mask = np.zeros(n, bool)
        for i in range(n):
            if kinds[i] == "raw-rgb8":
                # length validated at submit; one slice-view reshape copy
                pix[i] = buf[offs[i] : offs[i] + size * size * 3].reshape(
                    size, size, 3
                )
                keep[i] = True
            else:
                if kinds[i] == "jpeg":
                    n_fallback += 1
                pil_mask[i] = True

        def _pil_range(lo: int, hi: int) -> None:
            for i in range(lo, hi):
                if not pil_mask[i]:
                    continue
                try:
                    pix[i] = self.media.decode_frame(
                        buf[offs[i] : offs[i] + lens[i]].tobytes(),
                        size, "u8",
                    )
                    keep[i] = True
                except Exception:  # noqa: BLE001 - torn/corrupt frame: shed
                    pass

        if pil_mask.any():
            try:
                self._pool_map(_pil_range, self._ranges(n))
            except BaseException:
                # an aborted pool fan-out (teardown) must hand the
                # pixel staging back before the batch unwinds
                self._return_staging(pix)
                raise
        n_bad = int(n - keep.sum())
        if n_bad:
            self.metrics.counter("media_frames_bad_total").inc(n_bad)
        if n_fallback:
            self.metrics.counter(
                "media_native_decode_fallback_total"
            ).inc(n_fallback)
        if not keep.any():
            self._return_staging(pix)
            return ("none", None, [], "pixels")
        if n_bad:
            sel = np.flatnonzero(keep)
            pix[: sel.shape[0]] = pix[sel]
            keep_metas = [metas[i] for i in sel]
        else:
            keep_metas = metas
        bucket = next(b for b in self._buckets() if b >= len(keep_metas))
        return ("pix", (pix, bucket), keep_metas, "pixels")

    def _decode_and_dispatch(self, entry, metas):
        """Decode stage + jit dispatch, one executor hop. Returns
        ``(pv, iv, plan_mode, payload, keep_metas, codec, wire_bytes,
        decode_s, dispatch_s, h2d_bytes, bucket)`` or None when every
        frame shed. ``dispatch_s`` times ONLY the jit dispatch call —
        the decode stage has its own figure, so the flightrec field
        keeps one meaning across the compressed and legacy legs."""
        buf, offs, lens = entry
        n = len(metas)
        wire_bytes = int(lens[:n].sum())
        t0 = time.perf_counter()
        mode, payload, keep_metas, codec = self._decode_batch(
            buf, offs, lens, metas
        )
        decode_s = time.perf_counter() - t0
        self.metrics.histogram(
            "media_decode_seconds", unit="s", tenant=self.tenant
        ).record(decode_s)
        if mode == "none":
            return None
        t_d = time.perf_counter()
        try:
            if mode == "coef":
                (py, pcb, pcr), layout, bucket = payload
                pv, iv = self.media.classify_coeffs_dispatch(
                    py, pcb, pcr, layout, top_k=self.top_k, tiny=self.tiny
                )
                h2d = py.nbytes + pcb.nbytes + pcr.nbytes
            else:
                pix, bucket = payload
                pv, iv = self.media.classify_frames_dispatch(
                    pix[:bucket], self.top_k, self.tiny
                )
                h2d = int(pix[:bucket].nbytes)
        except BaseException:
            # a failed dispatch must hand its staging back to the pool
            # (the caller only sees None/raise, never the payload)
            if mode == "coef":
                self._return_packed(payload[2], payload[1], payload[0])
            else:
                self._return_staging(payload[0])
            raise
        dispatch_s = time.perf_counter() - t_d
        self.metrics.counter(
            "media_h2d_bytes_total", tenant=self.tenant
        ).inc(h2d)
        return (pv, iv, mode, payload, keep_metas, codec, wire_bytes,
                decode_s, dispatch_s, h2d, bucket)

    async def _finish_classify(
        self,
        pv,
        iv,
        metas_sst: List[Tuple],   # (stream_id, seq, t0) per kept frame
        topic: str,
        frames_ctr,
        lat,
        bucket: int,
        t_disp1: float,
        dispatch_s: float,
        disp_end_wall_ms: float,
        codec: str,
        wire_bytes: int,
        decode_s: Optional[float] = None,
    ) -> None:
        """Shared classify tail (BOTH legs): materialize the dispatched
        top-k off the loop, record d2h-wait/overlap + device-time/MFU +
        the flightrec flush record, publish per-frame events.

        The readback materializes OFF the loop: is_ready would only
        prove the compute finished, not that the async d2h copy crossed
        the link — overlap is measured, not inferred (same rule as the
        scoring reaper's D2H_OVERLAP_EPS_S). The device window runs
        dispatch RETURN → top-k landed (the scoring path's device_s
        definition; the host decode/dispatch stages are NOT chip time),
        and on-device decode FLOPs stay OUT of the ViT MFU numerator
        (the model's flops_per_frame is the honest numerator; decode
        adds < 0.04% and is reported by bench config 5)."""
        loop = asyncio.get_running_loop()
        n = len(metas_sst)
        fn = self.media.topk_results
        if self.faultplan is not None:
            # chaos: the classify readback is a supervised fault domain
            # like the scoring lanes (hang/slow/late-fail inject here)
            fn = self.faultplan.wrap_callable(
                fn, f"vit_b16[{self.tenant}]", 0, "media"
            )
        t_wait = time.perf_counter()
        try:
            results = await asyncio.wait_for(
                loop.run_in_executor(None, fn, pv, iv, n),
                timeout=self._classify_deadline_s(),
            )
        except asyncio.TimeoutError:
            # classify deadline expired: drop the batch's frames (media
            # is lossy by design — intake already sheds oldest), count
            # the timeout against this tenant's classify lane, and
            # freeze the blackbox. The inflight permit releases in the
            # caller's finally, so the pipeline keeps classifying.
            key = f"vit_b16[{self.tenant}]"
            self.metrics.counter(
                "tpu_flush_timeout_total", family=key, slice="media"
            ).inc()
            self.metrics.counter("media.classify_timeouts").inc()
            if self.flightrec is not None:
                self.flightrec.record(
                    "flush", key,
                    ts_ms=disp_end_wall_ms,
                    rows=n, bucket=bucket, codec=codec,
                    wire_bytes=wire_bytes,
                    dispatch_s=round(dispatch_s, 6),
                    status="timeout",
                )
                self.flightrec.snapshot(
                    f"flush-timeout:{key}", family=key, lane="media",
                )
            self._record_error(
                "classify-timeout",
                TimeoutError(
                    f"classify readback blew its deadline "
                    f"({n} frames dropped)"
                ),
            )
            return
        waited_s = time.perf_counter() - t_wait
        self.metrics.histogram("media.d2h_wait", unit="s").record(waited_s)
        overlapped = waited_s < D2H_OVERLAP_EPS_S
        if overlapped:
            self.metrics.counter("media.d2h_overlapped").inc()
        device_s = time.perf_counter() - t_disp1
        # deadline history: the next classify's budget tracks this
        # tenant's observed dispatch→landed p99 (flush supervision)
        self._classify_p99.add(device_s)
        if self._mfu is not None and self._flops_per_frame:
            self._mfu.record(self._flops_per_frame * bucket, device_s)
        if self.flightrec is not None:
            # ts_ms marks the DISPATCH return, not this (post-resolution)
            # record call: the Chrome export anchors the host phases to
            # end and the device window to start at ts_ms
            extra = (
                {} if decode_s is None else {"decode_s": round(decode_s, 6)}
            )
            self.flightrec.record(
                "flush", f"vit_b16[{self.tenant}]",
                ts_ms=disp_end_wall_ms,
                rows=n, bucket=bucket,
                codec=codec,
                wire_bytes=wire_bytes,
                dispatch_s=round(dispatch_s, 6),
                d2h_wait_s=round(waited_s, 6),
                d2h_overlapped=overlapped,
                device_s=round(device_s, 6),
                status="ok",
                **extra,
            )
        now_mono = time.monotonic()
        now = time.time() * 1000.0
        for (stream_id, seq, t0), top in zip(metas_sst, results):
            payload_ev = {
                "type": "media_classification",
                "tenant": self.tenant,
                "stream_id": stream_id,
                "seq": seq,
                "top_k": top,
                "ts": now,
            }
            if self.state is LifecycleState.STARTED:
                await self.bus.publish(topic, payload_ev)
            else:  # teardown: the consumer may already be gone
                self.bus.publish_nowait(topic, payload_ev)
            lat.record(now_mono - t0)
        frames_ctr.inc(n)

    async def _classify_compressed(
        self, entry, metas, topic: str, frames_ctr, lat
    ) -> None:
        """Compressed-wire classify leg: decode stage + dispatch run in
        one executor hop; readback/materialize in a second (same overlap
        accounting as the legacy leg — the async d2h copy rides under
        the next batch's compute)."""
        payload = None
        layout = bucket = None
        mode = "none"
        try:
            loop = asyncio.get_running_loop()
            out = await loop.run_in_executor(
                None, self._decode_and_dispatch, entry, metas
            )
            self._return_bytes(entry)
            entry = None
            if out is None:
                return
            (pv, iv, mode, payload, keep_metas, codec, wire_bytes,
             decode_s, dispatch_s, h2d, bucket) = out
            if mode == "coef":
                layout = payload[1]
            t_disp1 = time.perf_counter()
            await self._finish_classify(
                pv, iv,
                [(m[1], m[2], m[3]) for m in keep_metas],
                topic, frames_ctr, lat, bucket,
                t_disp1, dispatch_s, time.time() * 1000.0,
                codec, wire_bytes, decode_s,
            )
        except asyncio.CancelledError:
            raise
        except Exception as exc:  # noqa: BLE001 - one bad batch must not
            # kill the classification loop
            self._record_error("classify", exc)
        finally:
            self._inflight.release()
            if entry is not None:
                self._return_bytes(entry)
            if mode == "coef" and payload is not None:
                self._return_packed(bucket, layout, payload[0])
            elif mode == "pix" and payload is not None:
                self._return_staging(payload[0])

    async def _classify_and_publish(
        self, staging: np.ndarray, metas: List[Tuple], topic: str, frames_ctr, lat
    ) -> None:
        try:
            # smallest fitting bucket shape; rows past n are whatever the
            # staging buffer held before (valid pixel data, results
            # sliced off) — no pad allocation, no concatenate
            n = len(metas)
            bucket = next(b for b in self._buckets() if b >= n)
            # jit dispatch off the loop (the classify output is a jit
            # result nothing donates — worker-thread materialization is
            # safe, see checkpoint.host_copy_params). staging[:bucket]
            # is one contiguous buffer → one contiguous host→device put;
            # concurrent classifies on pooled buffers overlap transfer
            # with the previous batch's compute. The d2h copy starts
            # inside the dispatch (copy_to_host_async — same async
            # treatment as the scoring reaper), so by materialize time
            # it has been riding under compute, not starting cold.
            loop = asyncio.get_running_loop()
            t_disp0 = time.perf_counter()
            pv, iv = await loop.run_in_executor(
                None, self.media.classify_frames_dispatch, staging[:bucket],
                self.top_k, self.tiny,
            )
            t_disp1 = time.perf_counter()
            self.metrics.counter(
                "media_h2d_bytes_total", tenant=self.tenant
            ).inc(int(staging[:bucket].nbytes))
            # shared tail: readback/overlap accounting, device-time/MFU,
            # flightrec, publish. wire_bytes = the bytes each chunk
            # ARRIVED as (jpeg/png on this path decoded at submit —
            # pixel bytes would disagree with media_wire_bytes_total by
            # the compression ratio).
            await self._finish_classify(
                pv, iv,
                [(m[0], m[1], m[2]) for m in metas],
                topic, frames_ctr, lat, bucket,
                t_disp1, t_disp1 - t_disp0, time.time() * 1000.0,
                "pixels", int(sum(m[3] for m in metas)),
            )
        except asyncio.CancelledError:
            raise
        except Exception as exc:  # noqa: BLE001 - one bad batch must not
            # kill the classification loop
            self._record_error("classify", exc)
        finally:
            self._inflight.release()
            self._return_staging(staging)
