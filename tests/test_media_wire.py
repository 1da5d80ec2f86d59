"""Compressed media wire (ISSUE 12): variable-length byte ring, native
entropy decode + on-device IDCT parity, kill-switch rollback and the
fallback contract."""

import asyncio
import io

import numpy as np
import pytest

from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.pipeline import media as media_mod
from sitewhere_tpu.pipeline.media import (
    _ByteRing,
    media_classifications_topic,
)
from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig
from sitewhere_tpu.runtime.metrics import MetricsRegistry


# ---------------------------------------------------------------- helpers
def _smooth_frame(size: int, seed: int) -> np.ndarray:
    """One frame of the shared synthetic camera feed (the SAME content
    contract bench config 5 measures — single-sourced in sim.media so
    the wire-diet columns and these tests can't silently diverge)."""
    from sitewhere_tpu.sim.media import camera_frame

    return camera_frame(size, float(seed))


def _jpeg(frame: np.ndarray, quality: int = 75, subsampling=-1) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(frame).save(
        buf, format="JPEG", quality=quality, subsampling=subsampling
    )
    return buf.getvalue()


def _png(frame: np.ndarray) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(frame).save(buf, format="PNG")
    return buf.getvalue()


async def _media_instance():
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="mw", mesh=MeshConfig(slots_per_shard=2),
    ))
    await inst.start()
    await inst.tenant_management.create_tenant(
        "cam", template="media", media_tiny=True,
    )
    await inst.drain_tenant_updates()
    for _ in range(100):
        if "cam" in inst.tenants:
            break
        await asyncio.sleep(0.02)
    return inst


async def _classify_one_by_one(inst, chunks_with_kind):
    """Submit chunks strictly one at a time (bucket=1 for every frame —
    bitwise comparisons must not depend on batch-shape padding) and
    return [(seq, top_k)] in seq order."""
    rt = inst.tenants["cam"]
    pipe = rt.media_pipeline
    topic = media_classifications_topic(inst.bus, "cam")
    inst.bus.subscribe(topic, "t")
    stream = rt.media.create_stream("asn", content_type="video/raw")
    got = []
    for seq, (data, kind) in enumerate(chunks_with_kind):
        await pipe.submit_chunk(stream.stream_id, seq, data, kind=kind)
        for _ in range(400):
            got.extend(await inst.bus.consume(topic, "t", 10, timeout_s=0.05))
            if any(e["seq"] == seq for e in got):
                break
        else:
            raise AssertionError(f"frame {seq} never classified")
    return sorted(((e["seq"], e["top_k"]) for e in got), key=lambda t: t[0])


# ---------------------------------------------------------------- byte ring
def test_byte_ring_fifo_and_wrap_exact_bytes():
    m = MetricsRegistry()
    ring = _ByteRing(16, 1024, m)
    rng = np.random.RandomState(0)
    payloads = {}
    seq = 0
    popped = []
    staging = np.empty(1024, np.uint8)
    offs = np.empty(16, np.int64)
    lens = np.empty(16, np.int64)
    # push/pop across many wraps; every popped span must be byte-exact
    for round_ in range(40):
        for _ in range(3):
            nb = int(rng.randint(40, 200))
            data = rng.randint(0, 256, nb).astype(np.uint8).tobytes()
            assert ring.append(data, "jpeg", "s", seq, 0.0)
            payloads[seq] = data
            seq += 1
        metas = ring.pop_into(staging, offs, lens, 2)
        for i, (_kind, _sid, sq, _t0) in enumerate(metas):
            got = staging[offs[i] : offs[i] + lens[i]].tobytes()
            assert got == payloads[sq], f"corrupt span for seq {sq}"
            popped.append(sq)
    # FIFO order (no shedding happened: ring never exceeded capacity
    # pressure enough to shed — verify, then order)
    shed = m.counter("media_frames_shed_total").value
    kept = [s for s in sorted(payloads) if s not in set(popped)]
    assert popped == sorted(popped) or shed > 0
    # drain the rest: everything remaining still byte-exact
    while ring.qsize():
        metas = ring.pop_into(staging, offs, lens, 16)
        assert metas
        for i, (_k, _s, sq, _t) in enumerate(metas):
            assert staging[offs[i] : offs[i] + lens[i]].tobytes() == payloads[sq]
    assert ring.used_bytes() == 0


def test_byte_ring_sheds_oldest_on_byte_exhaustion():
    m = MetricsRegistry()
    ring = _ByteRing(64, 1000, m)
    for seq in range(10):
        assert ring.append(bytes([seq]) * 300, "jpeg", "s", seq, 0.0)
    # 1000-byte arena holds at most 3 × 300-byte frames → oldest shed
    assert ring.qsize() <= 3
    assert m.counter("media_frames_shed_total").value >= 7
    assert ring.used_bytes() <= 1000
    staging = np.empty(1000, np.uint8)
    offs = np.empty(64, np.int64)
    lens = np.empty(64, np.int64)
    metas = ring.pop_into(staging, offs, lens, 64)
    # newest-wins: the survivors are the LAST frames submitted
    seqs = [sq for (_k, _s, sq, _t) in metas]
    assert seqs == sorted(seqs) and seqs[-1] == 9
    assert staging[offs[0] : offs[0] + lens[0]].tobytes() == bytes([seqs[0]]) * 300


def test_byte_ring_sheds_oldest_on_index_exhaustion_and_oversize():
    m = MetricsRegistry()
    ring = _ByteRing(4, 1 << 20, m)
    for seq in range(6):
        assert ring.append(b"x" * 10, "jpeg", "s", seq, 0.0)
    assert ring.qsize() == 4  # index capacity bounds depth
    assert m.counter("media_frames_shed_total").value == 2
    # a frame larger than the whole arena can never fit: counted, refused
    assert not ring.append(b"y" * (1 << 21), "jpeg", "s", 99, 0.0)
    assert m.counter("media_frames_shed_total").value == 3
    assert ring.qsize() == 4  # pending frames untouched


# ------------------------------------------------------- decode parity
@pytest.mark.parametrize("size,subsampling,quality", [
    (32, 2, 75),    # 4:2:0, the camera/PIL default
    (32, 0, 90),    # 4:4:4
    (224, 2, 75),   # real frame geometry
    (48, 2, 95),    # high quality → wide spectral extent
])
def test_jpegwire_device_decode_parity_vs_pil(size, subsampling, quality):
    """jpegwire entropy decode + the fused on-device reconstruction must
    land within quantization tolerance of PIL's reference decode, and
    the zigzag truncation must be provably lossless (exact zeros past
    the reported extent)."""
    from PIL import Image

    import jax

    from sitewhere_tpu.native import jpegwire as jw
    from sitewhere_tpu.ops import dct

    if jw.jpegwire_lib() is None:
        pytest.skip("no cc toolchain")
    frame = _smooth_frame(size, 3)
    data = _jpeg(frame, quality, subsampling)
    cap = (((size + 15) // 16) * 2) ** 2
    y = np.zeros((cap, 64), np.int16)
    cb = np.zeros((cap, 64), np.int16)
    cr = np.zeros((cap, 64), np.int16)
    info = jw.decode_into(data, y, cb, cr)
    assert info is not None
    assert (info.width, info.height) == (size, size)
    # truncation honesty: nothing nonzero past the reported extents
    assert not y[: info.y_gw * info.y_gh, info.y_k :].any()
    assert not cb[: info.c_gw * info.c_gh, info.c_k :].any()
    assert not cr[: info.c_gw * info.c_gh, info.c_k :].any()
    k = dct.coef_bucket(max(info.y_k, info.c_k))
    lay = dct.FrameLayout(
        info.width, info.height, info.y_gw, info.y_gh,
        info.c_gw, info.c_gh, info.sub, k,
    )
    out = np.asarray(jax.jit(
        dct.decode_frames, static_argnums=3
    )(
        y[None, : lay.y_blocks, :k],
        cb[None, : lay.c_blocks, :k],
        cr[None, : lay.c_blocks, :k],
        lay,
    ))[0]
    ref = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"), np.float64)
    d = np.abs(out - ref)
    # IDCT in f32 + triangle chroma upsample vs libjpeg's fixed-point
    # path: sub-levels mean error, a few levels worst-case
    assert d.mean() < 1.5, f"mean |d| {d.mean():.3f}"
    assert d.max() <= 8.0, f"max |d| {d.max():.1f}"


def test_wire_reduction_at_real_frame_geometry():
    """The acceptance figure: compressed wire bytes per 224² frame at
    camera quality are ≥5× under raw RGB (raw = 150528 B)."""
    frame = _smooth_frame(224, 1)
    data = _jpeg(frame, 75)
    assert len(data) * 5 <= 224 * 224 * 3, (
        f"jpeg frame {len(data)} B is under 5x smaller than raw"
    )


# ------------------------------------------------- pipeline behaviors
async def test_compressed_coef_path_engaged_end_to_end():
    """JPEG chunks ride the coefficient path: dct codec in flightrec,
    wire/h2d/decode metrics populated, zero fallbacks."""
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        assert pipe.compressed and pipe._native_ok
        size = pipe.image_size
        chunks = [(_jpeg(_smooth_frame(size, s)), "jpeg") for s in range(6)]
        results = await _classify_one_by_one(inst, chunks)
        assert len(results) == 6
        assert all(len(top) == 5 for _seq, top in results)
        m = inst.metrics
        assert m.counter("media_wire_bytes_total", tenant="cam").value > 0
        assert m.counter("media_h2d_bytes_total", tenant="cam").value > 0
        assert m.counter("media_native_decode_fallback_total").value == 0
        assert m.histogram(
            "media_decode_seconds", unit="s", tenant="cam").count >= 1
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert recs and all(r["codec"].startswith("dct") for r in recs)
        assert all(r["wire_bytes"] > 0 for r in recs)
    finally:
        await inst.terminate()


async def test_lossless_png_topk_bitwise_vs_kill_switch():
    """Lossless inputs: compressed-wire top-k must be BITWISE identical
    to the kill-switch (legacy) pipeline's — both decode via PIL, so the
    only acceptable difference is where the decode runs."""
    frames = [_smooth_frame(32, s) for s in range(3)]
    chunks = [(_png(f), "png") for f in frames]
    inst = await _media_instance()
    try:
        assert inst.tenants["cam"].media_pipeline.compressed
        compressed = await _classify_one_by_one(inst, chunks)
    finally:
        await inst.terminate()
    saved = media_mod.MEDIA_WIRE_COMPRESSED_ENABLED
    media_mod.MEDIA_WIRE_COMPRESSED_ENABLED = False
    try:
        inst = await _media_instance()
        try:
            assert not inst.tenants["cam"].media_pipeline.compressed
            legacy = await _classify_one_by_one(inst, chunks)
        finally:
            await inst.terminate()
    finally:
        media_mod.MEDIA_WIRE_COMPRESSED_ENABLED = saved
    assert compressed == legacy  # bitwise: same floats, same classes


async def test_kill_switch_restores_raw_path_bitwise():
    """MEDIA_WIRE_COMPRESSED_ENABLED=False rebuilds the raw-RGB pipeline
    (decoded-frame ring, submit-time decode) and classifies the same raw
    feed bitwise-identically to the compressed byte-ring path."""
    size = 32
    frames = [_smooth_frame(size, s) for s in range(3)]
    chunks = [(f.tobytes(), "raw-rgb8") for f in frames]
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        assert isinstance(pipe._ring, _ByteRing)
        compressed = await _classify_one_by_one(inst, chunks)
    finally:
        await inst.terminate()
    saved = media_mod.MEDIA_WIRE_COMPRESSED_ENABLED
    media_mod.MEDIA_WIRE_COMPRESSED_ENABLED = False
    try:
        inst = await _media_instance()
        try:
            pipe = inst.tenants["cam"].media_pipeline
            assert not pipe.compressed
            assert not isinstance(pipe._ring, _ByteRing)  # _FrameRing
            legacy = await _classify_one_by_one(inst, chunks)
        finally:
            await inst.terminate()
    finally:
        media_mod.MEDIA_WIRE_COMPRESSED_ENABLED = saved
    assert compressed == legacy


async def test_native_absent_degrades_to_pil_counted():
    """A missing native build must degrade the compressed wire to the
    PIL path — frames still classify, fallbacks counted, no errors."""
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        pipe._native_ok = False  # what a toolchain-less host resolves to
        size = pipe.image_size
        chunks = [(_jpeg(_smooth_frame(size, s)), "jpeg") for s in range(3)]
        results = await _classify_one_by_one(inst, chunks)
        assert len(results) == 3
        m = inst.metrics
        assert m.counter("media_native_decode_fallback_total").value >= 3
        assert m.counter("media_frames_bad_total").value == 0
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert recs and all(r["codec"] == "pixels" for r in recs)
    finally:
        await inst.terminate()


async def test_late_native_build_upgrades_pipeline():
    """A pipeline whose start() outran the background cc build must not
    freeze on the PIL path forever: once the build resolves, the next
    batch's nonblocking re-probe upgrades to the coefficient path."""
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        # simulate start() timing out before the build landed
        pipe._native_ok = False
        pipe._native_resolved = False
        size = pipe.image_size
        chunks = [(_jpeg(_smooth_frame(size, s)), "jpeg") for s in range(2)]
        results = await _classify_one_by_one(inst, chunks)
        assert len(results) == 2
        assert pipe._native_ok and pipe._native_resolved  # upgraded
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert recs and all(r["codec"].startswith("dct") for r in recs)
    finally:
        await inst.terminate()


async def test_late_build_never_cold_compiles_a_prewarmed_pipeline():
    """If the pipeline PREWARMED while native was absent, no coefficient
    variant was ever compiled — a late-landing build must keep riding
    PIL (never a 20-40 s cold XLA compile mid-traffic) until prewarm
    re-runs."""
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        pipe._prewarmed = True        # prewarm ran (native absent then)
        pipe._warm_variants = set()   # so zero coef variants compiled
        pipe._native_ok = True        # build landed late
        size = pipe.image_size
        chunks = [(_jpeg(_smooth_frame(size, s)), "jpeg") for s in range(2)]
        results = await _classify_one_by_one(inst, chunks)
        assert len(results) == 2
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert recs and all(r["codec"] == "pixels" for r in recs)
        # a re-run prewarm (native now present) re-opens the coef path
        await asyncio.get_running_loop().run_in_executor(None, pipe.prewarm)
        assert pipe._warm_variants
        chunks2 = [(_jpeg(_smooth_frame(size, s + 7)), "jpeg") for s in range(2)]
        await _classify_one_by_one(inst, chunks2)
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert any(r["codec"].startswith("dct") for r in recs)
    finally:
        await inst.terminate()


async def test_torn_and_short_chunks_counted_not_raised():
    """Satellite regression: torn jpeg mid-stream + short raw chunk are
    counted (media_frames_bad_total) and shed; the pipeline keeps
    classifying subsequent good frames."""
    inst = await _media_instance()
    try:
        rt = inst.tenants["cam"]
        pipe = rt.media_pipeline
        size = pipe.image_size
        topic = media_classifications_topic(inst.bus, "cam")
        inst.bus.subscribe(topic, "t")
        stream = rt.media.create_stream("asn-torn")
        good = _jpeg(_smooth_frame(size, 1))
        # torn jpeg (entropy data cut), short raw, then a good frame —
        # none of these may raise out of submit_chunk
        await pipe.submit_chunk(stream.stream_id, 0, good[: len(good) * 2 // 3], kind="jpeg")
        await pipe.submit_chunk(stream.stream_id, 1, b"short", kind="raw-rgb8")
        await pipe.submit_chunk(stream.stream_id, 2, good, kind="jpeg")
        got = []
        for _ in range(400):
            got.extend(await inst.bus.consume(topic, "t", 10, timeout_s=0.05))
            if any(e["seq"] == 2 for e in got):
                break
        assert any(e["seq"] == 2 for e in got)
        assert all(e["seq"] not in (0, 1) for e in got)
        assert inst.metrics.counter("media_frames_bad_total").value >= 2
        # the torn jpeg fell back to PIL (which also failed) — counted
        assert inst.metrics.counter(
            "media_native_decode_fallback_total").value >= 1
    finally:
        await inst.terminate()


async def test_legacy_torn_jpeg_counted_not_raised():
    """Same satellite on the kill-switch path: a torn jpeg at submit is
    counted and shed instead of raising through submit_chunk."""
    saved = media_mod.MEDIA_WIRE_COMPRESSED_ENABLED
    media_mod.MEDIA_WIRE_COMPRESSED_ENABLED = False
    try:
        inst = await _media_instance()
        try:
            rt = inst.tenants["cam"]
            pipe = rt.media_pipeline
            stream = rt.media.create_stream("asn-lt")
            await pipe.submit_chunk(stream.stream_id, 0, b"\xff\xd8junk", kind="jpeg")
            assert inst.metrics.counter("media_frames_bad_total").value >= 1
            # short raw chunk: counted, no raise (pre-fix it raised)
            await pipe.submit_chunk(stream.stream_id, 1, b"xx", kind="raw-rgb8")
            assert inst.metrics.counter("media_frames_bad_total").value >= 2
        finally:
            await inst.terminate()
    finally:
        media_mod.MEDIA_WIRE_COMPRESSED_ENABLED = saved


def test_sos_reordered_scan_bails_instead_of_crossing_planes():
    """A stream whose SOS lists components in a different order than SOF
    violates B.2.3 — jpegwire must return UNSUPPORTED (we decode MCUs
    positionally; accepting it would entropy-decode Y data into the
    chroma buffers with the wrong tables and publish garbage silently).
    libjpeg/PIL rejects it too, so on the pipeline such a frame is
    counted bad and shed — never classified."""
    from sitewhere_tpu.native import jpegwire as jw

    if jw.jpegwire_lib() is None:
        pytest.skip("no cc toolchain")
    clean = _jpeg(_smooth_frame(32, 1))
    data = bytearray(clean)
    sos = data.find(b"\xff\xda")
    assert sos > 0
    # SOS: FF DA len(2) ns(1) then (Cs, Td/Ta) pairs — swap comps 2 & 3
    base = sos + 5
    data[base + 2], data[base + 4] = data[base + 4], data[base + 2]
    data[base + 3], data[base + 5] = data[base + 5], data[base + 3]
    cap = 64
    y = np.zeros((cap, 64), np.int16)
    c = np.zeros((cap, 64), np.int16)
    rc = np.zeros(1, np.int64)
    assert jw.decode_into(bytes(data), y, c, c.copy(), rc_out=rc) is None
    assert rc[0] == jw.SW_UNSUPPORTED
    # the untouched stream decodes fine with the same buffers
    assert jw.decode_into(clean, y, c, c.copy(), rc_out=rc) is not None


async def test_chroma_buffers_upgrade_on_444_stream():
    """Decode buffers are sized for the 4:2:0 camera default; the SOF
    peek detects a 4:4:4 stream before any entropy decode, upgrades the
    cached mode, and the very first batch already rides the coefficient
    path with full-grid chroma buffers — zero fallbacks, zero wasted
    decodes."""
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        assert pipe._coef_sub == 2
        assert pipe._chroma_cap_blocks * 4 == pipe._coef_cap_blocks
        size = pipe.image_size
        # quality 70: these seeds' spectral extents stay ≤ 32, so the
        # 4:4:4 coefficient payload fits the oversize guard (k=64 at
        # 4:4:4 would exceed raw bytes and ride pixels BY DESIGN)
        chunks = [
            (_jpeg(_smooth_frame(size, s), quality=70, subsampling=0), "jpeg")
            for s in range(4)
        ]
        results = await _classify_one_by_one(inst, chunks)
        assert len(results) == 4
        assert pipe._coef_sub == 1  # upgraded by the SOF peek
        assert pipe._chroma_cap_blocks == pipe._coef_cap_blocks
        m = inst.metrics
        assert m.counter("media_native_decode_fallback_total").value == 0
        assert m.counter("media_frames_bad_total").value == 0
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert recs and all(r["codec"].startswith("dct") for r in recs)
    finally:
        await inst.terminate()


async def test_444_oversize_stream_stops_paying_entropy_decode():
    """A 4:4:4 stream whose full-precision payload exceeds raw pixels
    loses the size guard; after two rejected attempts the SOF-peek
    hysteresis routes it straight to PIL — no recurring wasted Huffman
    pass per batch."""
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        size = pipe.image_size
        # quality 95 at 4:4:4: spectral extent hits k=64 → payload 2x raw
        chunks = [
            (_jpeg(_smooth_frame(size, s), quality=95, subsampling=0), "jpeg")
            for s in range(4)
        ]
        results = await _classify_one_by_one(inst, chunks)
        assert len(results) == 4
        assert pipe._sub1_rejects >= 2  # hysteresis latched
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert recs and all(r["codec"] == "pixels" for r in recs)
        assert inst.metrics.counter(
            "media_native_decode_fallback_total").value >= 4
    finally:
        await inst.terminate()


async def test_offsize_stream_skips_native_attempt():
    """A camera posting frames at a size ≠ the classifier's must not
    pay a wasted entropy decode per batch: the SOF peek routes the
    batch straight to the PIL path (which resizes), counted once per
    frame as a native fallback."""
    inst = await _media_instance()
    try:
        pipe = inst.tenants["cam"].media_pipeline
        size = pipe.image_size
        chunks = [(_jpeg(_smooth_frame(size * 2, s)), "jpeg") for s in range(3)]
        results = await _classify_one_by_one(inst, chunks)
        assert len(results) == 3
        m = inst.metrics
        assert m.counter("media_native_decode_fallback_total").value >= 3
        assert m.counter("media_frames_bad_total").value == 0
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        assert recs and all(r["codec"] == "pixels" for r in recs)
    finally:
        await inst.terminate()


def test_peek_geometry_contract():
    from sitewhere_tpu.native import jpegwire as jw

    f = _smooth_frame(32, 1)
    assert jw.peek_geometry(_jpeg(f)) == (32, 32, 2)
    assert jw.peek_geometry(_jpeg(f, subsampling=0)) == (32, 32, 1)
    assert jw.peek_geometry(_png(f)) is None
    assert jw.peek_geometry(b"") is None
    # progressive streams peek as unsupported (no native attempt)
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(f).save(buf, format="JPEG", progressive=True)
    assert jw.peek_geometry(buf.getvalue()) is None


def test_buffer_pools_are_thread_safe():
    """Compressed-mode decode runs on up to max_inflight executor
    threads concurrently while returns land on the loop thread — the
    pooled check-then-pop must never race into 'pop from empty deque'
    (which would silently drop a whole popped batch)."""
    import threading

    from sitewhere_tpu.pipeline.media import MediaClassificationPipeline
    from sitewhere_tpu.runtime.bus import EventBus
    from sitewhere_tpu.services.streaming_media import StreamingMedia

    async def build():
        return MediaClassificationPipeline(
            "t", EventBus(), StreamingMedia("t"),
            MetricsRegistry(), tiny=True, max_batch=4,
        )

    pipe = asyncio.run(build())
    errors = []

    def hammer(seed):
        rng = np.random.RandomState(seed)
        try:
            for _ in range(400):
                which = rng.randint(4)
                if which == 0:
                    pipe._return_staging(pipe._checkout_staging())
                elif which == 1:
                    pipe._return_bytes(pipe._checkout_bytes(1024))
                elif which == 2:
                    pipe._return_coefs(pipe._checkout_coefs())
                else:
                    lay = pipe._expected_layout(2, 16)
                    pipe._return_packed(
                        4, lay, pipe._checkout_packed(4, lay))
        except Exception as exc:  # noqa: BLE001 - the race under test
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(s,)) for s in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


def test_decode_flops_formula_and_scale():
    """The analytic decode-FLOPs figure (bench attribution column) must
    match a hand count and stay negligible next to the model forward —
    the reason it is KEPT OUT of the ViT MFU numerator."""
    from sitewhere_tpu.models.common import vit_flops_per_image
    from sitewhere_tpu.models.vit import VIT_B16
    from sitewhere_tpu.ops.dct import decode_flops_per_frame, layout_for

    lay = layout_for(224, 224, 2, 64)
    n_blocks = 28 * 28 + 2 * 14 * 14
    hand = n_blocks * (2 * 64 * 64 + 2 * 2 * 8 * 8 * 8)
    assert decode_flops_per_frame(lay) == hand
    assert decode_flops_per_frame(lay) < 0.0004 * vit_flops_per_image(VIT_B16)


# ------------------------------------------------------- lints & gating
def test_dct_fusion_lint_clean_and_catches():
    import importlib.util as iu
    from pathlib import Path

    spec = iu.spec_from_file_location(
        "check_fusion",
        Path(__file__).resolve().parent.parent / "tools" / "check_fusion.py",
    )
    mod = iu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.lint_dct() == []
    # an impossible layout must surface as a trace-failure finding, not
    # silently pass (the registry-rot contract every lint here keeps)
    findings = mod.lint_dct({"bogus": (3, 1000)})
    assert findings and "failed to trace" in findings[0]
