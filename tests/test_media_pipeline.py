"""Streaming-media → ViT pipeline: chunks → frame decode → micro-batched
classification → events on the bus (the service must FLOW, not just
store chunks)."""

import asyncio
import io

import numpy as np

from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.pipeline.media import media_classifications_topic
from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig


async def _media_instance():
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="med", mesh=MeshConfig(slots_per_shard=2),
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


def _raw_chunk(size: int, seed: int) -> bytes:
    rng = np.random.RandomState(seed)
    return rng.randint(0, 255, (size, size, 3), np.uint8).tobytes()


async def test_chunks_flow_to_classification_events():
    inst = await _media_instance()
    try:
        rt = inst.tenants["cam"]
        pipe = rt.media_pipeline
        assert pipe is not None and pipe.tiny
        topic = media_classifications_topic(inst.bus, "cam")
        inst.bus.subscribe(topic, "test")
        stream = rt.media.create_stream("asn-1", content_type="video/raw")
        size = pipe.image_size
        for seq in range(20):
            await pipe.submit_chunk(stream.stream_id, seq, _raw_chunk(size, seq))
        got: list = []
        for _ in range(200):
            got.extend(await inst.bus.consume(topic, "test", 100, timeout_s=0.05))
            if len(got) >= 20:
                break
        assert len(got) >= 20
        ev = got[0]
        assert ev["type"] == "media_classification"
        assert ev["stream_id"] == stream.stream_id
        assert len(ev["top_k"]) == 5
        assert all(0.0 <= p <= 1.0 for _, p in ev["top_k"])
        # chunks also landed in the store (playback parity preserved)
        assert len(rt.media.get_stream(stream.stream_id).chunks) == 20
        # latency histogram filled
        assert inst.metrics.counter("media.frames_classified").value >= 20
    finally:
        await inst.terminate()


async def test_jpeg_chunks_decode_and_classify():
    from PIL import Image

    inst = await _media_instance()
    try:
        rt = inst.tenants["cam"]
        pipe = rt.media_pipeline
        topic = media_classifications_topic(inst.bus, "cam")
        inst.bus.subscribe(topic, "test")
        stream = rt.media.create_stream("asn-2", content_type="image/jpeg")
        rng = np.random.RandomState(0)
        buf = io.BytesIO()
        Image.fromarray(
            rng.randint(0, 255, (64, 64, 3), np.uint8)
        ).save(buf, format="JPEG")
        await pipe.submit_chunk(stream.stream_id, 0, buf.getvalue(), kind="jpeg")
        got: list = []
        for _ in range(200):
            got.extend(await inst.bus.consume(topic, "test", 10, timeout_s=0.05))
            if got:
                break
        assert got and got[0]["seq"] == 0
    finally:
        await inst.terminate()


async def test_bad_chunk_does_not_kill_pipeline():
    inst = await _media_instance()
    try:
        rt = inst.tenants["cam"]
        pipe = rt.media_pipeline
        topic = media_classifications_topic(inst.bus, "cam")
        inst.bus.subscribe(topic, "test")
        stream = rt.media.create_stream("asn-3")
        # short raw chunk raises at submit — caller's error, loop unharmed
        try:
            await pipe.submit_chunk(stream.stream_id, 0, b"short")
        except ValueError:
            pass
        await pipe.submit_chunk(
            stream.stream_id, 1, _raw_chunk(pipe.image_size, 1)
        )
        got: list = []
        for _ in range(200):
            got.extend(await inst.bus.consume(topic, "test", 10, timeout_s=0.05))
            if got:
                break
        assert got and got[0]["seq"] == 1
    finally:
        await inst.terminate()


async def test_classify_dispatch_materialize_split_matches_sync():
    """The async readback halves (dispatch + topk_results) must agree
    with the one-shot classify_frames — same jit, same top-k — and the
    pipeline flow through them records the media d2h metrics."""
    inst = await _media_instance()
    try:
        rt = inst.tenants["cam"]
        media = rt.media
        size = rt.media_pipeline.image_size
        rng = np.random.RandomState(7)
        frames = rng.randint(0, 255, (3, size, size, 3), np.uint8)
        sync = media.classify_frames(frames, top_k=4, tiny=True)
        pv, iv = media.classify_frames_dispatch(frames, top_k=4, tiny=True)
        split = media.topk_results(pv, iv, 3)
        assert split == sync
        # n-slicing drops padded rows
        assert len(media.topk_results(pv, iv, 2)) == 2
        # drive one batch through the pipeline: the d2h wait histogram
        # must populate (overlap counter is rig-dependent, not asserted)
        topic = media_classifications_topic(inst.bus, "cam")
        inst.bus.subscribe(topic, "test")
        stream = rt.media.create_stream("asn-split", content_type="video/raw")
        await rt.media_pipeline.submit_chunk(
            stream.stream_id, 0, _raw_chunk(size, 3)
        )
        got: list = []
        for _ in range(200):
            got.extend(await inst.bus.consume(topic, "test", 10, timeout_s=0.05))
            if got:
                break
        assert got
        assert inst.metrics.histogram("media.d2h_wait", unit="s").count >= 1
    finally:
        await inst.terminate()
