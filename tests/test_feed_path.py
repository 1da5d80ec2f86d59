"""Zero-copy feed path unit tests (docs/PERFORMANCE.md): lane rings,
reusable staging sets, the media frame ring, and the hot-path AST lint.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from sitewhere_tpu.pipeline.slices import _LaneRing, _StagingSet
from sitewhere_tpu.pipeline.media import _FrameRing
from sitewhere_tpu.runtime.metrics import MetricsRegistry

_spec = importlib.util.spec_from_file_location(
    "check_hotpath",
    Path(__file__).resolve().parent.parent / "tools" / "check_hotpath.py",
)
check_hotpath = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_hotpath)


# ------------------------------------------------------------ lane rings
def test_lane_ring_fifo_and_pop():
    r = _LaneRing(capacity=64)
    r.push(np.r_[1, 2, 3].astype(np.int32), np.r_[1.0, 2.0, 3.0], 7, np.r_[0, 1, 2])
    r.push(np.r_[4].astype(np.int32), np.r_[4.0], 8, np.r_[0])
    assert r.count == 4
    ids, vals, seqs, rows = r.pop(3)
    np.testing.assert_array_equal(ids, [1, 2, 3])
    np.testing.assert_array_equal(seqs, [7, 7, 7])
    ids, vals, seqs, rows = r.pop(10)  # only 1 left
    np.testing.assert_array_equal(ids, [4])
    np.testing.assert_array_equal(seqs, [8])
    assert r.count == 0


def test_lane_ring_wraparound_preserves_order():
    r = _LaneRing(capacity=64)  # floors at 64
    seen = []
    pushed = 0
    rng = np.random.RandomState(0)
    for round_i in range(40):
        n = int(rng.randint(1, 17))
        ids = (np.arange(n) + pushed).astype(np.int32)
        r.push(ids, ids.astype(np.float32), round_i, ids)
        pushed += n
        k = int(rng.randint(0, r.count + 1))
        got = r.pop(k)
        seen.extend(got[0].tolist())
    seen.extend(r.pop(r.count)[0].tolist())
    np.testing.assert_array_equal(seen, np.arange(pushed))


def test_lane_ring_growth_keeps_pending_rows():
    r = _LaneRing(capacity=64)
    r.push(np.arange(50, dtype=np.int32), np.zeros(50, np.float32), 1,
           np.arange(50, dtype=np.int32))
    r.pop(40)  # head now mid-ring
    big = np.arange(200, dtype=np.int32)
    r.push(big, big.astype(np.float32), 2, big)  # forces a grow
    assert r.capacity >= 210 and r.count == 210
    ids, _v, seqs, _r = r.pop(210)
    np.testing.assert_array_equal(ids[:10], np.arange(40, 50))
    np.testing.assert_array_equal(ids[10:], big)
    np.testing.assert_array_equal(seqs[:10], 1)
    np.testing.assert_array_equal(seqs[10:], 2)


def test_lane_ring_pop_into_staging_slices():
    r = _LaneRing(capacity=64)
    # wrap the ring first
    r.push(np.arange(60, dtype=np.int32), np.zeros(60, np.float32), 0,
           np.arange(60, dtype=np.int32))
    r.pop(58)
    ids0 = np.arange(100, 130, dtype=np.int32)
    r.push(ids0, ids0.astype(np.float32), 3, ids0)
    assert r.head + r.count > r.capacity  # genuinely wrapped
    ids_row = np.zeros((64,), np.uint16)  # staging slot row (wire dtype)
    vals_row = np.zeros((64,), np.float32)
    seqs = np.empty((32,), np.int64)
    rows = np.empty((32,), np.int32)
    k = r.count
    r.pop_into(k, ids_row, vals_row, 8, seqs, rows, 0)
    np.testing.assert_array_equal(ids_row[8 : 8 + 2], [58, 59])
    np.testing.assert_array_equal(ids_row[10 : 8 + k], ids0)
    np.testing.assert_array_equal(rows[2:k], ids0)
    assert r.count == 0


def test_staging_set_reuse_with_non_jax_arrays_is_noop():
    class FakeScorer:
        n_slots = 2
        ids_np_dtype = np.uint16
        vals_np_dtype = np.float32

        class mm:
            n_data_shards = 1

    st = _StagingSet(FakeScorer(), 8)
    st.staged = (np.zeros(3), np.zeros(3), np.zeros(1))
    st.ensure_reusable(MetricsRegistry())  # numpy has no is_ready: no raise
    assert st.staged is None
    st.ensure_reusable(MetricsRegistry())  # None: no-op


# ------------------------------------------------------------ frame ring
def test_frame_ring_contiguous_pop_and_metas():
    m = MetricsRegistry()
    ring = _FrameRing(8, 4, m)
    for i in range(5):
        ring.reserve()[...] = np.full((4, 4, 3), i, np.uint8)
        ring.commit(f"s{i}", i, float(i))
    staging = np.zeros((4, 4, 4, 3), np.uint8)
    metas = ring.pop_into(staging, 4)
    assert [mt[1] for mt in metas] == [0, 1, 2, 3]
    for j in range(4):
        assert (staging[j] == j).all()
    assert ring.qsize() == 1


def test_frame_ring_sheds_oldest_when_full():
    m = MetricsRegistry()
    ring = _FrameRing(4, 4, m)
    for i in range(7):
        ring.reserve()[...] = np.full((4, 4, 3), i, np.uint8)
        ring.commit("s", i, 0.0)
    assert m.counter("media_frames_shed_total").value == 3
    assert ring.qsize() == 4
    staging = np.zeros((4, 4, 4, 3), np.uint8)
    # oldest three were shed: newest four survive, in order (the shed
    # advanced the head mid-ring, so they drain across the wrap)
    metas = ring.pop_into(staging, 4) + ring.pop_into(staging, 4)
    assert [mt[1] for mt in metas] == [3, 4, 5, 6]


def test_frame_ring_wrap_remainder_rides_next_batch():
    m = MetricsRegistry()
    ring = _FrameRing(4, 4, m)
    for i in range(3):
        ring.reserve()[...] = i
        ring.commit("s", i, 0.0)
    staging = np.zeros((4, 4, 4, 3), np.uint8)
    ring.pop_into(staging, 3)  # head now at 3
    for i in range(3, 6):
        ring.reserve()[...] = i
        ring.commit("s", i, 0.0)
    metas = ring.pop_into(staging, 4)  # contiguous span is just slot 3
    assert [mt[1] for mt in metas] == [3]
    metas = ring.pop_into(staging, 4)  # wrapped remainder
    assert [mt[1] for mt in metas] == [4, 5]


# ------------------------------------------------------------ hotpath lint
def test_check_hotpath_lint_is_clean():
    assert check_hotpath.lint_hotpaths() == []


def test_check_hotpath_catches_violations(tmp_path):
    bad = tmp_path / "hot.py"
    bad.write_text(
        "import numpy as np\n"
        "def flush(items):\n"
        "    out = []\n"
        "    for it in items:\n"
        "        out.append(it.value)\n"
        "    arr = np.asarray(out, np.float32)\n"
        "    ids = np.char.add('p', arr.astype(str))\n"
        "    cols = np.stack([x for x in items])\n"
        "    return arr, ids, cols\n"
    )
    findings = check_hotpath.lint_hotpaths(
        {"hot.py": ["flush"]}, src_root=tmp_path
    )
    text = "\n".join(findings)
    assert "list accumulator 'out.append'" in text
    assert "np.asarray('out')" in text
    assert "np.char.add" in text
    assert "np.stack(<listcomp>)" in text


def test_check_hotpath_allows_optout_and_flags_stale_registry(tmp_path):
    ok = tmp_path / "hot.py"
    ok.write_text(
        "import numpy as np\n"
        "def cold(items):\n"
        "    out = []\n"
        "    for it in items:\n"
        "        out.append(it)  # hotpath: ok\n"
        "    return out\n"
    )
    findings = check_hotpath.lint_hotpaths(
        {"hot.py": ["cold", "vanished"]}, src_root=tmp_path
    )
    assert len(findings) == 1 and "stale HOT_PATHS" in findings[0]


# --------------------------------------------------- flush integration
async def test_flush_uses_staging_and_records_feed_metrics():
    """One real flush through TpuInferenceService must pack via the
    rotating staging sets, stage to device, and record the feed-path
    metrics (assembly + h2d histograms, lane depth gauge)."""
    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig

    inst = SiteWhereInstance(InstanceConfig(
        instance_id="feed", mesh=MeshConfig(slots_per_shard=2),
    ))
    await inst.start()
    try:
        await inst.tenant_management.create_tenant(
            "feed", template="iot-temperature", decoder="binary",
        )
        await inst.drain_tenant_updates()
        import asyncio

        for _ in range(200):
            if "feed" in inst.tenants:
                break
            await asyncio.sleep(0.02)
        rt = inst.tenants["feed"]
        devs = rt.device_management.bootstrap_fleet(4)
        toks = [d.token for d in devs]
        from sitewhere_tpu.core.batch import MeasurementBatch

        batch = MeasurementBatch.from_columns(
            "feed", [toks[i % 4] for i in range(64)],
            ["temperature"] * 64, [float(i) for i in range(64)], [0.0] * 64,
        )
        await inst.bus.publish(inst.bus.naming.decoded_events("feed"), batch)
        scored = inst.metrics.counter("tpu_inference.scored_total")
        for _ in range(400):
            if scored.value >= 64:
                break
            await asyncio.sleep(0.02)
        assert scored.value >= 64
        assert inst.metrics.counter("tpu_inference.h2d_staged").value >= 1
        assert inst.metrics.counter("tpu_inference.staged_bytes").value > 0
        hist = inst.metrics.histogram("tpu_inference.flush_assembly", unit="s")
        assert hist.summary()["count"] >= 1
        # staging sets exist and rotated for the family
        svc = inst.inference
        assert any(
            s.family == "lstm_ad" and s.staging for s in svc._slices.values()
        )
        # ...and the result path reaped the flush through the device-side
        # gather: d2h volume is rows-sized, never MORE than the slice's
        # T×lane plane (with per-slice serving the plane itself is small
        # — a slice at/below the gather floor transfers exactly plane)
        assert inst.metrics.counter("tpu_inference.reaped").value >= 1
        d2h = inst.metrics.counter("tpu_inference.d2h_bytes").value
        plane = inst.metrics.counter("tpu_inference.d2h_plane_bytes").value
        assert 0 < d2h <= plane
        assert inst.metrics.gauge("tpu_inference_deliver_inflight").value == 0
    finally:
        await inst.terminate()
