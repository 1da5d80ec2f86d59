"""One (family, mesh slice) as an object (``pipeline/slices.py``).

A slice is born whole in ``scorer_for_slice`` and dies with the service;
the flush policy (``due`` / ``held`` / ``in_flight``), the staging pool,
the deadline and the quarantine resets are its own rules, tested here on
a bare ``SliceRuntime`` with stand-in flushes — no device, no traffic.
The service's three public views read the same table.
"""

import asyncio
import time

import numpy as np
import pytest

from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.pipeline.inference import (
    AmbiguousFamilyError,
    TpuInferenceService,
)
from sitewhere_tpu.pipeline.slices import (
    SliceRuntime,
    _empty_taken,
    _LaneRing,
    _PendingFlush,
    _ReapQueue,
    _TrainLaneRing,
)
from sitewhere_tpu.runtime.bus import CircuitBreaker, EventBus
from sitewhere_tpu.runtime.config import (
    FaultTolerancePolicy,
    MicroBatchConfig,
    tenant_config_from_template,
)
from sitewhere_tpu.runtime.metrics import MetricsRegistry, RollingQuantile

MB = MicroBatchConfig(max_batch=64, deadline_ms=5.0, buckets=(16, 64), window=8)


class _Scores:
    """A dispatch output that has landed, or has not."""

    def __init__(self, ready: bool) -> None:
        self.ready = ready

    def is_ready(self) -> bool:
        return self.ready


class _Scorer:
    """What ``_StagingSet`` asks of a scorer: its shapes and wire dtypes."""

    n_slots = 2
    ids_np_dtype = np.uint16
    vals_np_dtype = np.float32

    class mm:
        n_data_shards = 2


def _bare(metrics=None) -> SliceRuntime:
    metrics = metrics or MetricsRegistry()
    breaker = CircuitBreaker(
        "test.s0", policy=FaultTolerancePolicy(), metrics=metrics
    )
    return SliceRuntime(
        "lstm_ad", 0, _Scorer(), breaker, metrics,
        max_inflight=3, staging_slots=2,
    )


def _flush(ready: bool, lane: str = "serve", poisoned: bool = False):
    return _PendingFlush(
        "lstm_ad", None if poisoned else _Scores(ready), _empty_taken(), 0,
        False, 0, 0, poisoned=poisoned, lane=lane,
    )


def _rows(s: SliceRuntime, n: int, slot: int = 0, dshard: int = 0) -> None:
    s.lane(slot, dshard, 64).push(
        np.arange(n, dtype=np.int32), np.ones((n,), np.float32), 7,
        np.arange(n, dtype=np.int32),
    )
    s.mark_pending()


def _cfg(tenant: str):
    return tenant_config_from_template(
        tenant, "iot-temperature", microbatch=MB, max_streams=8,
        wire_dtype="f32", model_config={"hidden": 8},
    )


# ------------------------------------------------------ birth and death
async def test_slice_is_born_whole_and_dies_with_the_service():
    import jax

    svc = TpuInferenceService(
        EventBus(),
        mm=MeshManager(tenant=1, data=1, devices=jax.devices()[:1]),
        slots_per_shard=1, max_inflight=3,
    )
    await svc.start()
    try:
        scorer = svc.scorer_for_slice("lstm_ad", 0, _cfg("acme"))
        assert list(svc._slices) == [("lstm_ad", 0)]
        s = svc._slices[("lstm_ad", 0)]
        # every attribute exists from the first moment: nothing is made
        # by a setdefault at first use any more
        for name in SliceRuntime.__slots__:
            assert hasattr(s, name), name
        assert (s.family, s.sl) == ("lstm_ad", 0) and s.scorer is scorer
        assert isinstance(s.breaker, CircuitBreaker)
        assert isinstance(s.reap, _ReapQueue) and not s.reap
        assert isinstance(s.flush_p99, RollingQuantile)
        assert isinstance(s.permits, asyncio.Semaphore)
        assert s.permits._value == svc.max_inflight == 3
        assert (s.resolving, s.probing, s.quarantine) == (None, None, None)
        assert (s.first_pending_ts, s.last_scores) == (None, None)
        assert (s.consec_errors, s.lane_swap, s.last_landed) == (0, 0, 0.0)
        assert s.last_train_losses is None and s.lane_last_source is None
        # only what traffic sizes comes later, and inside the slice
        assert s.lanes == {} and s.train_lanes == {} and s.staging == {}
        assert s.seen_shapes == set() and s.train_ticks == {}
        assert s.mfu is None   # one device: the family account says it all
        # asking again builds nothing
        assert svc.scorer_for_slice("lstm_ad", 0, _cfg("acme")) is scorer
        assert len(svc._slices) == 1
        # none of the per-slice dictionaries is the service's any more
        for gone in (
            "_mfu_dev", "_last_landed", "_lanes", "_staging", "_last_scores",
            "_first_pending_ts", "_train_ticks", "_train_lanes", "_lane_swap",
            "_lane_last_source", "_consec_errors", "_inflight", "_reap",
            "_resolving", "_flush_p99", "_quarantined", "_probing",
        ):
            assert not hasattr(svc, gone), gone
    finally:
        await svc.terminate()
    assert svc._slices == {}, "on_stop must leave no slice behind"
    assert len(svc.scorers) == len(svc.breakers) == 0


async def test_lanes_and_staging_appear_inside_the_slice():
    metrics = MetricsRegistry()
    s = _bare(metrics)
    ring = s.lane(1, 0, 128)
    assert isinstance(ring, _LaneRing) and ring.capacity == 128
    assert s.lane(1, 0, 4096) is ring, "a lane is made once"
    assert isinstance(s.train_lane(1, 1), _TrainLaneRing)
    assert set(s.lanes) == {(1, 0)} and set(s.train_lanes) == {(1, 1)}
    # two sets rotate per bucket; a second bucket has its own rotation
    a, b, c = s.staging_set(16), s.staging_set(16), s.staging_set(16)
    assert a is not b and c is a
    assert a.ids.shape == (2, 2 * 16) and a.counts.shape == (2, 2)
    assert s.staging_set(64) is not a and set(s.staging) == {16, 64}
    assert metrics.gauge("tpu_inference_staging_sets").value == 4


# ------------------------------------------------------ the flush policy
def test_due_is_a_full_lane_or_the_collect_deadline():
    s = _bare()
    assert not s.due(MB), "nothing pending"
    _rows(s, 3)
    assert not s.due(MB), "under the bucket, inside the deadline"
    s.first_pending_ts = time.perf_counter() - 0.006
    assert s.due(MB), "the collect deadline"
    s.first_pending_ts = time.perf_counter()
    _rows(s, MB.max_batch - 3)
    assert s.due(MB), "a full lane is due at once"


def _no_flush(s):
    pass


def _train_only(s):
    s.reap.append(_flush(False, lane="train"))


def _landed_unresolved(s):
    s.reap.append(_flush(True))


def _serve_in_flight(s):
    s.reap.append(_flush(False))


def _lane_at_smallest_bucket(s):
    _serve_in_flight(s)
    _rows(s, MB.buckets[0], slot=1)


def _quarantined(s):
    _serve_in_flight(s)
    s.enter_quarantine("test", 1.0)


def _breaker_open(s):
    _serve_in_flight(s)
    s.breaker.trip()


@pytest.mark.parametrize("arrange, parked, want", [
    (_no_flush, False, False),
    (_train_only, False, False),
    (_landed_unresolved, False, False),
    (_serve_in_flight, False, True),
    (_lane_at_smallest_bucket, False, False),
    (_serve_in_flight, True, False),
    (_quarantined, False, False),
    (_breaker_open, False, False),
], ids=[
    "no-flush-in-flight", "train-lane-flush-only",
    "serve-flush-landed-but-unresolved", "serve-flush-in-flight",
    "lane-at-buckets0", "parked", "quarantined", "breaker-open",
])
def test_held_truth_table(arrange, parked, want):
    s = _bare()
    _rows(s, 3)
    arrange(s)
    assert s.held(MB, parked) is want


def test_in_flight_excludes_a_landed_head_and_a_poisoned_entry():
    s = _bare()
    landed, poisoned, flying, train = (
        _flush(True), _flush(False, poisoned=True), _flush(False),
        _flush(False, lane="train"),
    )
    s.reap.extend([landed, poisoned, flying, train])
    assert s.in_flight() == [flying, train]
    flying.resolved = True   # resolved, not yet popped by its finally
    assert s.in_flight() == [train]


def test_pick_bucket_walks_the_ladder():
    pick = SliceRuntime.pick_bucket
    assert pick(1, (16, 64), 64) == 16 and pick(16, (16, 64), 64) == 16
    assert pick(17, (16, 64), 64) == 64 and pick(500, (16, 64), 64) == 64
    assert pick(10, (16, 64), 8) == 8, "a bucket never exceeds max_batch"


# ---------------------------------------------------- flush supervision
def test_flush_deadline_follows_the_slices_own_p99():
    metrics = MetricsRegistry()
    s = _bare(metrics)
    ft = FaultTolerancePolicy(flush_deadline_ms=100.0, flush_deadline_x=4.0)
    off = FaultTolerancePolicy(flush_deadline_ms=0.0)
    assert s.flush_deadline_s(off) is None, "the rollback knob"
    assert s.flush_deadline_s(ft) == pytest.approx(0.1), "no history: floor"
    for _ in range(RollingQuantile.MIN_SAMPLES):
        s.note_device_s(0.5)
    assert s.flush_deadline_s(ft) == pytest.approx(2.0)
    g = metrics.gauge("tpu_flush_latency_p99_ms", family="lstm_ad", slice="0")
    assert g.value == pytest.approx(500.0)


# ------------------------------------------------- quarantine, probation
async def test_quarantine_then_readmission_returns_the_born_state():
    s = _bare()
    assert s.enter_quarantine("flush-timeout", 2.0)
    q = s.quarantine
    assert q["reason"] == "flush-timeout" and q["ok_probes"] == 0
    assert q["next_probe"] > time.monotonic()
    assert not s.enter_quarantine("again", 2.0), "idempotent"
    assert s.quarantine is q
    s.consec_errors = 2
    s.breaker.trip()
    s.readmit()
    assert s.quarantine is None and s.consec_errors == 0
    assert s.breaker.state == "closed"
    # the operator's way out cancels the probe in flight, and is a no-op
    # on a healthy slice
    assert not s.clear_quarantine()
    s.enter_quarantine("scorer-errors", 2.0)
    s.probing = probe = asyncio.ensure_future(asyncio.sleep(60))
    assert s.clear_quarantine()
    assert s.quarantine is None and s.probing is None
    await asyncio.sleep(0)
    assert probe.cancelled()


def test_a_leaving_tenant_takes_its_training_rows_and_tick():
    s = _bare()
    for slot in (0, 1):
        s.train_lane(slot, 0).push(
            np.zeros((5,), np.int32), np.ones((5,), np.float32), 0,
            np.zeros((5,), np.int32),
        )
        s.train_ticks[slot] = 9
    assert s.forget_slot_training(0) == 5
    assert set(s.train_lanes) == {(1, 0)} and s.train_ticks == {1: 9}
    assert s.forget_slot_training(0) == 0


def test_drain_lanes_hands_back_every_pending_row_once():
    s = _bare()
    _rows(s, 3, slot=0)
    _rows(s, 4, slot=1, dshard=1)
    s.lane(1, 0, 64)   # an empty lane yields nothing
    _rows(s, 2, slot=2)
    # one slot's lanes only (a tenant leaving), then all of them
    (d, ids, vals, seqs, rows), = s.drain_lanes(1)
    assert (d, seqs.tolist(), rows.tolist()) == (1, [7] * 4, [0, 1, 2, 3])
    assert ids.tolist() == [0, 1, 2, 3] and vals.tolist() == [1.0] * 4
    assert set(s.lanes) == {(0, 0), (2, 0)}
    drained = [
        (d, seqs.tolist(), rows.tolist())
        for d, _ids, _vals, seqs, rows in s.drain_lanes()
    ]
    assert drained == [(0, [7] * 3, [0, 1, 2]), (0, [7] * 2, [0, 1])]
    assert s.lanes == {} and list(s.drain_lanes()) == []


# --------------------------------------------------- the public views
async def test_views_resolve_one_slice_by_string_and_refuse_two():
    svc = TpuInferenceService(
        EventBus(), mm=MeshManager(tenant=2, data=4), slots_per_shard=1,
    )
    await svc.start()
    try:
        sc0 = svc.scorer_for_slice("lstm_ad", 0, _cfg("acme"))
        s0 = svc._slices[("lstm_ad", 0)]
        assert svc.scorers["lstm_ad"] is sc0 is svc.scorers[("lstm_ad", 0)]
        assert svc.breakers["lstm_ad"] is s0.breaker
        # several devices: the slice carries its chip's MFU account
        assert s0.mfu is not None
        assert "lstm_ad" in svc.scorers and ("lstm_ad", 0) in svc.scorers
        assert "deepar" not in svc.scorers and ("lstm_ad", 1) not in svc.scorers
        assert svc.scorers.get("deepar") is None
        with pytest.raises(KeyError):
            svc.scorers["deepar"]
        # a slice with no train step yet is not among the losses
        assert "lstm_ad" not in svc.last_train_losses
        assert len(svc.last_train_losses) == 0
        assert svc.last_train_losses.get(("lstm_ad", 0)) is None
        s0.last_train_losses = losses = np.zeros((1,), np.float32)
        assert svc.last_train_losses["lstm_ad"] is losses
        assert dict(svc.last_train_losses.items()) == {("lstm_ad", 0): losses}
        # a second slice of the family: the string no longer names one
        sc1 = svc.scorer_for_slice("lstm_ad", 1, _cfg("acme"))
        assert list(svc.scorers) == [("lstm_ad", 0), ("lstm_ad", 1)]
        assert list(svc.scorers.values()) == [sc0, sc1]
        assert svc.scorers.family_items("lstm_ad") == [(0, sc0), (1, sc1)]
        assert "lstm_ad" in svc.scorers
        for view in (svc.scorers, svc.breakers):
            with pytest.raises(AmbiguousFamilyError):
                view["lstm_ad"]
            with pytest.raises(AmbiguousFamilyError):
                view.get("lstm_ad")   # never defaulted: it is not absent
        assert svc.last_train_losses["lstm_ad"] is losses, (
            "one slice has losses: the string still names it")
        assert svc.quarantined_slices() == 0
        s0.enter_quarantine("test", 1.0)
        assert svc.quarantined_slices() == 1
        assert list(svc.describe()["quarantined"]) == ["lstm_ad@0"]
    finally:
        await svc.terminate()
