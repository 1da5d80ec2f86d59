"""Result-path tests (docs/PERFORMANCE.md "Result path"): device-side
score gather correctness, the completion reaper's ordering guarantees
(out of order across families, FIFO per tenant) and failure edges
(poisoned transfer, teardown with a stuck transfer — zero loss), and the
blocking-materialization hot-path lint rule."""

import asyncio
import importlib.util
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.parallel.sharded import ShardedScorer
from sitewhere_tpu.runtime.config import (
    InstanceConfig,
    MeshConfig,
    MicroBatchConfig,
)

_spec = importlib.util.spec_from_file_location(
    "check_hotpath",
    Path(__file__).resolve().parent.parent / "tools" / "check_hotpath.py",
)
check_hotpath = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_hotpath)


# ------------------------------------------------------- device-side gather
def _make_scorer(tenant_axis=4, data_axis=2, slots_per_shard=1):
    mm = MeshManager(tenant=tenant_axis, data=data_axis)
    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {"window": 8, "hidden": 8})
    return mm, ShardedScorer(
        mm, spec, cfg, slots_per_shard=slots_per_shard,
        max_streams=64, window=8,
    )


def test_gather_rows_matches_host_pick():
    """gather_rows must return exactly the flushed rows the host would
    have picked from the plane, in (slot, data-shard, lane-pos) order,
    with NaN padding past the row count."""
    mm, sc = _make_scorer()
    for i in range(sc.n_slots):
        sc.activate(i)
    t, d, b = sc.n_slots, mm.n_data_shards, 8
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 16, (t, d * b)).astype(sc.ids_np_dtype)
    vals = rng.randn(t, d * b).astype(sc.vals_np_dtype)
    counts = np.array([[3, 5], [0, 8], [2, 0], [1, 1]], np.int32)
    staged = sc.stage_inputs(ids, vals, counts)
    scores_dev = sc.step_counts(*staged)
    plane = np.asarray(scores_dev)
    moved = int(counts.sum())
    g = np.asarray(sc.gather_rows(scores_dev, staged[2], moved)).astype(
        np.float32
    )
    expected = np.concatenate([
        plane[ti, di * b : di * b + counts[ti, di]]
        for ti in range(t) for di in range(d)
    ]).astype(np.float32)
    np.testing.assert_allclose(g[:moved], expected)
    assert np.isnan(g[moved:]).all(), "padding must be NaN (scatter-drop)"
    # wire dtype survives the gather: d2h stays at the thin width
    assert sc.gather_rows(scores_dev, staged[2], moved).dtype == plane.dtype


def test_gather_ladder_shape():
    _mm, sc = _make_scorer()
    plane = sc.n_slots * sc.mm.n_data_shards * 64
    ladder = sc.gather_ladder(64)
    assert ladder[-1] == plane
    assert ladder == sorted(set(ladder)), "ladder must be increasing"
    assert ladder[0] <= sc.GATHER_FLOOR
    # every rung doubles (bounded compile count, <2x padding waste)
    for a, b in zip(ladder, ladder[1:]):
        assert b <= 2 * a


# ------------------------------------------------------------- test doubles
class GatedScores:
    """A score-plane double whose materialization blocks on a gate —
    no ``is_ready``/``copy_to_host_async``, so the service takes the
    fallback path (eager executor materialization + host-side pick)."""

    def __init__(self, inner, gate: threading.Event) -> None:
        self.inner = inner
        self.gate = gate

    def __getitem__(self, idx):
        return GatedScores(self.inner[idx], self.gate)

    def __array__(self, dtype=None):
        if not self.gate.wait(timeout=60.0):
            raise RuntimeError("gate never opened")
        a = np.asarray(self.inner)
        return a.astype(dtype) if dtype is not None else a


class PoisonScores:
    """A transfer that fails at materialization time."""

    def __init__(self, inner) -> None:
        self.inner = inner

    def __getitem__(self, idx):
        return PoisonScores(self.inner[idx])

    def __array__(self, dtype=None):
        raise RuntimeError("poisoned d2h transfer (chaos)")


def _slice(svc, family: str, sl: int = 0):
    """The (family, slice)'s SliceRuntime, or None before its birth."""
    return svc._slices.get((family, sl))


def _reap_len(svc, family: str, sl: int = 0) -> int:
    s = _slice(svc, family, sl)
    return len(s.reap) if s is not None else 0


def _gate_family(svc, family: str) -> threading.Event:
    scorer = svc.scorers[family]
    gate = threading.Event()
    orig = scorer.step_counts
    scorer.step_counts = lambda i, v, c: GatedScores(orig(i, v, c), gate)
    return gate


def _batch(tenant: str, toks, n: int, base: float = 0.0) -> MeasurementBatch:
    return MeasurementBatch.from_columns(
        tenant, [toks[i % len(toks)] for i in range(n)],
        ["temperature"] * n, [base + float(i) for i in range(n)], [0.0] * n,
    )


async def _wait_for(cond, timeout_s=20.0, interval=0.01):
    deadline = time.monotonic() + timeout_s
    while True:
        if cond():
            return True
        if time.monotonic() >= deadline:
            return False
        await asyncio.sleep(interval)


MB = MicroBatchConfig(max_batch=64, deadline_ms=1.0, buckets=(32, 64), window=8)


async def _instance(tenants) -> SiteWhereInstance:
    """tenants: {token: template}; small models, fast flush deadlines."""
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="rp",
        mesh=MeshConfig(tenant_axis=1, data_axis=1, slots_per_shard=4),
    ))
    await inst.start()
    for tok, template in tenants.items():
        cfgs = {"hidden": 8} if template == "iot-temperature" else {
            "context": 16, "hidden": 8,
        }
        await inst.tenant_management.create_tenant(
            tok, template=template, microbatch=MB,
            model_config=cfgs, max_streams=64,
        )
    await inst.drain_tenant_updates()
    for _ in range(300):
        if all(t in inst.tenants for t in tenants):
            break
        await asyncio.sleep(0.02)
    fleets = {
        tok: [d.token for d in
              inst.tenants[tok].device_management.bootstrap_fleet(4)]
        for tok in tenants
    }
    return inst, fleets


def _scored_consumer(inst, tenant: str):
    topic = inst.bus.naming.scored_events(tenant)
    inst.bus.subscribe(topic, "result-path-test")

    async def drain():
        return await inst.bus.consume(topic, "result-path-test", 64, timeout_s=0)

    return drain


# -------------------------------------------------------- reaper ordering
async def test_out_of_order_across_families():
    """A later flush of family B resolves while family A's earlier
    flush is still in flight — the reaper never head-of-line blocks one
    family behind another's slow transfer."""
    inst, fleets = await _instance(
        {"slowt": "iot-temperature", "fastt": "forecasting"}
    )
    svc = inst.inference
    gate_slow = gate_fast = None
    try:
        toks_s, toks_f = fleets["slowt"], fleets["fastt"]
        drain_slow = _scored_consumer(inst, "slowt")
        drain_fast = _scored_consumer(inst, "fastt")
        # compile both families' shapes BEFORE the gates go in: the timed
        # window below must measure reaper ordering, not XLA compiles
        await asyncio.get_running_loop().run_in_executor(None, svc.prewarm)
        gate_slow = _gate_family(svc, "lstm_ad")
        gate_fast = _gate_family(svc, "deepar")
        # dispatch the SLOW family first: its flush is the oldest head
        await inst.bus.publish(
            inst.bus.naming.inbound_events("slowt"), _batch("slowt", toks_s, 16)
        )
        assert await _wait_for(lambda: _reap_len(svc, "lstm_ad") == 1)
        await inst.bus.publish(
            inst.bus.naming.inbound_events("fastt"), _batch("fastt", toks_f, 16)
        )
        assert await _wait_for(lambda: _reap_len(svc, "deepar") == 1)
        gate_fast.set()  # only the NEWER family's transfer lands
        got_fast: list = []

        async def fast_arrived():
            got_fast.extend(await drain_fast())
            return len(got_fast) >= 1

        assert await _poll(fast_arrived), "fast family blocked behind slow"
        # the slow family is STILL in flight — nothing delivered for it
        assert _reap_len(svc, "lstm_ad") == 1
        assert not await drain_slow()
        gate_slow.set()
        got_slow: list = []

        async def slow_arrived():
            got_slow.extend(await drain_slow())
            return len(got_slow) >= 1

        assert await _poll(slow_arrived)
        assert np.isfinite(np.asarray(got_slow[0].scores)).all()
        assert np.isfinite(np.asarray(got_fast[0].scores)).all()
    finally:
        for g in (gate_slow, gate_fast):
            if g is not None:
                g.set()
        await inst.terminate()


async def _poll(async_cond, timeout_s=20.0, interval=0.02):
    deadline = time.monotonic() + timeout_s
    while True:
        if await async_cond():
            return True
        if time.monotonic() >= deadline:
            return False
        await asyncio.sleep(interval)


async def test_in_order_per_tenant_within_family():
    """Flush 2's transfer landing FIRST must not let its batch overtake
    flush 1's — per-family FIFO means a tenant's batches always publish
    in enqueue order."""
    inst, fleets = await _instance({"acme": "iot-temperature"})
    svc = inst.inference
    gates: list = []
    try:
        toks = fleets["acme"]
        drain = _scored_consumer(inst, "acme")
        scorer = svc.scorers["lstm_ad"]
        orig = scorer.step_counts

        def gated_step(i, v, c):
            gate = threading.Event()
            gates.append(gate)
            return GatedScores(orig(i, v, c), gate)

        scorer.step_counts = gated_step
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"),
            _batch("acme", toks, 8, base=100.0),
        )
        assert await _wait_for(lambda: _reap_len(svc, "lstm_ad") == 1)
        # a lane at the smallest bucket (32) does not wait for the flush
        # in flight: the second flush joins it through the policy's exit
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"),
            _batch("acme", toks, MB.buckets[0], base=200.0),
        )
        assert await _wait_for(lambda: _reap_len(svc, "lstm_ad") == 2)
        assert len(gates) == 2
        gates[1].set()  # flush 2 lands first...
        await asyncio.sleep(0.3)
        assert not await drain(), "batch 2 overtook batch 1"
        gates[0].set()  # ...but delivery stays FIFO
        got: list = []

        async def both():
            got.extend(await drain())
            return len(got) >= 2

        assert await _poll(both)
        # enqueue order preserved: batch 1 (values 100..) before batch 2
        assert float(got[0].values[0]) == 100.0
        assert float(got[1].values[0]) == 200.0
    finally:
        for g in gates:
            g.set()
        await inst.terminate()


async def test_failed_dispatch_stays_fifo_per_tenant():
    """A flush whose DISPATCH fails resolves unscored through the reap
    FIFO — its batches must not overtake an earlier in-flight flush of
    the same family (per-tenant order holds across scorer failures)."""
    inst, fleets = await _instance({"acme": "iot-temperature"})
    svc = inst.inference
    gate = threading.Event()
    try:
        toks = fleets["acme"]
        drain = _scored_consumer(inst, "acme")
        scorer = svc.scorers["lstm_ad"]
        orig = scorer.step_counts
        calls: list = []

        def step(i, v, c):
            calls.append(1)
            if len(calls) == 1:
                return GatedScores(orig(i, v, c), gate)
            raise RuntimeError("injected dispatch fault (chaos)")

        scorer.step_counts = step
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"),
            _batch("acme", toks, 8, base=100.0),
        )
        assert await _wait_for(lambda: _reap_len(svc, "lstm_ad") == 1)
        # (a full smallest bucket, so it dispatches beside the gated one)
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"),
            _batch("acme", toks, MB.buckets[0], base=200.0),
        )
        # the failed flush queues as a poisoned entry BEHIND the gated one
        assert await _wait_for(lambda: _reap_len(svc, "lstm_ad") == 2)
        await asyncio.sleep(0.3)
        assert not await drain(), "failed flush overtook the in-flight one"
        gate.set()
        got: list = []

        async def both():
            got.extend(await drain())
            return len(got) >= 2

        assert await _poll(both)
        assert float(got[0].values[0]) == 100.0
        assert np.isfinite(np.asarray(got[0].scores)).all()
        assert float(got[1].values[0]) == 200.0
        assert np.isnan(np.asarray(got[1].scores)).all(), (
            "failed flush's rows must resolve unscored"
        )
    finally:
        gate.set()
        await inst.terminate()


async def test_blocked_publish_does_not_stall_other_families():
    """A tenant whose scored topic is full (consumer stalled) blocks only
    its OWN family's resolve task — other families' landed transfers keep
    publishing. This is the cross-family isolation the reaper's
    per-family resolve tasks exist for: resolving inline in the reaper
    coroutine would head-of-line block every family behind one
    backpressured publish."""
    inst, fleets = await _instance(
        {"slowt": "iot-temperature", "fastt": "forecasting"}
    )
    svc = inst.inference
    svc.deliver_drain_timeout_s = 0.5
    topic_s = inst.bus.naming.scored_events("slowt")
    try:
        toks_s, toks_f = fleets["slowt"], fleets["fastt"]
        drain_fast = _scored_consumer(inst, "fastt")
        await asyncio.get_running_loop().run_in_executor(None, svc.prewarm)
        # wedge slowt's scored topic: a pinned group + retention 1 makes
        # the resolve task's awaited publish backpressure indefinitely
        inst.bus.subscribe(topic_s, "stall")
        tp = inst.bus.topic(topic_s)
        tp.retention = 1
        await inst.bus.publish(topic_s, _batch("slowt", toks_s, 1))
        await inst.bus.publish(
            inst.bus.naming.inbound_events("slowt"),
            _batch("slowt", toks_s, 16),
        )
        # the resolve task is now blocked INSIDE its publish: the flush
        # stays at the head of its queue (it only leaves on resolution)
        assert await _wait_for(
            lambda: _slice(svc, "lstm_ad").resolving is not None
            and _reap_len(svc, "lstm_ad") == 1
        )
        await asyncio.sleep(0.2)  # give a head-of-line bug time to wedge
        await inst.bus.publish(
            inst.bus.naming.inbound_events("fastt"),
            _batch("fastt", toks_f, 16),
        )
        got_fast: list = []

        async def fast_arrived():
            got_fast.extend(await drain_fast())
            return len(got_fast) >= 1

        assert await _poll(fast_arrived), (
            "healthy family stalled behind another family's full "
            "scored topic"
        )
        assert _slice(svc, "lstm_ad").resolving is not None, (
            "slow family resolved despite its wedged topic"
        )
        # unwedge: the pinned group leaves → the publish unblocks and the
        # slow family's batch delivers too (zero loss, order preserved)
        tp.retention = 65536
        inst.bus.unsubscribe(topic_s, "stall")
        assert await _wait_for(
            lambda: _slice(svc, "lstm_ad").resolving is None
            and not _reap_len(svc, "lstm_ad")
        )
        assert inst.metrics.counter("tpu_inference.scored_total").value >= 32
    finally:
        inst.bus.unsubscribe(topic_s, "stall")
        await inst.terminate()


# --------------------------------------------------------- failure edges
async def test_poisoned_transfer_resolves_unscored():
    """A transfer that dies mid-flight must resolve its popped rows
    unscored (batch still publishes — zero loss), record the failure on
    the family breaker, and leave no stranded registry entries."""
    inst, fleets = await _instance({"acme": "iot-temperature"})
    svc = inst.inference
    try:
        toks = fleets["acme"]
        drain = _scored_consumer(inst, "acme")
        scorer = svc.scorers["lstm_ad"]
        orig = scorer.step_counts
        scorer.step_counts = lambda i, v, c: PoisonScores(orig(i, v, c))
        breaker = svc.breakers["lstm_ad"]
        fails_before = sum(1 for o in breaker._outcomes if not o)
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"), _batch("acme", toks, 12)
        )
        got: list = []

        async def arrived():
            got.extend(await drain())
            return len(got) >= 1

        assert await _poll(arrived), "poisoned flush lost its batch"
        batch = got[0]
        assert batch.n == 12
        assert np.isnan(np.asarray(batch.scores)).all(), (
            "rows of a poisoned transfer must resolve unscored (NaN)"
        )
        assert sum(1 for o in breaker._outcomes if not o) > fails_before, (
            "breaker never saw the transfer failure"
        )
        assert not svc._batches, "stranded batch registry entries"
        assert not any(s.reap for s in svc._slices.values()), (
            "reap queue left non-empty"
        )
    finally:
        await inst.terminate()


async def test_teardown_with_stuck_transfer_loses_nothing():
    """Service stop with a transfer that never lands: after the drain
    grace the flush force-resolves unscored — the batch publishes
    (nowait) and no registry entry leaks."""
    inst, fleets = await _instance({"acme": "iot-temperature"})
    svc = inst.inference
    svc.deliver_drain_timeout_s = 0.3
    gate = None
    try:
        toks = fleets["acme"]
        scored = inst.metrics.counter("tpu_inference.scored_total")
        gate = _gate_family(svc, "lstm_ad")
        await inst.bus.publish(
            inst.bus.naming.inbound_events("acme"), _batch("acme", toks, 10)
        )
        assert await _wait_for(lambda: _reap_len(svc, "lstm_ad") == 1)
        assert scored.value == 0
    finally:
        await inst.terminate()
        if gate is not None:
            gate.set()  # free the executor thread
    assert inst.metrics.counter("tpu_inference.scored_total").value >= 10, (
        "stuck-transfer rows vanished at teardown"
    )
    assert not svc._batches
    # no slice outlives the service: no reap queue left non-empty, no
    # device scores left pinned
    assert svc._slices == {}, "teardown left a slice (and what it pins)"


async def test_result_path_metrics_flow():
    """Normal traffic populates the split histograms and counters the
    bench reports, and the in-flight gauge returns to zero."""
    inst, fleets = await _instance({"acme": "iot-temperature"})
    try:
        toks = fleets["acme"]
        scored = inst.metrics.counter("tpu_inference.scored_total")
        for i in range(3):
            await inst.bus.publish(
                inst.bus.naming.inbound_events("acme"),
                _batch("acme", toks, 32, base=i * 1000.0),
            )
        assert await _wait_for(lambda: scored.value >= 96)
        m = inst.metrics
        assert m.counter("tpu_inference.reaped").value >= 1
        assert m.counter("tpu_inference.d2h_bytes").value > 0
        # device gather engaged: plane bytes dwarf the gathered bytes
        assert (
            m.counter("tpu_inference.d2h_plane_bytes").value
            >= m.counter("tpu_inference.d2h_bytes").value
        )
        assert m.histogram("tpu_inference.d2h_wait", unit="s").count >= 1
        assert m.histogram("tpu_inference.resolve", unit="s").count >= 1
        assert m.gauge("tpu_inference_deliver_inflight").value == 0
        # the probe holds nothing once the family went idle (no leak of
        # a full flush of device score memory)
        assert await _wait_for(
            lambda: _slice(inst.inference, "lstm_ad").last_scores is None
        )
    finally:
        await inst.terminate()


# ---------------------------------------------------------- hot-path lint
def test_lint_flags_blocking_asarray_on_device_arrays(tmp_path):
    hot = tmp_path / "hot.py"
    hot.write_text(
        "import numpy as np\n"
        "def flush(scorer, staged, host_rows):\n"
        "    scores_dev = scorer.step_counts(*staged)\n"
        "    out = np.asarray(scores_dev)\n"
        "    ok = np.asarray(scores_dev)  # hotpath: ok\n"
        "    picked = scorer.gather_rows(scores_dev, None, 4)\n"
        "    arr = np.array(picked)\n"
        "    host = np.asarray(host_rows)\n"
        "    return out, ok, arr, host\n"
    )
    findings = check_hotpath.lint_hotpaths(
        {"hot.py": ["flush"]}, src_root=tmp_path
    )
    text = "\n".join(findings)
    assert "np.asarray('scores_dev') blocks on a device array" in text
    assert "np.array('picked') blocks on a device array" in text
    assert "host_rows" not in text, "host arrays must not be flagged"
    assert len(findings) == 2, findings


def test_lint_registry_covers_result_path():
    """The reaper functions are registered and currently clean."""
    quals = check_hotpath.HOT_PATHS["pipeline/inference.py"]
    for fn in ("TpuInferenceService._resolve_rows",
               "TpuInferenceService._reap_loop",
               "TpuInferenceService._resolve_flush"):
        assert fn in quals
    assert check_hotpath.lint_hotpaths() == []
