"""tpu-inference: the rebuild's new pipeline stage (the north star).

"A new tpu-inference tenant-engine microservice sits between
inbound-processing and event-management on the bus, micro-batching
DeviceMeasurement events into JAX/XLA pjit calls on a TPU pod"
(BASELINE.json north_star; no reference counterpart — SURVEY.md §2.3).

Dataflow per scoring cycle (the zero-copy columnar feed path —
docs/PERFORMANCE.md has the full stage walkthrough):

  inbound-events[tenant_i] ─┐  MeasurementBatch (struct-of-arrays)
  inbound-events[tenant_j] ─┼→ lane RINGS[(slot, data_shard)]: rows are
          ...              ─┘  written into preallocated numpy segments
                                AT ENQUEUE │ due on deadline_ms OR full;
                                           │ a due flush under the smallest
                                           │ bucket waits for the one in
                                           │ flight (``SliceRuntime.held``)
                                     ▼
              reusable staging buffers u16/bf16[T, D·B] (slice copies,
              two rotating sets per (family, bucket) — no fresh arrays)
                                     ▼
              stage_inputs — ASYNC h2d onto the step's shardings;
              overlaps the previous flush's device compute
                                     ▼
              ShardedScorer.step_counts — ONE jit call, every tenant
                                     ▼
              gather_rows — device-side compaction: only the flushed
              rows' scores leave the chip (wire dtype; d2h bytes are
              rows-proportional, never the T×lane plane)
                                     ▼ (copy_to_host_async issued at
                                        dispatch — the transfer rides
                                        under the next flush's compute)
              completion REAPER — resolves flushes as transfers land:
              out of order across families, FIFO per family (so every
              tenant's batches publish in order)
                                     ▼
              columnar resolve: scores slice-assign back into each
              batch's ``scores`` column; completed batches →
              tpu-scored-events[tenant]

Three latency-hiding moves matter here (SURVEY.md §7 hard parts):
- the host side never touches per-event Python objects — rows move as
  numpy slices end to end, and a flush is slice+pad into reusable
  staging, never ``np.asarray`` over freshly built lists
  (tools/check_hotpath.py lints this invariant);
- the staged device put is issued BEFORE dispatch and is asynchronous,
  so where flushes pipeline, flush N+1's host→device transfer rides
  under flush N's compute (``tpu_inference.h2d_overlapped`` /
  ``h2d_staged`` expose the ratio);
- the readback is the mirror image: a device-side gather returns only
  the flushed rows (``ShardedScorer.gather_rows``), its d2h copy is
  started asynchronously at dispatch, and a completion reaper resolves
  the in-flight flushes as their transfers land
  (``tpu_inference.d2h_overlapped`` counts transfers that landed before
  the reaper asked). One device round-trip never stalls the collect
  loop; p99 still lands in the ``tpu_inference.latency`` histogram.

When flushes pipeline and when they wait (``SliceRuntime.due`` and
``.held``, ``pipeline/slices.py``): a due flush joins the device queue
behind a serve flush that has not landed — up to ``max_inflight`` deep —
only if some lane already holds the smallest bucket; below it a bigger flush is
the same program in the same device time, so the rows wait on their
lanes and leave together when the flush in flight lands. Small-flush
traffic therefore runs one flush deep, full flushes ``max_inflight``
deep (``tpu_inference.flush_held`` / ``flush_pipelined``).

Tenant start/stop flips the scorer's active mask — no recompile; batch-size
buckets keep XLA at a handful of compiled shapes.

Multi-chip serving (docs/PERFORMANCE.md "Multi-chip serving"): the whole
pipeline above is instantiated PER (family, mesh-slice) — the router
places each tenant on a tenant-axis slice, and that slice's scorer,
lane rings, staging pool, in-flight budget, and reap queue are its own:
one ``SliceRuntime`` (``pipeline/slices.py``) in the service's one table.
Slices flush concurrently with zero cross-slice collectives; tenant
moves between slices (failover/rebalance) hold per-tenant FIFO through
``_SliceFence``. A single-slice mesh degenerates to exactly the
single-funnel path described above.
"""

from __future__ import annotations

import asyncio
import time
from collections import OrderedDict
from collections.abc import Mapping
from typing import Dict, List, Optional, Tuple

import numpy as np

from sitewhere_tpu.core.batch import MeasurementBatch
from sitewhere_tpu.core.events import DeviceMeasurement
from sitewhere_tpu.models import get_model, make_config
from sitewhere_tpu.parallel.mesh import MeshManager
from sitewhere_tpu.parallel.sharded import ShardedScorer
from sitewhere_tpu.parallel.tenant_router import (
    PlacementError,
    TenantPlacement,
    TenantRouter,
)
from sitewhere_tpu.runtime.bus import (
    CircuitBreaker,
    EventBus,
    publish_at_least_once,
)
from sitewhere_tpu.runtime.config import (
    FaultTolerancePolicy,
    TenantEngineConfig,
)
from sitewhere_tpu.runtime.lifecycle import (
    LifecycleState,
    SupervisedTask,
    cancel_and_wait,
)
from sitewhere_tpu.runtime.loopledger import spanned, sw
from sitewhere_tpu.pipeline.slices import (
    SliceRuntime, _empty_taken, _LaneRing, _PendingFlush,
)
from sitewhere_tpu.runtime.metrics import (
    D2H_OVERLAP_EPS_S as _D2H_OVERLAP_EPS_S,
    MfuAccount,
    MetricsRegistry,
)
from sitewhere_tpu.runtime.tenant import MultitenantService, TenantEngine


class StreamRegistry:
    """Per-tenant map (device_token, name) → (data_shard, local_id).

    Streams are pinned to a data shard at first sight (least-loaded wins),
    so window updates for a stream always land on the same device and the
    scoring step needs no collectives (see ``parallel.sharded``).
    """

    def __init__(self, n_data_shards: int, local_capacity: int) -> None:
        self.n_data_shards = n_data_shards
        self.local_capacity = local_capacity
        self._map: Dict[Tuple[str, str], Tuple[int, int]] = {}
        self._next: List[int] = [0] * n_data_shards

    def lookup_or_assign(
        self, device_token: str, name: str
    ) -> Optional[Tuple[int, int]]:
        key = (device_token, name)
        hit = self._map.get(key)
        if hit is not None:
            return hit
        shard = min(range(self.n_data_shards), key=lambda d: self._next[d])
        if self._next[shard] >= self.local_capacity:
            return None  # capacity exhausted; caller passes event through unscored
        local_id = self._next[shard]
        self._next[shard] += 1
        self._map[key] = (shard, local_id)
        return shard, local_id

    def lookup_or_assign_bulk(
        self, batch: MeasurementBatch
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized per-row (data_shard, local_id): one dict lookup per
        UNIQUE (token, name) pair; rows inherit via inverse indices. Rows
        that can't get a slot come back with shard == -1. Group indices
        come from the batch's cached token/name index (integer codes — no
        string sorts here)."""
        _, first, inverse = np.unique(
            batch.pair_codes(), return_index=True, return_inverse=True
        )
        tokens, names = batch.device_tokens, batch.names
        d_u = np.empty((len(first),), np.int32)
        l_u = np.empty((len(first),), np.int32)
        lookup = self.lookup_or_assign
        for j, fi in enumerate(first.tolist()):
            assigned = lookup(str(tokens[fi]), str(names[fi]))
            if assigned is None:
                d_u[j] = -1
                l_u[j] = 0
            else:
                d_u[j], l_u[j] = assigned
        return d_u[inverse], l_u[inverse]

    @property
    def n_streams(self) -> int:
        return len(self._map)


class AmbiguousFamilyError(KeyError):
    """A family-string lookup matched MORE than one mesh slice — the
    caller must key by (family, slice). Distinct from a plain missing
    key so ``get()`` can default only the truly-absent case."""


class _ScorerMap(Mapping):
    """(family, slice) → one attribute of each ``SliceRuntime`` (its
    scorer, its breaker, its last train losses): a read-only view over
    the service's slice table, with family-string convenience lookup —
    ``scorers["lstm_ad"]`` resolves when exactly one slice hosts the
    family; ambiguous lookups must name the slice. A slice whose
    attribute is still None (no train step yet) is not in the view."""

    def __init__(self, slices: Dict[Tuple[str, int], SliceRuntime], attr: str) -> None:
        self._slices = slices
        self._attr = attr

    def __iter__(self):
        return (
            k for k, s in self._slices.items()
            if getattr(s, self._attr) is not None
        )

    def __len__(self) -> int:
        return sum(1 for _ in self)

    def __getitem__(self, key):
        if isinstance(key, str):
            hits = [k for k in self if k[0] == key]
            if len(hits) > 1:
                raise AmbiguousFamilyError(
                    f"family '{key}' is served on {len(hits)} mesh slices "
                    f"({sorted(k[1] for k in hits)}) — key "
                    f"scorers[(family, slice)]"
                )
            if not hits:
                raise KeyError(key)
            key = hits[0]
        value = getattr(self._slices[key], self._attr)
        if value is None:
            raise KeyError(key)
        return value

    def __contains__(self, key) -> bool:
        if isinstance(key, str):
            return any(k[0] == key for k in self)
        return Mapping.__contains__(self, key)

    def get(self, key, default=None):
        try:
            return self[key]
        except AmbiguousFamilyError:
            # defaulting here would make a multi-slice family look
            # ABSENT at exactly the moment a slice move spread it
            raise
        except KeyError:
            return default

    def family_items(self, family: str):
        return sorted((k[1], v) for k, v in self.items() if k[0] == family)


class _SliceFence:
    """Holds one re-placed tenant's rows until every flush that was in
    flight on its OLD (family, slice) queue at move time has resolved.

    Without the fence, a tenant moving from slice A to slice B could
    have batch N still riding an unresolved slice-A flush while batch
    N+1 flushes (and lands) on slice B first — breaking the per-tenant
    FIFO guarantee the per-slice reap queues otherwise provide. Rows
    re-keyed off the old lanes AND new bus intake stash here (FIFO
    ``_LaneRing`` per data shard, counted against the tenant's lane
    watermark so a long fence backpressures into the bus); the scoring
    loop lifts the fence when the snapshot drains and pushes the stash
    into the new slice's lanes in arrival order.

    Weight paging reuses the same machinery with ``new_sl=None``: a
    NON-RESIDENT tenant's fence has no landing target yet (its weights
    live host-side as encoded bytes), so rows park indefinitely —
    ``_lift_fences`` skips target-less fences — until a page-in
    activates the tenant and retargets the fence at its new slot."""

    __slots__ = ("tenant", "family", "pending", "stash", "new_sl", "new_slot")

    def __init__(self, tenant: str, family: str, pending: List[_PendingFlush],
                 new_sl: Optional[int], new_slot: Optional[int]) -> None:
        self.tenant = tenant
        self.family = family
        self.pending = pending        # old-slice flushes to outwait
        self.stash: Dict[int, _LaneRing] = {}   # dshard → parked rows
        self.new_sl = new_sl
        self.new_slot = new_slot

    def ready(self) -> bool:
        return all(pf.resolved for pf in self.pending)

    def park(self, dshard: int, ids, vals, seq, rows) -> None:
        ring = self.stash.get(dshard)
        if ring is None:
            ring = self.stash[dshard] = _LaneRing()
        ring.push(ids, vals, seq, rows)

    def depth(self) -> int:
        return sum(r.count for r in self.stash.values())


class TpuInferenceEngine(TenantEngine):
    """Per-tenant engine: placement on the mesh + stream registry."""

    def __init__(self, config: TenantEngineConfig, service: "TpuInferenceService") -> None:
        super().__init__("tpu-inference", config)
        self.service = service
        self.placement = None
        self.streams: Optional[StreamRegistry] = None
        self._feed_subscribed = False  # train-feed group registered

    async def on_start(self) -> None:
        svc = self.service
        try:
            self.placement = svc.router.place(
                self.tenant, family=self.config.model
            )
        except PlacementError:
            if svc.pager is None:
                raise
            # family at physical capacity and weight paging is on: the
            # tenant starts NON-RESIDENT (virtualized slot). Its ghost
            # placement points at a real slice (for scorer/lane lookups)
            # with slot -1 = no device slot held; arriving rows park
            # behind a paging fence and the first demand (or a rising-lag
            # prefetch) pages it in, evicting the LRU victim.
            self.placement = svc._ghost_placement(
                self.tenant, self.config.model
            )
        # the tenant's scorer is its mesh SLICE's scorer: one compiled
        # step per (family, tenant-axis slice), dispatching only to that
        # slice's devices (docs/PERFORMANCE.md "Multi-chip serving")
        scorer = svc.scorer_for_slice(
            self.config.model, self.placement.shard, self.config
        )
        self.streams = StreamRegistry(
            svc.mm.n_data_shards, scorer.max_streams // svc.mm.n_data_shards
        )
        svc.bus.subscribe(svc.bus.naming.inbound_events(self.tenant), svc.group)
        if (
            self.config.training.enabled
            and self.config.training.train_lane
            and getattr(scorer, "train_lane", False)
        ):
            # replay-fed continual learning: scored history published by
            # the replay engine's ``train`` target lands here and the
            # scoring loop's low-priority intake pulls it into the train
            # lane rings. Subscribed ONLY when something will actually
            # consume it — a registered group engages the bus's publish
            # backpressure, so subscribing with the lane off (tenant
            # opt-out / TRAIN_LANE_ENABLED rollback / non-fused family)
            # would wedge a replay train job forever once the topic
            # fills; unsubscribed, the topic keeps its lossy retention
            # tail exactly as before the lane existed.
            svc.bus.subscribe(
                svc.bus.naming.train_feed(self.tenant), svc.group
            )
            self._feed_subscribed = True
        # fair-queue registration: this tenant's intake is rationed by
        # its OverloadPolicy weight from the first poll
        svc.fair.configure(self.tenant, self.config.overload.weight)
        if self.placement.slot >= 0:
            params = None
            if svc.checkpoints is not None:
                # resume this tenant's trained weights (possibly onto a
                # DIFFERENT slot/shard than before — mesh re-placement)
                params = await asyncio.get_running_loop().run_in_executor(
                    None, svc.checkpoints.load_params,
                    self.tenant, self.config.model,
                )
            scorer.activate(
                self.placement.slot, params=params,
                trainable=self.config.training.enabled,
                lr=self.config.training.lr,
            )
            # score-health registration: bind this tenant to its stacked
            # slot so the resolve path can attribute device sketches, and
            # start a FRESH drift baseline — an engine (re)start activates
            # params explicitly, so the reference must re-learn the current
            # model's output distribution (docs/OBSERVABILITY.md
            # "re-baseline")
            svc.scorehealth.register(
                self.tenant, self.config.model,
                self.placement.slot,
                getattr(scorer, "sketch_edges", []),
                mesh_slice=self.placement.shard,
                variant={
                    "fused": bool(getattr(scorer, "fused", False)),
                    "k_steps": int(getattr(scorer, "k_steps", 1)),
                    "param_dtype": getattr(scorer, "param_dtype", "f32"),
                    "wire_dtype": getattr(scorer, "wire_dtype", "f32"),
                },
            )
            svc.scorehealth.rebaseline(self.tenant)
            if svc.pager is not None:
                # residency ledger: this tenant holds a physical slot —
                # it is an LRU eviction candidate from now on
                svc.pager.slice_pager(
                    self.config.model, self.placement.shard,
                    svc.slots_per_shard,
                ).note_resident(self.tenant, self.placement.slot)
        else:
            # NON-RESIDENT start: no device work at all. Install the
            # paging fence so rows arriving before the first page-in
            # park (counted against the lane watermark → backpressure)
            # instead of landing in a slot the tenant doesn't hold.
            svc._install_paging_fence(self)
            svc.metrics.counter(
                "tpu_paging.virtual_starts", family=self.config.model
            ).inc()
        # a tenant lifecycle event is the unpark signal for its family —
        # and clears the family breaker's failure history with it
        svc._parked.discard(self.config.model)
        svc._failover_rounds.pop(self.config.model, None)
        for s in svc._family_slices(self.config.model):
            s.breaker.reset()
        # ...and the quarantine ledger: an explicit engine (re)start is
        # the operator's heal signal, the same contract as the breaker
        # resets above — probation probes are for UNATTENDED recovery
        svc.clear_quarantine(self.config.model)

    async def on_stop(self) -> None:
        svc = self.service
        if self.placement is not None:
            slot = self.placement.slot
            # None only if the slice's scorer never built (a failed start)
            s = svc._slices.get((self.config.model, self.placement.shard))
            scorer = s.scorer if s is not None else None
            if slot >= 0 and scorer is not None and svc.checkpoints is not None:
                # save this tenant's (possibly trained) weights BEFORE the
                # slot wipe below destroys them. Materialize to numpy ON
                # THIS (loop) thread: the reset_slot below DONATES the
                # stacked params buffer, and a worker-thread zero-copy view
                # into it would be a use-after-free (see host_copy_params)
                from sitewhere_tpu.runtime.checkpoint import host_copy_params

                params = host_copy_params(scorer.slot_params(slot))
                await asyncio.get_running_loop().run_in_executor(
                    None, svc.checkpoints.save_params,
                    self.tenant, self.config.model, params,
                )
            if slot >= 0 and scorer is not None:
                # full wipe: a recycled slot must not leak this tenant's
                # window history or params to the next occupant
                scorer.reset_slot(slot)
            if slot < 0 and svc.pager is not None:
                # PAGED-OUT tenant leaving: its only durable state is the
                # host-side segment blob — persist it iff dirty (train-lane
                # tenants mutate weights between page-outs) so the cached
                # training progress survives the engine teardown
                blob = svc.pager.cache.get(self.tenant)
                if (
                    blob is not None
                    and blob[1]
                    and svc.checkpoints is not None
                ):
                    from sitewhere_tpu.runtime.checkpoint import (
                        decode_segment,
                    )

                    def _persist(data=blob[0]):
                        p, _opt = decode_segment(data)
                        svc.checkpoints.save_params(
                            self.tenant, self.config.model, p
                        )

                    await asyncio.get_running_loop().run_in_executor(
                        None, _persist
                    )
            # drain pending lanes keyed by the freed slot: the bus cursor
            # already advanced past these rows, so dropping them would lose
            # them from the store on every tenant restart — resolve them
            # unscored (NaN) instead
            if s is not None:
                drained = svc.metrics.counter("tpu_inference.drained_on_stop")
                for _d, _i, _v, seqs, rows in s.drain_lanes(slot):
                    await svc._resolve_rows(
                        seqs, rows, None, publish_nowait=True,
                        family=self.config.model,
                    )
                    drained.inc(len(seqs))
            # a tenant removed mid-slice-move: its fenced rows were
            # consumed off the bus, so they resolve unscored too
            fence = svc._fences.pop(self.tenant, None)
            if fence is not None:
                svc.metrics.gauge("tpu_inference_fences").set(
                    len(svc._fences)
                )
                for ring in fence.stash.values():
                    if ring.count:
                        _i, _v, seqs, rows = ring.pop(ring.count)
                        await svc._resolve_rows(
                            seqs, rows, None, publish_nowait=True,
                            family=self.config.model,
                        )
            # the tenant's pending TRAIN rows and cadence tick go with
            # it; no loss accounting rides on the train lane
            if s is not None:
                svc._forget_slot_training(s, slot)
            # the train-feed cursor must leave with the tenant: a stale
            # registered group never advances and would backpressure the
            # topic forever — wedging any LATER replay train job exactly
            # like the never-consumed case the subscribe gate avoids.
            # Gated on the subscribe flag: bus.unsubscribe instantiates
            # absent topics, and a never-subscribed tenant's stop must
            # not litter the bus (and every checkpoint) with empty feeds
            if self._feed_subscribed:
                self._feed_subscribed = False
                svc.bus.unsubscribe(
                    svc.bus.naming.train_feed(self.tenant), svc.group
                )
            svc.router.remove(self.tenant)
            self.placement = None
        if svc.pager is not None:
            # drop every paging artifact (cached blob, queued page-in,
            # residency entry) — a restarted tenant begins cold
            svc.pager.forget(self.tenant)
        svc.fair.remove(self.tenant)
        svc.scorehealth.remove(self.tenant)
        # bounded label cardinality: the per-tenant train-lane ledger
        # tracks LIVE tenants only (scoped sweep — see drop_labeled)
        svc.metrics.drop_labeled(
            families=["tpu_train_steps_total"], tenant=self.tenant
        )
        svc._gates.pop(self.tenant, None)


class TpuInferenceService(MultitenantService):
    """Hosts the scorers + the scoring loop across all tenant engines."""

    def __init__(
        self,
        bus: EventBus,
        mm: Optional[MeshManager] = None,
        metrics: Optional[MetricsRegistry] = None,
        slots_per_shard: int = 8,
        poll_batch: int = 64,
        max_inflight: int = 8,
        checkpoints=None,
        tracer=None,
        overload=None,
        fair_quantum: int = 4096,
        staging_slots: int = 2,
        flightrec=None,
        scorehealth=None,
    ) -> None:
        super().__init__("tpu-inference", bus, self._make_engine)
        self.mm = mm or MeshManager()
        self.metrics = metrics or MetricsRegistry()
        self.checkpoints = checkpoints  # CheckpointManager | None
        # overload control: per-tenant deficit-round-robin intake (bus →
        # lanes is the shared chokepoint every tenant contends on), a
        # per-tenant deadline gate so expired work never reaches a
        # ShardedScorer flush, and degradation-mode sampling
        self.overload = overload
        from sitewhere_tpu.runtime.overload import DeficitRoundRobin

        self.fair = DeficitRoundRobin(quantum=fair_quantum)
        self._gates: Dict[str, object] = {}
        # tracing + scoring profile hooks: per-tenant inference spans and
        # a compile-count per (family, bucket) shape (the first flush at
        # a shape IS the XLA compile — a mid-traffic recompile is the p99
        # cliff SURVEY §7 warns about)
        self.tracer = tracer
        # flight recorder (runtime.flightrec): always-on per-flush
        # blackbox records + dump-on-incident (breaker trip) snapshots;
        # None (direct service construction in tests) = fully guarded out
        self.flightrec = flightrec
        # score-quality health (runtime.scorehealth): per-tenant drift
        # windows fed by the device-side score sketches the reaper
        # materializes, plus shadow-canary divergence — always on (the
        # per-flush host cost is one 64-bin add per touched slot)
        if scorehealth is None:
            from sitewhere_tpu.runtime.scorehealth import ScoreHealth

            scorehealth = ScoreHealth(self.metrics)
        self.scorehealth = scorehealth
        # live device-time/MFU attribution per family (runtime.metrics
        # .MfuAccount; fed by resolved flushes, decayed by refresh_mfu)
        self._mfu: Dict[str, object] = {}
        self._stage_timers: Dict[str, object] = {}
        # the flush records of the newest flushes by flush_id — the same
        # dicts the flight recorder's ring holds. The latency ledger
        # splits a batch's inference span on ITS OWN flush through the
        # flush_id the span carries (runtime.latency.stage_vector).
        self.flush_records: "OrderedDict[int, dict]" = OrderedDict()
        self._next_flush_id = 0
        self.slots_per_shard = slots_per_shard
        self.poll_batch = poll_batch  # bus items (batches) per poll
        self.router = TenantRouter(self.mm.n_tenant_shards, slots_per_shard)
        # THE per-slice table: (family, mesh-slice) → SliceRuntime, the
        # scorer over that slice's sub-mesh with everything kept per
        # slice (pipeline/slices.py). Each slice dispatches, stages and
        # reaps independently — the unit of horizontal scale. Filled by
        # ``scorer_for_slice``, emptied by ``on_stop``; nothing else
        # here is keyed by (family, slice) but the three views over it
        # (string lookup resolves single-slice families)
        self._slices: Dict[Tuple[str, int], SliceRuntime] = {}
        self.scorers = _ScorerMap(self._slices, "scorer")
        self.breakers = _ScorerMap(self._slices, "breaker")
        self.last_train_losses = _ScorerMap(self._slices, "last_train_losses")
        # first tenant of a family pins the family-wide knobs (wire
        # dtype, fused kernel shape, model config): EVERY slice scorer
        # of the family builds from this config so slices are
        # numerically interchangeable across failover/rebalance moves
        self._family_cfg: Dict[str, TenantEngineConfig] = {}
        self.staging_slots = max(2, int(staging_slots))
        self._loop_super: Optional[SupervisedTask] = None
        # batch registry: seq → [batch, rows_awaiting_scores]
        self._batches: Dict[int, list] = {}
        self._next_seq = 0
        # scratch columns for the train lane's packer
        self._train_scratch: Optional[tuple] = None
        self.metrics.describe(
            "tpu_train_skipped_total",
            "training work skipped per family and reason (no_trainer/"
            "optimizer_init/parked/throttled/saturated/capacity) — a "
            "misconfigured or starved trainable tenant must not be dark",
        )
        self.metrics.describe(
            "tpu_train_steps_total",
            "train-lane optimizer steps that included the tenant's slot "
            "(the overload arbiter's per-tenant ledger: a saturated "
            "tenant reads exactly 0 while idle tenants train)",
        )
        self.metrics.describe(
            "tpu_train_rows_total",
            "replayed history rows ingested into train microbatches, "
            "per family",
        )
        self.metrics.describe(
            "tpu_train_flops_total",
            "analytic FLOPs executed by train-lane steps per family — "
            "kept OUT of tpu_flops_total/tpu_mfu_pct (serving work); "
            "an overlap-MFU reading sums the two",
        )
        self.metrics.describe(
            "tpu_train_swaps_total",
            "train-lane weight commits (kernel-sidecar re-derivation + "
            "canary arm) per family — one every swap_every lane steps",
        )
        # auto-failover: at this many consecutive scorer errors on a
        # slice (errors are chip-local) only the sick slice's tenants
        # re-place onto different mesh shards (SURVEY.md §5:
        # "tenant-engine failover to a different mesh shard")
        self.failover_threshold = 3
        # escalation: failover rounds without an intervening healthy
        # delivery; past max_failover_rounds the family PARKS — events
        # flow through unscored (degraded, never lost) until a tenant
        # lifecycle event clears it
        self.max_failover_rounds = 3
        self._failover_rounds: Dict[str, int] = {}
        self._parked: set = set()
        # slice-move fences: tenant → _SliceFence while a failover/
        # rebalance move outwaits the old slice's in-flight flushes
        self._fences: Dict[str, _SliceFence] = {}
        # in-flight flush budget PER SLICE (``SliceRuntime.permits``): a
        # global semaphore would let one slow chip starve every other
        # slice's flush admission
        self.max_inflight = max_inflight
        self._deliver_pool = None  # created on start, shut down on stop
        # result path: the reaper drains every slice's FIFO as transfers
        # land (out of order across slices, in order per tenant)
        self._reap_event = asyncio.Event()
        self._reaper_super: Optional[SupervisedTask] = None
        # teardown grace for in-flight transfers before they force-resolve
        # unscored (a dead device must not hang the stop cascade)
        self.deliver_drain_timeout_s = 10.0
        # -- fault-domain supervision (docs/ROBUSTNESS.md) ---------------
        # injectable device faults (runtime.faultplan — the chaos layer;
        # None in production). Consulted at every dispatch: serve, train,
        # shadow, and probation-probe lanes.
        self.faultplan = None
        # poison-batch ejection: batch seqs already granted their one
        # retry — a second failure ships them to the scorer-poison DLQ
        self._retried_seqs: set = set()
        self.metrics.describe(
            "tpu_flush_timeout_total",
            "in-flight flushes force-resolved unscored because their "
            "completion deadline expired, per family and mesh slice — "
            "the flush supervisor's wedged-device signal",
        )
        self.metrics.describe(
            "tpu_inference_quarantined_slices",
            "(family, slice) scorers currently quarantined (SUSPECT) "
            "and under probation probing",
        )
        self.metrics.describe(
            "tpu_flush_latency_p99_ms",
            "rolling dispatch→transfer-landed p99 per (family, mesh "
            "slice) — the flush supervisor's deadline source, surfaced "
            "live for the latency waterfall",
        )
        # -- weight paging (runtime.paging; docs/PERFORMANCE.md "Weight
        # paging") -------------------------------------------------------
        # virtualized slots: tenants beyond a family's physical capacity
        # get a GHOST placement (slot=-1) and page in on demand/prefetch.
        # The kill switch is captured HERE, at build (FUSED_STEP_ENABLED
        # pattern): flip runtime.paging.WEIGHT_PAGING_ENABLED to False
        # before construction and pager is None — every hook below is
        # guarded on it, restoring physical-slot semantics bitwise.
        from sitewhere_tpu.runtime import paging as _paging

        self.paging_enabled = bool(_paging.WEIGHT_PAGING_ENABLED)
        self.pager = (
            _paging.WeightPager(self.metrics) if self.paging_enabled else None
        )
        # ≤ 1 page-in in flight: activation serializes device mutation
        # (set_slot donates the stacked buffer) exactly like failover
        self._pagein_task: Optional[asyncio.Task] = None
        self._paging_next_prefetch = 0.0
        self.metrics.describe(
            "tpu_paging.page_ins",
            "tenant activations from the host byte cache / checkpoint "
            "store per family and origin (demand|prefetch)",
        )
        self.metrics.describe(
            "tpu_paging.page_outs",
            "resident tenants evicted to the host byte cache per family "
            "(LRU weighted by OverloadController traffic)",
        )
        self.metrics.describe(
            "tpu_paging.train_rows_dropped",
            "pending train-lane rows dropped at page-out per family — "
            "replayed history the store still holds (PR 12 round-4 rule)",
        )
        self.metrics.describe(
            "tpu_paging.stalled",
            "page-in attempts that found no evictable victim (every "
            "resident pinned/fenced/quarantined) — the request re-queues "
            "on the next demand touch",
        )

    @property
    def group(self) -> str:
        return "tpu-inference"

    def _family_slices(self, family: str) -> List[SliceRuntime]:
        """Every slice that serves ``family``, in birth order."""
        return [s for s in self._slices.values() if s.family == family]

    def quarantined_slices(self) -> int:
        """How many (family, slice)s are quarantined right now."""
        return sum(s.quarantine is not None for s in self._slices.values())

    def _quarantine_gauge(self) -> None:
        self.metrics.gauge("tpu_inference_quarantined_slices").set(
            self.quarantined_slices()
        )

    # -- flush supervision -------------------------------------------------
    def _family_ft(self, family: str) -> FaultTolerancePolicy:
        """The family-pinned FaultTolerancePolicy (first tenant wins,
        like every other family knob)."""
        pin = self._family_cfg.get(family)
        return pin.fault_tolerance if pin is not None else (
            FaultTolerancePolicy()
        )

    def _make_engine(self, cfg: TenantEngineConfig) -> TpuInferenceEngine:
        return TpuInferenceEngine(cfg, self)

    def scorer_for_slice(
        self, family: str, sl: int, cfg: TenantEngineConfig
    ) -> ShardedScorer:
        """The (family, mesh-slice) scorer, built lazily over the
        slice's sub-mesh from the FAMILY-PINNED config (first tenant
        wins — every slice of a family must compile the identical
        kernel, or a failover move would change a tenant's numerics)."""
        # knob-conflict checks compare against the family's pinned
        # representative (any existing slice scorer of the family)
        scorer = next((s.scorer for s in self._family_slices(family)), None)
        if scorer is not None and scorer.wire_dtype != cfg.wire_dtype:
            # the wire dtype is a property of the FAMILY stack (first
            # tenant wins); a later tenant asking for a different wire
            # would silently score at the stack's precision — surface it
            self._record_error(
                "wire-dtype",
                ValueError(
                    f"tenant '{cfg.tenant}' asked wire_dtype="
                    f"'{cfg.wire_dtype}' but family '{family}' runs "
                    f"'{scorer.wire_dtype}' (first tenant pinned it)"
                ),
            )
            self.metrics.counter("tpu_inference.wire_dtype_conflicts").inc()
        from sitewhere_tpu.models.common import clamp_fuse_k

        # compare CLAMPED asks (fuse_k saturates at window-1): two
        # tenants whose requests compile to the identical kernel must
        # not be reported as a conflict
        _w = getattr(scorer, "window", cfg.microbatch.window) or 1
        if scorer is not None and (
            clamp_fuse_k(getattr(scorer, "fuse_k", 1), _w)
            != clamp_fuse_k(getattr(cfg, "fuse_k", 1), _w)
            or getattr(scorer, "requested_param_dtype", "f32")
            != getattr(cfg, "param_dtype", "f32")
        ):
            # like wire_dtype, the fused-kernel knobs are a property of
            # the FAMILY stack (one compiled step per family) — a later
            # tenant asking for different ones would silently score at
            # the stack's settings, so surface it
            self._record_error(
                "fused-knobs",
                ValueError(
                    f"tenant '{cfg.tenant}' asked fuse_k="
                    f"{getattr(cfg, 'fuse_k', 1)}/param_dtype="
                    f"'{getattr(cfg, 'param_dtype', 'f32')}' but family "
                    f"'{family}' runs fuse_k={getattr(scorer, 'fuse_k', 1)}"
                    f"/param_dtype="
                    f"'{getattr(scorer, 'requested_param_dtype', 'f32')}' "
                    f"(first tenant pinned them)"
                ),
            )
            self.metrics.counter("tpu_inference.fused_knob_conflicts").inc()
        s = self._slices.get((family, sl))
        if s is not None:
            return s.scorer
        # build THIS slice's scorer from the family-pinned config so
        # every slice compiles the identical kernel variant
        pin = self._family_cfg.setdefault(family, cfg)
        spec = get_model(family)
        mcfg = make_config(family, {
            **pin.model_config, "window": pin.microbatch.window,
        })
        scorer = ShardedScorer(
            self.mm.slice_manager(sl),
            spec,
            mcfg,
            slots_per_shard=self.slots_per_shard,
            max_streams=pin.max_streams,
            window=pin.microbatch.window,
            wire_dtype=pin.wire_dtype,
            fuse_k=getattr(pin, "fuse_k", 1),
            param_dtype=getattr(pin, "param_dtype", "f32"),
        )
        # shadow-canary fraction: family-pinned like the fused knobs
        # (first tenant wins; one shadow step per family stack)
        scorer.canary_frac = float(getattr(pin, "canary_frac", 0.0) or 0.0)
        # the failover→park escalation is the scorer's first-line
        # healing; by default the breaker must not open mid-escalation
        # and starve it of failure outcomes (parked families stop
        # flushing), so its verdict window is floored at the park
        # budget. Chaos/testing configs set breaker_defer_to_failover
        # False to let the breaker act first.
        from dataclasses import replace as _replace

        ft = cfg.fault_tolerance
        park_budget = (
            self.failover_threshold * (self.max_failover_rounds + 1) + 1
        )
        if (
            ft.breaker_defer_to_failover
            and ft.breaker_min_samples < park_budget
        ):
            ft = _replace(ft, breaker_min_samples=park_budget)
        breaker = CircuitBreaker(
            f"tpu_inference.{family}.s{sl}",
            policy=ft,
            metrics=self.metrics,
        )
        mfu = None
        if self.mm.n_devices > 1:
            # chip-level utilization under the DEVICE-labeled names: an
            # idle or skewed slice is visible instead of averaged away
            # by the family aggregate. Cardinality is mesh-bounded.
            f_name, s_name, g_name = MfuAccount.DEVICE_NAMES
            mfu = MfuAccount(
                self.metrics, family,
                flops_name=f_name, secs_name=s_name, gauge_name=g_name,
                device=self.mm.slice_device_label(sl),
            )
        if scorer.stateful:
            # the second kind of stream state: provisioned for
            # max_streams at tenant start, like the rings
            self.metrics.gauge(
                "tpu_inference_stream_state_bytes", family=family
            ).set(scorer.state_nbytes)
        # THE birth of a slice, whole
        self._slices[(family, sl)] = SliceRuntime(
            family, sl, scorer, breaker, self.metrics,
            max_inflight=self.max_inflight,
            staging_slots=self.staging_slots, mfu=mfu,
        )
        if self.mm.n_devices > 1:
            # how many mesh slices currently serve this family —
            # slice spread is the first thing to read when per-device
            # rows/MFU look uneven (docs/OBSERVABILITY.md)
            self.metrics.gauge(
                "tpu_inference_slice_scorers", family=family
            ).set(len(self._family_slices(family)))
        return scorer

    # -- lifecycle -------------------------------------------------------
    async def on_start(self) -> None:
        await super().on_start()
        # dedicated materialization pool: the default loop executor may have
        # fewer workers than max_inflight, which would serialize the very
        # device→host transfers the semaphore is meant to pipeline
        from concurrent.futures import ThreadPoolExecutor

        self._deliver_pool = ThreadPoolExecutor(
            # enough workers for every slice's in-flight window to
            # materialize concurrently (per-slice inflight budgets),
            # capped so a wide mesh doesn't spawn a thread army
            max_workers=min(
                32, self.max_inflight * max(1, self.mm.n_slices)
            ),
            thread_name_prefix="tpu-deliver",
        )
        # SUPERVISED scoring loop: a persistent loop error restarts it
        # with backoff instead of silently killing all scoring (the k8s
        # liveness-probe-restart analog, in-process)
        self._loop_super = SupervisedTask(
            "tpu-inference-loop", self._scoring_loop, max_restarts=5
        )
        await self._loop_super.initialize()
        await self._loop_super.start()
        # the completion reaper: resolves in-flight flushes as their d2h
        # transfers land; supervised so a resolve fault can't silently
        # end score delivery (pending queues survive a restart)
        self._reaper_super = SupervisedTask(
            "tpu-inference-reaper", self._reap_loop, max_restarts=5
        )
        await self._reaper_super.initialize()
        await self._reaper_super.start()

    async def on_stop(self) -> None:
        if getattr(self, "_loop_super", None) is not None:
            await self._loop_super.terminate()
            self._loop_super = None
        # an in-flight page-in dies with the loop that launched it; its
        # tenant's parked rows resolve unscored in the fence sweep below
        task = getattr(self, "_pagein_task", None)
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        self._pagein_task = None
        # let in-flight transfers land and resolve through the reaper
        # (they hold rows already popped from lanes — dropping them would
        # lose events); only give up if the device never answers
        deadline = time.monotonic() + self.deliver_drain_timeout_s
        while (
            any(s.reap for s in self._slices.values())
            and time.monotonic() < deadline
        ):
            await asyncio.sleep(0.02)
        if self._reaper_super is not None:
            await self._reaper_super.terminate()
            self._reaper_super = None
        # cancel per-family resolves still blocked (e.g. a publish against
        # a stopped consumer): their CancelledError path resolves the
        # popped rows unscored via publish_nowait before re-raising
        for task in [s.resolving for s in self._slices.values()]:
            if task is not None:
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
        # force-resolve anything still stuck, unscored (zero loss even
        # when a transfer never completes) — the SAME accounting helper
        # the supervisor's mid-run deadline path uses, so teardown and
        # in-flight force-resolution cannot diverge
        for s in self._slices.values():
            while s.reap:
                pf = s.reap.popleft()
                await self._force_resolve(pf, nowait=True)
                pf.resolved = True
                if pf.owns_permit:
                    s.permits.release()
        self._deliver_gauge()
        # probation probes die with the service; a hung chaos plan must
        # release its blocked worker threads or the deliver pool's
        # shutdown below strands them past interpreter exit
        for s in self._slices.values():
            if s.probing is not None:
                s.probing.cancel()
        if self.faultplan is not None:
            self.faultplan.clear()
        pool = getattr(self, "_probe_pool", None)
        if pool is not None:
            # wait=False: a probe thread parked inside a wedged chip's
            # materialization must not hang the stop cascade
            pool.shutdown(wait=False)
            self._probe_pool = None
        # final sweep: rows can land in lanes (or slice-move fences)
        # AFTER their engine's own stop-drain (the scoring loop keeps
        # consuming during the stop cascade) — resolve them unscored so
        # no consumed event is lost
        for s in self._slices.values():
            for _d, _i, _v, seqs, rows in s.drain_lanes():
                await self._resolve_rows(
                    seqs, rows, None, publish_nowait=True, family=s.family
                )
        for fence in list(self._fences.values()):
            for ring in fence.stash.values():
                if ring.count:
                    _i, _v, seqs, rows = ring.pop(ring.count)
                    await self._resolve_rows(
                        seqs, rows, None, publish_nowait=True,
                        family=fence.family,
                    )
        self._fences.clear()
        # pending train rows are droppable history (the segment store
        # still holds them; a future replay train job re-feeds) — no
        # unscored-resolve obligation on the train lane. Zero the depth
        # gauges as the rings go: a stopped service must not report
        # phantom pending training rows forever.
        for fam in {s.family for s in self._slices.values() if s.train_lanes}:
            self.metrics.gauge("tpu_inference_train_rows", family=fam).set(0)
        # THE death of every slice, with the scorer, the device scores
        # and the staging sets it pins: a service started again builds
        # them anew in ``scorer_for_slice``
        if any(s.staging for s in self._slices.values()):
            self.metrics.gauge("tpu_inference_staging_sets").set(0)
        self._slices.clear()
        if self.mm.n_devices > 1:
            # cardinality guard (the drop_labeled pattern): a stopped
            # service's device-labeled children must not be exported
            # forever — device labels track the LIVE mesh
            for lbl in self.mm.device_labels():
                self.metrics.drop_labeled(device=lbl)
        if self._deliver_pool is not None:
            self._deliver_pool.shutdown(wait=False)
            self._deliver_pool = None

    # -- ingestion → lanes (columnar) ------------------------------------
    @spanned("lanes")
    async def _enqueue_batch(
        self,
        engine: TpuInferenceEngine,
        batch: MeasurementBatch,
        sample_rate: float = 1.0,
    ) -> None:
        """Route a MeasurementBatch's rows into scoring lanes. Rows that
        can't get a stream slot resolve immediately as unscored.
        ``sample_rate < 1`` is the ``sample_inference`` degradation mode:
        only a strided sample of rows is scored, the rest resolve
        unscored right away (they still persist — degraded, never lost)
        so the TPU budget shrinks without breaking accounting."""
        t_lane = time.perf_counter()
        if batch.t_intake:
            # broker delivery → lane enqueue: receiver queue, decode,
            # inbound and their bus waits, per message
            self.metrics.histogram("pipeline.intake", unit="s").record(
                t_lane - batch.t_intake
            )
        batch.t_lane = t_lane
        family = engine.config.model
        sl = engine.placement.shard
        # a placement names a slice that exists: the engine's start (a
        # ghost's too) and every move build it before rows can arrive
        s = self._slices[(family, sl)]
        slot = engine.placement.slot
        fence = self._fences.get(engine.tenant)
        if self.pager is not None:
            if slot >= 0:
                # resident: LRU refresh + hit-rate / prefetch-accuracy
                # bookkeeping (pure dict ops — stays off check_hotpath's
                # forbidden list)
                self.pager.slice_pager(
                    family, sl, self.slots_per_shard
                ).touch(engine.tenant)
                self.pager.note_touch(engine.tenant, True)
            else:
                # non-resident: rows park behind the paging fence below;
                # queue a DEMAND page-in (always admitted — parked rows
                # must never strand behind an unserviceable fence)
                self.pager.note_touch(engine.tenant, False)
                self.pager.queue.push(
                    engine.tenant, "demand", time.monotonic()
                )
        n = batch.n
        if batch.scores is None:
            batch.scores = np.full((n,), np.nan, np.float32)
        seq = self._next_seq
        self._next_seq += 1
        entry = [batch, n]
        self._batches[seq] = entry
        batch.mark("inference_enqueue")  # inference span start / lane wait

        # per-row (dshard, local_id): one registry lookup per UNIQUE
        # (device, name) series, scattered back via inverse indices — no
        # event objects, no awaits, no per-row Python
        dshards, locals_ = engine.streams.lookup_or_assign_bulk(batch)
        skipped = int((dshards == -1).sum())
        if skipped:
            self.metrics.counter("tpu_inference.skipped_capacity").inc(skipped)
            entry[1] -= skipped
        if sample_rate < 1.0:
            step = max(1, int(round(1.0 / max(sample_rate, 1e-3))))
            sampled_out = np.ones((n,), bool)
            sampled_out[::step] = False
            sampled_out &= dshards != -1  # don't double-count skipped rows
            k = int(sampled_out.sum())
            if k:
                dshards = np.where(sampled_out, -1, dshards)
                entry[1] -= k
                self.metrics.counter("tpu_inference.sampled_out").inc(k)
        if entry[1] <= 0:
            # nothing left awaiting scores (all rows skipped, or an empty
            # batch) — publish now or the registry entry leaks forever
            await self._publish_batch(seq)
            return
        parked = 0
        # a new lane is sized to the lane watermark (2× max_batch split
        # across data shards) so steady state never reallocates
        lane_cap = max(
            4096,
            2 * engine.config.microbatch.max_batch
            // max(1, self.mm.n_data_shards),
        )
        for d in range(self.mm.n_data_shards):
            sel = np.nonzero(dshards == d)[0]
            if sel.size == 0:
                continue
            if fence is not None:
                # mid-slice-move: the tenant's new rows park behind the
                # fence (FIFO) until the old slice's in-flight flushes
                # resolve — per-tenant delivery order survives the move
                fence.park(d, locals_[sel], batch.values[sel], seq, sel)
                parked += sel.size
                continue
            # sel doubles as the row indices inside the batch; seq
            # broadcasts — rows land in the ring right here, at enqueue
            s.lane(slot, d, lane_cap).push(
                locals_[sel], batch.values[sel], seq, sel
            )
        if fence is not None:
            if parked:
                self.metrics.counter("tpu_inference.fenced_rows").inc(parked)
                if fence.new_sl is None and "paged" not in batch.trace:
                    # cold-start activation SLO (docs/OBSERVABILITY.md):
                    # the batch waited on a page-in — its parked time
                    # folds into lane_wait in the stage ledger, and this
                    # mark keys it out of the hot-path latency columns
                    batch.mark("paged")
            return
        s.mark_pending()

    # -- score write-back -------------------------------------------------
    async def _resolve_rows(
        self,
        seqs: np.ndarray,
        rows: np.ndarray,
        scores: Optional[np.ndarray],
        publish_nowait: bool = False,
        family: str = "",
        flush_id: Optional[int] = None,
    ) -> int:
        """Columnar score write-back: scatter ``scores`` (or NaN for an
        unscored resolution) into their batches' score columns one
        contiguous run at a time, then publish every batch that became
        complete — in seq (= enqueue) order, so a tenant's batches leave
        in order even when a flush carried several. Returns the number
        of batches published. ``flush_id`` names the flush that scored
        the rows: each batch it completes carries it on its inference
        span.

        Rows arrive grouped: lanes pop FIFO and flushes pack lanes in
        sorted order, so equal-seq runs are contiguous and their row
        indices ascend — a dense run is a pure slice assignment, a
        sampled/split one a single vectorized scatter. Run count is
        O(lanes × batches per flush), tiny next to row count; no
        per-row Python, no list accumulators (tools/check_hotpath.py
        keeps it that way)."""
        n = len(seqs)
        if n == 0:
            return 0
        if scores is None and family:
            # the poisoned/parked/drain deliveries used to publish NaN
            # rows with NO counter — an operator watching scored_total
            # could not tell a degraded family from a healthy one
            self.metrics.counter(
                "tpu_scores_unscored_total", family=family
            ).inc(n)
        cuts = np.flatnonzero(seqs[1:] != seqs[:-1]) + 1
        done = np.empty((len(cuts) + 1,), np.int64)
        k = 0
        a = 0
        for b in (*cuts.tolist(), n):
            s = int(seqs[a])
            entry = self._batches.get(s)
            if entry is not None:
                dst = entry[0].scores
                run = rows[a:b]
                # dense ⇔ consecutive ascending rows (one lane's FIFO pop
                # — the common case); a run spanning several lanes or a
                # sampled batch falls back to one vectorized scatter
                dense = b - a == 1 or bool((np.diff(run) == 1).all())
                if scores is None:
                    if dense:
                        dst[int(run[0]) : int(run[-1]) + 1] = np.nan
                    else:
                        dst[run] = np.nan
                elif dense:
                    dst[int(run[0]) : int(run[-1]) + 1] = scores[a:b]
                else:
                    dst[run] = scores[a:b]
                if scores is None:
                    # per-tenant delivery-quality accounting (one call
                    # per run, never per row — runtime.scorehealth)
                    self.scorehealth.note_unscored(entry[0].tenant, b - a)
                entry[1] -= b - a
                if entry[1] <= 0:
                    done[k] = s
                    k += 1
            a = b
        if k:
            # publish in ascending seq order (scatter above was
            # await-free, so no batch state moved under us)
            done[:k].sort()
            seq_list = done[:k].tolist()
            t_pub = time.perf_counter()
            with sw("publish"):
                for i, s in enumerate(seq_list):
                    try:
                        await self._publish_batch(
                            int(s), nowait=publish_nowait, flush_id=flush_id
                        )
                    except BaseException:
                        # cancelled (teardown) or a publish fault
                        # mid-loop: the remaining completed batches are
                        # already out of the registry's reach of any
                        # later resolve — flush them nowait or they
                        # strand in _batches and their events are lost
                        for s2 in seq_list[i + 1:]:
                            await self._publish_batch(int(s2), nowait=True)
                        raise
            # the publish loop alone — a child of ``resolve``, which ends
            # where this does
            self.metrics.histogram("tpu_inference.publish", unit="s").record(
                time.perf_counter() - t_pub
            )
        return k

    def _gate(self, tenant: str):
        """Per-tenant inference deadline gate (lazy): expired batches
        route to the expired topic BEFORE any lane/flush work — this is
        the 'no expired event reaches a ShardedScorer flush' guarantee."""
        g = self._gates.get(tenant)
        if g is None:
            from sitewhere_tpu.runtime.overload import DeadlineGate

            g = self._gates[tenant] = DeadlineGate(
                self.bus, tenant, "inference", self.metrics,
                tracer=self.tracer, controller=self.overload,
            )
        return g

    def _stage_timer(self, tenant: str):
        t = self._stage_timers.get(tenant)
        if t is None:
            from sitewhere_tpu.runtime.tracing import StageTimer

            t = self._stage_timers[tenant] = StageTimer(
                self.tracer, self.metrics, tenant, "inference"
            )
        return t

    async def _publish_batch(
        self, seq: int, nowait: bool = False,
        flush_id: Optional[int] = None,
    ) -> None:
        batch, _ = self._batches.pop(seq)
        # a retried batch that made it out scored is no longer suspect
        self._retried_seqs.discard(seq)
        # inference span: start = lane enqueue, queue wait = bus time since
        # the inbound stage published; it carries the id of the flush that
        # completed the batch — the latency ledger splits the span on that
        # flush's own record (``flush_records``), never on a neighbour's
        t_now = time.time() * 1000.0
        enq = batch.trace.get("inference_enqueue", t_now)
        prev = max(
            (v for k, v in batch.trace.items() if k != "inference_enqueue"),
            default=enq,
        )
        engine = self.engines.get(batch.tenant)
        family = engine.config.model if engine is not None else ""
        ann = {"family": family}
        if flush_id is not None:
            ann["flush_id"] = flush_id
        self._stage_timer(batch.tenant).observe(
            batch, enq, t_now, n_events=batch.n,
            queue_wait_ms=max(0.0, enq - prev), **ann,
        )
        batch.t_scored = time.perf_counter()
        batch.mark("scored")
        topic = self.bus.naming.scored_events(batch.tenant)
        if nowait:
            # teardown path: the consumer may already be stopped; an
            # awaitable publish against a full topic would never unblock
            self.bus.publish_nowait(topic, batch)
        else:
            # normal path: preserve backpressure toward persistence — a
            # lagging store slows scoring instead of silently evicting
            # whole batches past retention. The batch is already out of
            # the registry, so a transient publish fault must be retried
            # here (nowait fallback) or the whole batch would vanish.
            try:
                await publish_at_least_once(
                    self.bus, topic, batch, metrics=self.metrics
                )
            except asyncio.CancelledError:
                raise  # publish_at_least_once already appended nowait
            except Exception:
                # non-transient fault: same registry-reach argument —
                # append nowait before surfacing, or the batch is lost
                self.bus.publish_nowait(topic, batch)
                raise
        # latency accounting: sample rows (full per-row recording would be
        # a Python loop over 10^5 rows/s). Replayed history carries its
        # ORIGINAL received_ts — hours-old samples would flood the live
        # p99/SLO series for the whole replay, so only live traffic
        # records latency (replay progress has its own metric family).
        if "replay" not in batch.trace:
            lat = self.metrics.histogram("tpu_inference.latency", unit="s")
            now = time.time() * 1000.0
            rts = batch.received_ts[:: max(1, batch.n // 16)]
            lat.record_many(((now - rts) / 1000.0).tolist())
        self.metrics.counter("tpu_inference.scored_total").inc(batch.n)
        self.metrics.meter("tpu_inference.scored").mark(batch.n)

    # -- flush -----------------------------------------------------------
    async def _flush_slice(
        self, engine_cfgs: Dict[int, TenantEngineConfig], s: SliceRuntime,
    ) -> int:
        """Pack one (family, mesh-slice)'s lane rings into the slice's
        reusable staging set, stage the buffers to the SLICE's devices
        (async h2d — overlaps any in-flight flush's dispatch, on this
        slice or any other), dispatch the slice's jit step, and hand
        score materialization to the per-device reap queue. Slices flush
        independently: no cross-slice collectives, no shared staging
        pool, no shared completion stream."""
        family, sl, scorer, lanes = s.family, s.sl, s.scorer, s.lanes
        if family in self._parked or s.quarantine is not None:
            # degraded mode: resolve pending rows unscored so events keep
            # flowing to persistence/rules while the scorer is parked —
            # or while THIS slice is quarantined and its tenants could
            # not fail over (fleet at capacity): the slice passes its
            # events through unscored until probation re-admits it
            if family not in self._parked:
                self.metrics.counter(
                    "tpu_inference.quarantine_passthrough"
                ).inc()
            return await self._pass_unscored(s)
        if not any(l.count for l in lanes.values()):
            s.first_pending_ts = None
            return 0
        breaker = s.breaker
        if not breaker.allow():
            # breaker OPEN: stop hammering the scorer — resolve pending
            # rows unscored (degraded, never lost) until the half-open
            # schedule lets a trial flush probe recovery. Trial failures
            # keep feeding the failover→park escalation below.
            drained = await self._pass_unscored(s)
            self.metrics.counter("tpu_inference.breaker_short_circuits").inc()
            return drained
        any_cfg = next(iter(engine_cfgs.values()))
        mb = any_cfg.microbatch
        # acquire the in-flight slot BEFORE popping rows off the lanes:
        # a cancellation while waiting here must not strand popped rows
        # (everything from the pop to the reap enqueue below is
        # await-free).
        flush_id = self._next_flush_id
        self._next_flush_id += 1
        t_asked = time.perf_counter()
        sem = s.permits
        if sem.locked():
            # all of THIS slice's completion slots busy: the flush
            # backpressures here, where depth is the deliver_inflight
            # gauge (check_queues) — other slices' budgets are untouched
            self.metrics.counter("tpu_inference.deliver_backpressure").inc()
        with sw("permit_wait", flush_id=flush_id):
            await sem.acquire()
        t_got = time.perf_counter()
        self.metrics.histogram("tpu_inference.acquire_wait", unit="s").record(
            t_got - t_asked
        )
        # pick the bucket AFTER the (possibly long) acquire wait: rows that
        # accumulated while every slot was busy should ride out in ONE
        # bigger flush, not drain at the stale pre-wait size
        pending_max = max((l.count for l in lanes.values()), default=0)
        b_lane = s.pick_bucket(pending_max, tuple(mb.buckets), mb.max_batch)
        # wire-thin stacked batch: compact id/value dtypes + one count per
        # (slot, data-shard) lane instead of a bool mask — rows fill each
        # lane from the front, so validity is derivable on device (see
        # ShardedScorer.step_counts; h2d bytes are a first-class budget).
        # Assembly is slice copies lane-ring → REUSABLE staging buffers:
        # no fresh flush arrays, no list accumulators, no np.asarray over
        # Python lists (tools/check_hotpath.py enforces this stays true).
        with sw("flush_assembly", flush_id=flush_id):
            t_asm = time.perf_counter()
            st = s.staging_set(b_lane)
            ids, vals, counts = st.ids, st.vals, st.counts
            counts[:] = 0
            take_total = 0
            for lane in lanes.values():
                take_total += min(lane.count, b_lane)
            slots_cat = np.empty((take_total,), np.int32)
            cols_cat = np.empty((take_total,), np.int32)
            seqs_cat = np.empty((take_total,), np.int64)
            rows_cat = np.empty((take_total,), np.int32)
            moved = 0
            used_slots: set = set()
            # SORTED lane order: the device-side gather compacts valid rows
            # in (slot, data-shard, lane-position) order, so the host-side
            # seqs/rows bookkeeping must pack in exactly that order for
            # gathered[:moved] to line up with seqs_cat/rows_cat
            for (slot, dshard), lane in sorted(lanes.items()):
                k = min(lane.count, b_lane)
                if k == 0:
                    continue
                base = dshard * b_lane
                lane.pop_into(k, ids[slot], vals[slot], base, seqs_cat, rows_cat, moved)
                slots_cat[moved : moved + k] = slot
                cols_cat[moved : moved + k] = st.arange[base : base + k]
                counts[slot, dshard] = k
                used_slots.add(slot)
                moved += k
            depth_left = 0
            for lane in lanes.values():
                depth_left += lane.count
            self.metrics.gauge("tpu_inference_lane_rows", family=family).set(
                depth_left
            )
            s.first_pending_ts = time.monotonic() if depth_left else None
            if moved == 0:
                sem.release()
                breaker.release_trial()  # allowed, but no call was made
                return 0
            t_assembled = time.perf_counter()
            assembly_s = t_assembled - t_asm
            self.metrics.histogram(
                "tpu_inference.flush_assembly", unit="s"
            ).record(assembly_s)

        taken = (slots_cat, cols_cat, seqs_cat, rows_cat)
        compiling = b_lane not in s.seen_shapes
        h2d_stage_s: Optional[float] = None  # for the fault record when
        dispatch_s: Optional[float] = None   # the try below dies early
        rec: Optional[dict] = None           # blackbox record, once made
        try:
            # h2d prefetch: issue the ASYNC device copy before dispatch.
            # "Overlapped" is measured honestly: the previous flush's
            # dispatch output is not yet ready ⇔ this staging copy rides
            # under genuinely in-flight device compute (a pending deliver
            # task alone could just be awaiting its publish).
            prev_scores = s.last_scores
            try:
                overlapped = (
                    prev_scores is not None and not prev_scores.is_ready()
                )
            except Exception:  # noqa: BLE001 - monkeypatched scorers
                overlapped = any(x.reap for x in self._slices.values())
            t_stage = time.perf_counter()
            stage = getattr(scorer, "stage_inputs", None)
            if stage is not None:
                with sw("h2d_stage", flush_id=flush_id):
                    staged = stage(ids, vals, counts)
                st.staged = staged
            else:  # monkeypatched/minimal scorers (tests)
                staged = (ids, vals, counts)
            t_staged = time.perf_counter()
            h2d_stage_s = t_staged - t_stage
            self.metrics.histogram("tpu_inference.h2d_stage", unit="s").record(
                h2d_stage_s
            )
            self.metrics.counter("tpu_inference.h2d_staged").inc()
            if overlapped:
                self.metrics.counter("tpu_inference.h2d_overlapped").inc()
            try:
                self.metrics.counter("tpu_inference.staged_bytes").inc(
                    scorer.stage_nbytes(staged)
                )
            except Exception:  # noqa: BLE001 - observability only
                pass
            # shadow-scoring canary: when armed (non-f32/K>1 variant or a
            # recent hot-swap, at the family's canary_frac stride), score
            # this flush ALSO through the previous variant — the legacy
            # f32 step. It must dispatch BEFORE the primary step: it
            # reads the window state the primary is about to donate, and
            # same-queue dispatch order guarantees that read. Shadow
            # FLOPs land in tpu_shadow_flops_total — NEVER the MFU
            # account — so tpu_mfu_pct keeps meaning "serving work".
            shadow_dev = None
            take = getattr(scorer, "canary_take", None)
            if take is not None and take():
                try:
                    shadow_plane = scorer.shadow_step_counts(*staged)
                    shadow_dev = scorer.gather_rows(
                        shadow_plane, staged[2], moved
                    )
                    if self.faultplan is not None:
                        # chaos: the shadow lane is a fault domain too —
                        # a hung shadow transfer blocks the flush's
                        # materialization triple, and the same deadline
                        # must catch it
                        shadow_dev = self.faultplan.wrap(
                            shadow_dev, family, sl, "shadow"
                        )
                    shadow_dev.copy_to_host_async()
                    self.metrics.counter("tpu_inference.canary_flushes").inc()
                    self.metrics.counter(
                        "tpu_shadow_flops_total", family=family
                    ).inc(float(scorer.shadow_flops_per_flush(b_lane)))
                except Exception as exc:  # noqa: BLE001 - the canary is
                    # advisory: it must never take scoring down with it
                    self._record_error("canary", exc)
                    shadow_dev = None
            if self.faultplan is not None:
                # fail_dispatch injection (the poison-batch scenario) —
                # raises through the fault path below like a real
                # kernel crash on this batch's data
                self.faultplan.maybe_raise(family, sl, "serve")
            t_disp = time.perf_counter()
            with sw("dispatch", flush_id=flush_id):
                scores_dev = scorer.step_counts(*staged)  # async dispatch
            t_dispatched = time.perf_counter()
            ts_dispatched_ms = time.time() * 1000.0
            dispatch_s = t_dispatched - t_disp
            self.metrics.histogram("tpu_inference.dispatch", unit="s").record(
                dispatch_s
            )
            disp_labels = {"family": family}
            if self.mm.n_devices > 1:
                # multichip path: stamp the device so ROADMAP item 1's
                # mesh promotion lands with per-device attribution in
                # place. Cardinality is mesh-bounded (device labels come
                # only from live mesh devices) and the service drops its
                # device children on stop (drop_labeled)
                disp_labels["device"] = getattr(
                    scorer, "device_label", "device:?"
                )
            self.metrics.histogram(
                "tpu_inference_dispatch_seconds", **disp_labels
            ).record(dispatch_s)
            if compiling:
                # first flush at this (family, bucket) shape = XLA compile;
                # a counter bump here is how a mid-traffic recompile (new
                # bucket, missed prewarm) becomes attributable instead of
                # an anonymous p99 cliff
                s.seen_shapes.add(b_lane)
                self.metrics.counter("tpu_inference.compiles").inc()
                self.metrics.counter(
                    "tpu_inference_compiles", family=family,
                    bucket=str(b_lane),
                ).inc()
            if self.mm.n_devices > 1:
                # per-device throughput attribution: which chip scored
                # these rows (slice balance / skew ride on this)
                self.metrics.counter(
                    "tpu_inference_device_rows_total",
                    device=scorer.device_label,
                ).inc(moved)
            self.metrics.counter("tpu_inference.flushes").inc()
            self.metrics.counter("tpu_inference.flush_rows").inc(moved)
            # flushes already in flight on this slice as this one joins
            # the device queue (÷ .flushes = the mean depth it waits
            # behind). In flight as the ``inflight`` interval has it:
            # dispatched and not landed — a landed head whose resolve is
            # still publishing holds a queue slot, not the device
            ahead = s.in_flight()
            self.metrics.counter("tpu_inference.inflight_depth_sum").inc(
                len(ahead)
            )
            if any(p.lane == "serve" for p in ahead):
                # joined a device still busy with a serve flush: the
                # policy let it through because a lane had reached the
                # smallest bucket (``SliceRuntime.held``)
                self.metrics.counter("tpu_inference.flush_pipelined").inc()
            # lane wait, per carried batch: its enqueue → the flush asked
            # for its permit
            seq_list = np.unique(seqs_cat).tolist()
            lane_wait = self.metrics.histogram(
                "tpu_inference.lane_wait", unit="s"
            )
            t_oldest = t_asked
            for s_ in seq_list:
                entry = self._batches.get(s_)
                t_lane = entry[0].t_lane if entry is not None else 0.0
                if t_lane:
                    lane_wait.record(max(0.0, t_asked - t_lane))
                    if t_lane < t_oldest:
                        t_oldest = t_lane
            # the flush record — completed in place (landed / resolved /
            # d2h / service timings) when the reaper resolves the flush.
            # It lives in the flight recorder's ring (the blackbox) and,
            # by id, in ``flush_records`` (the latency ledger's lookup).
            rec = self._flush_record(
                family,
                ts_ms=ts_dispatched_ms,
                flush_id=flush_id,
                seqs=seq_list,
                t_oldest=t_oldest, t_asked=t_asked, t_got=t_got,
                t_assembled=t_assembled, t_staged=t_staged,
                t_dispatched=t_dispatched,
                lane="serve",
                rows=moved, bucket=b_lane,
                assembly_s=round(assembly_s, 6),
                h2d_stage_s=round(h2d_stage_s, 6),
                dispatch_s=round(dispatch_s, 6),
                h2d_overlapped=bool(overlapped),
                compiled=compiling,
                # kernel variant attribution: which fused-step shape
                # produced this flush's timings (incident snapshots
                # must name the variant, not just the family)
                k_steps=getattr(scorer, "k_steps", 1),
                param_dtype=getattr(scorer, "param_dtype", "f32"),
                # multi-chip attribution: WHICH slice/chip ran this
                # flush — incident snapshots must name the device
                mesh_slice=sl,
                device_label=getattr(scorer, "device_label", "device:?"),
                trace_id=self._flush_trace_id(seqs_cat),
                status="inflight",
            )
            # device-side gather: compact ONLY the flushed rows out of
            # the [T, D*B] score plane before anything crosses d2h —
            # transfer volume becomes rows-proportional (wire dtype),
            # independent of tenant count. Shapes come from the ladder
            # prewarm compiles (ShardedScorer.gather_ladder).
            plane_nbytes = int(getattr(scores_dev, "nbytes", 0))
            # the step's device-side score sketch (i32[T, D, NBINS]) —
            # a few hundred bytes riding the same async readback; its
            # host copy starts here like the scores' below
            sketch_dev = getattr(scorer, "last_sketch", None)
            if sketch_dev is not None:
                try:
                    sketch_dev.copy_to_host_async()
                except Exception:  # noqa: BLE001 - numpy/test doubles
                    pass
            gathered = False
            gather = getattr(scorer, "gather_rows", None)
            if gather is not None and hasattr(scores_dev, "is_ready"):
                try:
                    scores_dev = gather(scores_dev, staged[2], moved)
                    gathered = True
                except Exception as exc:  # noqa: BLE001 - fall back to
                    # the full-plane readback rather than lose the flush
                    self._record_error("gather", exc)
            slot_override = None
            if not gathered and len(used_slots) == 1 and scorer.n_slots > 1:
                # legacy d2h diet for gather-less scorers (monkeypatched
                # doubles): one used slot → slice that row on device
                only = next(iter(used_slots))
                scores_dev = scores_dev[np.full((1,), only, np.int32)]
                slots_cat[:] = 0  # rows now index row 0 of the slice
                slot_override = only  # keep NaN attribution honest
            if self.faultplan is not None:
                # hang/corrupt/slow/late-fail injection: the proxy
                # applies the fault exactly where the reaper's executor
                # materialization touches a real wedged device
                scores_dev = self.faultplan.wrap(
                    scores_dev, family, sl, "serve"
                )
            # overlap probe for the NEXT flush — now holds the gathered
            # rows (a few KB), not a full flush of plane memory; the
            # reaper drops it when the family goes idle
            s.last_scores = scores_dev
            try:
                # start the d2h copy NOW: it rides under the next
                # flush's compute and is (ideally) done by the time the
                # reaper asks — the mirror image of stage_inputs
                scores_dev.copy_to_host_async()
            except Exception:  # noqa: BLE001 - numpy/test doubles
                pass
        except Exception as exc:  # noqa: BLE001 - a failing scorer must
            # not strand popped rows or kill the loop; repeated failures
            # trigger shard failover
            self._record_error("step", exc)
            breaker.record_failure()
            err_rec = None
            if self.flightrec is not None:
                if rec is not None:
                    # the flush already has an inflight record (the fault
                    # hit AFTER dispatch, e.g. device-side slicing):
                    # complete IT — appending a second record would leave
                    # a phantom stuck forever at status="inflight" in the
                    # ring and in any breaker-trip snapshot
                    rec["status"] = "error"
                    rec["error"] = repr(exc)
                    err_rec = rec
                else:
                    err_rec = self.flightrec.record(
                        "flush", family,
                        lane="serve",
                        rows=moved, bucket=b_lane,
                        assembly_s=round(assembly_s, 6),
                        h2d_stage_s=(
                            round(h2d_stage_s, 6)
                            if h2d_stage_s is not None else None
                        ),
                        dispatch_s=(
                            round(dispatch_s, 6)
                            if dispatch_s is not None else None
                        ),
                        compiled=compiling,
                        k_steps=getattr(scorer, "k_steps", 1),
                        param_dtype=getattr(scorer, "param_dtype", "f32"),
                        mesh_slice=sl,
                        device_label=scorer.device_label,
                        trace_id=self._flush_trace_id(seqs_cat),
                        status="error", error=repr(exc),
                    )
            # poison-batch ejection, first strike: the staging set is
            # still intact in this synchronous handler, so the staged
            # bytes can be copied for ONE retry — a transient chip fault
            # recovers the rows scored; a deterministic data fault fails
            # again and ships the batch to the scorer-poison DLQ instead
            # of burning more breaker/failover capacity on it.
            ft = self._family_ft(family)
            retry_rows = None
            if (
                ft.poison_retry
                and moved > 0
                and not self._seqs_already_retried(seqs_cat)
            ):
                retry_rows = self._copy_retry_rows(
                    st, slots_cat, cols_cat, b_lane
                )
            if retry_rows is None:
                # resolve the rows unscored THROUGH the reap FIFO, not
                # inline: an earlier flush of this family may still be
                # in flight, and publishing these batches first would
                # hand a tenant its later batch before its earlier one.
                # The permit stays held until the reaper resolves the
                # entry.
                self._reap_enqueue(_PendingFlush(
                    family, None, taken, moved, False, 0, 0, poisoned=True,
                    rec=err_rec, sl=sl,
                ))
            if self.flightrec is not None and breaker.state == "open":
                # breaker TRIP: freeze the blackbox NOW, with the
                # faulting flush's record (timings + trace_id) already
                # in the ring it snapshots
                self.flightrec.snapshot(
                    f"breaker:{family}", family=family,
                    trace_id=err_rec.get("trace_id") if err_rec else None,
                )
            await self._note_scorer_error(s)
            if retry_rows is not None:
                # the rows leave through the retry dispatch's OWN permit
                # (possibly on another slice) — this flush's permit goes
                # back now, not via a pf resolution
                sem.release()
                # AFTER the failover pacing above: if the fault also
                # crossed the failover threshold, the retry lands on the
                # tenants' NEW slices (where a second failure confirms
                # the data owns the fault); below it, on the original
                # slice (where a second failure stays a chip signal)
                await self._retry_poison(family, sl, retry_rows, taken, exc)
            return moved
        try:
            self._train_tick(s, engine_cfgs)
        except Exception as exc:  # noqa: BLE001 - a training fault must not
            # leak the inflight permit or strand the step's rows (the
            # scoring step itself succeeded; delivery proceeds below)
            self._record_error("train", exc)
        flops_fn = getattr(scorer, "flops_per_flush", None)
        pf = _PendingFlush(
            family, scores_dev, taken, moved, gathered,
            int(getattr(scores_dev, "nbytes", 0)), plane_nbytes,
            flops=float(flops_fn(b_lane)) if flops_fn is not None else 0.0,
            rec=rec, sketch=sketch_dev, shadow=shadow_dev, sl=sl,
            flush_id=flush_id, t_dispatch=t_dispatched,
        )
        pf.slot_override = slot_override
        pf.stream_stats = getattr(scorer, "last_stats", None)
        # flush supervision: the completion deadline the reaper races
        # (family p99-derived, floored by flush_deadline_ms; None = off)
        ft = self._family_ft(family)
        dl = s.flush_deadline_s(ft)
        if dl is not None:
            pf.deadline = pf.t_dispatch + dl
            if ft.poison_retry:
                # staged-byte copies for the one-shot poison retry: a
                # TIMED-OUT flush needs them long after the staging set
                # recycled — the price of retry-with-identical-bytes.
                pf.retry_rows = self._copy_retry_rows(
                    st, slots_cat, cols_cat, b_lane
                )
        if not hasattr(scores_dev, "copy_to_host_async"):
            # no async copy available (test doubles): materialize eagerly
            # on the pool so fallback flushes still overlap each other
            pf.ensure_host_future(
                asyncio.get_running_loop(), self._deliver_pool
            )
        self._reap_enqueue(pf)
        return moved

    async def _pass_unscored(self, s: SliceRuntime) -> int:
        """Degraded mode: the slice's pending rows leave unscored."""
        drained = 0
        for _d, _i, _v, seqs, rows in s.drain_lanes():
            await self._resolve_rows(seqs, rows, None, family=s.family)
            drained += len(seqs)
        s.first_pending_ts = None
        return drained

    FLUSH_RECORDS = 512  # newest flush records kept for the ledger

    def _flush_record(self, family: str, **fields) -> dict:
        """One flush's record: appended to the flight recorder's ring
        (when the service has one) and indexed by ``flush_id``."""
        t0 = self.metrics.loop_ledger.clock()
        if self.flightrec is not None:
            rec = self.flightrec.record("flush", family, **fields)
        else:
            rec = fields
        self.flush_records[fields["flush_id"]] = rec
        if len(self.flush_records) > self.FLUSH_RECORDS:
            self.flush_records.popitem(last=False)
        self.metrics.loop_ledger.observe_from(t0)
        return rec

    @staticmethod
    def _copy_retry_rows(
        st, slots_cat: np.ndarray, cols_cat: np.ndarray, b_lane: int
    ) -> tuple:
        """Staged-byte copies for the one-shot poison retry (~6 B/row,
        two vectorized gathers). BOTH capture sites — the dispatch-fault
        handler and the supervised healthy dispatch — go through here so
        the retry-with-identical-bytes guarantee can't silently diverge
        between them."""
        return (
            st.ids[slots_cat, cols_cat].copy(),
            st.vals[slots_cat, cols_cat].astype(np.float32),
            (cols_cat // b_lane).astype(np.int32),
        )

    def _seqs_already_retried(self, seqs: np.ndarray) -> bool:
        """True when any packed batch already spent its ONE poison
        retry — every retry-granting site must consult this, or a batch
        whose rows span multiple flushes gets a retry per flush."""
        return any(
            int(s) in self._retried_seqs
            for s in np.unique(seqs).tolist()
        )

    def _flush_trace_id(self, seqs_cat: np.ndarray) -> Optional[str]:
        """The first packed batch's trace id — links a flight-recorder
        flush record to its GET /api/traces/{id} trace (one flush packs
        many batches; the head batch anchors the join)."""
        if not len(seqs_cat):
            return None
        entry = self._batches.get(int(seqs_cat[0]))
        if entry is None:
            return None
        ctx = getattr(entry[0], "trace_ctx", None)
        return getattr(ctx, "trace_id", None)

    def _reap_enqueue(self, pf: _PendingFlush) -> None:
        """Queue one pending flush (normal or poisoned) for the reaper:
        the single definition of the enqueue protocol — FIFO append on
        its slice, gauge refresh, reaper wake (the gauge and the reaper
        span slices, which is why this is the service's)."""
        self._slices[pf.key].reap.append(pf)
        self._deliver_gauge()
        self._reap_event.set()

    # -- poison-batch ejection ---------------------------------------------
    def _tenants_in_flight(
        self, family: str, sl: int, exclude: Optional[_PendingFlush]
    ) -> set:
        """Tenants with unresolved serve flushes queued on (family,
        slice) — the poison-retry FIFO guard reads this: a cross-slice
        retry for such a tenant could overtake (or be overtaken by) its
        other in-flight batches, so its rows take an ORDERED fallback
        instead."""
        out: set = set()
        for p in self._slices[(family, sl)].reap:
            if p is exclude or p.resolved or p.lane != "serve":
                continue
            for s in np.unique(p.taken[2]).tolist():
                entry = self._batches.get(int(s))
                if entry is not None:
                    out.add(entry[0].tenant)
        return out

    def _enqueue_ordered_unscored(
        self, family: str, sl: int, taken_sel: tuple
    ) -> None:
        """Append one host-only poisoned entry at a slice's FIFO tail
        WITHOUT a permit (``owns_permit=False``): the ordered fallback
        when rows must resolve after that queue's in-flight flushes but
        the caller may BE that queue's resolve task — acquiring there
        deadlocks against the head it is resolving."""
        pf = _PendingFlush(
            family, None, taken_sel, int(len(taken_sel[2])), False, 0, 0,
            poisoned=True, sl=sl,
        )
        pf.owns_permit = False
        self._reap_enqueue(pf)

    async def _retry_poison(
        self, family: str, sl_first: int, retry_rows: tuple, taken: tuple,
        exc: BaseException, inline: bool = False,
        exclude: Optional[_PendingFlush] = None,
    ) -> None:
        """First strike handled: re-dispatch the faulted flush's rows
        ONCE with the same staged host bytes — one solo flush per
        affected tenant, on the tenant's CURRENT placement (stream →
        data-shard routing is placement-independent, so the bytes are
        valid anywhere the tenant lands; after a quarantine/threshold
        failover that IS the failover slice). A second failure on a
        DIFFERENT slice than ``sl_first`` means two chips agreed — the
        data owns the fault and the batches ship to the DLQ
        (``_eject_poison``); a second failure on the SAME chip stays
        chip-attributed (unscored resolve + failover pacing).

        ``inline=True`` marks the resolve-task callers (deadline
        timeout / deliver fault of the queue HEAD, passed as
        ``exclude``): ordered fallbacks there resolve rows directly —
        resolves are sequential per (family, slice), so the head's own
        task runs before every queued entry — and never await a permit
        on the first slice (the head still holds one; waiting would
        deadlock the queue against itself).

        Per-tenant FIFO guard: a tenant with OTHER unresolved serve
        flushes on the first slice does not cross-slice retry at all —
        its rows resolve unscored in order (inline, or an ordered
        permit-less FIFO entry) rather than racing its own in-flight
        batches on two slices."""
        slots_cat, _cols_cat, seqs_cat, rows_cat = taken
        ids_rows, vals_rows, dshards = retry_rows
        uniq = np.unique(seqs_cat).tolist()
        by_tenant: Dict[str, list] = {}
        for s in uniq:
            entry = self._batches.get(int(s))
            if entry is not None:
                by_tenant.setdefault(entry[0].tenant, []).append(int(s))
        busy = self._tenants_in_flight(family, sl_first, exclude)
        for tenant, seq_list in sorted(by_tenant.items()):
            sel = np.isin(seqs_cat, np.asarray(seq_list, np.int64))
            engine = self.engines.get(tenant)
            if (
                not isinstance(engine, TpuInferenceEngine)
                or engine.placement is None
            ):
                # stopped mid-fault: no placement to retry on — resolve
                # unscored (its bus cursor already advanced; per-tenant
                # order is moot for a stopped tenant)
                await self._resolve_rows(
                    seqs_cat[sel], rows_cat[sel], None, family=family
                )
                continue
            p = engine.placement
            if (
                self._slices[(family, p.shard)].quarantine is not None
                or tenant in busy
            ):
                # capacity-stranded (retrying on a known-sick slice is
                # pointless) or FIFO-guarded (other in-flight batches
                # of this tenant on the first slice): ordered unscored
                # resolution instead of a retry
                if inline:
                    # the head's own resolve task: runs before every
                    # queued entry by construction
                    await self._resolve_rows(
                        seqs_cat[sel], rows_cat[sel], None, family=family
                    )
                else:
                    # FIFO guard outranks the quarantine shortcut: a
                    # busy tenant's rows must queue behind its earlier
                    # in-flight flushes on the FIRST slice even when
                    # its new placement is also quarantined — p.shard's
                    # (likely empty) queue would publish them ahead
                    self._enqueue_ordered_unscored(
                        family,
                        sl_first if tenant in busy else p.shard,
                        tuple(a[sel] for a in taken),
                    )
                continue
            self._retried_seqs.update(seq_list)
            self.metrics.counter("tpu_inference.poison_retries").inc()
            await self._dispatch_retry(
                engine, family, sl_first,
                ids_rows[sel], vals_rows[sel], dshards[sel],
                seqs_cat[sel], rows_cat[sel], exc, inline=inline,
            )

    async def _dispatch_retry(
        self, engine: "TpuInferenceEngine", family: str, sl_first: int,
        ids_r: np.ndarray, vals_r: np.ndarray, dsh: np.ndarray,
        seqs: np.ndarray, rows: np.ndarray, orig_exc: BaseException,
        inline: bool = False,
    ) -> None:
        """One tenant's poison-retry flush: identical bytes, the
        tenant's current (slice, slot), the normal reap FIFO. A second
        dispatch failure here either ejects to the DLQ (different slice
        than the first strike — two chips agreed on the data) or stays
        a chip fault (same slice: unscored resolve through the FIFO,
        breaker + failover pacing — exactly what an un-retried faulted
        flush would have done).

        ``inline=True`` + a retry landing back on ``sl_first`` means
        the caller IS that queue's resolve task with the head's permit
        still held — the retry entry rides permit-less
        (``owns_permit=False``) instead of awaiting a permit the head
        may be the last holder of."""
        p = engine.placement
        sl2, slot2 = p.shard, p.slot
        s2 = self._slices[(family, sl2)]
        scorer = s2.scorer
        try:
            mb = engine.config.microbatch
            # stable per-dshard regrouping keeps each lane's rows in
            # their original FIFO order (= the device gather's pack
            # order)
            order = np.argsort(dsh, kind="stable")
            ids_r, vals_r, dsh = ids_r[order], vals_r[order], dsh[order]
            seqs, rows = seqs[order], rows[order]
            lane_counts = np.bincount(
                dsh, minlength=self.mm.n_data_shards
            )
            b_lane = s2.pick_bucket(
                int(lane_counts.max()), tuple(mb.buckets), mb.max_batch
            )
            t, d = scorer.n_slots, self.mm.n_data_shards
            ids_st = np.zeros((t, d * b_lane), scorer.ids_np_dtype)
            vals_st = np.zeros((t, d * b_lane), scorer.vals_np_dtype)
            counts = np.zeros((t, d), np.int32)
            cols = np.empty((len(seqs),), np.int32)
            off = 0
            for dd in range(d):
                k = int(lane_counts[dd])
                if not k:
                    continue
                base = dd * b_lane
                ids_st[slot2, base : base + k] = ids_r[off : off + k]
                vals_st[slot2, base : base + k] = vals_r[off : off + k]
                cols[off : off + k] = np.arange(
                    base, base + k, dtype=np.int32
                )
                counts[slot2, dd] = k
                off += k
            slots2 = np.full((len(seqs),), slot2, np.int32)
            taken2 = (slots2, cols, seqs, rows)
        except Exception as exc2:  # noqa: BLE001 - retry infra failed
            # BEFORE dispatch (staging alloc): chip-attributed, never
            # poison — and the rows must still resolve (unscored,
            # permit-less, through the retry slice's FIFO) or the
            # zero-loss invariant breaks
            self._record_error("poison-retry-setup", exc2)
            for s in np.unique(seqs).tolist():
                self._retried_seqs.discard(int(s))
            pf2 = _PendingFlush(
                family, None,
                (
                    np.full((len(seqs),), slot2, np.int32),
                    np.zeros((len(seqs),), np.int32), seqs, rows,
                ),
                len(seqs), False, 0, 0, poisoned=True, sl=sl2,
            )
            pf2.owns_permit = False
            self._reap_enqueue(pf2)
            await self._note_scorer_error(s2)
            return
        sem = s2.permits
        own_permit = not (inline and sl2 == sl_first)
        if own_permit:
            await sem.acquire()
        enqueued = False
        try:
            stage = getattr(scorer, "stage_inputs", None)
            staged = (
                stage(ids_st, vals_st, counts) if stage is not None
                else (ids_st, vals_st, counts)
            )
            if self.faultplan is not None:
                # the retry carries its OWN lane so chaos plans can
                # target the second strike deterministically (a "serve"
                # selector would race other tenants' regular flushes on
                # the retry slice for the fault budget)
                self.faultplan.maybe_raise(family, sl2, "retry")
            if b_lane not in s2.seen_shapes:
                s2.seen_shapes.add(b_lane)
                self.metrics.counter("tpu_inference.compiles").inc()
            scores_dev = scorer.step_counts(*staged)
            gathered = False
            gather = getattr(scorer, "gather_rows", None)
            if gather is not None and hasattr(scores_dev, "is_ready"):
                scores_dev = gather(scores_dev, staged[2], len(seqs))
                gathered = True
            if self.faultplan is not None:
                scores_dev = self.faultplan.wrap(
                    scores_dev, family, sl2, "retry"
                )
            try:
                scores_dev.copy_to_host_async()
            except Exception:  # noqa: BLE001 - test doubles
                pass
            rec = None
            if self.flightrec is not None:
                rec = self.flightrec.record(
                    "flush", family,
                    lane="serve", retry=True,
                    rows=len(seqs), bucket=b_lane,
                    mesh_slice=sl2,
                    device_label=getattr(scorer, "device_label", "?"),
                    trace_id=self._flush_trace_id(seqs),
                    status="inflight",
                )
            pf = _PendingFlush(
                family, scores_dev, taken2, len(seqs), gathered,
                int(getattr(scores_dev, "nbytes", 0)), 0,
                rec=rec, sl=sl2,
            )
            pf.retried = True
            pf.retry_from = sl_first
            pf.owns_permit = own_permit
            if not gathered:
                pf.slot_override = slot2
            dl = s2.flush_deadline_s(self._family_ft(family))
            if dl is not None:
                pf.deadline = pf.t_dispatch + dl
            if not hasattr(scores_dev, "copy_to_host_async"):
                pf.ensure_host_future(
                    asyncio.get_running_loop(), self._deliver_pool
                )
            self._reap_enqueue(pf)
            enqueued = True
        except Exception as exc2:  # noqa: BLE001 - second strike
            self._record_error("poison-retry", exc2)
            if self._poison_confirmed(family, sl2, sl_first):
                # two DIFFERENT chips failed the same staged bytes: the
                # DATA is the fault — eject the batches, keep the tenant
                await self._eject_poison(family, seqs, exc2)
            else:
                # same chip twice (or the retry slice is already known-
                # sick): a chip signal — resolve the rows unscored
                # through the FIFO on the permit we hold, and pace
                # breaker/failover exactly like an un-retried fault
                for s in np.unique(seqs).tolist():
                    self._retried_seqs.discard(int(s))
                s2.breaker.record_failure()
                pf2 = _PendingFlush(
                    family, None, taken2, len(seqs), False, 0, 0,
                    poisoned=True, sl=sl2,
                )
                pf2.owns_permit = own_permit
                self._reap_enqueue(pf2)
                enqueued = True  # the poisoned entry inherits the permit
                await self._note_scorer_error(s2)
        finally:
            if own_permit and not enqueued:
                sem.release()

    def _poison_confirmed(
        self, family: str, sl_retry: int, sl_first: int
    ) -> bool:
        """Is a retry failure DATA-attributable? Only when the second
        strike ran on a different slice than the first (two independent
        chips) and that slice isn't itself already suspect — a parked
        family or quarantined retry slice means the fleet, not the
        batch, is sick."""
        return (
            sl_retry != sl_first
            and family not in self._parked
            and self._slices[(family, sl_retry)].quarantine is None
        )

    async def _eject_poison(
        self, family: str, seqs: np.ndarray, error: BaseException
    ) -> int:
        """Second strike: attribute the fault to the data. Each affected
        batch leaves the scoring pipeline for its tenant's
        ``scorer-poison`` dead-letter topic (trace-linked, requeue-able
        over the existing DLQ REST surface) and its registry entry is
        popped so no later resolve can publish it — exactly-once
        accounting moves the batch from 'store' to 'DLQ'. The tenant
        keeps serving: no breaker outcome, no failover pacing."""
        from sitewhere_tpu.runtime.bus import RetryingConsumer

        uniq = sorted({int(s) for s in np.asarray(seqs).tolist()})
        ejected = 0
        consumers: Dict[str, RetryingConsumer] = {}
        for s in uniq:
            entry = self._batches.pop(s, None)
            self._retried_seqs.discard(s)
            if entry is None:
                continue
            batch = entry[0]
            rc = consumers.get(batch.tenant)
            if rc is None:
                rc = consumers[batch.tenant] = RetryingConsumer(
                    self.bus, batch.tenant, "scorer-poison", self.group,
                    metrics=self.metrics, tracer=self.tracer,
                )
            await rc.dead_letter(
                batch, self.bus.naming.inbound_events(batch.tenant),
                attempts=2, error=error,
            )
            ejected += 1
            self.metrics.counter("tpu_inference.poison_ejected").inc()
            if self.flightrec is not None:
                self.flightrec.record(
                    "poison", family,
                    tenant=batch.tenant, seq=s, rows=batch.n,
                    error=repr(error),
                )
        return ejected

    # -- auto-failover ----------------------------------------------------
    async def _note_scorer_error(self, s: SliceRuntime) -> None:
        """Count consecutive scorer failures per (family, mesh-slice);
        at the threshold, rebuild the SICK SLICE's scorer runtime (a
        failed dispatch can invalidate the donated state buffer) and
        fail that slice's tenants over to DIFFERENT mesh shards
        (reference analog: tenant engines restarting on another replica
        after repeated probe failures [U]) — healthy slices keep
        serving untouched. Repeated rounds without a healthy delivery
        PARK the family: events pass through unscored rather than
        churning failovers forever — degraded, never lost."""
        family = s.family
        s.consec_errors += 1
        if s.consec_errors < self.failover_threshold or family in self._parked:
            return
        s.consec_errors = 0
        rounds = self._failover_rounds.get(family, 0) + 1
        self._failover_rounds[family] = rounds
        if rounds > self.max_failover_rounds:
            self._parked.add(family)
            self._record_error(
                "park", RuntimeError(
                    f"family '{family}' parked after {rounds - 1} failover "
                    f"rounds; events pass through unscored"
                ),
            )
            self.metrics.counter("tpu_inference.parked").inc()
            return
        # may reference dead buffers
        s.last_scores = None
        try:
            s.scorer.rebuild_runtime()
            # the rebuilt jit cache recompiles every shape: reset the
            # slice's seen-shape set so the compile counter stays true
            s.seen_shapes.clear()
        except Exception as exc:  # noqa: BLE001 - device may be gone
            self._record_error("rebuild", exc)
        # SUSPECT: quarantine the slice (router avoids it, tenants fail
        # over off it, probation probes re-admit it once it heals) —
        # failed-over tenants RETURN to a healed slice instead of the
        # pre-supervision one-way door
        await self._quarantine_slice(s, reason="scorer-errors")

    # -- quarantine & probation (slice re-adoption) ------------------------
    async def _quarantine_slice(self, s: SliceRuntime, reason: str) -> None:
        """Mark one (family, mesh-slice) SUSPECT: the router routes
        around it, its tenants fail over to healthy slices (those that
        can't — fleet at capacity — degrade to unscored pass-through on
        the quarantined slice), and a background probe re-dispatches
        synthetic flushes until ``probation_probes`` consecutive
        landings re-admit it. Idempotent per (family, slice)."""
        family, sl = s.family, s.sl
        if not s.enter_quarantine(
            reason, self._family_ft(family).probe_interval_s
        ):
            return
        self.metrics.counter("tpu_inference.quarantined").inc()
        self._quarantine_gauge()
        self.router.quarantine(family, sl)
        if self.flightrec is not None:
            self.flightrec.record(
                "quarantine", family,
                event="quarantine", mesh_slice=sl, reason=reason,
            )
        moved = 0
        stranded = 0
        for tenant, engine in list(self.engines.items()):
            if (
                isinstance(engine, TpuInferenceEngine)
                and engine.placement is not None
                and engine.config.model == family
                and engine.placement.shard == sl
            ):
                if engine.placement.slot < 0:
                    # paged-out tenant on the quarantined slice: its
                    # weights are host-side encoded bytes — failing over
                    # means re-pointing the ghost at a healthy slice, NO
                    # device touch (router.quarantine above already
                    # steers the eventual page-in's place() call)
                    engine.placement = self._ghost_placement(
                        engine.tenant, family
                    )
                    self.metrics.counter(
                        "tpu_paging.quarantine_ghosts", family=family
                    ).inc()
                    moved += 1
                    continue
                if await self._failover_tenant(engine):
                    moved += 1
                else:
                    stranded += 1
        if stranded and not moved:
            healthy = [
                s2 for s2 in range(self.router.n_shards)
                if (family, s2) in self._slices
                and s2 not in self.router.quarantined(family)
            ]
            if not healthy:
                # every serving slice of the family is quarantined and
                # no tenant could move: that IS the park condition —
                # events pass through unscored family-wide, and either
                # probation (slice heals) or a tenant lifecycle event
                # (operator) unparks
                self._parked.add(family)
                self._record_error(
                    "park", RuntimeError(
                        f"family '{family}' parked: every serving slice "
                        f"quarantined and no failover capacity"
                    ),
                )
                self.metrics.counter("tpu_inference.parked").inc()

    def clear_quarantine(self, family: str) -> int:
        """Re-admit every quarantined slice of ``family`` without
        probation — the operator-lifecycle escape hatch (engine
        (re)start), mirroring the breaker resets it rides beside."""
        n = 0
        for s in self._family_slices(family):
            if s.clear_quarantine():
                self.router.readmit(family, s.sl)
                n += 1
        if n:
            self._quarantine_gauge()
        return n

    async def host_probe(self, n: int = 1) -> int:
        """HOST-probation probes (docs/ROBUSTNESS.md "Host fault
        domains"): land ``n`` synthetic zero-row flushes through the
        real wire and report how many made deadline. A host re-appearing
        after a lease fence calls this and carries the count in its
        heartbeat (``probes_ok``); the coordinator's ``HostSupervisor``
        readmits the host only once the count clears its
        ``probation_probes`` bar — the process-level mirror of
        ``_probe_slice``. Each probe rides the first serving slice (the
        cheapest proof the whole staging→step→gather wire answers); a
        host with no serving state yet trivially passes — there is
        nothing to be wedged."""
        ok = 0
        for _ in range(max(1, int(n))):
            landed = not self._slices
            for _key, s in sorted(self._slices.items()):
                try:
                    landed = await self._dispatch_probe(s)
                except asyncio.CancelledError:
                    raise
                except Exception as exc:  # noqa: BLE001 - a probe fault
                    # IS the verdict, never a crash
                    self._record_error("host-probe", exc)
                    landed = False
                break
            if landed:
                ok += 1
                self.metrics.counter("tpu_inference.host_probes_ok").inc()
            else:
                self.metrics.counter(
                    "tpu_inference.host_probe_failures"
                ).inc()
        return ok

    def _probe_quarantined(self) -> None:
        """Scoring-loop tick: launch (at most one per slice) probation
        probes for quarantined slices whose probe interval elapsed.
        Probes defer while live traffic is under overload pressure —
        recovery bookkeeping never contends with shedding traffic."""
        for s in self._slices.values():
            qs = s.quarantine
            if qs is None or s.probing is not None:
                continue
            now = time.monotonic()
            if now < qs["next_probe"]:
                continue
            if self.overload is not None and self.overload.any_pressure():
                ft = self._family_ft(s.family)
                qs["next_probe"] = now + ft.probe_interval_s
                continue
            task = asyncio.get_running_loop().create_task(
                self._probe_slice(s)
            )
            s.probing = task

            def _done(t: asyncio.Task, s: SliceRuntime = s) -> None:
                if s.probing is t:
                    s.probing = None
                if not t.cancelled() and t.exception() is not None:
                    self._record_error("probe", t.exception())

            task.add_done_callback(_done)

    async def _probe_slice(self, s: SliceRuntime) -> None:
        """One probation probe: a synthetic prewarmed-shape flush on the
        quarantined slice, supervised by its own deadline. N consecutive
        landings re-admit the slice; any failure restarts the count."""
        ft = self._family_ft(s.family)
        try:
            ok = await self._dispatch_probe(s)
        except asyncio.CancelledError:
            raise
        except Exception as exc:  # noqa: BLE001 - a probe fault IS
            # the verdict, never a crash
            self._record_error("probe", exc)
            ok = False
        qs = s.quarantine
        if qs is None:
            return  # re-admitted/cleared while the probe was in flight
        if ok:
            qs["ok_probes"] += 1
            self.metrics.counter("tpu_inference.probe_flushes").inc()
            if qs["ok_probes"] >= max(1, ft.probation_probes):
                await self._readmit_slice(s)
                return
        else:
            qs["ok_probes"] = 0
            self.metrics.counter("tpu_inference.probe_failures").inc()
        qs["next_probe"] = time.monotonic() + ft.probe_interval_s

    def _probe_executor(self):
        """The dedicated single-thread probe pool. Probes materialize
        against a possibly GENUINELY wedged chip — a blocked np.asarray
        there never returns, and running it on the shared deliver pool
        would leak one worker per timed-out probe until the pool
        starved HEALTHY slices' deliveries (the fleet-wide wedge this
        layer exists to prevent). One dedicated thread bounds the
        damage: a stuck probe blocks only later probes, which queue
        behind it and time out as failures."""
        pool = getattr(self, "_probe_pool", None)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            pool = self._probe_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-probe"
            )
        return pool

    async def _dispatch_probe(self, s: SliceRuntime) -> bool:
        """Run one zero-row synthetic flush through the REAL wire
        (staging → step → gather → materialization) with its own
        deadline, entirely ON the probe thread — a quarantined slice's
        jit cache may have been wiped by the failover rebuild, and the
        recompile (tens of seconds on a real chip) must stall the probe
        thread, never the scoring loop. Zero counts leave window state
        untouched (scatter mode=drop — the prewarm contract), so
        probing a quarantined slice cannot corrupt anything a returning
        tenant would see."""
        import numpy as _np

        family, sl, scorer = s.family, s.sl, s.scorer
        t, d = scorer.n_slots, scorer.mm.n_data_shards
        # smallest shape the slice already compiled; a wiped cache
        # (failover rebuild) recompiles on the probe thread
        seen = sorted(b for b in s.seen_shapes if isinstance(b, int))
        b = seen[0] if seen else 64
        ids = _np.zeros((t, d * b), scorer.ids_np_dtype)
        vals = _np.zeros((t, d * b), scorer.vals_np_dtype)
        counts = _np.zeros((t, d), _np.int32)
        plan = self.faultplan

        def _probe_flush():
            stage = getattr(scorer, "stage_inputs", None)
            staged = (
                stage(ids, vals, counts) if stage else (ids, vals, counts)
            )
            if plan is not None:
                plan.maybe_raise(family, sl, "probe")
            out = scorer.step_counts(*staged)
            gather = getattr(scorer, "gather_rows", None)
            if gather is not None and hasattr(out, "is_ready"):
                out = gather(out, staged[2], 1)
            if plan is not None:
                out = plan.wrap(out, family, sl, "probe")
            return np.asarray(out)

        deadline = s.flush_deadline_s(self._family_ft(family)) or (
            self.deliver_drain_timeout_s
        )
        fut = asyncio.get_running_loop().run_in_executor(
            self._probe_executor(), _probe_flush
        )
        try:
            await asyncio.wait_for(fut, timeout=deadline)
        except asyncio.TimeoutError:
            # NOT tpu_flush_timeout_total: no in-flight flush was
            # force-resolved (that counter's contract) — the caller's
            # probe_failures counter carries this outcome
            return False
        return True

    async def _readmit_slice(self, s: SliceRuntime) -> None:
        """Probation passed: the slice rejoins the router, its breaker
        and escalation history clear, the family unparks, and tenants
        REBALANCE BACK through the same FIFO-preserving fences every
        slice move rides."""
        family, sl = s.family, s.sl
        s.readmit()
        self._quarantine_gauge()
        self.router.readmit(family, sl)
        self._failover_rounds.pop(family, None)
        self._parked.discard(family)
        self.metrics.counter("tpu_inference.readmitted").inc()
        if self.flightrec is not None:
            self.flightrec.record(
                "quarantine", family, event="readmit", mesh_slice=sl,
            )
        # capacity self-heal: tenants displaced by the quarantine come
        # home (load-gap-driven, so a balanced fleet moves nothing)
        await self.apply_rebalance(family)

    async def _failover_tenant(self, engine: "TpuInferenceEngine") -> bool:
        """Re-place one tenant onto another shard (usually a different
        MESH SLICE): carry its params (live copy if the old slice still
        answers, else last checkpoint, else pristine), wipe + free the
        old slot, and move pending rows through a ``_SliceFence`` so
        per-tenant delivery order survives the move. Stream →
        data-shard assignments are placement-independent, so no rows
        and no window routing are lost (window HISTORY restarts on the
        new slice, as before)."""
        from sitewhere_tpu.parallel.tenant_router import PlacementError

        tenant = engine.tenant
        try:
            old_p = engine.placement
            new_p = self.router.failover(tenant)
        except PlacementError as exc:
            self._record_error("failover", exc)
            return False
        await self._apply_move(engine, old_p, new_p)
        self.metrics.counter("tpu_inference.failovers").inc()
        return True

    async def _apply_move(
        self, engine: "TpuInferenceEngine", old_p, new_p
    ) -> None:
        """Migrate one tenant's live serving state between placements —
        the shared mechanics of failover and rebalance. The router has
        ALREADY committed ``new_p``."""
        from sitewhere_tpu.runtime.checkpoint import host_copy_params

        tenant = engine.tenant
        family = engine.config.model
        old = self._slices[(family, old_p.shard)]
        params = None
        try:  # live params may be unreachable on a sick slice
            params = host_copy_params(old.scorer.slot_params(old_p.slot))
        except Exception:  # noqa: BLE001
            if self.checkpoints is not None:
                try:
                    params = await asyncio.get_running_loop().run_in_executor(
                        None, self.checkpoints.load_params, tenant, family,
                    )
                except Exception as exc:  # noqa: BLE001
                    self._record_error("failover-params", exc)
        try:
            old.scorer.reset_slot(old_p.slot)
        except Exception as exc:  # noqa: BLE001 - slice may be dead
            self._record_error("failover-reset", exc)
        # the tenant's pending TRAIN rows and cadence tick stay keyed to
        # the OLD (slot, data-shard): they go, or the slot's next tenant
        # would train on THIS tenant's replayed data
        self._forget_slot_training(old, old_p.slot)
        engine.placement = new_p
        new_scorer = self.scorer_for_slice(family, new_p.shard, engine.config)
        new_scorer.activate(
            new_p.slot, params=params,
            trainable=engine.config.training.enabled,
            lr=engine.config.training.lr,
        )
        # slot re-map only: the model didn't change, so the drift
        # reference survives the move (register keeps same-family
        # history — see ScoreHealth.register)
        self.scorehealth.register(
            tenant, family, new_p.slot,
            getattr(new_scorer, "sketch_edges", []),
            mesh_slice=new_p.shard,
        )
        self._begin_fence(engine, old_p, new_p)

    def _begin_fence(self, engine: "TpuInferenceEngine", old_p, new_p) -> None:
        """Start (or re-target) the tenant's slice-move fence: snapshot
        the OLD slice queue's in-flight flushes and park the tenant's
        pending lane rows behind them. Same-slice moves (the old
        single-slice failover shape) need no ordering fence — rows
        re-key directly."""
        tenant = engine.tenant
        family = engine.config.model
        fence = self._fences.get(tenant)
        if fence is not None:
            # a second move before the first fence lifted: rows are
            # already parked and the ORIGINAL old-slice snapshot still
            # gates them — only the landing target changes
            fence.new_sl, fence.new_slot = new_p.shard, new_p.slot
            return
        old = self._slices[(family, old_p.shard)]
        pending = list(old.reap)
        if old_p.shard == new_p.shard:
            # same-slice slot move: FIFO is already guaranteed by the
            # single slice queue — re-key the rows in place
            for d, li, lv, ls, lr in old.drain_lanes(old_p.slot):
                old.lane(new_p.slot, d, max(4096, len(ls))).push(
                    li, lv, ls, lr
                )
            return
        self.metrics.counter("tpu_inference.slice_moves").inc()
        fence = _SliceFence(
            tenant, family, pending, new_p.shard, new_p.slot
        )
        for d, li, lv, ls, lr in old.drain_lanes(old_p.slot):
            fence.park(d, li, lv, ls, lr)
        if not pending and not fence.depth():
            return  # nothing in flight, nothing parked — no fence needed
        self._fences[tenant] = fence
        self.metrics.gauge("tpu_inference_fences").set(len(self._fences))

    def _lift_fences(self) -> None:
        """Release every fence whose old-slice snapshot has fully
        resolved: parked rows push into the NEW slice's lanes in arrival
        order. Driven from the scoring loop (cheap no-op while no move
        is in flight)."""
        for tenant in list(self._fences):
            fence = self._fences[tenant]
            if fence.new_sl is None:
                # paging fence: the tenant is non-resident — rows stay
                # parked until a page-in retargets the fence at the
                # landed (slice, slot); only _page_in lifts it
                continue
            if not fence.ready():
                continue
            del self._fences[tenant]
            s = self._slices[(fence.family, fence.new_sl)]
            moved = 0
            for d, ring in sorted(fence.stash.items()):
                if not ring.count:
                    continue
                li, lv, ls, lr = ring.pop(ring.count)
                s.lane(fence.new_slot, d, max(64, ring.capacity)).push(
                    li, lv, ls, lr
                )
                moved += len(ls)
            if moved:
                s.mark_pending()
        self.metrics.gauge("tpu_inference_fences").set(len(self._fences))

    async def apply_rebalance(self, family: Optional[str] = None) -> int:
        """Router-planned load rebalance (tenant add/remove skew):
        apply each move through the same fenced migration as failover —
        per-tenant FIFO delivery holds across every slice move. Returns
        the number of tenants moved."""
        moves = self.router.rebalance(family)
        applied = 0
        for old_p, new_p in moves:
            engine = self.engines.get(old_p.tenant)
            if engine is None or not isinstance(engine, TpuInferenceEngine):
                continue
            await self._apply_move(engine, old_p, new_p)
            applied += 1
            self.metrics.counter("tpu_inference.rebalanced").inc()
        return applied

    # -- weight paging (runtime.paging; docs/PERFORMANCE.md) ---------------
    def _ghost_placement(
        self, tenant: str, family: str
    ) -> TenantPlacement:
        """A slot=-1 placement for a non-resident tenant: the shard is
        a real serving slice of the family (preferring healthy ones) so
        stream→data-shard routing and fence parking have a home, but no
        physical slot is held — a page-in claims one later."""
        slices = sorted(s.sl for s in self._family_slices(family))
        avoid = self.router.quarantined(family)
        healthy = [s for s in slices if s not in avoid]
        shard = (healthy or slices or [0])[0]
        return TenantPlacement(tenant, family, shard, -1)

    def _install_paging_fence(self, engine: "TpuInferenceEngine") -> None:
        """Park every row a non-resident tenant receives: a paging
        fence (``new_sl=None``) with an EMPTY old-slice snapshot —
        nothing gates it but the page-in that retargets it at the
        landed (slice, slot). Parked depth counts against the lane
        watermark, so a long page-in backpressures intake into the bus
        instead of buffering unboundedly host-side."""
        if engine.tenant in self._fences:
            return
        self._fences[engine.tenant] = _SliceFence(
            engine.tenant, engine.config.model, [], None, None
        )
        self.metrics.gauge("tpu_inference_fences").set(len(self._fences))

    def _page_out(self, engine: "TpuInferenceEngine") -> None:
        """Evict one RESIDENT tenant to the host byte cache and leave a
        ghost placement behind. Synchronous on the event loop — the
        whole evict→write-back→commit runs without an await, so no
        flush can interleave with a half-freed slot (the commit section
        tools/check_commit.py guards: ``host_copy_params`` …
        ``commit_page_out``)."""
        from sitewhere_tpu.runtime.checkpoint import (
            encode_segment, host_copy_params,
        )

        p = engine.placement
        tenant = engine.tenant
        family = engine.config.model
        s = self._slices[(family, p.shard)]
        scorer = s.scorer
        trainable = bool(engine.config.training.enabled)
        cached = self.pager.cache.get(tenant)
        if not trainable and cached is not None:
            # clean write-back elided: a non-trainable tenant's weights
            # cannot have diverged from the blob its last page-in used
            blob, dirty = cached[0], False
        else:
            # materialize on THIS (loop) thread: reset_slot below
            # donates the stacked buffers (see host_copy_params)
            params = host_copy_params(scorer.slot_params(p.slot))
            opt = scorer.slot_opt_state(p.slot)
            blob = encode_segment(params, opt)
            dirty = trainable
        scorer.reset_slot(p.slot)
        # pending TRAIN rows are droppable history (the store re-feeds —
        # PR 12 round-4 rule), but COUNTED: a paging storm that starves
        # training must be visible
        dropped = self._forget_slot_training(s, p.slot)
        if dropped:
            self.metrics.counter(
                "tpu_paging.train_rows_dropped", family=family
            ).inc(dropped)
        # serve rows still pending re-park behind a paging fence, FIFO
        # behind the old slice's in-flight flushes — the same ordering
        # machinery as a failover move, targetless until the next
        # page-in lands
        fence = self._fences.get(tenant)
        if fence is None:
            fence = self._fences[tenant] = _SliceFence(
                tenant, family, list(s.reap), None, None,
            )
            self.metrics.gauge(
                "tpu_inference_fences"
            ).set(len(self._fences))
        else:
            fence.new_sl, fence.new_slot = None, None
        for d, li, lv, ls, lr in s.drain_lanes(p.slot):
            fence.park(d, li, lv, ls, lr)
            # eviction raced these batches' rows: key them out of the
            # hot-path latency columns like any fence-parked arrival
            for seq in np.unique(ls):
                entry = self._batches.get(int(seq))
                if entry is not None and "paged" not in entry[0].trace:
                    entry[0].mark("paged")
        # score-health: free the slot binding WITHOUT touching the
        # frozen reference or PSI window history — they survive
        # residency gaps exactly like failover re-maps
        self.scorehealth.unbind_slot(tenant)
        self.router.remove(tenant)
        engine.placement = TenantPlacement(
            tenant, family, p.shard, -1, generation=p.generation + 1
        )
        self.pager.slice_pager(
            family, p.shard, self.slots_per_shard
        ).drop(tenant)
        self.pager.cache.commit_page_out(tenant, blob, dirty)
        self.metrics.counter("tpu_paging.page_outs", family=family).inc()
        if self.flightrec is not None:
            self.flightrec.record(
                "paging", family, paged=True, event="page_out",
                tenant=tenant, mesh_slice=p.shard, slot=p.slot,
                dirty=dirty,
            )

    def _pick_victim(
        self, family: str
    ) -> Optional["TpuInferenceEngine"]:
        """The cheapest resident tenant of ``family`` to evict: LRU
        weighted by the OverloadController's live traffic signal.
        Pinned, fenced (mid-move), quarantined-slice, and already-ghost
        tenants are exempt. Tenants with rows already packed in serve
        lanes rank BEHIND row-free ones regardless of LRU score:
        evicting them parks those rows behind the paging fence for a
        full page-out/page-in cycle — hot-path latency spent on a tenant
        that is demonstrably still serving (used only when every
        candidate has pending rows: a demand page-in must not stall)."""
        if self.overload is not None:
            traffic = self.overload.tenant_lag
        else:
            def traffic(_t: str) -> float:
                return 0.0
        now = time.monotonic()
        best = busy_best = None
        best_score = busy_score = -1.0
        for (fam, sl), pager in self.pager.pagers.items():
            home = self._slices.get((fam, sl))
            if fam != family or home is None or home.quarantine is not None:
                continue
            lanes = home.lanes
            for tenant in pager.residents():
                if tenant in pager.pinned or tenant in self._fences:
                    continue
                eng = self.engines.get(tenant)
                if (
                    not isinstance(eng, TpuInferenceEngine)
                    or eng.state is not LifecycleState.STARTED
                    or eng.placement is None
                    or eng.placement.slot < 0
                ):
                    continue
                score = pager.eviction_score(tenant, traffic, now)
                slot = eng.placement.slot
                pending = any(
                    ring.count for (s, _d), ring in lanes.items()
                    if s == slot
                )
                if pending:
                    if score > busy_score:
                        busy_score, busy_best = score, eng
                elif score > best_score:
                    best_score, best = score, eng
        return best if best is not None else busy_best

    async def _page_in(
        self, tenant: str, origin: str, t_req: float
    ) -> None:
        """Activate one non-resident tenant: claim a slot (evicting the
        LRU victim if the family is at physical capacity), stage its
        cached params asynchronously onto the slice's shardings
        (``stage_slot_params`` — the stage_inputs double-buffer pattern
        for weights), then activate + restore opt state and retarget
        the paging fence so parked rows drain FIFO into the new slot."""
        engine = self.engines.get(tenant)
        if (
            not isinstance(engine, TpuInferenceEngine)
            or engine.state is not LifecycleState.STARTED
            or engine.placement is None
            or engine.placement.slot >= 0
        ):
            return  # stopped / already resident: request is stale
        family = engine.config.model
        try:
            new_p = self.router.place(tenant, family=family)
        except PlacementError:
            victim = self._pick_victim(family)
            if victim is None:
                # every resident is pinned/fenced/quarantined — the
                # request re-queues on the tenant's next demand touch
                self.metrics.counter(
                    "tpu_paging.stalled", family=family
                ).inc()
                return
            self._page_out(victim)
            new_p = self.router.place(tenant, family=family)
        scorer = self.scorer_for_slice(family, new_p.shard, engine.config)
        loop = asyncio.get_running_loop()
        params = opt = None
        entry = self.pager.cache.get(tenant)
        if entry is not None:
            from sitewhere_tpu.runtime.checkpoint import decode_segment

            params, opt = await loop.run_in_executor(
                None, decode_segment, entry[0]
            )
        elif self.checkpoints is not None:
            params = await loop.run_in_executor(
                None, self.checkpoints.load_params, tenant, family
            )
        staged = (
            scorer.stage_slot_params(params) if params is not None else None
        )
        if (
            self.engines.get(tenant) is not engine
            or engine.state is not LifecycleState.STARTED
            or engine.placement is None
            or engine.placement.slot >= 0
        ):
            # the tenant stopped (or somehow activated) during the
            # decode/stage awaits: release the slot we claimed
            self.router.remove(tenant)
            return
        scorer.activate(
            new_p.slot, params=staged,
            trainable=engine.config.training.enabled,
            lr=engine.config.training.lr,
        )
        scorer.restore_slot_opt(new_p.slot, opt)
        engine.placement = new_p
        # slot re-map only: same-family register keeps the frozen drift
        # reference and PSI window history — NO rebaseline (the whole
        # point of surviving page-out like a failover re-map)
        self.scorehealth.register(
            tenant, family, new_p.slot,
            getattr(scorer, "sketch_edges", []),
            mesh_slice=new_p.shard,
        )
        self.pager.slice_pager(
            family, new_p.shard, self.slots_per_shard
        ).note_resident(tenant, new_p.slot)
        fence = self._fences.get(tenant)
        if fence is not None and fence.new_sl is None:
            # retarget: _lift_fences releases it once the snapshot (if
            # any) resolves, draining parked rows FIFO into the slot
            fence.new_sl, fence.new_slot = new_p.shard, new_p.slot
        wait_ms = (time.monotonic() - t_req) * 1e3
        self.metrics.histogram(
            "tenant_activation_ms", unit="ms", family=family
        ).record(wait_ms)
        self.pager.note_activation(tenant, wait_ms, origin)
        self.metrics.counter(
            "tpu_paging.page_ins", family=family, origin=origin
        ).inc()
        if self.flightrec is not None:
            self.flightrec.record(
                "paging", family, paged=True, event="page_in",
                tenant=tenant, origin=origin,
                wait_ms=round(wait_ms, 3),
                mesh_slice=new_p.shard, slot=new_p.slot,
            )

    def _paging_tick(self) -> None:
        """One scoring-loop pass of paging work: (a) queue prefetches
        for ghost tenants whose bus lag is RISING (the
        OverloadController's lag_prev comparison — pressure building
        before any row is consumed), (b) re-demand tenants whose paging
        fence holds parked rows — rows parked at EVICTION time precede
        any future arrival, so without this they'd strand until the
        tenant happens to get new traffic (arrival-side demand pushes
        only fire in ``_enqueue_batch``), (c) launch at most ONE page-in
        task (activation mutates the stacked buffers; serializing keeps
        it off the flush critical path and race-free)."""
        now = time.monotonic()
        if self.overload is not None and now >= self._paging_next_prefetch:
            self._paging_next_prefetch = now + 0.25
            for tenant in self.overload.rising_tenants():
                eng = self.engines.get(tenant)
                if (
                    isinstance(eng, TpuInferenceEngine)
                    and eng.state is LifecycleState.STARTED
                    and eng.placement is not None
                    and eng.placement.slot < 0
                ):
                    self.pager.queue.push(tenant, "prefetch", now)
        for tenant, fence in self._fences.items():
            if fence.new_sl is None and fence.depth():
                self.pager.queue.push(tenant, "demand", now)
        task = self._pagein_task
        if task is not None and not task.done():
            return
        self._pagein_task = None
        req = self.pager.queue.pop()
        if req is None:
            return
        task = asyncio.get_running_loop().create_task(
            self._page_in(*req)
        )
        self._pagein_task = task

        def _done(t: asyncio.Task, _tenant: str = req[0]) -> None:
            if t.cancelled():
                return
            exc = t.exception()
            if exc is not None:
                self._record_error(f"page-in:{_tenant}", exc)

        task.add_done_callback(_done)

    def _train_tick(
        self, s: SliceRuntime, engine_cfgs: Dict[int, TenantEngineConfig],
    ) -> int:
        """Per-flush training cadence bookkeeping, two regimes:

        - **inline** slots (the pre-lane path — ``TRAIN_LANE_ENABLED``
          off, a non-fused family, or ``training.train_lane=False``):
          every Nth scoring flush dispatches ONE legacy optimizer step
          for the mature slots on their resident window state, right
          here on the flush path — bitwise the pre-lane behavior.
        - **lane** slots: the tick only ACCUMULATES; maturity is checked
          (and reset) by ``_train_lane_tick`` at dispatch, off the flush
          critical path, so a throttled slot keeps its mature tick until
          the overload arbiter admits it.

        Either way the jit dispatch is async and tenants with training
        disabled are excluded by the scorer's per-slot train mask."""
        enabled = {
            slot: c.training
            for slot, c in engine_cfgs.items()
            if c.training.enabled
        }
        if not enabled:
            return 0
        family, sl, scorer = s.family, s.sl, s.scorer
        if getattr(scorer.spec, "loss", None) is None:
            # a tenant opted into training on a family with no loss
            # contract: it would silently never train — surface it
            self.metrics.counter(
                "tpu_train_skipped_total", family=family, reason="no_trainer"
            ).inc()
            return 0
        lane_on = bool(getattr(scorer, "train_lane", False))
        # per-TENANT cadence: each slot matures on its own every_n_flushes
        # (and trains at its own lr — see ShardedScorer.slot_lr)
        ticks = s.train_ticks
        mature = []
        for slot, tc in enabled.items():
            if lane_on and tc.train_lane:
                ticks[slot] = ticks.get(slot, 0) + 1
                continue
            n = ticks.get(slot, 0) + 1
            if n >= tc.every_n_flushes:
                mature.append(slot)
                ticks[slot] = 0
            else:
                ticks[slot] = n
        if not mature:
            return 0
        if getattr(scorer, "_train", None) is None:
            try:
                scorer.init_optimizer()  # scale_by_adam + per-slot lr
            except Exception:
                self.metrics.counter(
                    "tpu_train_skipped_total", family=family,
                    reason="optimizer_init",
                ).inc()
                raise
        mask = np.zeros((scorer.n_slots,), bool)
        mask[mature] = True
        s.last_train_losses = scorer.train_resident(mask)
        self.metrics.counter("tpu_inference.train_steps").inc()
        if getattr(scorer, "train_lane", False) and s.lane_swap > 0:
            # MIXED stack (inline + lane tenants): train_resident just
            # invalidated the shared sidecar, which publishes the lane
            # tenants' in-flight uncommitted weights to serving too —
            # that IS a commit, so it must arm the canary and count as
            # a swap instead of silently bypassing the swap contract
            s.lane_swap = 0
            scorer.arm_canary()
            self.metrics.counter(
                "tpu_train_swaps_total", family=family
            ).inc()
            if self.flightrec is not None:
                self.flightrec.record(
                    "swap", family,
                    lane="train", mesh_slice=sl,
                    device_label=scorer.device_label,
                    inline=True,
                    canary_armed=bool(scorer.canary_active()),
                )
        return 1

    # -- continual-learning train lane ------------------------------------
    def _train_admit(self, tenant: str) -> bool:
        """The serve/train arbitration: a tenant's training is admitted
        only while live traffic leaves headroom — i.e. the tenant shows
        NO overload signal (full credit, no degradation rung: the one
        shared ``under_pressure`` definition, so the shed gates and the
        train lane can never disagree about what pressure means). Live
        traffic always wins; the hostile-tenant chaos suite pins this
        at exactly 0 train steps under sustained pressure."""
        ov = self.overload
        return ov is None or not ov.under_pressure(tenant)

    async def _consume_train_feed(
        self, tenant: str, engine: "TpuInferenceEngine", s: SliceRuntime
    ) -> None:
        """Low-priority intake from the tenant's replay-train-feed topic
        into the train lane rings. Bounded: past the lane watermark
        (2 × replay_microbatch) the consumer parks and the backlog stays
        in the bus topic (counted; the replay pump's own overload
        arbitration already throttles the producer). A throttled tenant
        (credit < 1 / rung engaged) doesn't pull either — its feed waits
        out the pressure. The feed topic is EXCLUDED from the overload
        credit signal (runtime.overload._tenant_lag), so a parked train
        backlog can never throttle the tenant's serve path."""
        family = s.family
        if not getattr(s.scorer, "train_lane", False):
            return
        if not self._train_admit(tenant):
            return
        pin = self._family_cfg.get(family, engine.config).training
        micro = max(1, int(getattr(pin, "replay_microbatch", 1024)))
        slot = engine.placement.slot
        depth = sum(
            r.count for (t, _d), r in s.train_lanes.items() if t == slot
        )
        if depth >= 2 * micro:
            self.metrics.counter(
                "tpu_inference.train_feed_backpressure"
            ).inc()
            return
        items = await self.bus.consume(
            self.bus.naming.train_feed(tenant), self.group,
            self.poll_batch, timeout_s=0,
        )
        if not items:
            return
        if (
            engine.state is not LifecycleState.STARTED
            or engine.placement is None
        ):
            return  # stopped mid-consume: training rows are droppable
        for b in items:
            if isinstance(b, MeasurementBatch):
                self._enqueue_train_batch(engine, b, s)
        self._train_rows_gauge(family)

    def _enqueue_train_batch(
        self, engine: "TpuInferenceEngine", batch: MeasurementBatch,
        s: SliceRuntime,
    ) -> None:
        """Route one replayed batch's rows into the train lane rings —
        the train twin of ``_enqueue_batch``, minus every delivery
        obligation: no seq registry, no score column, no publish (the
        rows are already persisted history; training is their only
        consumer). Stream routing shares the tenant's serve
        StreamRegistry, so a replayed row's window lands in the SAME
        (slot, data-shard, local-id) ring position its live twin would."""
        slot = engine.placement.slot
        dshards, locals_ = engine.streams.lookup_or_assign_bulk(batch)
        skipped = int((dshards == -1).sum())
        if skipped:
            self.metrics.counter(
                "tpu_train_skipped_total",
                family=engine.config.model, reason="capacity",
            ).inc(skipped)
        for d in range(self.mm.n_data_shards):
            sel = np.nonzero(dshards == d)[0]
            if sel.size == 0:
                continue
            # seq/row bookkeeping is vestigial on the train lane (rows
            # never resolve back into a batch) — seq broadcasts 0
            s.train_lane(slot, d).push(
                locals_[sel], batch.values[sel], 0, sel
            )

    def _train_rows_gauge(self, family: str) -> None:
        # the gauge is FAMILY-labeled, so it must sum every slice's
        # rings — a per-slice sum would let slices of one family
        # overwrite each other's depth (the last_train_losses keying
        # lesson from the multi-chip review, applied to the gauge)
        depth = sum(
            r.count
            for s in self._family_slices(family)
            for r in s.train_lanes.values()
        )
        self.metrics.gauge("tpu_inference_train_rows", family=family).set(
            depth
        )

    def _forget_slot_training(self, s: SliceRuntime, slot: int) -> int:
        """``SliceRuntime.forget_slot_training``, and the family's depth
        gauge after it where the slice had train rings at all."""
        had_rings = bool(s.train_lanes)
        dropped = s.forget_slot_training(slot)
        if had_rings:
            self._train_rows_gauge(s.family)
        return dropped

    async def _train_lane_tick(
        self, fam_cfgs: Dict[SliceRuntime, Dict[int, TenantEngineConfig]]
    ) -> int:
        """One pass of the async low-priority train lane: for each
        (family, slice) whose scorer carries the fused lane, dispatch at
        most ONE train step — replay-fed when an admitted microbatch is
        buffered, else resident-state when a slot's cadence matured —
        and only when the slice has a FREE in-flight permit right now
        (``sem.locked()`` ⇒ the serve path owns every slot: a saturated
        slice trains exactly 0 steps) and the overload arbiter admits
        the tenant. The dispatch rides the slice's semaphore + reap FIFO
        as ``lane="train"``, so its completion, teardown drain, and
        queue-depth accounting are the serve path's own machinery."""
        steps = 0
        for s, cfgs in fam_cfgs.items():
            family, scorer = s.family, s.scorer
            if not getattr(scorer, "train_lane", False):
                continue
            lane_cfgs = {
                t: c for t, c in cfgs.items()
                if c.training.enabled and c.training.train_lane
            }
            if not lane_cfgs:
                continue
            if family in self._parked:
                self.metrics.counter(
                    "tpu_train_skipped_total", family=family,
                    reason="parked",
                ).inc()
                continue
            pin = self._family_cfg.get(
                family, next(iter(lane_cfgs.values()))
            ).training
            micro = max(1, int(getattr(pin, "replay_microbatch", 1024)))
            admitted = {
                t: c for t, c in lane_cfgs.items()
                if self._train_admit(c.tenant)
            }
            throttled = len(lane_cfgs) - len(admitted)
            if not admitted:
                if throttled:
                    self.metrics.counter(
                        "tpu_train_skipped_total", family=family,
                        reason="throttled",
                    ).inc(throttled)
                continue
            feed_rows = sum(
                r.count for (t, _d), r in s.train_lanes.items()
                if t in admitted
            )
            mature = [
                t for t, c in admitted.items()
                if s.train_ticks.get(t, 0) >= c.training.every_n_flushes
            ]
            replay = feed_rows >= micro
            if not replay and not mature:
                continue
            if replay and mature and s.lane_last_source == "replay":
                # both sources pending: ALTERNATE. A long replay
                # backfill holding feed_rows ≥ micro for hours must not
                # starve a co-tenant's mature resident cadence (the
                # mature slot is admitted but never fed, so no skip
                # counter would ever name its starvation)
                replay = False
            if throttled:
                # mature-but-throttled siblings sat this dispatch out
                self.metrics.counter(
                    "tpu_train_skipped_total", family=family,
                    reason="throttled",
                ).inc(throttled)
            q = s.reap
            if s.permits.locked() or any(p.lane != "train" for p in q):
                # the slice is busy SERVING — in-flight flushes hold the
                # window (or every permit): training yields and waits
                # for a genuinely idle gap. "Idle headroom" is literal:
                # a train step only ever enters an EMPTY in-flight
                # window, so a saturated slice trains exactly 0 steps
                # and a serve flush never queues behind a train step it
                # could have preceded.
                self.metrics.counter(
                    "tpu_train_skipped_total", family=family,
                    reason="saturated",
                ).inc()
                continue
            if q:
                # only the lane's OWN previous step is in flight: lane
                # steps self-serialize per slice — normal pacing, not
                # starvation, so it must not pollute the "saturated"
                # signal operators read as serve pressure
                continue
            steps += await self._dispatch_train(
                s, admitted, mature, replay, pin,
            )
        return steps

    def _pack_train(
        self, s: SliceRuntime, admitted: Dict[int, object],
    ) -> Tuple[int, List[int]]:
        """Pack the admitted slots' pending train rows into a rotating
        staging set (the SAME per-slice pool and wire dtypes as scoring
        flushes), stage them h2d, and scatter them into the scorer's
        train feed windows. Returns (rows moved, slots that contributed
        rows — the only slots the replay step may train: an admitted
        co-tenant with an empty feed must not take a zero-gradient Adam
        step, which would drift its weights on stale momentum and skew
        its bias-correction count). The ingest dispatch is async and
        precedes the train step on the device queue."""
        family, scorer, tlanes = s.family, s.scorer, s.train_lanes
        mbcfg = self._family_cfg[family].microbatch
        pending = max(
            (r.count for (t, _d), r in tlanes.items() if t in admitted),
            default=0,
        )
        if pending == 0:
            return 0, []
        b_lane = s.pick_bucket(
            pending, tuple(mbcfg.buckets), mbcfg.max_batch
        )
        scratch = self._train_scratch
        if scratch is None or len(scratch[0]) < b_lane:
            # pop_into needs seqs/rows landing zones; train rows never
            # resolve, so one reusable scratch pair serves every pack
            scratch = self._train_scratch = (
                np.empty((max(b_lane, mbcfg.max_batch),), np.int64),
                np.empty((max(b_lane, mbcfg.max_batch),), np.int32),
            )
        sc_seqs, sc_rows = scratch
        st = s.staging_set(b_lane)
        ids, vals, counts = st.ids, st.vals, st.counts
        counts[:] = 0
        moved = 0
        fed: set = set()
        for (slot, dshard), lane in sorted(tlanes.items()):
            if slot not in admitted:
                continue
            k = min(lane.count, b_lane)
            if k == 0:
                continue
            lane.pop_into(
                k, ids[slot], vals[slot], dshard * b_lane,
                sc_seqs, sc_rows, 0,
            )
            counts[slot, dshard] = k
            fed.add(slot)
            moved += k
        self._train_rows_gauge(family)
        if moved == 0:
            return 0, []
        staged = scorer.stage_inputs(ids, vals, counts)
        st.staged = staged
        try:
            self.metrics.counter("tpu_inference.staged_bytes").inc(
                scorer.stage_nbytes(staged)
            )
        except Exception:  # noqa: BLE001 - observability only
            pass
        scorer.train_feed_ingest(*staged)
        self.metrics.counter(
            "tpu_train_rows_total", family=family
        ).inc(moved)
        return moved, sorted(fed)

    async def _dispatch_train(
        self, s: SliceRuntime, admitted: Dict[int, object],
        mature: List[int], replay: bool, pin,
    ) -> int:
        """Dispatch one train-lane step and enqueue its completion on the
        slice's reap FIFO. The permit is held until the reaper resolves
        the entry — train steps count against the slice's in-flight
        window exactly like flushes, which is what keeps them off the
        serve critical path (a full window defers training, never
        scoring)."""
        family, sl, scorer = s.family, s.sl, s.scorer
        sem = s.permits
        # locked() was False with no await since: acquire returns now
        await sem.acquire()
        enqueued = False
        try:
            if getattr(scorer, "_train_fused", None) is None:
                try:
                    scorer.init_optimizer()
                except Exception as exc:  # noqa: BLE001 - optimizer
                    # construction is config-driven; surface, don't die
                    self._record_error("train-init", exc)
                    self.metrics.counter(
                        "tpu_train_skipped_total", family=family,
                        reason="optimizer_init",
                    ).inc()
                    return 0
            compiling = "train" not in s.seen_shapes
            rows_moved = 0
            source = "resident"
            if replay:
                source = "replay"
                rows_moved, trained = self._pack_train(s, admitted)
            else:
                trained = sorted(mature)
            # EVERY trained slot's cadence resets — a replay step IS the
            # slot's training for this interval, so a feed oscillating
            # around the microbatch threshold must not double the
            # configured cadence with a back-to-back resident step
            for t in trained:
                s.train_ticks[t] = 0
            if not trained:
                return 0
            s.lane_last_source = source
            mask = np.zeros((scorer.n_slots,), bool)
            mask[trained] = True
            if self.faultplan is not None:
                self.faultplan.maybe_raise(family, sl, "train")
            t_disp = time.perf_counter()
            losses_dev = scorer.train_lane_step(mask, replay=replay)
            dispatch_s = time.perf_counter() - t_disp
            if self.faultplan is not None:
                # the train lane is a supervised fault domain too: a
                # hung train step must not wedge the slice's in-flight
                # window forever
                losses_dev = self.faultplan.wrap(
                    losses_dev, family, sl, "train"
                )
            try:
                losses_dev.copy_to_host_async()
            except Exception:  # noqa: BLE001 - test doubles
                pass
            if compiling:
                s.seen_shapes.add("train")
                self.metrics.counter("tpu_inference.compiles").inc()
            self.metrics.counter("tpu_inference.train_steps").inc()
            for t in trained:
                self.metrics.counter(
                    "tpu_train_steps_total", tenant=admitted[t].tenant
                ).inc()
            # zero-stall hot-swap cadence: every swap_every lane steps
            # the master weights commit to the serving kernel view (the
            # activate(params=...) tail — sidecar re-derive + canary
            # arm); between commits scoring runs the previous weights
            swaps = s.lane_swap + 1
            swap_every = max(1, int(getattr(pin, "swap_every", 8)))
            if swaps >= swap_every:
                swaps = 0
                scorer.commit_swap()
                self.metrics.counter(
                    "tpu_train_swaps_total", family=family
                ).inc()
                if self.flightrec is not None:
                    self.flightrec.record(
                        "swap", family,
                        lane="train", mesh_slice=sl,
                        device_label=scorer.device_label,
                        steps=swap_every,
                        canary_armed=bool(scorer.canary_active()),
                    )
            s.lane_swap = swaps
            rec = None
            if self.flightrec is not None:
                rec = self.flightrec.record(
                    "flush", family,
                    lane="train", source=source,
                    rows=rows_moved, slots=len(trained),
                    dispatch_s=round(dispatch_s, 6),
                    compiled=compiling,
                    mesh_slice=sl,
                    device_label=scorer.device_label,
                    status="inflight",
                )
            flops_fn = getattr(scorer, "train_flops_per_step", None)
            pf = _PendingFlush(
                family, losses_dev, _empty_taken(), 0, False,
                int(getattr(losses_dev, "nbytes", 0)), 0,
                flops=float(flops_fn()) if flops_fn is not None else 0.0,
                rec=rec, sl=sl, lane="train",
            )
            dl = s.flush_deadline_s(self._family_ft(family))
            if dl is not None:
                pf.deadline = pf.t_dispatch + dl
            if not hasattr(losses_dev, "copy_to_host_async"):
                pf.ensure_host_future(
                    asyncio.get_running_loop(), self._deliver_pool
                )
            self._reap_enqueue(pf)
            enqueued = True
            return 1
        except Exception as exc:  # noqa: BLE001 - the train lane is
            # best-effort: a faulting step must not take serving down
            # (the serve path's own flushes drive breaker/failover if
            # the device is truly sick)
            self._record_error("train", exc)
            return 0
        finally:
            if not enqueued:
                sem.release()

    def _deliver_gauge(self) -> None:
        self.metrics.gauge("tpu_inference_deliver_inflight").set(
            sum(len(s.reap) for s in self._slices.values())
        )
        # labeled variants beside the legacy aggregate: the reap queues
        # are PER-(family, slice), so per-family depth is where a wedged
        # tenant family shows and per-DEVICE depth is where one slow
        # chip shows (the aggregate hides both). Separate names —
        # mixing bare and labeled children under one name would
        # double-count sum() aggregations.
        fam_depth: Dict[str, int] = {}
        dev_depth: Dict[str, int] = {}
        multi = self.mm.n_devices > 1
        for (family, sl), s in self._slices.items():
            fam_depth[family] = fam_depth.get(family, 0) + len(s.reap)
            if multi:
                lbl = self.mm.slice_device_label(sl)
                dev_depth[lbl] = dev_depth.get(lbl, 0) + len(s.reap)
        for family, depth in fam_depth.items():
            self.metrics.gauge(
                "tpu_inference_deliver_inflight_family", family=family
            ).set(depth)
        for lbl, depth in dev_depth.items():
            self.metrics.gauge(
                "tpu_inference_deliver_inflight_device", device=lbl
            ).set(depth)

    # -- device-time / MFU attribution -----------------------------------
    def _mfu_account(self, family: str):
        acc = self._mfu.get(family)
        if acc is None:
            acc = self._mfu[family] = MfuAccount(self.metrics, family)
        return acc

    def refresh_mfu(self) -> None:
        """Decay idle families' ``tpu_mfu_pct`` gauges from the sliding
        window (called by the instance's 1 s history tick and the
        /metrics scrape — a family that stopped flushing must read 0,
        not its last busy value)."""
        for acc in self._mfu.values():
            acc.refresh()
        for s in self._slices.values():
            if s.mfu is not None:
                s.mfu.refresh()
        # same tick drives the score-health time-based window rotation:
        # a slow stream must still rotate its drift windows instead of
        # waiting hours to fill window_rows
        self.scorehealth.refresh()

    async def _reap_loop(self) -> None:
        """The completion reaper: resolve in-flight flushes as their d2h
        transfers land. Heads that look complete (``landed`` — a cheap
        priority signal) dispatch first; when several families are in
        flight and none does, the reaper waits on ALL their heads and
        takes whichever finishes first — out of order across families,
        strictly FIFO within one (a tenant lives in exactly one family,
        so its batches deliver in order). The reaper itself only WAITS —
        each landed head resolves in a per-family task
        (``_spawn_resolve``), so one tenant's backpressured scored-topic
        publish can't head-of-line block other families' landed
        transfers. Overlap accounting happens at materialize time in
        ``_resolve_flush``: only a transfer whose materialization
        returned without measurable wait (and that the reaper never
        raced on) counts as ``d2h_overlapped``."""
        loop = asyncio.get_running_loop()
        while True:
            # a family with a resolve in flight is ineligible: its next
            # head must wait its turn (per-tenant FIFO)
            heads = [
                s.reap[0] for s in self._slices.values()
                if s.reap and s.resolving is None
            ]
            if not heads:
                # clear-then-wait is race-free on the single-threaded
                # loop: any set() that mattered already showed in heads
                self._reap_event.clear()
                await self._reap_event.wait()
                continue
            # landed heads resolve first; an OVERDUE head (its flush
            # deadline expired without the transfer landing) resolves
            # too — _resolve_flush's bounded wait turns it into the
            # force-resolve + quarantine path within one grace tick
            pf = next(
                (h for h in heads if h.landed() or h.overdue()), None
            )
            if pf is not None:
                self._spawn_resolve(pf)
                continue
            # no head has landed: race every eligible family's head (plus
            # the enqueue/resolve-done event — a NEW family's flush must
            # be able to join the race and win, or one family's slow
            # transfer would head-of-line block every other family — and
            # a timer for the SOONEST flush deadline, so a transfer that
            # never lands wakes the supervisor instead of parking it)
            self._reap_event.clear()
            waiter = asyncio.ensure_future(self._reap_event.wait())
            now = time.perf_counter()
            soonest = min(
                (h.deadline for h in heads if h.deadline is not None),
                default=None,
            )
            timer = (
                asyncio.ensure_future(
                    asyncio.sleep(max(0.0, soonest - now))
                )
                if soonest is not None
                else None
            )
            futs = []
            for h in heads:
                if h.t_wait is None:
                    h.t_wait = now
                # one future per in-flight FAMILY (a handful), not per row
                futs.append(h.ensure_host_future(loop, self._deliver_pool))  # hotpath: ok
            try:
                with sw("reap_wait", flush_id=heads[0].flush_id):
                    await asyncio.wait(  # supervised: ok(flush-deadline timer races in futs)
                        [*futs, waiter]
                        + ([timer] if timer is not None else []),
                        return_when=asyncio.FIRST_COMPLETED,
                    )
            finally:
                waiter.cancel()
                if timer is not None:
                    timer.cancel()
            pf = next((h for h, f in zip(heads, futs) if f.done()), None)
            if pf is not None:
                self._spawn_resolve(pf)

    def _spawn_resolve(self, pf: _PendingFlush) -> None:
        """Resolve one landed flush in a per-family task. At most one
        resolve runs per family (the loop skips families in
        ``resolving``), which preserves per-tenant in-order delivery;
        separate tasks restore the cross-family isolation the old
        per-flush deliver tasks had — a full scored topic only stalls
        its own family, and only until ``max_inflight`` backpressures
        the scoring loop as a whole."""
        task = asyncio.get_running_loop().create_task(
            self._resolve_flush(pf),
            # the loop ledger charges a task by its name's prefix
            name=f"tpu-inference-resolve[{pf.family}/{pf.sl}]",
        )
        s = self._slices[pf.key]
        s.resolving = task

        def _done(t: asyncio.Task) -> None:
            if s.resolving is t:
                s.resolving = None
            if not t.cancelled() and t.exception() is not None:
                # _resolve_flush handles its own failures; anything
                # escaping would otherwise vanish with the task
                self._record_error("deliver", t.exception())
            # wake the reaper: this family's next head is eligible now
            self._reap_event.set()

        task.add_done_callback(_done)

    # the honest boundary for the d2h_overlapped counter, since jax has
    # no "host copy done" probe — shared with the media readback (see
    # runtime/metrics.py for the rationale)
    D2H_OVERLAP_EPS_S = _D2H_OVERLAP_EPS_S

    # top-k size for the canary's rank-agreement verdict: the rows an
    # alerting/thresholding consumer actually acts on are the highest
    # scores, so rank stability there matters more than mean delta
    CANARY_TOPK = 64

    def _count_stream_step(self, pf: _PendingFlush, scorer) -> None:
        """A stateful family's step, counted (docs/OBSERVABILITY.md
        "Stream-state families"): which program each row rode, the
        expert layers' routing, the state the step moved. The device
        counters left the programs the scores left, so they have landed."""
        from sitewhere_tpu.parallel.streamstate import DEVICE_STATS

        dev, host = pf.stream_stats
        counts = dict(zip(DEVICE_STATS, np.asarray(dev).tolist()), **host)
        traffic = scorer.spec.state_traffic
        if traffic is not None:
            counts["state_read_bytes"], counts["state_written_bytes"] = (
                traffic(scorer.cfg, host["streams_advanced"],
                        host["rows_one_step"] + host["rows_chunked"]))
        for name, value in counts.items():
            if value:
                self.metrics.counter(f"tpu_inference.stream_{name}").inc(value)
        if pf.rec is not None:
            pf.rec["stream"] = counts

    def _canary_compare(
        self, pf: _PendingFlush, picks: np.ndarray, shadow_np: np.ndarray
    ) -> None:
        """Divergence of the serving scores vs the shadow (previous
        variant) scores for one flush — one shared verdict definition
        (``scorehealth.canary_divergence``, also the bench's canary
        columns); results land in ``score_canary_*`` and the flush's
        blackbox record."""
        from sitewhere_tpu.runtime.scorehealth import canary_divergence

        sp = shadow_np[: pf.moved].astype(np.float32, copy=False)
        verdict = canary_divergence(picks, sp, self.CANARY_TOPK)
        if verdict is None:
            return
        mean_abs, agree, n = verdict
        self.scorehealth.canary_note(pf.family, mean_abs, agree, n)
        if pf.rec is not None:
            pf.rec["canary_mean_abs_delta"] = round(mean_abs, 6)
            pf.rec["canary_topk_agreement"] = round(agree, 4)

    async def _resolve_flush(self, pf: _PendingFlush) -> None:
        """Materialize one flush's (gathered) scores and resolve its rows.

        Materialization ALWAYS happens off the loop (executor) unless an
        earlier race already produced the host array — ``is_ready`` only
        proves device compute finished, so an inline ``np.asarray`` here
        could still stall the loop for the copy's remaining link time.
        Worker-thread materialization is safe because ``pf.scores`` is a
        jit output nothing ever donates — unlike param trees, whose
        buffers later loop-thread calls donate (see
        ``checkpoint.host_copy_params`` for the full invariant)."""
        s = self._slices[pf.key]
        _slots, _cols, seqs, rows = pf.taken
        scattered = False  # did the (possibly unscored) write-back start?
        # flush supervision: every materialization await below is bounded
        # by the flush's remaining deadline (None = supervision off). An
        # already-overdue head gets one short grace tick so the timeout
        # path — not a 0s race — decides.
        budget = (
            None if pf.deadline is None
            else max(0.05, pf.deadline - time.perf_counter())
        )
        try:
            if pf.lane == "train":
                # train-lane completion: no rows to resolve — materialize
                # the per-slot loss vector (same executor discipline as
                # scores), publish it to last_train_losses, and attribute
                # the step's device window + FLOPs to the TRAIN families
                # (never the serving MFU account)
                scattered = True  # nothing row-shaped to salvage on cancel
                losses_np, _sk, _sh = await asyncio.wait_for(
                    pf.ensure_host_future(
                        asyncio.get_running_loop(), self._deliver_pool
                    ),
                    timeout=budget,
                )
                now = time.perf_counter()
                # a train step holds the slice's device queue like a
                # serve flush: the next flush's service time starts here
                s.last_landed = now
                s.last_train_losses = losses_np
                device_s = max(0.0, now - pf.t_dispatch)
                # train steps feed the same deadline history as serve
                # flushes (they share the in-flight window): mixing only
                # RAISES the p99-derived deadline — conservative-safe
                s.note_device_s(device_s)
                self.metrics.histogram(
                    "tpu_inference.train_step", unit="s"
                ).record(device_s)
                if pf.flops:
                    self.metrics.counter(
                        "tpu_train_flops_total", family=pf.family
                    ).inc(pf.flops)
                if pf.rec is not None:
                    pf.rec["device_s"] = round(device_s, 6)
                    finite = losses_np[np.isfinite(losses_np)]
                    pf.rec["loss_max"] = (
                        round(float(finite.max()), 6) if finite.size else None
                    )
                    pf.rec["status"] = "ok"
                return
            if pf.poisoned:
                # the dispatch itself failed (breaker/failover already
                # recorded at the flush site): no transfer to wait for —
                # resolve the rows unscored, but through this FIFO slot
                # so they can't overtake an earlier in-flight flush
                scattered = True
                await self._resolve_rows(seqs, rows, None, family=pf.family)
                return
            t0 = time.perf_counter()
            with sw("reap_wait", flush_id=pf.flush_id):
                scores_np, sketch_np, shadow_np = await asyncio.wait_for(
                    pf.ensure_host_future(
                        asyncio.get_running_loop(), self._deliver_pool
                    ),
                    timeout=budget,
                )
            # the transfer has landed: the in-flight interval ends and
            # ``resolve`` begins, on one stamp
            now = time.perf_counter()
            service_s = max(0.0, now - max(pf.t_dispatch, s.last_landed))
            s.last_landed = now
            # cumulative wait: from the FIRST time the reaper waited on
            # this flush (race rounds included), not just the last await
            waited_s = now - pf.t_wait if pf.t_wait is not None else now - t0
            self.metrics.histogram("tpu_inference.d2h_wait", unit="s").record(
                waited_s
            )
            if pf.stream_stats is not None:
                self._count_stream_step(pf, s.scorer)
            d2h_overlapped = (
                pf.t_wait is None and waited_s < self.D2H_OVERLAP_EPS_S
            )
            if d2h_overlapped:
                # the transfer had fully landed before the reaper asked —
                # it rode under later compute (raced-on heads never count,
                # however fast their future resolved afterwards)
                self.metrics.counter("tpu_inference.d2h_overlapped").inc()
            with sw("resolve", flush_id=pf.flush_id):
                t1 = time.perf_counter()
                # wire dtype (bf16/f16) widens back to f32 at the batch edge
                if pf.gathered:
                    picks = scores_np[: pf.moved].astype(np.float32, copy=False)
                else:
                    picks = scores_np[_slots, _cols].astype(np.float32, copy=False)
                # score-quality accounting: per-flush NaN census + the
                # device sketch folded into the tenant drift windows, all
                # vectorized (runtime.scorehealth; nan attribution rides the
                # pack-order slots — one bincount, never a per-row loop)
                nan_mask = np.isnan(picks)
                nan_rows = int(nan_mask.sum())
                if nan_rows:
                    self.metrics.counter(
                        "tpu_scores_nan_total", family=pf.family
                    ).inc(nan_rows)
                if sketch_np is not None:
                    nan_by_slot = None
                    if nan_rows:
                        # picks align with the pack-order slots on BOTH the
                        # gathered and full-plane fallback paths; only the
                        # single-slot slice zeroed them (override carries it)
                        if pf.slot_override is not None:
                            nan_by_slot = np.zeros(
                                (sketch_np.shape[0],), np.int64
                            )
                            nan_by_slot[pf.slot_override] = nan_rows
                        else:
                            nan_by_slot = np.bincount(
                                _slots[nan_mask], minlength=sketch_np.shape[0]
                            )
                    self.scorehealth.ingest_sketch(
                        pf.family, sketch_np.sum(axis=1), nan_by_slot,
                        mesh_slice=pf.sl,
                    )
                if shadow_np is not None:
                    self._canary_compare(pf, picks, shadow_np)
                # cancellation past this point observes only INSIDE
                # _resolve_rows' publish loop (the scatter is await-free), so
                # scores are written and counts decremented exactly once —
                # the cancel path below must not resolve a second time
                scattered = True
                await self._resolve_rows(
                    seqs, rows, picks, flush_id=pf.flush_id
                )
                t_resolved = time.perf_counter()
                resolve_s = t_resolved - t1
            self.metrics.histogram("tpu_inference.resolve", unit="s").record(
                resolve_s
            )
            self.metrics.counter("tpu_inference.reaped").inc()
            self.metrics.counter("tpu_inference.d2h_bytes").inc(pf.nbytes)
            # the in-flight interval: dispatch returned → transfer landed.
            # With ``max_inflight`` flushes queued on the device these
            # windows OVERLAP — it is what an event waits, and what the
            # flush supervisor's deadline bounds (the next flush's
            # deadline tracks this (family, slice)'s observed p99)
            device_s = max(0.0, now - pf.t_dispatch)
            self.metrics.histogram("tpu_inference.inflight", unit="s").record(
                device_s
            )
            s.note_device_s(device_s)
            # the service time (taken at the landing, above): the slice's
            # device queue is FIFO, so this flush had the device from the
            # later of its own dispatch and the previous landing. These
            # windows never overlap — they are what device-time and MFU
            # attribution divide by (this flush's executed FLOPs: padded
            # plane, see ShardedScorer.flops_per_flush)
            self.metrics.histogram("tpu_inference.service", unit="s").record(
                service_s
            )
            if pf.flops:
                self._mfu_account(pf.family).record(pf.flops, service_s)
                if s.mfu is not None:
                    # per-chip utilization beside the family aggregate:
                    # each slice's flushes feed ITS device's account
                    s.mfu.record(pf.flops, service_s)
            d2h_labels = {"family": pf.family}
            if self.mm.n_devices > 1:
                d2h_labels["device"] = getattr(
                    s.scorer, "device_label", "device:?"
                )
            self.metrics.counter(
                "tpu_inference_d2h_bytes_total", **d2h_labels
            ).inc(pf.nbytes)
            if pf.rec is not None:
                # complete the flush record in place (see flightrec)
                pf.rec["d2h_wait_s"] = round(waited_s, 6)
                pf.rec["d2h_overlapped"] = d2h_overlapped
                pf.rec["resolve_s"] = round(resolve_s, 6)
                pf.rec["device_s"] = round(device_s, 6)
                pf.rec["service_s"] = round(service_s, 6)
                pf.rec["t_landed"] = now
                pf.rec["t_resolved"] = t_resolved
                pf.rec["status"] = "ok"
                # score-quality fields: incident snapshots can now see
                # WHAT the flush scored, not just how long it took
                pf.rec["nan_rows"] = nan_rows
                finite = picks[~nan_mask]
                pf.rec["score_p99"] = (
                    round(float(np.quantile(finite, 0.99)), 6)
                    if finite.size else None
                )
            if pf.plane_nbytes:
                # what the pre-gather path would have moved — the bench's
                # d2h_plane_reduction column is this ratio
                self.metrics.counter("tpu_inference.d2h_plane_bytes").inc(
                    pf.plane_nbytes
                )
            s.consec_errors = 0  # healthy again
            self._failover_rounds.pop(pf.family, None)
            s.breaker.record_success()
        except asyncio.CancelledError:
            # cancelled mid-flight (forced teardown): the rows were already
            # popped from lanes, so resolve them unscored or they're lost.
            # But ONLY if the real-score pass never ran — re-resolving
            # after it would decrement batch row counts a second time
            # (premature NaN publishes) and overwrite written scores
            if not scattered:
                await self._resolve_rows(
                    seqs, rows, None, publish_nowait=True, family=pf.family
                )
            raise
        except asyncio.TimeoutError:
            # the flush deadline expired with the transfer unlanded: the
            # supervisor's SUSPECT path — force-resolve unscored in this
            # FIFO slot (or retry/eject the rows), trip the breaker,
            # snapshot the blackbox, quarantine the slice
            await self._on_flush_timeout(pf, scattered)
        except Exception as exc:  # noqa: BLE001 - a poisoned transfer
            # must not strand the batches: resolve rows unscored — but
            # only if the write-back never ran (same double-decrement
            # hazard as the cancel path above; a fault AFTER it, e.g. a
            # non-transient publish error, already flushed the remaining
            # completed batches inside _resolve_rows)
            self._record_error("deliver", exc)
            poison = pf.retried and self._poison_confirmed(
                pf.family, pf.sl, pf.retry_from
            )
            if not scattered:
                if poison:
                    # a cross-slice retry faulted AGAIN: two chips
                    # agreed — eject to the scorer-poison DLQ
                    await self._eject_poison(pf.family, seqs, exc)
                elif (
                    pf.retry_rows is not None
                    and not pf.retried
                    and pf.lane != "train"
                    and not self._seqs_already_retried(pf.taken[2])
                ):
                    # first strike at materialize time (late device
                    # error): same one-shot retry as a dispatch fault —
                    # inline because this IS the queue head's resolve
                    # task (its permit is still held; exclude it from
                    # the FIFO guard)
                    await self._retry_poison(
                        pf.family, pf.sl, pf.retry_rows, pf.taken, exc,
                        inline=True, exclude=pf,
                    )
                else:
                    if pf.retried:
                        # same-chip second strike: chip-attributed —
                        # the rows leave unscored, unmarked
                        for q in np.unique(seqs).tolist():
                            self._retried_seqs.discard(int(q))
                    await self._resolve_rows(
                        seqs, rows, None, family=pf.family
                    )
            if pf.rec is not None and not pf.poisoned:
                pf.rec["status"] = "error"
                pf.rec["error"] = repr(exc)
            if not pf.poisoned and not poison and pf.lane != "train":
                # a poisoned flush's dispatch failure was already counted
                # at the flush site — recording it again here would let a
                # downstream bus hiccup double-pace failover/parking;
                # train-lane faults are best-effort and must not pace
                # breaker/failover either (serve flushes own that signal)
                s.breaker.record_failure()
                if self.flightrec is not None and s.breaker.state == "open":
                    self.flightrec.snapshot(
                        f"breaker:{pf.family}", family=pf.family,
                        trace_id=pf.rec.get("trace_id") if pf.rec else None,
                    )
                await self._note_scorer_error(s)
        finally:
            # the head leaves the queue only once its resolution is DONE
            # (either way) — queue length and the deliver_inflight gauge
            # honestly count unfinished flushes, the teardown drain
            # can't miss a flush the reaper was cancelled inside, and
            # slice-move fences wait on exactly this flag
            pf.resolved = True
            if s.reap and s.reap[0] is pf:
                s.reap.popleft()
            self._deliver_gauge()
            if pf.owns_permit:
                s.permits.release()
            if s.last_scores is pf.scores and not s.reap:
                # slice idle: the overlap probe must not pin this
                # flush's device scores until the next (maybe never)
                # flush — by now the probe is ready, so dropping it
                # can't change the next overlap verdict
                s.last_scores = None

    async def _on_flush_timeout(
        self, pf: _PendingFlush, scattered: bool
    ) -> None:
        """One flush blew its completion deadline: the supervisor's
        SUSPECT verdict. The rows force-resolve UNSCORED in this FIFO
        slot (exact PR 5 poisoned-flush semantics — zero loss, per-
        tenant order preserved) unless the poison-retry path takes
        ownership of them; the breaker trips (a hung device yields no
        raised outcome for its window to count), the blackbox freezes,
        and the slice enters quarantine + probation. Runs inside
        ``_resolve_flush``'s try — its ``finally`` still pops the queue
        head and releases the permit exactly once."""
        family, sl = pf.key
        s = self._slices[pf.key]
        self.metrics.counter(
            "tpu_flush_timeout_total", family=family, slice=str(sl)
        ).inc()
        if pf.rec is not None:
            pf.rec["status"] = "timeout"
        # decide attribution BEFORE quarantining: a confirmed-poison
        # verdict (cross-slice retry that ALSO failed) means the DATA,
        # not this chip, owns the fault — quarantining/tripping the
        # retry slice would churn tenants for a data bug, exactly the
        # capacity drain poison ejection exists to stop
        poison = pf.retried and self._poison_confirmed(
            family, sl, pf.retry_from
        )
        if self.flightrec is not None:
            # evidence first: the snapshot carries the wedged flush's
            # own record (timings, kernel variant, slice, trace_id)
            self.flightrec.snapshot(
                f"flush-timeout:{family}", family=family, mesh_slice=sl,
                lane=pf.lane,
                trace_id=pf.rec.get("trace_id") if pf.rec else None,
            )
        _s, _c, seqs, rows = pf.taken
        err = TimeoutError(f"flush deadline expired ({family}@s{sl})")
        if poison:
            await self._eject_poison(family, seqs, err)
            return
        s.breaker.trip()
        await self._quarantine_slice(s, reason="flush-timeout")
        if scattered or pf.lane == "train":
            return  # no rows to salvage (train) / already written back
        if pf.retried:
            # same-chip (or fleet-sick) second timeout: chip-attributed
            for q in np.unique(seqs).tolist():
                self._retried_seqs.discard(int(q))
            await self._force_resolve(pf)
        elif (
            pf.retry_rows is not None
            and not self._seqs_already_retried(seqs)
        ):
            # first strike: the tenants just failed over (quarantine
            # above) — retry the same staged bytes on their new slices
            # (inline: this runs inside the head's own resolve task)
            await self._retry_poison(
                family, sl, pf.retry_rows, pf.taken, err,
                inline=True, exclude=pf,
            )
        else:
            await self._force_resolve(pf)

    async def _force_resolve(
        self, pf: _PendingFlush, nowait: bool = False
    ) -> None:
        """THE force-resolve accounting path: one pending flush's rows
        resolve unscored (NaN, counted via tpu_scores_unscored_total +
        per-tenant note_unscored inside ``_resolve_rows``). Shared by
        the supervisor's deadline timeout (normal backpressure) and
        service teardown (``nowait`` — the consumer may be gone), so
        the two can never diverge on accounting."""
        _s, _c, seqs, rows = pf.taken
        if pf.lane != "train":
            await self._resolve_rows(
                seqs, rows, None, publish_nowait=nowait, family=pf.family
            )

    # -- legacy object path (low-volume / tests) --------------------------
    async def _enqueue_events(self, engine: TpuInferenceEngine, events: List) -> List:
        """Object events: wrap measurements into a single-row batch each is
        wasteful — instead convert the poll's measurements into one batch."""
        measurements = [e for e in events if isinstance(e, DeviceMeasurement)]
        passthrough = [e for e in events if not isinstance(e, DeviceMeasurement)]
        if measurements:
            batch = MeasurementBatch.from_events(
                measurements, [0] * len(measurements), tenant=engine.tenant
            )
            batch.assignment_tokens = np.asarray(
                [e.assignment_token for e in measurements], object
            )
            batch.area_tokens = np.asarray(
                [e.area_token for e in measurements], object
            )
            await self._enqueue_batch(engine, batch)
        return passthrough

    # -- main loop -------------------------------------------------------
    async def _scoring_loop(self) -> None:
        iters = self.metrics.counter("tpu_inference.loop_iters")
        throttled = self.metrics.counter("tpu_inference.fair_throttled")
        held = self.metrics.counter("tpu_inference.flush_held")
        while True:
            iters.inc()
            moved = 0
            holding = False
            fam_cfgs: Dict[SliceRuntime, Dict[int, TenantEngineConfig]] = {}
            # weighted fair queuing: every pass replenishes each tenant's
            # deficit (quantum × weight); a tenant that overdrew sits out
            # until its deficit refills, so sustained intake converges to
            # the weight ratio and a hostile tenant's backlog stays in
            # ITS bus topic (where lag → credit → receiver shed)
            self.fair.replenish()
            if self._fences:
                # slice moves in flight: release any whose old-slice
                # snapshot fully resolved (parked rows re-enter lanes)
                self._lift_fences()
            # probation: launch due probes for quarantined slices (a
            # walk over a handful of slices on the healthy path)
            self._probe_quarantined()
            if self.pager is not None:
                # weight paging: issue prefetches for rising-lag ghost
                # tenants, then service ≤ 1 queued page-in — all device
                # mutation stays OFF the flush critical path
                self._paging_tick()
            for tenant, engine in list(self.engines.items()):
                if engine.state is not LifecycleState.STARTED:
                    continue
                assert isinstance(engine, TpuInferenceEngine)
                s = self._slices.get(
                    (engine.config.model, engine.placement.shard)
                )
                if s is None:
                    # its slice's scorer never built (a move onto a dead
                    # chip): its rows stay on the bus, where lag shows
                    continue
                if engine.placement is not None and engine.placement.slot >= 0:
                    # register for flush even when throttled below: lanes
                    # already holding this tenant's rows must still drain.
                    # Ghost (paged-out, slot=-1) tenants register nothing:
                    # their rows park behind the paging fence and no slot
                    # of theirs exists to flush or train
                    fam_cfgs.setdefault(s, {})[
                        engine.placement.slot
                    ] = engine.config
                    tc = engine.config.training
                    if tc.enabled and tc.train_lane:
                        # replay-fed continual learning: low-priority
                        # intake from the train feed topic into the
                        # train lane rings (bounded + credit-gated —
                        # never charged against the serve fair budget)
                        await self._consume_train_feed(tenant, engine, s)
                budget = self.fair.budget(tenant)
                if budget <= 0:
                    throttled.inc()
                    continue
                # per-tenant lane watermark: a slow/contended scorer must
                # backpressure intake into the BUS (where depth is a
                # gauge, lag drives the credit signal, and retention
                # bounds memory) instead of buffering unboundedly in
                # lanes. 2× max_batch keeps the next flush fed.
                lanes_now = s.lanes
                slot_now = engine.placement.slot
                pending_rows = sum(
                    l.count for (s, _d), l in lanes_now.items()
                    if s == slot_now
                )
                fence_now = self._fences.get(tenant)
                if fence_now is not None:
                    # parked rows count against the watermark: a long
                    # fence must backpressure intake into the bus, not
                    # buffer unboundedly host-side
                    pending_rows += fence_now.depth()
                if pending_rows >= 2 * engine.config.microbatch.max_batch:
                    self.metrics.counter(
                        "tpu_inference.lane_backpressure"
                    ).inc()
                    continue
                # a tenant in deficit debt polls ONE item at a time so
                # the overshoot past its budget is bounded by one batch
                items = await self.bus.consume(
                    self.bus.naming.inbound_events(tenant),
                    self.group,
                    self.poll_batch if budget >= self.fair.quantum else 1,
                    timeout_s=0,
                )
                # the engine can stop DURING the consume await (stop
                # cascade); its cursor already advanced, so resolve the
                # items unscored instead of crashing on a dead placement
                if engine.state is not LifecycleState.STARTED or engine.placement is None:
                    await self._passthrough(
                        self.bus.naming.scored_events(tenant), items
                    )
                    continue
                if not items:
                    continue
                batches = [i for i in items if isinstance(i, MeasurementBatch)]
                objects = [i for i in items if not isinstance(i, MeasurementBatch)]
                self.fair.charge(
                    tenant, sum(b.n for b in batches) + len(objects)
                )
                gate = self._gate(tenant)
                sample_rate = 1.0
                if self.overload is not None and self.overload.degraded(
                    tenant, "sample_inference"
                ):
                    pol = self.overload.policy_for(tenant)
                    sample_rate = pol.inference_sample_rate if pol else 1.0
                for b in batches:
                    if gate.check(b):
                        continue  # expired: never reaches a scorer flush
                    await self._enqueue_batch(engine, b, sample_rate)
                    moved += b.n
                objects = [o for o in objects if not gate.check(o)]
                if objects:
                    passthrough = await self._enqueue_events(engine, objects)
                    topic = self.bus.naming.scored_events(tenant)
                    for ev in passthrough:
                        await publish_at_least_once(
                            self.bus, topic, ev, metrics=self.metrics
                        )
                    moved += len(objects)
            for s, cfgs in fam_cfgs.items():
                mb = next(iter(cfgs.values())).microbatch
                if s.due(mb):
                    if s.held(mb, s.family in self._parked):
                        held.inc()
                        holding = True
                        continue
                    moved += await self._flush_slice(cfgs, s)
            if fam_cfgs:
                # the async train lane runs AFTER serve flushes, off the
                # flush critical path: at most one low-priority train
                # dispatch per (family, slice) per pass, and only into a
                # free in-flight permit (a saturated slice trains 0)
                moved += await self._train_lane_tick(fam_cfgs)
            if moved == 0 or holding:
                # a pass that held a due flush yields like an idle one,
                # even if intake moved rows: nothing can leave before the
                # flush in flight lands, and under a firehose this is the
                # turn the full semaphore used to give the other stages
                # (``bus.consume(timeout_s=0)`` never suspends)
                await asyncio.sleep(0.001)

    async def _passthrough(self, topic: str, items: list) -> None:
        """Forward consumed items downstream unscored. While the service is
        up (e.g. a tenant restart mid-flight) this backpressures like the
        normal path — a lagging persistence consumer must slow us down, not
        have retained batches evicted out from under it. The lossy
        ``publish_nowait`` is reserved for service teardown, when the
        consumer may already be gone and an awaitable publish would never
        unblock. The consume cursor has already advanced past these items,
        so even a cancellation mid-publish must still emit them."""
        pending = list(items)
        try:
            while pending:
                item = pending[0]
                if isinstance(item, MeasurementBatch):
                    item.mark("passthrough_stop")
                if self.state is LifecycleState.STARTED:
                    await publish_at_least_once(
                        self.bus, topic, item, metrics=self.metrics
                    )
                else:
                    self.bus.publish_nowait(topic, item)
                pending.pop(0)
        except asyncio.CancelledError:
            for item in pending:
                if isinstance(item, MeasurementBatch):
                    item.mark("passthrough_stop")
                self.bus.publish_nowait(topic, item)
            raise

    def prewarm(self) -> None:
        """Compile every active family's bucket shapes (see
        ShardedScorer.prewarm). Call after tenants are added, before
        latency-sensitive traffic."""
        # union of every resident engine's bucket sizes per (family,
        # slice): tenants sharing a slice may configure different
        # buckets, and a missed size is a mid-scoring-loop XLA compile
        wanted: Dict[Tuple[str, int], set] = {}
        for tenant, engine in self.engines.items():
            assert isinstance(engine, TpuInferenceEngine)
            if engine.placement is None:
                continue
            key = (engine.config.model, engine.placement.shard)
            mb = engine.config.microbatch
            wanted.setdefault(key, set()).update(
                [min(b, mb.max_batch) for b in mb.buckets] + [mb.max_batch]
            )
        lane_keys: set = set()
        for tenant, engine in self.engines.items():
            assert isinstance(engine, TpuInferenceEngine)
            if engine.placement is None:
                continue
            tc = engine.config.training
            if tc.enabled and tc.train_lane:
                lane_keys.add(
                    (engine.config.model, engine.placement.shard)
                )
        for key, sizes in wanted.items():
            s = self._slices.get(key)
            if s is not None:
                scorer = s.scorer
                scorer.prewarm(sorted(sizes))
                # every bucket's step is compiled now: its first real
                # flush must not report a (false) compile either — same
                # rule as the train lane below
                s.seen_shapes.update(sizes)
                if key in lane_keys and getattr(
                    scorer, "train_lane", False
                ):
                    # the train lane's first step/ingest must not pay a
                    # mid-traffic XLA compile either — same rule as the
                    # scoring shapes above
                    if getattr(scorer, "_train_fused", None) is None:
                        scorer.init_optimizer()
                    scorer.prewarm_train_lane(sorted(sizes))
                    # the lane's executables are compiled now: the first
                    # real dispatch must not report a (false) compile —
                    # that would fire the steady_state_recompile
                    # watchdog the moment a replay train job starts
                    s.seen_shapes.add("train")

    def params_source(self, tenant: str):
        """A zero-arg callable yielding the tenant's CURRENT slot params
        (live-trained, or checkpoint-restored after a restart) — the
        CEP→TPU bridge binds ModelUdf evaluation to this so rule verdicts
        track the tenant's actual model, never a fresh init. Returns None
        while the tenant has no placement (caller falls back)."""

        def source():
            engine = self.engines.get(tenant)
            if engine is None or engine.placement is None:
                return None
            if engine.placement.slot < 0:
                # paged out: the host byte cache is the source of truth
                # (slot_params(-1) would read ANOTHER tenant's last slot)
                return self._cached_params(tenant)
            scorer = self.scorers.get(
                (engine.config.model, engine.placement.shard)
            )
            if scorer is None:
                return None
            return scorer.slot_params(engine.placement.slot)

        return source

    def _cached_params(self, tenant: str):
        """Decode a paged-out tenant's params from its cache blob (host
        numpy tree) — None when no blob exists (a pristine ghost)."""
        if self.pager is None:
            return None
        entry = self.pager.cache.get(tenant)
        if entry is None:
            return None
        from sitewhere_tpu.runtime.checkpoint import decode_segment

        params, _opt = decode_segment(entry[0])
        return params

    def snapshot_params(self) -> Dict[Tuple[str, str], object]:
        """Live param cut for checkpointing: (tenant, family) → param
        pytree for that tenant's slot. The leaves are jax arrays
        (immutable), so the caller can hand them to an executor thread for
        host transfer + serialization without racing ongoing training."""
        out: Dict[Tuple[str, str], object] = {}
        for tenant, engine in self.engines.items():
            assert isinstance(engine, TpuInferenceEngine)
            if engine.placement is None:
                continue
            if engine.placement.slot < 0:
                # paged out: snapshot from the cache blob, not the
                # device (slot -1 would alias another tenant's slot)
                cached = self._cached_params(tenant)
                if cached is not None:
                    out[(tenant, engine.config.model)] = cached
                continue
            scorer = self.scorers.get(
                (engine.config.model, engine.placement.shard)
            )
            if scorer is None:
                continue
            out[(tenant, engine.config.model)] = scorer.slot_params(
                engine.placement.slot
            )
        return out

    # -- introspection ---------------------------------------------------
    def describe(self) -> dict:
        return {
            "mesh": self.mm.describe(),
            "router": self.router.describe(),
            "quarantined": {
                f"{fam}@{sl}": {
                    k: v for k, v in qs.items() if k != "next_probe"
                }
                for (fam, sl), s in sorted(self._slices.items())
                for qs in [s.quarantine] if qs is not None
            },
            "families": {
                f"{fam}@{sl}": {
                    "n_slots": s.n_slots,
                    "max_streams": s.max_streams,
                    "device": s.device_label,
                    "train_lane": bool(getattr(s, "train_lane", False)),
                }
                for (fam, sl), s in sorted(self.scorers.items())
            },
            "paging": self.pager.stats() if self.pager is not None else None,
        }
