#!/usr/bin/env python
"""Single source of truth for every analyzer registry.

Each ``tools/check_*`` lint used to carry its own registry literal —
which meant a refactor could update five of them and silently orphan
the sixth. Every registry now lives here and the tools import it; a
stale entry (module or symbol gone) is a finding in the owning tool
that NAMES the missing symbol (``astlib.stale_registry``).

Registering a new site:

- **hot path** (allocation discipline): add ``"Class.method"`` under
  its module in ``HOT_PATHS``;
- **bounded queue**: add a ``(module, construction regex)`` key to
  ``QUEUE_REGISTRY`` declaring its depth gauge + shed/backpressure
  counter;
- **supervised await**: add the function to ``SUPERVISED_PATHS`` —
  every watched await inside must be ``asyncio.wait_for``-wrapped or
  carry ``# supervised: ok(<watchdog>)``;
- **fused kernel / train grad / decode variant**: add the family to
  ``FUSION_REGISTRY`` / ``TRAIN_REGISTRY`` / ``DCT_REGISTRY``;
- **commit section** (cancellation-atomicity): add an entry to
  ``COMMIT_SECTIONS`` naming the begin/end operations — no ``await``
  may appear between them;
- **counter/gauge pair**: add the decrement site to ``COUNTER_PAIRS``
  — the decrement must live in a ``finally``;
- **executor-shared state**: add the class's executor-side and
  loop-side functions to ``THREAD_SHARED`` so cross-thread attribute
  mutation stays lock-protected.

See docs/STATIC_ANALYSIS.md for rule semantics and the opt-out
grammar table.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

# =====================================================================
# check_hotpath — zero-copy feed discipline (docs/PERFORMANCE.md)
# =====================================================================
# module (relative to sitewhere_tpu/) → hot functions ("name" for
# module-level, "Class.method" for methods). Point this at the functions
# that run per flush / per enqueue at full ingest rate — NOT at cold
# paths (drain, failover, teardown), which may keep convenient idioms.
HOT_PATHS: Dict[str, List[str]] = {
    "pipeline/inference.py": [
        "TpuInferenceService._enqueue_batch",
        # the slice-routed flush + completion path (multi-chip serving):
        # every function here runs per flush per SLICE at full rate
        "TpuInferenceService._flush_slice",
        "TpuInferenceService._resolve_rows",
        "TpuInferenceService._reap_loop",
        "TpuInferenceService._resolve_flush",
        "TpuInferenceService._canary_compare",
        "TpuInferenceService._deliver_gauge",
        # the continual-learning train lane: feed intake + microbatch
        # packing + the per-pass lane tick all run at full ingest /
        # loop rate — rows must stay columnar, and the loss device
        # array must resolve via the reaper, never a blocking asarray
        "TpuInferenceService._enqueue_train_batch",
        "TpuInferenceService._pack_train",
        "TpuInferenceService._train_lane_tick",
        "TpuInferenceService._dispatch_train",
        "_SliceFence.park",
        # weight paging: the evict path runs synchronously ON the event
        # loop (no await may split the commit section) and the per-pass
        # tick runs every scoring-loop iteration — both must stay free
        # of list accumulators and blocking materialization beyond the
        # single loop-thread host_copy the donation hazard requires
        "TpuInferenceService._page_out",
        "TpuInferenceService._paging_tick",
    ],
    # what a (family, mesh slice) is made of: the lane rings take every
    # row at enqueue and give it up at flush; the slice's own rules run
    # per scoring-loop pass (due/held) and per flush (the staging set)
    "pipeline/slices.py": [
        "_LaneRing.push",
        "_LaneRing.pop_into",
        "SliceRuntime.due",
        "SliceRuntime.held",
        "SliceRuntime.in_flight",
        "SliceRuntime.staging_set",
    ],
    # the weight-paging bookkeeping runs per enqueue (touch/hit-rate) and
    # per page-in/evict: pure dict/deque ops, no per-row Python, no
    # device round-trips (the module is deliberately jax-free)
    "runtime/paging.py": [
        "SlotPager.touch",
        "SlotPager.note_resident",
        "SlotPager.eviction_score",
        "_HostByteCache.commit_page_out",
        "_PageInQueue.push",
        "WeightPager.note_touch",
    ],
    # the score-quality feed runs once per resolved flush at full ingest
    # rate: sketches fold in as vectorized 64-bin adds per touched slot,
    # never per-row Python (docs/OBSERVABILITY.md "Score health")
    "runtime/scorehealth.py": [
        "ScoreHealth.ingest_sketch",
        "ScoreHealth.note_unscored",
        "ScoreHealth.canary_note",
    ],
    "pipeline/media.py": [
        "MediaClassificationPipeline.submit_chunk",
        "MediaClassificationPipeline._classify_and_publish",
        "MediaClassificationPipeline._classify_compressed",
        "MediaClassificationPipeline._finish_classify",
        # the compressed-wire decode stage runs once per classify batch
        # at camera rate: coefficient packing must stay one vectorized
        # copy per component, frame fan-out rides preallocated
        # index/keep arrays (per-FRAME loops are the unit here — the
        # per-EVENT ban still holds)
        "MediaClassificationPipeline._decode_batch",
        "_FrameRing.reserve",
        "_FrameRing.pop_into",
        "_ByteRing.append",
        "_ByteRing.pop_into",
    ],
    # the native decode binding runs per frame on the decode pool; its
    # job is pointer hand-off — any per-coefficient Python here would
    # multiply by 64 blocks × rate
    "native/jpegwire.py": [
        "decode_into",
    ],
    # the on-device decode kernels trace under jit (check_fusion asserts
    # batch-invariant lowering); at the Python layer they must stay free
    # of per-frame/per-block list building
    "ops/dct.py": [
        "decode_frames",
        "idct_plane",
        "upsample2x",
        "ycbcr_to_rgb",
    ],
    "core/batch.py": [
        "make_event_ids",
        "encode_batch_wire",
    ],
    # the storage/replay axis runs at feed-path rates (docs/STORAGE.md):
    # segment scans and replay staging must move rows as vectorized
    # column picks, never as per-event Python objects
    "storage/segstore.py": [
        "SegmentColumns.append_batch",
        "SegmentColumns.scan",
        "slice_columns",
    ],
    "pipeline/replay.py": [
        "_slice_to_batch",
        "ReplayEngine._scan_loop",
        "ReplayEngine._pump_loop",
    ],
    # the latency-attribution feed runs once per TRACE at tail-decide
    # time (per batch, not per event): the stage-vector flatten, ledger
    # window push, and burn-bucket update must stay O(spans)/O(1) with
    # no per-row collections — decompose()/reports are read-path and
    # may sort freely
    "runtime/latency.py": [
        "stage_vector",
        "LatencyEngine.ingest_trace",
        "StageLedger.add",
        "_BurnAccount.note",
    ],
}

# =====================================================================
# check_queues — bounded-queue observability (docs/ROBUSTNESS.md)
# =====================================================================
# (relative file, construction regex) → declared observability.
# depth_gauge / shed_counter are metric family names as passed to
# MetricsRegistry (labeled families without the exposition suffix).
QUEUE_REGISTRY: Dict[Tuple[str, str], Dict[str, str]] = {
    ("pipeline/sources.py", r"PriorityClassQueue\(maxsize="): {
        "queue": "receiver ingest queue (priority-classed admission)",
        "depth_gauge": "receiver_queue_depth",
        "shed_counter": "receiver_shed_total",
    },
    ("pipeline/media.py", r"_FrameRing\("): {
        "queue": "media frame ring (newest-frame-wins shedding; the "
                 "legacy/kill-switch decoded-pixel ring)",
        "depth_gauge": "media_queue_depth",
        "shed_counter": "media_frames_shed_total",
    },
    ("pipeline/media.py", r"_ByteRing\("): {
        "queue": "compressed media byte ring (variable-length frame "
                 "spans in one preallocated arena; newest-frame-wins "
                 "shedding on index OR byte exhaustion)",
        "depth_gauge": "media_queue_depth",
        # the byte watermark: arena_bytes bounds RESIDENT bytes, so the
        # byte gauge — not frame count — is the capacity signal here
        "bytes_gauge": "media_ring_bytes",
        "shed_counter": "media_frames_shed_total",
    },
    ("pipeline/inference.py", r"ThreadPoolExecutor\("): {
        "queue": "deliver materialization pool (one job per in-flight "
                 "flush transfer; occupancy bounded by the per-slice "
                 "max_inflight semaphores that also bound the reap "
                 "queues feeding it)",
        "depth_gauge": "tpu_inference_deliver_inflight",
        # the pool never sheds: a full in-flight window backpressures
        # the NEXT flush at the semaphore, same bound as the reap FIFO
        "backpressure_counter": "tpu_inference.deliver_backpressure",
    },
    ("pipeline/media.py", r"ThreadPoolExecutor\("): {
        "queue": "media native-decode pool (per-WORKER range jobs over "
                 "a batch's frames; gauge ceiling = max_inflight × "
                 "decode_workers concurrent jobs)",
        "depth_gauge": "media_decode_inflight",
        # the pool never sheds: a saturated pool queues jobs and the
        # classify semaphore backpressures the batching loop (counted
        # when a submission lands behind a fully busy pool)
        "backpressure_counter": "media.decode_backpressure",
    },
    ("pipeline/inference.py", r"_LaneRing\("): {
        "queue": "slice-move fence stash (_SliceFence: a re-placed or "
                 "paged-out tenant's parked rows per data shard)",
        # open fences; the rows they hold count against the tenant's
        # lane watermark, so a long fence backpressures like a full lane
        "depth_gauge": "tpu_inference_fences",
        "parked_counter": "tpu_inference.fenced_rows",
        "backpressure_counter": "tpu_inference.lane_backpressure",
    },
    ("pipeline/slices.py", r"_LaneRing\("): {
        "queue": "scoring lane rings (pending rows per (slot, data-shard))",
        "depth_gauge": "tpu_inference_lane_rows",
        # lanes never shed: the per-tenant watermark backpressures intake
        # into the bus (where lag is a gauge and drives overload credit)
        "backpressure_counter": "tpu_inference.lane_backpressure",
    },
    ("pipeline/slices.py", r"_TrainLaneRing\("): {
        "queue": "continual-learning train lane rings (replay-fed "
                 "training rows per (slot, data-shard); watermark "
                 "2 × replay_microbatch)",
        "depth_gauge": "tpu_inference_train_rows",
        # the lane never sheds admitted rows: past the watermark the
        # feed CONSUMER parks (counted) and the backlog stays in the bus
        # topic, which the replay pump's overload arbitration already
        # throttles at the producer side
        "backpressure_counter": "tpu_inference.train_feed_backpressure",
    },
    ("pipeline/replay.py", r"_ReplayRing\("): {
        "queue": "replay intake ring (prepared scan slices between the "
                 "segment scanner and the publish pump)",
        "depth_gauge": "replay_ring_depth",
        # replay never sheds: a throttled pump backpressures the disk
        # scanner through the ring instead of buffering the store
        "backpressure_counter": "replay.ring_backpressure",
    },
    ("pipeline/slices.py", r"_ReapQueue\("): {
        "queue": "deliver reap queues (in-flight flush completions per "
                 "(family, mesh slice); bounded by the max_inflight "
                 "semaphore)",
        "depth_gauge": "tpu_inference_deliver_inflight",
        # per-family labeled variant beside the legacy aggregate: the
        # queues ARE per-(family, slice), so a wedged family shows here
        # while the aggregate hides it behind healthy siblings
        "family_depth_gauge": "tpu_inference_deliver_inflight_family",
        # ...and the per-DEVICE variant (multi-chip serving): one slow
        # chip's queue depth must be visible as THAT chip's, not
        # averaged into the fleet
        "device_depth_gauge": "tpu_inference_deliver_inflight_device",
        # completions never shed: a full in-flight window backpressures
        # the NEXT flush at the semaphore (counted before the acquire)
        "backpressure_counter": "tpu_inference.deliver_backpressure",
        # the flush policy in front of the semaphore (SliceRuntime.held): a
        # due flush waits for the one in flight while every lane is
        # under the smallest bucket (passes held), and joins an occupied
        # device only from the smallest bucket up (flushes pipelined)
        "held_counter": "tpu_inference.flush_held",
        "pipelined_counter": "tpu_inference.flush_pipelined",
    },
    ("runtime/netbus.py", r"= _ReplRing\("): {
        "queue": "broker replication ring (primary-side mutation tail — "
                 "WAL appends, journaled cursors, lease + control ops — "
                 "the warm standby drains via repl_poll long-polls)",
        "depth_gauge": "netbus_repl_ring_depth",
        # the ring sheds OLDEST when a standby lags past capacity; the
        # evicted poller is told to resync from a full snapshot, so the
        # shed is a forced resync, never silent record loss
        "shed_counter": "netbus_repl_evicted_total",
    },
    ("runtime/netbus.py", r"_pending_nowait: deque = deque\(\)"): {
        "queue": "client fire-and-forget reconnect buffer (bounded at "
                 "NOWAIT_BUFFER_MAX; flushed in order on reconnect / "
                 "failover; subscriptions replay separately via _subs)",
        "depth_gauge": "netbus_nowait_buffered",
        # overflow drops the OLDEST buffered frame, counted by op —
        # bounded memory during an outage, loud loss accounting
        "shed_counter": "netbus_frames_lost_total",
    },
    ("runtime/paging.py", r"self\.cache = _HostByteCache\("): {
        "queue": "weight-paging host byte cache (encoded param+opt "
                 "segments for paged-out tenants; bounded by cap_bytes)",
        "depth_gauge": "tpu_paging_cache_entries",
        # the byte watermark is the capacity signal: overflow evicts
        # CLEAN blobs oldest-first (they re-fetch from the checkpoint
        # store at page-in); dirty blobs never silently drop
        "bytes_gauge": "tpu_paging_cache_bytes",
        "shed_counter": "tpu_paging.cache_evictions",
    },
    ("runtime/paging.py", r"self\.queue = _PageInQueue\("): {
        "queue": "page-in staging queue (pending tenant activations, "
                 "deduplicated; demand always admits, prefetch sheds "
                 "at capacity)",
        "depth_gauge": "tpu_paging_pending",
        "shed_counter": "tpu_paging.prefetch_shed",
    },
    ("pipeline/slices.py", r"\[_StagingSet\("): {
        "queue": "per-(family, mesh-slice, bucket) rotating flush "
                 "staging sets (bounded by staging_slots per rotation)",
        "depth_gauge": "tpu_inference_staging_sets",
        # staging never sheds: recycling a set whose async h2d copy is
        # still in flight BLOCKS until the transfer lands (counted)
        "backpressure_counter": "tpu_inference.stage_reuse_waits",
    },
}

# =====================================================================
# check_supervised — deadline supervision on device awaits
# =====================================================================
# module (relative to sitewhere_tpu/) → hot-path functions whose device
# awaits must be deadline-supervised ("Class.method" or bare name).
SUPERVISED_PATHS: Dict[str, List[str]] = {
    "pipeline/inference.py": [
        # the completion reaper's race over in-flight heads
        "TpuInferenceService._reap_loop",
        # per-flush materialization (serve + train lanes)
        "TpuInferenceService._resolve_flush",
        # probation probes on quarantined slices
        "TpuInferenceService._dispatch_probe",
        # host-probation probes (host fault domain): same wire, same
        # deadline contract, driven by a re-appearing host's heartbeat
        "TpuInferenceService.host_probe",
    ],
    "pipeline/media.py": [
        # the classify readback (media lane)
        "MediaClassificationPipeline._finish_classify",
    ],
    # the host fault domain's control-plane loops: the lease heartbeat
    # and the coordinator's lease-table watch. Neither may grow an
    # unsupervised device/executor await — a wedged probe inside the
    # heartbeat would silently stop renewals and fence a healthy host.
    "runtime/hostlease.py": [
        "HostLeaseClient._renew_loop",
        "HostLeaseClient.renew_once",
        "HostSupervisor._watch_loop",
        "HostSupervisor.poll_once",
    ],
}

# call names whose await is a device-future / reap wait
SUPERVISED_WATCHED_NAMES: Tuple[str, ...] = (
    "ensure_host_future", "run_in_executor",
)

# =====================================================================
# check_fusion — fused-kernel lowering invariants
# =====================================================================
# family → config overrides small enough to trace instantly; every entry
# must exist in MODEL_REGISTRY with a score_stacked contract
FUSION_REGISTRY: Dict[str, dict] = {
    "lstm_ad": {"window": 8, "hidden": 8},
    "deepar": {"hidden": 8},
    "transformer": {"context": 8, "dim": 16, "depth": 1, "heads": 2},
}

# the continual-learning train lane's registry: every entry must also
# carry a loss_stacked contract — its masked-mean GRADIENT is traced at
# S=2 and S=4 with the same invariants (bounded scan-body dots, slot-
# count-invariant total, zero collectives): a refactor that resurrects
# the per-slot vmap in the backward pass would silently hand the MXU S
# small matmul chains per train step again.
TRAIN_REGISTRY: Dict[str, dict] = dict(FUSION_REGISTRY)

# media decode kernels (ops/dct.py): the compressed-wire ViT leg fuses
# JPEG reconstruction into the classifier jit. Traced at B=2 and B=4
# with the same invariants as the scoring kernels. Entries:
# name → (subsampling, truncation k).
DCT_REGISTRY: Dict[str, Tuple[int, int]] = {
    "vit_dct_420": (2, 16),
    "vit_dct_444": (1, 64),
}

# =====================================================================
# check_async — whole-program async-safety analysis
# =====================================================================
# Rule 1 (blocking-in-coroutine) roots: every ``async def`` in these
# top-level package locations runs on the serving event loop. comm/,
# api/, sim/ carry protocol adapters and harness code whose async defs
# are covered by the package-wide rules 2–4 but are not reachability
# roots (their blocking cost is not the serving loop's p99).
ASYNC_ROOT_DIRS: Tuple[str, ...] = (
    "pipeline", "runtime", "services", "instance.py",
)

# Package functions that ARE blocking primitives even though the AST
# can't see it (ctypes trampolines, PIL decode wrappers, fsync'ing
# writers). Reaching one from a loop coroutine without an executor hop
# is a rule-1 finding; the description completes the finding message.
BLOCKING_LEAVES: Dict[str, str] = {
    # the ctypes jpegwire bindings block the calling thread for the full
    # native decode (and a cold jpegwire_lib(wait=True) blocks on cc)
    "native/jpegwire.py::decode_into": "ctypes native JPEG decode",
    "native/jpegwire.py::jpegwire_lib": "native build wait (compiles the .so)",
    "native/__init__.py::jsonwire_lib": "native build wait (compiles the .so)",
    "native/__init__.py::build_native_lib": "native toolchain invocation (cc)",
    "native/__init__.py::parse_json_bulk": "ctypes native JSON parse",
    # PIL decode path: the ONE image-decode helper — media hops it
    # through the decode pool; anything else must too
    "services/streaming_media.py::StreamingMedia.decode_frame":
        "PIL image decode",
    # the WAL appenders fsync/flush to disk per call
    "runtime/dlog.py::SegmentWriter.append": "WAL append (flush+fsync)",
    "runtime/dlog.py::SegmentWriter.close": "WAL close (flush+fsync)",
    "runtime/dlog.py::OffsetsJournal.record": "cursor journal write",
    # the shared frame-journal base (cursor + lease journals): per-frame
    # flush and the threshold-triggered snapshot rewrite+fsync
    "runtime/dlog.py::FrameJournal._write": "journal frame write (flush)",
    "runtime/dlog.py::FrameJournal.compact": "journal rewrite+fsync",
    # broker generation file: fsync + atomic replace on promotion/fence
    "runtime/netbus.py::BrokerGeneration._persist":
        "broker generation fsync+replace",
}

# Rule 3a (cancellation-atomicity) commit sections: between the ``begin``
# call and the ``end`` call inside the registered function there must be
# NO ``await`` — a cancellation delivered at an await point would split
# the pair (double-publish on resume, stranded rows, phantom cursor).
# ``begin``/``end`` match the called name/attribute exactly.
COMMIT_SECTIONS: Dict[str, List[Dict[str, str]]] = {
    "pipeline/replay.py": [
        {
            "function": "ReplayEngine._pump_loop",
            "name": "replay publish → cursor commit",
            "begin": "publish",
            "end": "_persist",
        },
    ],
    "pipeline/inference.py": [
        {
            "function": "TpuInferenceService._resolve_flush",
            "name": "reap-registry pop → gauge publish → permit release",
            "begin": "popleft",
            "end": "release",
        },
        {
            # page-out atomicity: the host copy of the slot's weights,
            # the slot wipe, the placement ghosting, and the byte-cache
            # commit must land as one step — an await in between lets a
            # flush (or a cancellation) observe a half-freed slot whose
            # only weight copy is neither on device nor committed
            "function": "TpuInferenceService._page_out",
            "name": "evict (host copy → slot wipe → cache commit)",
            "begin": "host_copy_params",
            "end": "commit_page_out",
        },
    ],
    "runtime/bus.py": [
        {
            "function": "RetryingConsumer.dead_letter",
            "name": "DLQ move (publish → enqueued accounting)",
            "begin": "publish_nowait",
            "end": "inc",
        },
    ],
    "storage/segstore.py": [
        {
            "function": "SegmentColumns.maintain",
            "name": "manifest commit → doomed-file delete",
            "begin": "_commit_manifest",
            "end": "unlink",
        },
    ],
    "runtime/hostlease.py": [
        {
            # lease-commit → adoption: the SUSPECT mark, the placement
            # moves, and the adoption counters must land as one step —
            # an await between them lets a cancellation strand tenants
            # half-moved (fenced at the broker but never adopted)
            "function": "HostSupervisor._commit_adoption",
            "name": "host suspect mark → tenant adoption bookkeeping",
            "begin": "mark_suspect",
            "end": "inc",
        },
        {
            # epoch-bump → fence-lift: the cross-host fences release
            # together with their counter, only after the adopter
            # confirmed (the epoch bump already happened at the broker)
            "function": "HostSupervisor._commit_fence_lift",
            "name": "cross-host fence lift → accounting",
            "begin": "lift_fences",
            "end": "inc",
        },
    ],
    "runtime/netbus.py": [
        {
            # standby → primary takeover: durable generation bump, role
            # flip, and lease grace extension must land as one step — a
            # cancellation between them yields a primary serving
            # un-graced leases (mass host expiry) or a standby whose
            # generation already outranks the fleet
            "function": "BusBrokerServer._commit_promotion",
            "name": "promotion (generation bump → role flip → lease grace)",
            "begin": "bump_to",
            "end": "inc",
        },
        {
            # zombie self-fence: the durable fence and its counter land
            # together, so a fenced broker is never un-counted (or a
            # counted broker un-fenced) across a cancellation
            "function": "BusBrokerServer._commit_fence_generation",
            "name": "generation fence → accounting",
            "begin": "fence",
            "end": "inc",
        },
        {
            # replication batch apply: records apply in ring order and
            # the applied-seq watermark moves with them — an await in
            # between lets a cancellation strand the watermark past
            # records that never applied (silent standby divergence)
            "function": "StandbyReplicator._commit_records",
            "name": "replication apply → watermark advance",
            "begin": "_apply_record",
            "end": "inc",
        },
        {
            # snapshot resync: logs, cursors, lease table, and the
            # watermark move to the snapshot as ONE unit
            "function": "StandbyReplicator._commit_snapshot",
            "name": "resync snapshot apply → watermark reset",
            "begin": "restore_state",
            "end": "inc",
        },
    ],
    "api/rest.py": [
        {
            # DLQ → source-topic move: republish and requeue accounting
            # land together, so a client disconnect cancelling the
            # requeue request (or a broker restart racing it) cannot
            # strand an entry between "taken from the DLQ poll" and
            # "counted as requeued"
            "function": "RestApi._commit_requeue",
            "name": "DLQ requeue move (republish → accounting)",
            "begin": "publish_nowait",
            "end": "inc",
        },
    ],
}

# Rule 3b: tracked decrement sites that must pair their increment in a
# ``finally`` (or the in-flight count / permit leaks on any raise or
# cancellation path). ``op`` is a called attribute name ("release") or
# an aug-assign attribute ("_decode_inflight" for ``self.x -= n``).
COUNTER_PAIRS: Dict[str, List[Dict[str, str]]] = {
    "pipeline/inference.py": [
        {
            "function": "TpuInferenceService._resolve_flush",
            "name": "per-slice in-flight permit",
            "op": "release",
            "kind": "call",
        },
    ],
    "pipeline/media.py": [
        {
            "function": "MediaClassificationPipeline._classify_and_publish",
            "name": "classify in-flight permit",
            "op": "release",
            "kind": "call",
        },
        {
            "function": "MediaClassificationPipeline._classify_compressed",
            "name": "classify in-flight permit",
            "op": "release",
            "kind": "call",
        },
        {
            "function": "MediaClassificationPipeline._pool_map",
            "name": "decode-pool in-flight count",
            "op": "_decode_inflight",
            "kind": "augassign",
        },
    ],
}

# Rule 5 (cross-thread-mutation) scope: per class, the functions that
# run ON the executor pools vs the loop-side functions that share the
# instance. Attributes both sides mutate must be protected by one of
# the named locks (``with self.<lock>``) on BOTH sides. Registry-scoped
# to stay tractable: these are the classes that actually split work
# across the deliver/decode pools.
THREAD_SHARED: Dict[str, List[Dict[str, object]]] = {
    "pipeline/media.py": [
        {
            "class": "MediaClassificationPipeline",
            "executor_fns": [
                "MediaClassificationPipeline._pool_map",
                "MediaClassificationPipeline._decode_batch",
            ],
            "loop_fns": [
                "MediaClassificationPipeline._run",
                "MediaClassificationPipeline.submit_chunk",
                "MediaClassificationPipeline._classify_and_publish",
                "MediaClassificationPipeline._classify_compressed",
                "MediaClassificationPipeline._finish_classify",
            ],
            "locks": ["_decode_lock", "_pool_lock"],
        },
    ],
    "pipeline/slices.py": [
        {
            "class": "_PendingFlush",
            "executor_fns": ["_PendingFlush._materialize"],
            "loop_fns": [
                "_PendingFlush.landed",
                "_PendingFlush.overdue",
                "_PendingFlush.ensure_host_future",
            ],
            "locks": [],
        },
    ],
}


# ---------------------------------------------------------------------
# cross-registry staleness: the per-tool registries above are keyed by
# module path + function; lint_all asserts every referenced module
# exists via the owning tool's stale checks. This map names which tool
# owns which registry so docs and findings can say so.
REGISTRY_OWNERS: Dict[str, str] = {
    "HOT_PATHS": "check_hotpath",
    "QUEUE_REGISTRY": "check_queues",
    "SUPERVISED_PATHS": "check_supervised",
    "FUSION_REGISTRY": "check_fusion",
    "TRAIN_REGISTRY": "check_fusion",
    "DCT_REGISTRY": "check_fusion",
    "ASYNC_ROOT_DIRS": "check_async",
    "BLOCKING_LEAVES": "check_async",
    "COMMIT_SECTIONS": "check_async",
    "COUNTER_PAIRS": "check_async",
    "THREAD_SHARED": "check_async",
}
