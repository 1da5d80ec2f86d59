"""Stacked multi-tenant scoring under ``shard_map`` — the SPMD hot path.

The 32-tenant concurrent-scoring config (BASELINE.json:10) runs here. Layout
(one model family per stack; SURVEY.md §7 "tenants-on-mesh"):

- params:  every leaf gains a leading stacked-tenant dim ``[T, ...]``,
  sharded along the mesh ``tenant`` axis (T = n_tenant_shards ×
  slots_per_shard).
- window state: logically ``[T, S, W]`` (``ops.windows`` owns the physical,
  lane-dense shape) — T over ``tenant``, stream capacity S over ``data``
  (each data shard owns a disjoint set of streams, so window
  updates never race across shards and the hot path needs **zero
  collectives**: pure SPMD fan-out, ICI stays free for training traffic).
- batches: ``[T, B]`` with B over ``data``; the micro-batcher routes each
  stream to its owning (tenant-slot, data-shard) lane and uses *local*
  stream ids, so device code never translates indices.
- active mask ``[T]``: tenants start/stop by flipping a mask bit — no
  recompile (SURVEY.md §7 hard parts: "handle tenant start/stop without
  recompiling the world").

``shard_map`` + vmap-over-slots is the whole trick: each device scores its
resident tenants' events against its resident window state.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from sitewhere_tpu.models import ModelSpec
from sitewhere_tpu.models.common import (
    DEFAULT_SCORE_RANGE,
    PARAM_DTYPES,
    SKETCH_NBINS,
    clamp_fuse_k,
    quantize_params,
    sketch_edges,
)
from sitewhere_tpu.ops.windows import (
    WindowState,
    gather_windows,
    init_window_state,
    ring_values,
    update_and_gather,
    update_gather_ranked,
    update_windows,
)
from sitewhere_tpu.parallel.mesh import AXIS_DATA, AXIS_TENANT, MeshManager
from sitewhere_tpu.parallel.streamstate import StreamPrograms, plan

Params = Any

# Fused megabatch kernels kill switch (mirrors core.batch.WIRE_CODEC_ENABLED):
# flip to False BEFORE scorer construction to build the legacy
# vmap-over-slots step — bit for bit the pre-fusion path (fuse_k/param_dtype
# are ignored there: single-step scores, full-width f32 master weights).
# The rollback knob for a numerics incident in production.
FUSED_STEP_ENABLED = True

# Device-side score sketch kill switch (same pattern): flip to False
# BEFORE scorer construction to build steps that emit no per-slot score
# histogram — the rollback knob if the sketch's segment_sum ever shows up
# in a device profile, and the bench's control twin for measuring the
# sketch's step-time overhead (``scorehealth_pct``).
SCORE_SKETCH_ENABLED = True

# Continual-learning train lane kill switch (same pattern): flip to
# False BEFORE scorer construction to disable the fused stacked train
# step AND the service's async train lane — training then runs the
# pre-lane path bitwise: the legacy per-slot vmap ``_build_train_step``
# dispatched INLINE from the scoring loop every ``every_n_flushes``
# (docs/PERFORMANCE.md "Continual learning lane" → rollback).
TRAIN_LANE_ENABLED = True

# After a param hot-swap (``activate(params=...)``) an armed canary
# shadow-scores its configured fraction of the next this-many flushes, so
# freshly swapped weights get immediate divergence coverage (see
# ``canary_take`` / docs/OBSERVABILITY.md "Score health & canaries").
CANARY_SWAP_FLUSHES = 64


def stack_params(params_list: List[Params]) -> Params:
    """[pytree, ...] → pytree with leading stacked-tenant dim."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def unstack_slot(stacked: Params, idx: int) -> Params:
    return jax.tree_util.tree_map(lambda x: x[idx], stacked)


def set_slot(stacked: Params, idx: int, params: Params) -> Params:
    """Write one tenant's params into its slot (donate under jit for
    in-place HBM update — how tenant hot-swap avoids recompiles)."""
    return jax.tree_util.tree_map(
        lambda s, p: s.at[idx].set(p.astype(s.dtype)), stacked, params
    )


def init_stacked_state(
    n_slots: int, max_streams: int, window: int, data_shards: int = 1
) -> WindowState:
    """Stacked window state, slot-major: every leaf of
    ``init_window_state`` with a leading [T]. S is the *global* stream
    capacity; the stream axis (leaf axis 1) is split ``data_shards``
    ways inside shard_map, each shard owning whole rows of the store.
    Traceable: ``ShardedScorer._fresh_state`` runs it under ``jit`` with
    the store's sharding as ``out_shardings``, so the store is written
    once where it lives — outside a jit each leaf is a one-slot array
    AND its stacked copy until the former is dropped."""
    st = init_window_state(max_streams, window, shards=data_shards)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_slots,) + x.shape).copy(), st
    )


def _zero_slot(state, idx: int):
    """One slot's stream state (rings, cursors and counts, or a stateful
    family's store) back to empty."""
    return jax.tree_util.tree_map(lambda x: x.at[idx].set(0), state)


# in place: without donation a reset holds the whole store twice
_zero_slot_donated = jax.jit(_zero_slot, static_argnums=1, donate_argnums=0)


class ShardedScorer:
    """Compiled multi-tenant scoring step over the mesh.

    One instance per model family. Host-side state (params, windows) lives
    as sharded jax.Arrays owned by this object; ``step`` is the only device
    round-trip on the hot path.
    """

    def __init__(
        self,
        mm: MeshManager,
        spec: ModelSpec,
        cfg,
        slots_per_shard: int = 8,
        max_streams: int = 4096,
        window: int = 32,
        seed: int = 0,
        wire_dtype: str = "f32",
        fuse_k: int = 1,
        param_dtype: str = "f32",
    ) -> None:
        # two kinds of stream state behind one owner: a window of raw
        # values every flush re-scans (``spec.score``), or a state the
        # family advances one step an event (``spec.advance`` —
        # parallel.streamstate)
        self.stateful = getattr(spec, "advance", None) is not None
        if spec.score is None and not self.stateful:
            raise ValueError(f"model '{spec.name}' has no scorer contract")
        if self.stateful and mm.mesh.devices.size != 1:
            raise ValueError(
                f"model '{spec.name}' keeps a recurrent stream state: its "
                f"slice is one device (got a mesh of "
                f"{mm.mesh.devices.size}); the exchange between chips "
                f"that share a layer is not built"
            )
        self.mm = mm
        self.spec = spec
        self.cfg = cfg
        # -- fused megabatch kernels (docs/PERFORMANCE.md "Fused tenant
        # kernels"): slot axis folded into the gate contractions via the
        # family's score_stacked entry point. Captured at BUILD time so
        # FUSED_STEP_ENABLED=False reconstructs the legacy path exactly.
        if param_dtype not in PARAM_DTYPES:
            raise ValueError(
                f"param_dtype must be one of {PARAM_DTYPES}, got "
                f"{param_dtype!r}"
            )
        if int(fuse_k) < 1:
            raise ValueError(f"fuse_k must be >= 1, got {fuse_k}")
        self.fused = bool(FUSED_STEP_ENABLED and spec.score_stacked is not None)
        self.fuse_k = int(fuse_k)
        # effective knobs: the legacy path ignores both (pre-fusion
        # semantics — newest-position scores off f32 master weights)
        self.k_steps = clamp_fuse_k(self.fuse_k, window) if self.fused else 1
        self.requested_param_dtype = param_dtype  # family-pin conflict checks
        self.param_dtype = param_dtype if self.fused else "f32"
        self._kernel_params = None   # quantized sidecar (lazy; see below)
        self._kernel_dirty = True
        self._quantize_jit = None
        # -- device-side score sketch (score-quality observability) ------
        # per-slot fixed-bin score histogram emitted by the jitted step
        # (both fused and legacy branches) and materialized by the result
        # reaper; edges are log-spaced over the family's declared score
        # range. Captured at BUILD time like the fused kill switch.
        # -- continual-learning train lane (captured at BUILD time like
        # the fused kill switch): the fused stacked train step + the
        # replay-fed feed state only exist when the family has a
        # loss_stacked contract AND the scorer runs the fused path —
        # the lane's grads must lower through the SAME stacked einsums
        # as scoring, or the MXU win evaporates. False ⇒ the service
        # keeps the inline every_n_flushes path bitwise.
        self.train_lane = bool(
            TRAIN_LANE_ENABLED
            and self.fused
            and getattr(spec, "loss_stacked", None) is not None
        )
        self._train_fused = None       # built by init_optimizer
        self._train_feed_state = None  # replay-fed windows (lazy)
        self._ingest = None            # counts-mode feed scatter jit
        self.sketch = bool(SCORE_SKETCH_ENABLED)
        self.nbins = SKETCH_NBINS
        lo, hi = getattr(spec, "score_range", DEFAULT_SCORE_RANGE)
        self.sketch_edges = sketch_edges(lo, hi, self.nbins)
        self.last_sketch = None  # the latest dispatch's i32[T, D, NBINS]
        # -- shadow-scoring canary (previous-variant divergence) ---------
        # fraction of flushes shadow-scored with the legacy f32 step while
        # a canary condition holds (non-f32 / K>1 variant, or a recent
        # hot-swap); set by the service from TenantEngineConfig.canary_frac
        self.canary_frac = 0.0
        self._canary_tick = 0
        self._canary_countdown = 0
        self._shadow_step_fn = None  # built lazily / at prewarm
        self.slots_per_shard = slots_per_shard
        self.n_slots = mm.n_tenant_shards * slots_per_shard
        if max_streams % mm.n_data_shards:
            raise ValueError(
                f"max_streams {max_streams} must divide across "
                f"{mm.n_data_shards} data shards"
            )
        self.max_streams = max_streams
        self.window = window
        # -- wire format for step_counts (the host↔device byte diet) ------
        # Host↔device bandwidth is a real budget: stream ids ship as u16
        # when the per-shard capacity fits, values/scores ship as bf16/f16
        # when the tenant opts in, and the bool valid-mask is replaced by
        # one i32 count per (slot, data-shard) lane — 6 bytes per event
        # instead of 36 at slots_per_shard=4.
        import ml_dtypes as _mld
        import numpy as _np
        if wire_dtype not in ("f32", "bf16", "f16"):
            raise ValueError(f"wire_dtype must be f32|bf16|f16, got {wire_dtype}")
        self.wire_dtype = wire_dtype
        local_cap = max_streams // mm.n_data_shards
        self.ids_np_dtype = _np.uint16 if local_cap <= 65536 else _np.int32
        self.vals_np_dtype = {
            "f32": _np.float32, "bf16": _mld.bfloat16, "f16": _np.float16,
        }[wire_dtype]

        # identical init per slot; per-tenant training diverges them later
        key = jax.random.PRNGKey(seed)
        self._base_key = key
        if self.stateful:
            # a chip-filling model: the stack is made in place and no
            # pristine second copy is kept (``_pristine_params`` makes
            # it again from the key when a slot is recycled)
            self._base_params = None
            stacked = self._init_stacked()
        else:
            base = spec.init(key, cfg)
            self._base_params = base  # pristine copy for slot recycling
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    x[None], (self.n_slots,) + x.shape
                ).copy(),
                base,
            )
        t_shard = mm.tenant_stacked()
        # param placement by PARTITION RULES (parallel.partition — the
        # SNIPPETS [2][3] match_partition_rules pattern): leaf paths map
        # to PartitionSpecs, the stacked slot dim rides the tenant axis,
        # and big dense kernels offer their output dim to the model axis
        # when it exists. On model=1 meshes every spec degenerates to
        # P(tenant) — bit-compatible with the blanket stacked placement.
        from sitewhere_tpu.parallel.partition import (
            DEFAULT_RULES,
            make_shard_and_gather_fns,
            shard_tree,
            stacked_specs,
        )

        self.partition_rules = getattr(spec, "partition_rules", None) or (
            DEFAULT_RULES
        )
        self.param_specs = stacked_specs(
            self.partition_rules, stacked, mm.mesh
        )
        self._param_shard_fns, self._param_gather_fns = (
            make_shard_and_gather_fns(mm.mesh, self.param_specs)
        )
        self.params = shard_tree(stacked, self._param_shard_fns)
        # the compiled step consumes kernel_params(): for quantized
        # variants that tree has a DIFFERENT structure (qw/scale sidecar
        # nodes), so its in_specs come from a shape-only template of the
        # quantized tree — same rules, matched against the sidecar paths
        if self.fused and self.param_dtype != "f32":
            _pd = self.param_dtype
            kernel_template = jax.eval_shape(
                lambda p: quantize_params(p, _pd), stacked
            )
            self.step_param_specs = stacked_specs(
                self.partition_rules, kernel_template, mm.mesh
            )
        else:
            self.step_param_specs = self.param_specs
        self.state = self._fresh_state()
        self.active = jax.device_put(
            jnp.zeros((self.n_slots,), bool), t_shard
        )
        # which slots may TRAIN (tenants opt in via TrainingConfig): slots
        # sharing the stack with training disabled score normally but are
        # masked out of train_resident's gradient step
        self.train_mask = jax.device_put(
            jnp.zeros((self.n_slots,), bool), t_shard
        )
        # per-slot learning rate: tenants sharing a family stack keep
        # their OWN lr (the optimizer is scale_by_adam; the lr multiplies
        # the transformed update per slot inside the train step)
        self.slot_lr = jax.device_put(
            jnp.ones((self.n_slots,), jnp.float32), t_shard
        )
        if self.stateful:
            self._step = self._step_counts = None
            self.programs = StreamPrograms(
                spec, cfg, self.n_slots, max_streams,
                {"f32": jnp.float32, "bf16": jnp.bfloat16,
                 "f16": jnp.float16}[wire_dtype],
                self.sketch_edges,
            )
            # the plan of the flush ``stage_inputs`` saw last, keyed by
            # the staged ids it returned; and the last step's counters
            # (device i32[3], host dict) for the service to publish
            self._staged_plan = None
            self.last_stats = None
        else:
            self._step = self._build_step()
            self._step_counts = self._build_step(counts_mode=True)
        # input shardings for the counts wire (ids/vals [T, D*B], counts
        # [T, D] — both tenant×data): stage_inputs puts flush buffers onto
        # these so the jit never reshards and the h2d copy can overlap a
        # previous flush's dispatch
        self._wire_sharding = mm.sharding(AXIS_TENANT, AXIS_DATA)
        # lazy per-slot (unstacked) shard fns for weight paging's
        # stage_slot_params — most scorers never page and must not pay
        self._slot_shard_fns = None

    def _init_stacked(self) -> Params:
        """Every slot's weights as first initialised, made as one stack
        (no unstacked original beside it)."""
        spec, cfg = self.spec, self.cfg
        return jax.jit(jax.vmap(lambda k: spec.init(k, cfg)))(
            jnp.stack([self._base_key] * self.n_slots)
        )

    def _fresh_state(self):
        """The empty stream state on the mesh, written once where it
        lives (jit + ``out_shardings``: no unstacked original, no
        default-device copy beside the placed one): slots over the
        tenant axis, streams over the data axis."""
        if self.stateful:
            spec, cfg, s, t = (
                self.spec, self.cfg, self.max_streams, self.n_slots)

            def make():
                return jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (t,) + x.shape),
                    spec.init_state(cfg, s),
                )
        else:
            def make():
                return init_stacked_state(
                    self.n_slots, self.max_streams, self.window,
                    self.mm.n_data_shards,
                )
        return jax.jit(
            make, out_shardings=self.mm.sharding(AXIS_TENANT, AXIS_DATA)
        )()

    @property
    def state_nbytes(self) -> int:
        """Bytes of stream state provisioned on the mesh."""
        return int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.state)
        ))

    def ring_values(self) -> jnp.ndarray:
        """The serve state's logical rings f32[T, S, W] (ring order)."""
        return ring_values(self.state, self.mm.n_data_shards)

    # -- fused kernel param view -----------------------------------------
    def _invalidate_kernel(self) -> None:
        """Mark the quantized sidecar stale — call after ANY mutation of
        ``self.params`` (activate/set_slot/reset/train/rebuild) so the
        next flush scores against the tenant's current weights."""
        self._kernel_dirty = True

    def kernel_params(self) -> Params:
        """The param tree the compiled step consumes. ``f32`` (or the
        legacy path) reads the master stack directly; ``bf16``/``int8``
        read a lazily re-derived quantized sidecar (per-slot per-channel
        scales — models.common.quantize_params). Deriving is one jitted
        elementwise tree-map dispatched asynchronously, so a post-train
        refresh rides the device queue like any other dispatch; the
        master f32 params stay the single source of truth for training,
        checkpointing, and slot swaps."""
        if not self.fused or self.param_dtype == "f32":
            return self.params
        if self._kernel_dirty or self._kernel_params is None:
            if self._quantize_jit is None:
                pd = self.param_dtype
                self._quantize_jit = jax.jit(
                    lambda p: quantize_params(p, pd)
                )
            self._kernel_params = self._quantize_jit(self.params)
            self._kernel_dirty = False
        return self._kernel_params

    # -- h2d staging (double-buffered feed path) -------------------------
    def stage_inputs(self, stream_ids, values, counts):
        """Asynchronously stage one flush's wire buffers onto the step's
        input shardings. ``jax.device_put`` returns immediately with the
        transfer in flight, so the caller can issue flush N+1's copy while
        flush N's dispatch is still executing — transfer overlaps compute.
        The HOST buffers must stay unmodified until the returned arrays
        are ready (the service rotates staging buffers to guarantee it).
        Returns (ids, vals, counts) device arrays for ``step_counts``."""
        s = self._wire_sharding
        staged = jax.device_put((stream_ids, values, counts), (s, s, s))
        if self.stateful:
            # which program each row rides is decided here, where the
            # flush is still host memory (its own copies: the staging
            # buffers are reused)
            self._staged_plan = (staged[0], self._plan(
                stream_ids, values, counts))
        return staged

    def _plan(self, stream_ids, values, counts):
        import numpy as _np

        return plan(
            _np.asarray(stream_ids), self.programs.tokens(values),
            _np.asarray(counts).sum(axis=1), self.programs.chunk,
            self.max_streams,
        )

    def _stream_step(self, stream_ids, values, counts) -> jnp.ndarray:
        """A stateful family's flush: the staged plan's calls, in order,
        through the one-step and chunked programs."""
        staged, self._staged_plan = self._staged_plan, None
        if staged is not None and staged[0] is stream_ids:
            calls, host_stats = staged[1]
        else:  # not staged through ``stage_inputs`` (tests, a probe)
            calls, host_stats = self._plan(stream_ids, values, counts)
        self.state, scores, hist, dev_stats = self.programs.run(
            self.params, self.state, calls, stream_ids.shape[1])
        if self.sketch:
            self.last_sketch = hist
        # the counters ride back beside the scores: by the time the
        # flush lands the service reads them without a wait
        dev_stats.copy_to_host_async()
        self.last_stats = (dev_stats, host_stats)
        return scores

    @staticmethod
    def stage_nbytes(staged) -> int:
        """Host→device bytes one staged flush moves (feed observability)."""
        return int(sum(a.nbytes for a in staged))

    # -- device-time / MFU attribution -----------------------------------
    def flops_per_row(self, b_lane: int = 0) -> float:
        """Analytic matmul FLOPs the device executes per lane row of one
        scoring step (``models.common`` — the family's declared
        ``flops_per_row`` at this scorer's window). ``b_lane`` rides the
        contract for future bucket-dependent models; the window-scan
        models here are bucket-independent."""
        fn = getattr(self.spec, "flops_per_row", None)
        if fn is None:
            return 0.0
        if self.fused:
            # the fused kernel's honest count: heads apply to the last
            # k_steps positions only, and quantized weight matmuls count
            # at their real MAC width (models.common.QUANT_MAC_WIDTH)
            return float(fn(
                self.cfg, self.window,
                k=self.k_steps, param_dtype=self.param_dtype,
            ))
        return float(fn(self.cfg, self.window))

    def flops_per_flush(self, b_lane: int) -> float:
        """FLOPs one flush at lane bucket ``b_lane`` executes: the FULL
        padded plane (every slot × data-shard × lane row runs through the
        model, valid or not) × per-row flops. This is what feeds
        ``tpu_flops_total{family}`` — executed work, the honest MFU
        numerator for a padded-static-shape engine."""
        plane_rows = self.n_slots * self.mm.n_data_shards * int(b_lane)
        return plane_rows * self.flops_per_row(b_lane)

    @property
    def device_label(self) -> str:
        """Metric label for the device that anchors this scorer's result
        path (the gather consolidation target — mesh device 0). Per-flush
        device attribution on a multi-device mesh stamps this; finer
        per-shard attribution arrives with the mesh promotion (ROADMAP
        item 1)."""
        d = self.mm.mesh.devices.flat[0]
        return f"{d.platform}:{d.id}"

    # -- d2h result path (device-side row gather) ------------------------
    # smallest compiled gather size: flushes smaller than this pad up to
    # it (a few KB of d2h — noise), and the ladder stays short enough to
    # prewarm every size per bucket
    GATHER_FLOOR = 2048

    def gather_ladder(self, b_lane: int) -> List[int]:
        """Padded gather output sizes compiled for one bucket's score
        plane: powers of two from GATHER_FLOOR up to the full plane.
        A flush's d2h volume is the smallest rung ≥ its row count, so
        padding waste is < 2× while the compile count stays O(log).
        Cached per bucket — gather_rows runs per flush, and the ladder
        is fixed by (n_slots, data shards, b_lane)."""
        ladders = getattr(self, "_ladders", None)
        if ladders is None:
            ladders = self._ladders = {}
        cached = ladders.get(b_lane)
        if cached is not None:
            return cached
        plane = self.n_slots * self.mm.n_data_shards * b_lane
        sizes: List[int] = []
        g = min(self.GATHER_FLOOR, plane)
        while g < plane:
            sizes.append(g)
            g *= 2
        sizes.append(plane)
        ladders[b_lane] = sizes
        return sizes

    def _gather_fn(self) -> Callable:
        if getattr(self, "_gather", None) is None:
            # "sw/gather" names the program's operations in a profile
            # whatever XLA calls the module (today ``jit_gather``)
            @jax.named_scope("sw/gather")
            def gather(scores, counts, size):
                # scores [T, D*B] wire dtype, counts i32[T, D]; the valid
                # rows are front-contiguous per (slot, data-shard) lane,
                # so their COMPACTION indices are derivable on device —
                # no index upload, the counts wire already crossed h2d.
                # Output order is (slot, data-shard, lane position): the
                # flush packs its host-side seqs/rows bookkeeping in the
                # same sorted order (see _flush_slice).
                t, l = scores.shape
                d = counts.shape[1]
                b = l // d
                lanepos = jnp.arange(b, dtype=jnp.int32)
                valid = (
                    lanepos[None, None, :] < counts[:, :, None]
                ).reshape(-1)
                pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
                idx = jnp.where(valid, pos, size)  # pads scatter-drop
                out = jnp.full((size,), jnp.nan, scores.dtype)
                return out.at[idx].set(scores.reshape(-1), mode="drop")

            self._gather = jax.jit(gather, static_argnums=2)
        return self._gather

    def gather_rows(self, scores_dev, counts_dev, n_rows: int):
        """Compact one flush's scored rows out of the [T, D*B] plane ON
        DEVICE: returns a wire-dtype device vector of the smallest ladder
        size ≥ ``n_rows`` (entries past ``n_rows`` are NaN padding).
        This is what makes d2h volume rows-proportional instead of
        tenant-count-proportional — the caller materializes rows×2 bytes
        per flush, never the T×lane score plane."""
        t, l = scores_dev.shape
        b_lane = l // self.mm.n_data_shards
        size = next(
            (s for s in self.gather_ladder(b_lane) if s >= n_rows), t * l
        )
        if self.mm.mesh.devices.size > 1:
            # consolidate onto one device BEFORE the jitted compaction:
            # the cumsum/scatter crosses shards, and letting GSPMD emit
            # an AllGather gang-schedules a rendezvous across every
            # device per flush — on the CPU backend (8 virtual devices
            # on few cores) concurrent flush dispatches deadlock that
            # rendezvous, and on a pod it serializes the mesh. A
            # device_put is point-to-point (d2d/ICI, no rendezvous),
            # rides the same async dispatch, and the single-chip
            # production mesh skips it entirely.
            dev = self.mm.mesh.devices.flat[0]
            scores_dev, counts_dev = jax.device_put(
                (scores_dev, counts_dev), dev
            )
        return self._gather_fn()(scores_dev, counts_dev, size)

    # -- compiled step ---------------------------------------------------
    def _build_step(
        self, counts_mode: bool = False, shadow: bool = False
    ) -> Callable:
        """The scoring jit. Variants sharing this builder:

        - mask mode (``step``): per-row bool valid mask, f32 wire — the
          fully general path (tests, arbitrary row patterns).
        - counts mode (``step_counts``): rows are front-contiguous per
          (slot, data-shard) lane, so validity is ONE i32 count per lane,
          derived on device; ids/values arrive in the thin wire dtypes and
          scores return in the wire dtype. The service hot path uses this.
        - ``shadow``: the canary's reference step — FORCES the legacy
          vmap branch (f32 master weights, single-step scores: exactly
          what the FUSED_STEP_ENABLED kill switch would restore), does
          NOT donate the window state (its state output is discarded —
          the primary step dispatched after it owns the commit), and
          emits no sketch. Dispatch order guarantees the shadow reads
          the pre-flush windows the primary is about to consume.

        Unless ``shadow`` (or the SCORE_SKETCH_ENABLED kill switch is
        off), the step also emits the per-slot score sketch: an
        ``i32[T, D, NBINS]`` fixed-bin histogram of the masked scores,
        accumulated with one ``segment_sum`` over the local score plane
        per data shard — zero collectives; the host merges the D partials
        (a 64-int add per slot). NaN scores are excluded on device (the
        resolve path counts them separately).
        """
        mesh = self.mm.mesh
        spec, cfg = self.spec, self.cfg
        fused = self.fused and not shadow
        k_steps = self.k_steps if not shadow else 1
        emit_sketch = self.sketch and not shadow
        nbins = self.nbins
        edges = jnp.asarray(self.sketch_edges)
        score_dtype = (
            {"f32": jnp.float32, "bf16": jnp.bfloat16, "f16": jnp.float16}[
                self.wire_dtype
            ]
            if counts_mode
            else jnp.float32
        )

        def sketch_of(s, valid):
            # s [T_loc, B_loc] scores, valid bool[T_loc, B_loc]: per-slot
            # histogram via ONE segment_sum over the masked plane. Bin =
            # searchsorted side='right' (np.histogram's left-closed bins);
            # invalid/NaN rows map to the dropped overflow segment.
            t = s.shape[0]
            sf = s.astype(jnp.float32)
            b = jnp.searchsorted(edges, sf, side="right").astype(jnp.int32)
            b = jnp.where(valid & ~jnp.isnan(sf), b, nbins)
            flat = (
                jnp.arange(t, dtype=jnp.int32)[:, None] * (nbins + 1) + b
            ).reshape(-1)
            hist = jax.ops.segment_sum(
                jnp.ones_like(flat), flat, num_segments=t * (nbins + 1)
            )
            return hist.reshape(t, nbins + 1)[:, :nbins]

        # "sw/step" names the step's operations in a profile whatever
        # XLA calls the module (today ``jit_local_step``)
        @jax.named_scope("sw/step")
        def local_step(params, state, active, ids, vals, validity):
            # local shapes: params [T_loc, ...], state T_loc x S_loc rings,
            # ids/vals [T_loc, B_loc]; validity is bool[T_loc, B_loc]
            # (mask mode) or i32[T_loc, 1] lane counts (counts mode)
            if counts_mode:
                m = (
                    jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
                    < validity
                )
            else:
                m = validity
            if not fused:
                def one(p, st, act, i, v, m1):
                    i = i.astype(jnp.int32)
                    v = v.astype(jnp.float32)
                    st2, w, n = update_and_gather(st, i, v, m1)
                    s1 = spec.score(p, cfg, w, n)
                    return st2, jnp.where(act & m1, s1, 0.0).astype(
                        score_dtype
                    )

                st2, s = jax.vmap(one)(params, state, active, ids, vals, m)
            else:
                # fused megabatch path: the window scatter/gather (memory
                # ops, no matmuls) stays vmapped per slot, but scoring
                # runs ONE weight-stacked kernel over the whole
                # [T_loc, B_loc] tenant plane (spec.score_stacked — a
                # single wide einsum per gate contraction instead of
                # T_loc small matmuls)
                def upd(st, i, v, m1):
                    i = i.astype(jnp.int32)
                    v = v.astype(jnp.float32)
                    st2, w, n, later = update_gather_ranked(st, i, v, m1)
                    return st2, w, n, later

                st2, w, n, later = jax.vmap(upd)(state, ids, vals, m)
                sk = spec.score_stacked(params, cfg, w, n, k=k_steps)
                if k_steps > 1:
                    # per-row timestep resolution: a row with ``later``
                    # valid same-stream rows after it in this flush sits
                    # at window position W-1-later, i.e. K-step column
                    # K-1-later; rows older than the K window take the
                    # oldest column
                    idx = jnp.clip(k_steps - 1 - later, 0, k_steps - 1)
                    s = jnp.take_along_axis(sk, idx[..., None], axis=-1)[
                        ..., 0
                    ]
                else:
                    s = sk[..., 0]
                s = jnp.where(active[:, None] & m, s, 0.0).astype(
                    score_dtype
                )
            if emit_sketch:
                hist = sketch_of(s, active[:, None] & m)
                return st2, s, hist[:, None, :]
            return st2, s

        out_specs = [
            P(AXIS_TENANT, AXIS_DATA),       # new state
            P(AXIS_TENANT, AXIS_DATA),       # scores
        ]
        if emit_sketch:
            # each data shard contributes its local partial histogram
            # along axis 1 — no cross-shard reduction on device
            out_specs.append(P(AXIS_TENANT, AXIS_DATA, None))
        # the primary step reads the (possibly quantized) kernel tree;
        # the shadow canary always reads the f32 MASTER tree
        p_specs = self.param_specs if shadow else self.step_param_specs
        smapped = shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                p_specs,                     # params (per-leaf rules)
                P(AXIS_TENANT, AXIS_DATA),   # window state (S over data)
                P(AXIS_TENANT),              # active mask
                P(AXIS_TENANT, AXIS_DATA),   # stream ids (B over data)
                P(AXIS_TENANT, AXIS_DATA),   # values
                P(AXIS_TENANT, AXIS_DATA),   # valid mask / lane counts
            ),
            out_specs=tuple(out_specs),
        )
        if shadow:
            return jax.jit(smapped)  # NO donation: state stays live
        return jax.jit(smapped, donate_argnums=(1,))

    def prewarm(self, lane_sizes) -> None:
        """Compile every bucketed batch shape up front (counts wire — the
        service hot path). A first-use compile inside the scoring loop
        blocks the event loop for seconds (tens of seconds on TPU) and
        torpedoes p99 — pay it at startup instead. Zero-count lanes leave
        window state untouched (scatter mode=drop)."""
        import numpy as _np

        t, d = self.n_slots, self.mm.n_data_shards
        if self.stateful:
            # both programs at every shape, on padding rows (the state
            # is left as it was), placed into every bucket's plane
            for b in sorted(set(int(x) for x in lane_sizes)):
                for slot in range(t):
                    warm = self.programs.warm_calls(slot)
                    # each shape as a flush's first call and as a later one
                    for calls in (warm, warm[::-1]):
                        self.state, s, hist, _st = self.programs.run(
                            self.params, self.state, calls, d * b)
                        _np.asarray(hist)
        for b in sorted(set(int(x) for x in lane_sizes)):
            ids = _np.zeros((t, d * b), self.ids_np_dtype)
            vals = _np.zeros((t, d * b), self.vals_np_dtype)
            counts = _np.zeros((t, d), _np.int32)
            # prewarm THROUGH the staging path: committed device arrays
            # and host numpy args hit different jit cache entries, and the
            # hot path always stages first
            ids, vals, counts = self.stage_inputs(ids, vals, counts)
            s = self.step_counts(ids, vals, counts)
            _np.asarray(s)
            # the result path's device-side gather: compile every ladder
            # size for this bucket's plane — a mid-loop gather compile
            # would stall the pipeline exactly like a step compile
            for g in self.gather_ladder(b):
                _np.asarray(self.gather_rows(s, counts, g))
            if self.last_sketch is not None:
                # the sketch rides the same executable; settle its output
                # so nothing compiles lazily later
                _np.asarray(self.last_sketch)
            if self.fused and self.canary_frac > 0:
                # canary-capable scorer: compile the shadow (legacy) step
                # + its gather sizes too — a hot-swap can arm the canary
                # at any time, and its first shadow flush must not pay a
                # mid-traffic compile
                sh = self.shadow_step_counts(ids, vals, counts)
                _np.asarray(sh)
                for g in self.gather_ladder(b):
                    _np.asarray(self.gather_rows(sh, counts, g))
            if t > 1:
                # the single-used-slot d2h slice the flush path takes
                # (see TpuInferenceService._flush_slice) — same rule:
                # never compile inside the scoring loop
                # int32 index: the flush path slices with np.unique of
                # int32 slot ids — dtype must match or it recompiles
                _np.asarray(s[_np.zeros((1,), _np.int32)])

    # chaos knob: >0 makes the next N step() calls raise (fault-injection
    # hook for the auto-failover path, like the bus FaultPlan)
    fault_steps: int = 0

    def step(
        self,
        stream_ids: jnp.ndarray,  # i32[T, B] LOCAL ids per data shard lane
        values: jnp.ndarray,      # f32[T, B]
        valid: jnp.ndarray,       # bool[T, B]
    ) -> jnp.ndarray:
        """Score one stacked micro-batch; returns f32[T, B] scores."""
        if self.fault_steps > 0:
            self.fault_steps -= 1
            raise RuntimeError("injected scorer fault (chaos)")
        if self.stateful:
            raise NotImplementedError(
                "a stateful family takes the counts wire (step_counts)")
        out = self._step(
            self.kernel_params(), self.state, self.active,
            stream_ids, values, valid,
        )
        if self.sketch:
            self.state, scores, self.last_sketch = out
        else:
            self.state, scores = out
        return scores

    def step_counts(
        self,
        stream_ids,  # ids_np_dtype[T, D*B] LOCAL ids, front-contiguous/lane
        values,      # vals_np_dtype[T, D*B]
        counts,      # i32[T, D] valid rows per (slot, data-shard) lane
    ) -> jnp.ndarray:
        """Wire-thin scoring step: validity is one count per lane (rows
        fill each lane from the front), so no bool mask crosses
        host→device and ids/values ride the compact wire dtypes. Returns
        scores in the wire dtype, f32[T, D*B]-shaped."""
        if self.fault_steps > 0:
            self.fault_steps -= 1
            raise RuntimeError("injected scorer fault (chaos)")
        if self.stateful:
            return self._stream_step(stream_ids, values, counts)
        out = self._step_counts(
            self.kernel_params(), self.state, self.active,
            stream_ids, values, counts,
        )
        if self.sketch:
            self.state, scores, self.last_sketch = out
        else:
            self.state, scores = out
        return scores

    # -- shadow-scoring canary -------------------------------------------
    def arm_canary(self) -> None:
        """A param hot-swap landed: shadow-score the configured fraction
        of the next CANARY_SWAP_FLUSHES flushes (no-op while
        ``canary_frac`` is 0 or the scorer runs the legacy path)."""
        self._canary_countdown = CANARY_SWAP_FLUSHES

    def canary_active(self) -> bool:
        """A canary condition holds: the stack scores through a variant
        the legacy step would not produce (quantized weights / K-step
        fusion) or a hot-swap recently landed."""
        if not self.fused or self.canary_frac <= 0 or self.spec.score is None:
            return False
        return (
            self.param_dtype != "f32"
            or self.k_steps > 1
            or self._canary_countdown > 0
        )

    def canary_take(self) -> bool:
        """Per-flush decision: True ⇔ this flush also shadow-scores.
        Deterministic stride at ``canary_frac`` (1.0 = every flush);
        the post-swap countdown burns down per flush while armed."""
        if not self.canary_active():
            return False
        if self._canary_countdown > 0:
            self._canary_countdown -= 1
        self._canary_tick += 1
        stride = max(1, int(round(1.0 / min(1.0, self.canary_frac))))
        return self._canary_tick % stride == 0

    def shadow_step_counts(self, stream_ids, values, counts):
        """Score one staged flush with the PREVIOUS variant: the legacy
        vmap step over the f32 MASTER params (exactly the program the
        FUSED_STEP_ENABLED kill switch would restore). Reads — never
        donates or commits — the window state, so it must dispatch
        BEFORE the primary ``step_counts`` consumes the same state
        buffer (dispatch order on one device queue guarantees the read
        sees the pre-flush windows). Returns the wire-dtype score plane;
        the caller gathers it with the same counts for pick-aligned
        comparison."""
        if self._shadow_step_fn is None:
            self._shadow_step_fn = self._build_step(
                counts_mode=True, shadow=True
            )
        _st, scores = self._shadow_step_fn(
            self.params, self.state, self.active,
            stream_ids, values, counts,
        )
        return scores

    def shadow_flops_per_flush(self, b_lane: int) -> float:
        """FLOPs one SHADOW flush executes (legacy full-width count over
        the padded plane). Attributed to ``tpu_shadow_flops_total`` —
        never to ``tpu_flops_total``/``tpu_mfu_pct``, which must reflect
        serving work only."""
        fn = getattr(self.spec, "flops_per_row", None)
        if fn is None:
            return 0.0
        plane = self.n_slots * self.mm.n_data_shards * int(b_lane)
        return plane * float(fn(self.cfg, self.window))

    # -- slot management -------------------------------------------------
    def activate(
        self,
        global_slot: int,
        params: Params = None,
        trainable: bool = True,
        lr: Optional[float] = None,
    ) -> None:
        if params is not None:
            self.params = jax.jit(set_slot, static_argnums=1, donate_argnums=0)(
                self.params, global_slot, params
            )
            self._invalidate_kernel()
            # a hot-swap landed: the canary (if configured) shadow-scores
            # the next window of flushes against the swapped weights
            self.arm_canary()
        self.active = self.active.at[global_slot].set(True)
        self.train_mask = self.train_mask.at[global_slot].set(trainable)
        if lr is not None:
            self.slot_lr = self.slot_lr.at[global_slot].set(lr)

    def deactivate(self, global_slot: int) -> None:
        self.active = self.active.at[global_slot].set(False)
        self.train_mask = self.train_mask.at[global_slot].set(False)

    def reset_slot(self, global_slot: int) -> None:
        """Wipe a slot's window state + params + optimizer moments back to
        pristine — a recycled slot must not leak the previous tenant's
        history, trained weights, or Adam momentum."""
        self.deactivate(global_slot)
        self.slot_lr = self.slot_lr.at[global_slot].set(1.0)
        if self._base_params is None and self.n_slots == 1:
            # a chip-filling model: two sets of weights do not fit, so
            # the one slot's stack is dropped before it is made again
            from sitewhere_tpu.parallel.partition import shard_tree

            self.params = None
            self.params = shard_tree(
                self._init_stacked(), self._param_shard_fns)
        else:
            self.params = set_slot(
                self.params, global_slot, self._pristine_params())
        self._invalidate_kernel()
        self.state = _zero_slot_donated(self.state, global_slot)
        if getattr(self, "_opt_state", None) is not None:
            self._opt_state = jax.tree_util.tree_map(
                lambda s, f: s.at[global_slot].set(f.astype(s.dtype)),
                self._opt_state,
                self._fresh_opt,
            )
        if self._train_feed_state is not None:
            # a recycled slot must not leak the previous tenant's
            # replayed training windows either
            self._train_feed_state = _zero_slot(
                self._train_feed_state, global_slot
            )

    def _pristine_params(self) -> Params:
        """One slot's weights as first initialised: the kept copy, or —
        where the model fills the chip and none is kept — made again
        from the key."""
        if self._base_params is not None:
            return self._base_params
        return self.spec.init(self._base_key, self.cfg)

    def slot_params(self, global_slot: int) -> Params:
        return unstack_slot(self.params, global_slot)

    # -- weight paging (runtime.paging / docs/PERFORMANCE.md) ------------
    def stage_slot_params(self, params: Params) -> Params:
        """Asynchronously stage ONE tenant's unstacked param tree onto
        the slice mesh ahead of ``activate`` — the ``stage_inputs``
        double-buffer pattern applied to weights: ``device_put`` returns
        with the h2d copy in flight, so a page-in's transfer overlaps
        the previous flush's dispatch and ``set_slot`` consumes
        already-device-resident leaves instead of blocking the
        activation (and the flush critical path) on the copy. Specs are
        the partition rules matched WITHOUT the tenant-axis prepend
        (parallel.partition.unstacked_specs)."""
        if self._slot_shard_fns is None:
            from sitewhere_tpu.parallel.partition import (
                make_shard_and_gather_fns,
                unstacked_specs,
            )

            specs = unstacked_specs(
                self.partition_rules,
                jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                    self.params,
                ),
                self.mm.mesh,
            )
            self._slot_shard_fns, _ = make_shard_and_gather_fns(
                self.mm.mesh, specs
            )
        from sitewhere_tpu.parallel.partition import shard_tree

        return shard_tree(params, self._slot_shard_fns)

    def slot_opt_state(self, global_slot: int):
        """One slot's optimizer state as COPIED host numpy (None while
        no optimizer is attached). Must run ON THE EVENT-LOOP THREAD:
        train steps donate the stacked opt buffer, so a worker-thread
        zero-copy view would be the same use-after-free
        ``checkpoint.host_copy_params`` guards params against."""
        if getattr(self, "_opt_state", None) is None:
            return None
        import numpy as np

        return jax.tree_util.tree_map(
            lambda x: np.array(x[global_slot], copy=True), self._opt_state
        )

    def restore_slot_opt(self, global_slot: int, opt) -> None:
        """Write one slot's saved optimizer moments back after a
        page-in, so a train-lane tenant resumes mid-descent instead of
        restarting Adam cold. No-op when either side has no optimizer
        state (the family-pinned optimizer is identical across slices,
        so saved/live tree structures always match)."""
        if opt is None or getattr(self, "_opt_state", None) is None:
            return
        self._opt_state = jax.tree_util.tree_map(
            lambda s, o: s.at[global_slot].set(jnp.asarray(o).astype(s.dtype)),
            self._opt_state,
            opt,
        )

    def rebuild_runtime(self) -> None:
        """Recover from a poisoned device runtime: re-materialize params
        host-side if they still answer (else pristine), allocate FRESH
        window/opt state (the step donates its state buffer, so a failed
        dispatch can leave ``self.state`` invalidated), and re-build the
        jitted step. Window history is lost — it rebuilds from live
        traffic; correctness (exactly-once, routing) is unaffected."""
        import numpy as np

        from sitewhere_tpu.parallel.partition import shard_tree

        t_shard = self.mm.tenant_stacked()

        def rematerialize(tree, fallback, shard_fns=None):
            try:
                host = jax.tree_util.tree_map(
                    lambda x: np.array(x, copy=True), tree
                )
                if shard_fns is not None:
                    return shard_tree(host, shard_fns)
                return jax.device_put(host, t_shard)
            except Exception:  # noqa: BLE001 - buffers may be dead
                return fallback()

        def pristine_params():
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    x[None], (self.n_slots,) + x.shape
                ).copy(),
                self._pristine_params(),
            )
            return shard_tree(stacked, self._param_shard_fns)

        self.params = rematerialize(
            self.params, pristine_params, self._param_shard_fns
        )
        self.active = rematerialize(
            self.active,
            lambda: jax.device_put(jnp.zeros((self.n_slots,), bool), t_shard),
        )
        self.train_mask = rematerialize(
            self.train_mask,
            lambda: jax.device_put(jnp.zeros((self.n_slots,), bool), t_shard),
        )
        self.slot_lr = rematerialize(
            self.slot_lr,
            lambda: jax.device_put(
                jnp.ones((self.n_slots,), jnp.float32), t_shard
            ),
        )
        self.state = self._fresh_state()
        if self.stateful:
            self.programs._advance.clear()
            self._staged_plan = self.last_stats = None
        else:
            self._step = self._build_step()
            self._step_counts = self._build_step(counts_mode=True)
        self._kernel_params = None   # may reference dead buffers
        self._kernel_dirty = True
        self._quantize_jit = None
        self._gather = None  # fresh jit cache for the result-path gather
        self._shadow_step_fn = None  # rebuilt lazily on next canary flush
        self.last_sketch = None      # may reference dead buffers
        self._wire_sharding = self.mm.sharding(AXIS_TENANT, AXIS_DATA)
        self._slot_shard_fns = None  # rebuilt lazily on next page-in
        if getattr(self, "_optimizer", None) is not None:
            from sitewhere_tpu.parallel.partition import (
                make_shard_and_gather_fns,
                stacked_specs,
            )

            opt_state = jax.vmap(self._optimizer.init)(self.params)
            self._opt_specs = stacked_specs(
                self.partition_rules, opt_state, self.mm.mesh
            )
            opt_shard_fns, _ = make_shard_and_gather_fns(
                self.mm.mesh, self._opt_specs
            )
            self._opt_state = shard_tree(opt_state, opt_shard_fns)
            self._train = self._build_train_step(
                self._optimizer, self._lr_sign
            )
            if self.train_lane:
                self._train_fused = self._build_train_step_fused(
                    self._optimizer, self._lr_sign
                )
        # the train lane's feed state may reference dead buffers too:
        # drop it — replayed history re-accumulates from the feed (the
        # same windows-rebuild-from-traffic posture as the serve state)
        had_feed = self._train_feed_state is not None
        self._train_feed_state = None
        self._ingest = None
        if had_feed:
            self.init_train_feed()

    # -- training (per-tenant divergence) --------------------------------
    def init_optimizer(self, optimizer=None) -> None:
        """Attach an optimizer; opt state is stacked per slot and sharded
        along the tenant axis like the params.

        Default (None): ``optax.scale_by_adam`` with the PER-SLOT learning
        rates in ``self.slot_lr`` applied inside the train step — tenants
        sharing a family stack each train at their own lr. A custom
        optimizer is also accepted (its update already encodes -lr);
        ``slot_lr`` then acts as a per-slot multiplier (default 1.0)."""
        import optax

        if optimizer is None:
            optimizer = optax.scale_by_adam()
            lr_sign = -1.0   # update is gradient-signed: descend
        else:
            lr_sign = 1.0    # update already encodes the step direction
        self._optimizer = optimizer
        opt_state = jax.vmap(optimizer.init)(self.params)
        # optimizer state placed by the SAME partition rules as the
        # params it mirrors (adam moments share the param paths; the
        # per-slot step count matches no trailing dims → tenant-only)
        from sitewhere_tpu.parallel.partition import (
            make_shard_and_gather_fns,
            shard_tree,
            stacked_specs,
        )

        self._opt_specs = stacked_specs(
            self.partition_rules, opt_state, self.mm.mesh
        )
        opt_shard_fns, _ = make_shard_and_gather_fns(
            self.mm.mesh, self._opt_specs
        )
        self._opt_state = shard_tree(opt_state, opt_shard_fns)
        self._fresh_opt = optimizer.init(self._pristine_params())  # for reset_slot
        self._lr_sign = lr_sign
        self._train = self._build_train_step(optimizer, lr_sign)
        if self.train_lane:
            self._train_fused = self._build_train_step_fused(
                optimizer, lr_sign
            )

    def _build_train_step(self, optimizer, lr_sign: float = 1.0) -> Callable:
        """Train every slot on its RESIDENT window state — the windows
        already live sharded on device, so training moves ZERO bytes over
        host↔device; grads ride ICI via a single pmean over the data axis
        (the one collective in the whole framework's steady state)."""
        mesh = self.mm.mesh
        spec, cfg, window = self.spec, self.cfg, self.window

        def local_step(params, opt_state, state, active, lr):
            # params/opt [T_loc, ...], state: the local stacked window
            # state, active [T_loc]
            def one(p, o, st, act, lr1):
                ids = jnp.arange(st.capacity, dtype=jnp.int32)
                windows, n = gather_windows(st, ids)
                # only streams with a full-enough history contribute; a
                # masked per-row mean keeps cold/garbage windows out of the
                # gradient and stays well-defined with 0 live streams
                mask = (n >= jnp.minimum(window, 8)).astype(jnp.float32) * act
                def masked_loss(pp):
                    per_row = jax.vmap(
                        lambda w: spec.loss(pp, cfg, w[None])
                    )(windows)  # [S_loc]
                    # psum numerator and denominator SEPARATELY across data
                    # shards: a local mean + pmean would weight shards
                    # equally regardless of how many live streams each holds
                    num = jax.lax.psum((per_row * mask).sum(), AXIS_DATA)
                    den = jnp.maximum(jax.lax.psum(mask.sum(), AXIS_DATA), 1.0)
                    return num / den
                l, grads = jax.value_and_grad(masked_loss)(p)
                # masked_loss is already globally normalized, so the full
                # gradient is the SUM of the shards' partials
                grads = jax.lax.psum(grads, AXIS_DATA)
                updates, o2 = optimizer.update(grads, o, p)
                step_scale = lr_sign * lr1  # per-slot lr (see init_optimizer)
                p2 = jax.tree_util.tree_map(
                    lambda a, u: (a + step_scale * u).astype(a.dtype),
                    p, updates,
                )
                # inactive slots keep pristine params AND optimizer state
                # (an advancing Adam step count would skew bias correction
                # when the slot later activates)
                p2 = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(act > 0, new, old), p2, p
                )
                o2 = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(act > 0, new, old), o2, o
                )
                return p2, o2, l
            act_f = active.astype(jnp.float32)
            return jax.vmap(one)(params, opt_state, state, act_f, lr)

        smapped = shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                self.param_specs,            # params (per-leaf rules)
                self._opt_specs,             # opt state (same rules)
                P(AXIS_TENANT, AXIS_DATA),   # window state (S over data)
                P(AXIS_TENANT),              # active mask
                P(AXIS_TENANT),              # per-slot lr
            ),
            out_specs=(self.param_specs, self._opt_specs, P(AXIS_TENANT)),
        )
        return jax.jit(smapped, donate_argnums=(0, 1))

    def train_resident(
        self, slots_mask: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        """One optimizer step for every trainable active slot on its
        resident window state; returns per-slot loss f32[T]. Call
        ``init_optimizer`` first. ``slots_mask`` (bool[T]) further
        restricts which slots step — per-tenant training CADENCE in a
        shared stack rides this."""
        if getattr(self, "_train", None) is None:
            raise RuntimeError("call init_optimizer() first")
        mask = self.active & self.train_mask
        if slots_mask is not None:
            mask = mask & slots_mask
        self.params, self._opt_state, losses = self._train(
            self.params, self._opt_state,
            self.state, mask, self.slot_lr,
        )
        # live weights changed: the next flush's fused step must score
        # against a re-quantized sidecar (hot-swap between flushes)
        self._invalidate_kernel()
        return losses

    # -- fused stacked training (the continual-learning train lane) -------
    def _build_train_step_fused(
        self, optimizer, lr_sign: float = 1.0
    ) -> Callable:
        """The train-lane twin of ``_build_train_step``: same masked-mean
        loss semantics (psum'd num/den across data shards, per-slot lr,
        inactive slots frozen), but the loss — and therefore the whole
        BACKWARD pass — runs through the family's ``loss_stacked``
        contract: one wide weight-stacked einsum chain over the [S·B]
        tenant plane per scan step, slot-count-invariant, instead of S
        per-slot vmapped matmuls (tools/check_fusion.py lints the grad
        jaxpr). Slot s's loss depends only on slot s's param slices, so
        the stacked gradient IS the per-slot gradients. The optax
        transform is elementwise, so vmapping it over the slot axis
        stays fused elementwise code — no dots re-enter. Params and opt
        state are DONATED: the step updates HBM in place rather than
        doubling resident weights for the training copy. Window state is
        read-only (never donated), so one compiled step trains on EITHER
        the resident serve windows or the replay-fed feed state."""
        mesh = self.mm.mesh
        spec, cfg, window = self.spec, self.cfg, self.window

        def local_step(params, opt_state, state, active, lr):
            # params/opt [T_loc, ...], state: the local stacked window state
            def gather_one(st):
                ids = jnp.arange(st.capacity, dtype=jnp.int32)
                return gather_windows(st, ids)

            # window materialization is memory ops (gather/roll) — it
            # stays vmapped per slot like the scoring step's scatter
            windows, n = jax.vmap(gather_one)(state)
            act_f = active.astype(jnp.float32)
            # same per-row gate as the legacy step: only streams with a
            # full-enough history contribute, masked mean stays
            # well-defined with 0 live streams
            mask = (
                (n >= jnp.minimum(window, 8)).astype(jnp.float32)
                * act_f[:, None]
            )

            def stacked_loss(p):
                per_row = spec.loss_stacked(p, cfg, windows)  # [T_loc, S_loc]
                # psum numerator and denominator SEPARATELY across data
                # shards (the legacy step's normalization, verbatim)
                num = jax.lax.psum((per_row * mask).sum(-1), AXIS_DATA)
                den = jnp.maximum(
                    jax.lax.psum(mask.sum(-1), AXIS_DATA), 1.0
                )
                per_slot = num / den                          # [T_loc]
                # sum over slots: grads of independent per-slot losses
                # land in their own param slices — one backward pass
                return per_slot.sum(), per_slot

            (_total, per_slot_loss), grads = jax.value_and_grad(
                stacked_loss, has_aux=True
            )(params)
            grads = jax.lax.psum(grads, AXIS_DATA)
            updates, o2 = jax.vmap(
                lambda g, o, p: optimizer.update(g, o, p)
            )(grads, opt_state, params)
            step_scale = lr_sign * lr                         # [T_loc]

            def apply(a, u):
                sc = step_scale.reshape(
                    (-1,) + (1,) * (u.ndim - 1)
                )
                return (a + sc * u).astype(a.dtype)

            p2 = jax.tree_util.tree_map(apply, params, updates)
            # inactive slots keep pristine params AND optimizer state
            # (same freeze as the legacy step)
            def keep_active(new, old):
                sel = active.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(sel, new, old)

            p2 = jax.tree_util.tree_map(keep_active, p2, params)
            o2 = jax.tree_util.tree_map(keep_active, o2, opt_state)
            return p2, o2, per_slot_loss

        smapped = shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                self.param_specs,            # params (per-leaf rules)
                self._opt_specs,             # opt state (same rules)
                P(AXIS_TENANT, AXIS_DATA),   # window state (S over data)
                P(AXIS_TENANT),              # active mask
                P(AXIS_TENANT),              # per-slot lr
            ),
            out_specs=(self.param_specs, self._opt_specs, P(AXIS_TENANT)),
        )
        return jax.jit(smapped, donate_argnums=(0, 1))

    def init_train_feed(self) -> None:
        """Allocate the replay-fed TRAIN window state — the same
        stacked rings as serving, fed by the train lane's
        replayed microbatches instead of live traffic, so continual
        learning sees windows BEYOND the resident serve state. Lazy:
        only a slice with a replay-fed trainable tenant pays the HBM."""
        if self._train_feed_state is not None:
            return
        self._train_feed_state = self._fresh_state()
        self._ingest = self._build_ingest_step()

    def _build_ingest_step(self) -> Callable:
        """Counts-mode window scatter WITHOUT scoring: replayed rows ride
        the identical staging wire (ids/vals/counts through
        ``stage_inputs``) into the train feed state. Donates the feed
        state — in-place ring update, zero extra resident memory."""
        mesh = self.mm.mesh

        def local_ingest(state, ids, vals, validity):
            m = (
                jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
                < validity
            )

            def upd(st, i, v, m1):
                return update_windows(
                    st, i.astype(jnp.int32), v.astype(jnp.float32), m1
                )

            return jax.vmap(upd)(state, ids, vals, m)

        smapped = shard_map(
            local_ingest,
            mesh=mesh,
            in_specs=(
                P(AXIS_TENANT, AXIS_DATA),   # feed window state
                P(AXIS_TENANT, AXIS_DATA),   # stream ids (B over data)
                P(AXIS_TENANT, AXIS_DATA),   # values
                P(AXIS_TENANT, AXIS_DATA),   # lane counts
            ),
            out_specs=P(AXIS_TENANT, AXIS_DATA),
        )
        return jax.jit(smapped, donate_argnums=(0,))

    def train_feed_ingest(self, stream_ids, values, counts) -> None:
        """Scatter one staged replay microbatch into the train feed
        windows (async dispatch; same wire/staging contract as
        ``step_counts``)."""
        self.init_train_feed()
        self._train_feed_state = self._ingest(
            self._train_feed_state, stream_ids, values, counts
        )

    def train_lane_step(
        self,
        slots_mask: Optional[jnp.ndarray] = None,
        replay: bool = False,
    ) -> jnp.ndarray:
        """One FUSED optimizer step on the train lane: resident serve
        windows (``replay=False`` — live adaptation) or the replay-fed
        feed state (``replay=True`` — history beyond the resident
        state). Async jit dispatch; returns the per-slot loss device
        array the caller rides through the completion reaper.

        Unlike ``train_resident`` this does NOT invalidate the serving
        kernel sidecar: the lane's weight updates stay invisible to
        scoring until ``commit_swap`` re-derives the kernel view every
        ``swap_every`` steps — the zero-stall hot-swap boundary."""
        if self._train_fused is None:
            raise RuntimeError(
                "train lane not built — call init_optimizer() on a "
                "train_lane-capable scorer first"
            )
        mask = self.active & self.train_mask
        if slots_mask is not None:
            mask = mask & slots_mask
        st = self._train_feed_state if replay else self.state
        self.params, self._opt_state, losses = self._train_fused(
            self.params, self._opt_state,
            st, mask, self.slot_lr,
        )
        return losses

    def prewarm_train_lane(self, lane_sizes=()) -> None:
        """Compile the train lane's executables BEFORE traffic — the
        same no-mid-loop-compile rule as ``prewarm``. Runs the REAL
        programs with no observable effect: a zero-count ingest per
        bucket size (scatter drops every row) and one all-False-mask
        train step (the inactive-slot freeze passes params and opt
        state through ``jnp.where`` bitwise). Requires
        ``init_optimizer`` to have run."""
        import numpy as _np

        if self._train_fused is None:
            raise RuntimeError(
                "call init_optimizer() before prewarm_train_lane()"
            )
        self.init_train_feed()
        t, d = self.n_slots, self.mm.n_data_shards
        for b in sorted(set(int(x) for x in lane_sizes)) or [64]:
            ids = _np.zeros((t, d * b), self.ids_np_dtype)
            vals = _np.zeros((t, d * b), self.vals_np_dtype)
            counts = _np.zeros((t, d), _np.int32)
            self.train_feed_ingest(*self.stage_inputs(ids, vals, counts))
        none = _np.zeros((self.n_slots,), bool)
        for replay in (False, True):
            _np.asarray(self.train_lane_step(none, replay=replay))

    def commit_swap(self) -> None:
        """The train lane's between-flush weight commit — the tail of
        ``activate(params=...)``: the fused train steps already updated
        the master stack in place (buffer donation), so committing means
        re-deriving the serving kernel view (the quantized sidecar —
        for bf16/int8 stacks scoring keeps the PREVIOUS weights until
        this runs) and arming the PR 9 shadow canary so the freshly
        swapped weights get immediate divergence coverage. f32 fused
        stacks read the master directly (kernel view == master), so for
        them the commit is the canary arm + observability cadence."""
        self._invalidate_kernel()
        self.arm_canary()

    def train_flops_per_step(self) -> float:
        """Analytic matmul FLOPs ONE fused train step executes: the full
        padded stream plane (every slot × stream row gathers a window
        and runs the teacher-forced loss, live or not) × per-row forward
        FLOPs × 3 (the standard fwd+bwd multiplier: backward re-runs
        ~2× the forward's matmul work). Feeds
        ``tpu_train_flops_total{family}`` — kept OUT of the serving MFU
        account (``tpu_mfu_pct`` means serving work), summed beside it
        by the bench's overlap-MFU column."""
        fn = getattr(self.spec, "flops_per_row", None)
        if fn is None:
            return 0.0
        plane = self.n_slots * self.max_streams
        return 3.0 * plane * float(fn(self.cfg, self.window))
