"""Chip smoke: ingest → score → persist on a TPU, through the entry points
a user calls, checked against plain references. The quickest proof that
the system still starts — and really scores — on the chip.

    python chip_smoke.py            # one chip: phases `events`, `media`
    python chip_smoke.py --chips 4  # four chips: phase `chips4` only

One process, one touch of JAX. It sets no platform and no XLA_FLAGS: JAX
must report platform ``tpu`` on its own or the script exits non-zero
before printing anything. It exits non-zero the moment a check fails.
Every phase prints one JSON line; the LAST stdout line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

The product degrades to unscored pass-through when a chip misbehaves (a
delivery guarantee), so "it ran" proves nothing here: every phase also
demands finite scores on every row and zero on every error / failover /
quarantine / park / timeout / shed / dead-letter counter.

- ``events``: SiteWhereInstance → 32 ``iot-temperature`` tenants (binary
  decoder, bf16 wire, max_streams 2048, hidden 64, window 32, buckets
  1024/4096/16384 — BASELINE.json config 4 through the product) →
  prewarm → seeded DeviceSimulators publish 160 rounds (2,048,000 events)
  at ``EVENTS_PER_SEC`` → drain. One tenant's last round is re-scored by
  the plain f32 numpy LSTM-AD below.
- ``media``: one media tenant, ViT-B/16 at published widths, compressed
  wire: seeded JPEG frames → native entropy decode → on-device IDCT →
  classify → events; top-5 probabilities and top-1 against
  ``models.vit.apply`` on PIL pixels, to the bf16 bound below.
- ``chips4`` (``--chips 4``): the same tenants and payloads on a
  tenant_axis=4 mesh (8 tenants a slice), published in lockstep so the
  scores do not depend on timing, compared row for row with a one-slice
  run made first in the same process; then one ``train_resident`` step
  on a tenant=2 × data=2 mesh against one device.
"""

from __future__ import annotations

import argparse
import asyncio
import io
import json
import sys
import time
from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple

import numpy as np

# offered load of the `events` phase, open loop. Stated, not measured: the
# publisher is paced here so that overload control never sheds, samples or
# expires a row — a check that fails on a shed row is answered by lowering
# this rate, never by loosening the published == scored == stored equality.
# (At twice this rate the chip run of PR 24 also passed, with a p99 of
# 153 ms against the 500 ms admission deadline; this leaves more room for
# a busy host.)
EVENTS_PER_SEC = 128_000

# |device − reference| bound for one score. Inputs are the bf16 wire values
# on both sides; the device then runs the 31-step scan in bf16 (8-bit
# significand: 2^-8 per rounding, through a recurrence of gain ≤ ~1) and
# returns the score on the bf16 wire (2^-8 relative). Scores are O(1).
SCORE_ATOL = 31 * 2.0 ** -8 / 2
SCORE_RTOL = 2.0 ** -7


# |log p(device, compressed wire) − log p(reference, PIL pixels)| bound for
# one class of one frame, i.e. a bound on the logit difference between the
# two paths. Both run ViT-B/16 in bf16: ~70 roundings of 2^-8 on O(1)
# activations through 12 blocks accumulate to ~0.03 per path, and the two
# decoders differ by up to a few pixel levels on top. With RANDOM weights
# the top-1 / top-2 logit gap (median 0.08, my chip run, PR 24) is of that
# same size, so top-1 must match only where the reference separates the
# two classes by more than this bound.
VIT_LOGIT_ATOL = 0.1


class SmokeFailure(Exception):
    """A check failed; the message names what and the numbers."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class EventsSize:
    tenants: int = 32
    devices: int = 4          # per tenant
    burst: int = 100          # samples per wire message
    rounds: int = 160
    max_streams: int = 2048
    buckets: Tuple[int, ...] = (1024, 4096, 16384)
    hidden: int = 64
    window: int = 32

    @property
    def round_events(self) -> int:
        return self.tenants * self.devices * self.burst


# ------------------------------------------------------------ references
def lstm_ad_reference(params: dict, windows: np.ndarray) -> np.ndarray:
    """Plain f32 numpy LSTM-AD: windows f32[B, W] (oldest → newest, all W
    samples real) → anomaly score f32[B], |normalized last sample − the
    one-step-ahead prediction|. Independent of models/lstm_ad.py: same
    published equations, no JAX, no bf16."""
    w = windows.astype(np.float32)
    mu = w.mean(-1, keepdims=True)
    x = (w - mu) / (w.std(-1, keepdims=True) + 1e-6)
    wx, wh = params["wx"]["w"], params["wh"]["w"]
    bias = params["wx"]["b"] + params["wh"]["b"]
    h = c = np.zeros((w.shape[0], wh.shape[0]), np.float32)

    def sig(a):
        return 1.0 / (1.0 + np.exp(-a))

    for t in range(w.shape[1] - 1):
        i, f, g, o = np.split(x[:, t : t + 1] @ wx + h @ wh + bias, 4, -1)
        c = sig(f + 1.0) * c + sig(i) * np.tanh(g)
        h = sig(o) * np.tanh(c)
    pred = (h @ params["head"]["w"])[:, 0] + params["head"]["b"][0]
    return np.abs(x[:, -1] - pred)


def bf16_wire(values: np.ndarray) -> np.ndarray:
    """What the bf16 host→device wire makes of f32 values."""
    import ml_dtypes

    return values.astype(ml_dtypes.bfloat16).astype(np.float32)


def burst_values(payload: bytes, burst: int) -> np.ndarray:
    """The f32 samples a bulk binary wire message carries (they are its
    trailing bytes — pipeline.decoders.encode_measurements_bulk_binary)."""
    return np.frombuffer(payload[-4 * burst :], "<f4")


def count_collectives(hlo_text: str) -> Dict[str, int]:
    ops = ("all-reduce", "all-gather", "reduce-scatter",
           "collective-permute", "all-to-all")
    found = {op: hlo_text.count(f" {op}(") + hlo_text.count(f" {op}-start(")
             for op in ops}
    return {op: n for op, n in found.items() if n}


# --------------------------------------------------------- events driver
def _counter_sum(metrics, family: str) -> float:
    """Sum over an unlabeled counter or every child of a labeled family."""
    return float(sum(
        v for v in metrics.snapshot_families((family,)).values()
        if isinstance(v, (int, float))
    ))


# every one of these must read zero after the traffic: a non-zero value
# means rows were shed, sampled, delayed by a sick slice or passed through
# unscored — the outcomes the fault-tolerance layer would otherwise hide
ZERO_COUNTERS = (
    "tpu_inference.failovers", "tpu_inference.quarantined",
    "tpu_inference.parked", "tpu_inference.poison_ejected",
    "tpu_inference.poison_retries", "tpu_inference.breaker_short_circuits",
    "tpu_inference.quarantine_passthrough", "tpu_flush_timeout_total",
    "tpu_scores_unscored_total", "tpu_inference.sampled_out",
    "tpu_inference.fair_throttled", "tpu_inference.skipped_capacity",
    "receiver_shed_total", "pipeline_shed_total",
    "rules.skipped_degraded", "outbound.skipped_degraded",
    "tpu_inference.wire_dtype_conflicts",
    "tpu_inference.fused_knob_conflicts",
)


async def _wait_for(pred, timeout_s: float, what: str) -> None:
    t_end = time.monotonic() + timeout_s
    while not pred():
        check(time.monotonic() < t_end, f"timed out after {timeout_s}s: {what}")
        await asyncio.sleep(0.02)


class EventsRun:
    """One instance serving ``size.tenants`` tenants and the seeded
    simulators that feed it. ``mesh`` None = the instance's own default
    over every device; ``mesh_cfg`` carries the tenant-axis layout."""

    def __init__(self, size: EventsSize, seed: int, platform: str,
                 mesh_cfg=None, mesh=None) -> None:
        self.size, self.seed, self.platform = size, seed, platform
        self.mesh_cfg, self.mesh = mesh_cfg, mesh
        self.inst = None
        self.sims: list = []
        self.rounds: list = []     # [tenant][round] pregenerated payloads
        self.published = 0
        self.compiles_before = 0.0  # tpu_inference.compiles at traffic start
        self.alerts: Dict[str, int] = {}
        self.info: dict = {}

    def tenant(self, i: int) -> str:
        return f"t{i:02d}"

    async def start(self) -> None:
        from sitewhere_tpu.instance import SiteWhereInstance
        from sitewhere_tpu.native import jsonwire_lib
        from sitewhere_tpu.runtime.config import (
            InstanceConfig,
            MeshConfig,
            MicroBatchConfig,
        )
        from sitewhere_tpu.sim import DeviceSimulator, SimProfile

        sz = self.size
        self.inst = inst = SiteWhereInstance(
            InstanceConfig(
                instance_id="smoke",
                mesh=self.mesh_cfg or MeshConfig(slots_per_shard=sz.tenants),
                inference_max_inflight=6,
            ),
            mesh=self.mesh,
        )
        await inst.start()
        mb = MicroBatchConfig(
            max_batch=sz.buckets[-1], deadline_ms=5.0, buckets=sz.buckets,
            window=sz.window,
        )
        for i in range(sz.tenants):
            await inst.tenant_management.create_tenant(
                self.tenant(i), template="iot-temperature", microbatch=mb,
                decoder="binary", max_streams=sz.max_streams,
                wire_dtype="bf16", model_config={"hidden": sz.hidden},
            )
        await inst.drain_tenant_updates()
        await _wait_for(lambda: len(inst.tenants) == sz.tenants, 60.0,
                        "tenants to start")

        async def on_alert(topic: str, _payload: bytes) -> None:
            tok = topic.split("/")[1]
            self.alerts[tok] = self.alerts.get(tok, 0) + 1

        inst.broker.subscribe("sitewhere/+/output/+/alert", on_alert)
        for i in range(sz.tenants):
            tok = self.tenant(i)
            inst.tenants[tok].device_management.bootstrap_fleet(sz.devices)
            self.sims.append(DeviceSimulator(
                inst.broker,
                SimProfile(n_devices=sz.devices, seed=self.seed + i,
                           samples_per_message=sz.burst, wire="binary"),
                topic_pattern=f"sitewhere/{tok}/input/{{device}}",
            ))
        t0 = time.perf_counter()
        await asyncio.get_running_loop().run_in_executor(
            None, inst.inference.prewarm
        )
        self.info["prewarm_s"] = round(time.perf_counter() - t0, 2)
        self.info["jsonwire_native"] = jsonwire_lib(wait=True) is not None
        self.rounds = [s.pregenerate(16, t0=1.0) for s in self.sims]

    def scored(self) -> int:
        return int(self.inst.metrics.counter(
            "tpu_inference.scored_total").value)

    async def publish(self, events_per_sec: Optional[float]) -> float:
        """Publish ``size.rounds`` rounds (cycling the 16 pregenerated
        ones — the bench's publish loop). A rate paces them open loop;
        None publishes in LOCKSTEP — the next round leaves when the last
        is scored — so each stream's burst rides a flush of its own and
        the scores do not depend on timing. Returns the traffic seconds."""
        sz = self.size
        interval = sz.round_events / events_per_sec if events_per_sec else 0
        self.compiles_before = self.inst.metrics.counter(
            "tpu_inference.compiles").value
        t0 = time.perf_counter()
        for r in range(sz.rounds):
            for sim, rounds in zip(self.sims, self.rounds):
                await sim.publish_pregenerated(rounds[r % 16])
            self.published += sz.round_events
            if events_per_sec is None:
                await _wait_for(lambda: self.scored() >= self.published,
                                120.0, f"round {r} to be scored")
            else:
                delay = t0 + (r + 1) * interval - time.perf_counter()
                await asyncio.sleep(max(delay, 0))
        await _wait_for(lambda: self.scored() >= self.published, 600.0,
                        f"drain: scored {self.scored()} of {self.published}")
        return time.perf_counter() - t0

    def store_columns(self, tok: str) -> Tuple[np.ndarray, ...]:
        """(device token, value, score) per row of one tenant's store, in
        persist order."""
        from sitewhere_tpu.storage.segstore import slice_columns

        cols = [slice_columns(sl) for sl in
                self.inst.tenants[tok].event_store.measurements.scan()]
        return (
            np.concatenate([c["tok"][0][c["tok"][1]] for c in cols]),
            np.concatenate([c["values"] for c in cols]),
            np.concatenate([c["scores"] for c in cols]),
        )

    def check_numerics(self, tenant_idx: int = 0) -> dict:
        """Re-score one tenant's LAST round with the numpy reference.

        A flush scores every row of a stream with the window that ends at
        the stream's newest row IN THAT FLUSH (fuse_k = 1), so a burst's
        rows share the score of the window ending at the burst's last
        sample — unless a flush boundary split the burst, in which case
        the earlier rows end at the split. Walk each burst from its last
        row backwards and hold every row to the reference score of the
        window its flush segment ends on."""
        sz = self.size
        tok = self.tenant(tenant_idx)
        dev, vals, scores = self.store_columns(tok)
        eng = self.inst.inference.engines[tok]
        scorer = self.inst.inference.scorers[
            (eng.config.model, eng.placement.shard)]
        params = {
            k: {kk: np.asarray(vv, np.float32) for kk, vv in v.items()}
            for k, v in scorer.slot_params(eng.placement.slot).items()
        }
        last, prev = (sz.rounds - 1) % 16, (sz.rounds - 2) % 16
        max_err, n_rows = 0.0, 0
        for d, (_topic, payload, _k) in enumerate(
                self.rounds[tenant_idx][last]):
            sent = burst_values(payload, sz.burst)
            before = burst_values(
                self.rounds[tenant_idx][prev][d][1], sz.burst)
            rows = np.flatnonzero(
                dev == self.sims[tenant_idx].device_tokens()[d]
            )[-sz.burst :]
            check(np.array_equal(vals[rows], sent),
                  f"{tok} device {d}: stored values differ from the "
                  f"published last burst")
            series = bf16_wire(np.concatenate([before, sent]))
            ends = np.arange(sz.burst) + sz.burst  # index of each end row
            windows = np.stack(
                [series[e - sz.window + 1 : e + 1] for e in ends])
            ref = lstm_ad_reference(params, windows)
            got = scores[rows].astype(np.float64)
            tol = SCORE_ATOL + SCORE_RTOL * np.abs(ref)
            end = sz.burst - 1
            for i in reversed(range(sz.burst)):
                if abs(got[i] - ref[end]) > tol[end]:
                    end = i  # a flush boundary: this row ended its flush
                err = abs(got[i] - ref[end])
                check(err <= tol[end],
                      f"{tok} device {d} row {i}: score {got[i]:.5f} vs "
                      f"reference {ref[end]:.5f} (|d|={err:.5f} > tol)")
                max_err = max(max_err, float(err))
            n_rows += sz.burst
        return {"ref_rows": n_rows, "ref_max_abs_err": round(max_err, 6),
                "ref_atol": round(SCORE_ATOL, 6), "ref_rtol": SCORE_RTOL}

    async def check_rule_leg(self) -> None:
        """One spike sample per tenant, alone in its message: it is its
        stream's newest row, scores far above the template's 3-sigma
        anomaly rule, and must come out as an alert on the tenant's
        outbound MQTT topic."""
        from sitewhere_tpu.pipeline.decoders import encode_measurement_binary

        for i, sim in enumerate(self.sims):
            dev = sim.device_tokens()[0]
            await self.inst.broker.publish(
                f"sitewhere/{self.tenant(i)}/input/{dev}",
                encode_measurement_binary(dev, "temperature", 60.0),
            )
        self.published += self.size.tenants
        await _wait_for(
            lambda: all(self.alerts.get(self.tenant(i), 0) >= 1
                        for i in range(self.size.tenants)),
            60.0, f"an outbound alert per tenant (got {self.alerts})")

    async def wait_delivered(self) -> None:
        """``scored_total`` counts at the publish on scored-events;
        persist and outbound follow it. Wait for the LAST stage before
        the accounting reads what it delivered."""
        per_tenant = self.published // self.size.tenants
        await _wait_for(
            lambda: all(
                self.inst.tenants[self.tenant(i)].outbound.connectors[0]
                .batch_rows >= per_tenant
                for i in range(self.size.tenants)),
            60.0, "the outbound connectors to deliver every row")

    def check_accounting(self) -> dict:
        """published == scored == stored, all finite, nothing degraded."""
        from sitewhere_tpu.core.batch import MeasurementBatch

        inst, sz, m = self.inst, self.size, self.inst.metrics
        check(self.scored() == self.published,
              f"scored_total {self.scored()} != published {self.published}")
        per_tenant = self.published // sz.tenants
        for i in range(sz.tenants):
            tok = self.tenant(i)
            rt = inst.tenants[tok]
            store = rt.event_store.measurements
            check(len(store) == per_tenant,
                  f"{tok}: {len(store)} rows stored, {per_tenant} published")
            bad = sum(
                int((~np.isfinite(sl.seg.numeric("score")[sl.sel])).sum())
                for sl in store.scan())
            check(bad == 0, f"{tok}: {bad} stored rows without a finite score")
            topic = inst.bus.naming.scored_events(tok)
            on_bus = sum(
                b.n for _off, b in inst.bus.peek(topic, 1 << 30)["entries"]
                if isinstance(b, MeasurementBatch))  # alerts ride it too
            check(on_bus == per_tenant,
                  f"{tok}: {on_bus} rows on {topic}, {per_tenant} published")
            check(rt.outbound.connectors[0].batch_rows == per_tenant,
                  f"{tok}: outbound delivered "
                  f"{rt.outbound.connectors[0].batch_rows} of {per_tenant}")
        # every measurement row went through the tenant's rule (the alert
        # events it derived are evaluated too, hence >=)
        evaluated = _counter_sum(m, "rules.evaluated")
        check(evaluated >= self.published,
              f"rules.evaluated {evaluated} < published {self.published}")
        for name in ZERO_COUNTERS:
            value = _counter_sum(m, name)
            check(value == 0, f"{name} = {value} (must be 0)")
        lost = {
            t: inst.bus.peek(t, 1)["latest"] for t in inst.bus.topics()
            if ".dead-letter." in t or t.endswith((
                "expired-events", "event-source-failed-decode",
                "unregistered-device-events"))
        }
        lost = {t: n for t, n in lost.items() if n}
        check(not lost, f"dead-letter/expired/failed topics hold {lost}")
        check(not inst.errors, f"instance errors: {inst.errors[:3]}")
        check(not inst.inference.errors,
              f"inference errors: {inst.inference.errors[:3]}")
        compiles = m.counter("tpu_inference.compiles").value
        check(compiles == self.compiles_before,
              f"tpu_inference.compiles moved {self.compiles_before} -> "
              f"{compiles} inside the traffic window")
        labels = sorted({s.device_label
                         for s in inst.inference.scorers.values()})
        check(all(lbl.startswith(self.platform + ":") for lbl in labels),
              f"scorer device labels {labels} are not on {self.platform}")
        flushes = m.counter("tpu_inference.flushes").value
        return {
            "published": self.published, "scored": self.scored(),
            "stored": per_tenant * sz.tenants, "flushes": int(flushes),
            "rows_per_flush": round(
                m.counter("tpu_inference.flush_rows").value
                / max(flushes, 1), 1),
            "alerts": sum(self.alerts.values()), "devices": labels,
            "latency_p99_ms": round(m.histogram(
                "tpu_inference.latency", unit="s").quantile(0.99) * 1e3, 1),
        }

    async def stop(self) -> None:
        if self.inst is not None:
            await self.inst.terminate()


async def phase_events(size: EventsSize, seed: int, platform: str,
                       events_per_sec: Optional[float]) -> dict:
    run = EventsRun(size, seed, platform)
    try:
        await run.start()
        traffic_s = await run.publish(events_per_sec)
        numerics = run.check_numerics()
        await run.check_rule_leg()
        await run.wait_delivered()
        out = run.check_accounting()
        return {"phase": "events", "ok": True, **out, **numerics,
                "offered_ev_s": events_per_sec,
                "traffic_s": round(traffic_s, 2), **run.info}
    finally:
        await run.stop()


# ----------------------------------------------------------------- media
async def phase_media(tiny: bool, seed: int, batch: int = 64,
                      n_batches: int = 3) -> dict:
    from PIL import Image

    from sitewhere_tpu.instance import SiteWhereInstance
    from sitewhere_tpu.native import jpegwire
    from sitewhere_tpu.pipeline.media import media_classifications_topic
    from sitewhere_tpu.runtime.config import InstanceConfig, MeshConfig
    from sitewhere_tpu.sim.media import camera_frame

    check(jpegwire.jpegwire_lib(wait=True) is not None,
          "native jpegwire did not build (no working `cc`?) — the "
          "compressed wire would fall back to PIL and ops/dct.py never run")
    inst = SiteWhereInstance(InstanceConfig(
        instance_id="smoke-media", mesh=MeshConfig(slots_per_shard=2)))
    await inst.start()
    try:
        await inst.tenant_management.create_tenant(
            "cam", template="media", media_tiny=tiny)
        await inst.drain_tenant_updates()
        await _wait_for(lambda: "cam" in inst.tenants, 60.0, "media tenant")
        rt = inst.tenants["cam"]
        pipe = rt.media_pipeline
        pipe.max_batch = batch
        pipe.store_chunks = False
        stream = rt.media.create_stream("asn-cam", content_type="video/raw")
        t0 = time.perf_counter()
        await asyncio.get_running_loop().run_in_executor(None, pipe.prewarm)
        prewarm_s = time.perf_counter() - t0
        check(pipe.compressed and pipe._native_ok,
              "media pipeline is not on the native compressed wire")
        size = pipe.image_size
        topic = media_classifications_topic(inst.bus, "cam")
        inst.bus.subscribe(topic, "smoke")
        # the classifier's own jitted ``models.vit.apply`` on PIL pixels
        _spec, cfg, params, reference = rt.media._get_classifier(tiny)
        agree, max_err = [], 0.0
        for b in range(n_batches):
            chunks = []
            for j in range(batch):
                buf = io.BytesIO()
                Image.fromarray(camera_frame(
                    size, (b * batch + j) * 0.7, seed)).save(
                        buf, format="JPEG", quality=75)
                chunks.append(buf.getvalue())
            # one burst with no await between submits lands in the ring
            # whole, so the pipeline classifies it as ONE batch
            for j, data in enumerate(chunks):
                await pipe.submit_chunk(
                    stream.stream_id, b * batch + j, data, kind="jpeg")
            got: dict = {}
            t_end = time.monotonic() + 300.0
            while len(got) < batch:
                check(time.monotonic() < t_end,
                      f"media batch {b}: {len(got)}/{batch} frames classified")
                for ev in await inst.bus.consume(topic, "smoke", 256,
                                                 timeout_s=0.05):
                    got[ev["seq"]] = ev["top_k"]
            pixels = np.stack(
                [rt.media.decode_frame(c, size, "f32") for c in chunks])
            logits = np.asarray(reference(params, cfg, pixels), np.float64)
            check(bool(np.isfinite(logits).all()),
                  f"media batch {b}: reference logits not finite")
            m_ = logits.max(-1, keepdims=True)
            ref_logp = logits - m_ - np.log(
                np.exp(logits - m_).sum(-1, keepdims=True))
            exact = 0
            for j in range(batch):
                top = got[b * batch + j]
                cls = np.asarray([c for c, _p in top])
                logp = np.log(np.asarray([p for _c, p in top], np.float64))
                check(bool(np.isfinite(logp).all()),
                      f"media frame {b}/{j}: probabilities {top} not finite")
                err = float(np.abs(logp - ref_logp[j, cls]).max())
                check(err <= VIT_LOGIT_ATOL,
                      f"media frame {b}/{j}: top-5 log-probabilities differ "
                      f"from the pixel path by {err:.4f} > {VIT_LOGIT_ATOL}")
                gap = float(ref_logp[j].max() - ref_logp[j, cls[0]])
                check(gap <= VIT_LOGIT_ATOL,
                      f"media frame {b}/{j}: top-1 class {cls[0]} is "
                      f"{gap:.4f} below the pixel path's top-1 (not a tie "
                      f"within {VIT_LOGIT_ATOL})")
                exact += int(gap == 0.0)
                max_err = max(max_err, err)
            agree.append(exact)
        m = inst.metrics
        for name in ("media_native_decode_fallback_total",
                     "media_frames_bad_total", "media_frames_shed_total",
                     "media.classify_timeouts", "tpu_flush_timeout_total"):
            value = _counter_sum(m, name)
            check(value == 0, f"{name} = {value} (must be 0)")
        recs = inst.flightrec._ring("flush", "vit_b16[cam]").records()
        codecs = sorted({r.get("codec") for r in recs})
        check(bool(recs) and all(str(c).startswith("dct") for c in codecs),
              f"media flushes did not all ride the coefficient path: {codecs}")
        check(not inst.errors, f"instance errors: {inst.errors[:3]}")
        return {
            "phase": "media", "ok": True, "model": "vit_tiny" if tiny
            else "vit_b16", "frames": batch * n_batches, "batch": batch,
            "top1_exact": agree, "logp_max_abs_err": round(max_err, 4),
            "logp_atol": VIT_LOGIT_ATOL, "native_decode": True,
            "pil_fallbacks": 0,
            "codecs": codecs, "prewarm_s": round(prewarm_s, 2),
            "h2d_bytes_per_frame": round(m.counter(
                "media_h2d_bytes_total", tenant="cam").value
                / (batch * n_batches)),
        }
    finally:
        await inst.terminate()


# ---------------------------------------------------------------- chips 4
def _train_step_check(size: EventsSize, seed: int, devices) -> dict:
    """One ``train_resident`` step on a tenant=2 × data=2 mesh against
    the same step on one device: losses agree; psum is the only
    collective in the sharded step's HLO."""
    import optax

    from sitewhere_tpu.models import get_model, make_config
    from sitewhere_tpu.parallel.mesh import MeshManager
    from sitewhere_tpu.parallel.sharded import ShardedScorer

    spec = get_model("lstm_ad")
    cfg = make_config("lstm_ad", {"window": size.window,
                                  "hidden": size.hidden})
    per_shard = size.max_streams // 2
    n, b_lane, steps = size.tenants, min(256, per_shard), 12
    rng = np.random.RandomState(seed)

    def build(devs, t_ax, d_ax):
        mm = MeshManager(tenant=t_ax, data=d_ax, devices=devs)
        sc = ShardedScorer(mm, spec, cfg, slots_per_shard=n // t_ax,
                           max_streams=size.max_streams, window=size.window)
        for i in range(n):
            sc.activate(i)
        return sc

    sharded, single = build(devices[:4], 2, 2), build(devices[:1], 1, 1)
    for _ in range(steps):
        # each data shard's lane carries its OWN streams 0..b_lane-1;
        # the single device sees them at flat id shard * per_shard + local
        local = np.tile(np.arange(b_lane, dtype=np.int32), (n, 2))
        flat = local + np.repeat([0, per_shard], b_lane)[None, :]
        vals = rng.randn(n, 2 * b_lane).astype(np.float32)
        valid = np.ones((n, 2 * b_lane), bool)
        np.asarray(sharded.step(local, vals, valid))
        np.asarray(single.step(flat, vals, valid))
    for sc in (sharded, single):
        sc.init_optimizer(optax.adam(1e-3))
    hlo = sharded._train.lower(
        sharded.params, sharded._opt_state, sharded.state,
        sharded.active & sharded.train_mask, sharded.slot_lr,
    ).compile().as_text()
    coll = count_collectives(hlo)
    check(set(coll) == {"all-reduce"},
          f"sharded train step collectives {coll}: psum (all-reduce) must "
          f"be the only kind")
    l4 = np.asarray(sharded.train_resident(), np.float32)
    l1 = np.asarray(single.train_resident(), np.float32)
    check(bool(np.isfinite(l4).all() and (l4 > 0).all()),
          "sharded train losses not finite and positive")
    err = float(np.abs(l4 - l1).max())
    check(bool(np.allclose(l4, l1, rtol=2e-2, atol=1e-3)),
          f"train losses differ across meshes: max |d| = {err}")
    return {"train_all_reduces": coll["all-reduce"],
            "train_loss_max_abs_diff": round(err, 6),
            "train_loss_mean": round(float(l4.mean()), 5)}


async def phase_chips4(size: EventsSize, seed: int, platform: str) -> dict:
    import jax

    from sitewhere_tpu.parallel.mesh import MeshManager
    from sitewhere_tpu.runtime.config import MeshConfig

    devices = jax.devices()
    check(len(devices) >= 4, f"needs 4 devices, JAX reports {len(devices)}")
    per_slice = size.tenants // 4

    async def serve(mesh_cfg, mesh):
        run = EventsRun(size, seed, platform, mesh_cfg, mesh)
        try:
            await run.start()
            await run.publish(None)  # lockstep: timing-independent scores
            await run.wait_delivered()
            cols = {run.tenant(i): run.store_columns(run.tenant(i))
                    for i in range(size.tenants)}
            acct = run.check_accounting()
            return run, cols, acct
        except BaseException:
            await run.stop()
            raise

    run1, one, _ = await serve(
        MeshConfig(slots_per_shard=size.tenants),
        MeshManager(tenant=1, data=1, devices=devices[:1]))
    await run1.stop()
    run4, four, acct = await serve(
        MeshConfig(tenant_axis=4, slots_per_shard=per_slice),
        MeshManager(tenant=4, data=1, devices=devices[:4]))
    try:
        svc, m = run4.inst.inference, run4.inst.metrics
        placed: Dict[int, int] = {}
        for eng in svc.engines.values():
            placed[eng.placement.shard] = placed.get(
                eng.placement.shard, 0) + 1
        check(placed == {sl: per_slice for sl in range(4)},
              f"router placement per slice {placed}, want {per_slice} each")
        per_device = {}
        for sl in range(4):
            lbl = svc.mm.slice_device_label(sl)
            scorer = svc.scorers[("lstm_ad", sl)]
            home = {f"{d.platform}:{d.id}"
                    for x in jax.tree_util.tree_leaves(scorer.state)
                    for d in x.devices()}
            check(home == {lbl} == {f"{platform}:{devices[sl].id}"},
                  f"slice {sl} state lives on {home}, label {lbl}")
            rows = m.counter(
                "tpu_inference_device_rows_total", device=lbl).value
            busy = m.counter("tpu_device_busy_seconds_total",
                             family="lstm_ad", device=lbl).value
            flushes = m.histogram("tpu_inference_dispatch_seconds",
                                  family="lstm_ad", device=lbl).count
            check(rows == run4.published // 4 and busy > 0 and flushes > 0,
                  f"{lbl}: rows {rows} busy_s {busy} flushes {flushes}")
            per_device[lbl] = {"flushes": int(flushes), "rows": int(rows),
                               "busy_s": round(busy, 3)}
            staged = scorer.stage_inputs(
                np.zeros((per_slice, size.buckets[0]), scorer.ids_np_dtype),
                np.zeros((per_slice, size.buckets[0]), scorer.vals_np_dtype),
                np.zeros((per_slice, 1), np.int32))
            coll = count_collectives(scorer._step_counts.lower(
                scorer.kernel_params(), scorer.state, scorer.active, *staged
            ).compile().as_text())
            check(not coll, f"slice {sl} serving step has collectives {coll}")
        max_err = 0.0
        for tok, (dev1, vals1, sc1) in one.items():
            dev4, vals4, sc4 = four[tok]
            check(np.array_equal(dev1, dev4) and np.array_equal(vals1, vals4),
                  f"{tok}: the two runs stored different rows")
            err = np.abs(sc4 - sc1)
            check(bool((err <= SCORE_ATOL / 8 + SCORE_RTOL * np.abs(sc1)).all()),
                  f"{tok}: 4-slice scores differ from the one-slice run, "
                  f"max |d| = {err.max()}")
            max_err = max(max_err, float(err.max()))
    finally:
        await run4.stop()
    train = await asyncio.get_running_loop().run_in_executor(
        None, _train_step_check, size, seed, devices)
    return {"phase": "chips4", "ok": True, "per_device": per_device,
            "tenants_per_slice": per_slice, "published": acct["published"],
            "one_vs_four_max_abs_diff": round(max_err, 6),
            "serving_collectives": 0, **train}


# ------------------------------------------------------------------ main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX reports "
              f"{len(devs)} device(s) on platform {dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)

    from sitewhere_tpu.runtime.compilecache import enable_compile_cache
    from sitewhere_tpu.runtime.metrics import peak_flops_bf16

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    # persistent compile cache traffic, printed with every phase: a second
    # run in the same tree must show hits
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            kind = event.rsplit("_", 1)[-1]
            if kind in cache:
                cache[kind] += 1

    jax.monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    print(json.dumps({
        "phase": "setup", "jax": jax.__version__, "device": device,
        # fails here on a TPU kind with no published peak in the table
        "peak_bf16_flops": peak_flops_bf16(dev.platform, dev.device_kind),
        "compile_cache": enable_compile_cache(),
    }), flush=True)
    size = EventsSize()
    phases = (
        # lockstep traffic never leaves the smallest bucket: compile no
        # other on four chips
        [lambda: phase_chips4(replace(size, buckets=size.buckets[:1]),
                              args.seed, dev.platform)]
        if args.chips == 4 else
        [lambda: phase_events(size, args.seed, dev.platform, EVENTS_PER_SEC),
         lambda: phase_media(False, args.seed)]
    )
    for phase in phases:
        t_phase = time.perf_counter()
        try:
            line = asyncio.run(phase())
        except SmokeFailure as exc:
            print(json.dumps({"ok": False, "error": str(exc)}), flush=True)
            sys.exit(1)
        line["wall_s"] = round(time.perf_counter() - t_phase, 1)
        line["compile_cache"] = dict(cache)
        print(json.dumps(line), flush=True)
    print(f"chip_smoke: all phases ok in {time.perf_counter() - t0:.0f}s",
          file=sys.stderr)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
