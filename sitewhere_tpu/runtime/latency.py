"""End-to-end latency attribution: stage ledgers, p99 decomposition,
SLO burn rates.

ROADMAP item 3 names latency the headline deficit (e2e p99 ~197 ms vs
the paper's 50 ms target), and every raw signal already exists — the
per-stage spans with their queue-wait/service split (``runtime.tracing``),
the flush sub-stage profile the flight recorder keeps, the
``RollingQuantile`` windows the flush supervisor runs on. This module
JOINS them. It answers the one question none of those surfaces answer
alone: *which stage, tenant, and priority class own the p99?*

Mechanism
---------
``Tracer._decide`` feeds EVERY deciding trace — kept or dropped — into
the engine (``ingest_trace``). Each trace flattens into an additive
per-stage vector (``stage_vector``): the spans' queue-wait/service
split maps onto the canonical stage axis

    ingest → decode → inbound → lane_wait → permit_wait → flush_assembly
    → h2d_stage → dispatch → inflight → resolve → persistence → rules
    → outbound

where the inference span's service time (lane enqueue → published on
scored-events) is cut at the boundaries of the batch's OWN flush: the
span carries the ``flush_id`` of the flush that completed the batch,
and the flush record (``TpuInferenceService.flush_records``) holds that
flush's contiguous ``time.perf_counter()`` stamps — permit asked, permit
got, assembled, h2d staged, dispatch returned, transfer landed. The
cuts are contiguous, so the seven sub-stages sum to the span exactly;
nothing is scaled. ``lane_wait`` (enqueue → permit asked) and
``resolve`` (landed → this batch published) are the batch's own; the
five between are its flush's. ``inflight`` is where an event waits
behind the flushes queued on the device before its own
(``tpu_inference.d2h_wait`` lies inside it and stays a histogram only).
``rules`` runs on the persisted-events fork concurrently with outbound,
so it is recorded in the waterfall but excluded from the additive
critical path.

Decomposition is additive **by construction**: the per-(tenant,
priority) ledger keeps a bounded window of whole vectors, picks the
cohort of traces ranked around the p99, and averages each stage over
that cohort — stage contributions + inter-stage residual equal the
cohort mean exactly, and the cohort mean tracks the p99 by
construction. No quantile-of-stage-quantiles fallacy (stage p99s do
not add; cohort means do).

Burn rate: per tenant, 10 s buckets over a 1 h ring give the 5 min /
1 h breach fractions; burn = breach_fraction / error_budget where the
budget is ``1 - SLO_TARGET``. The ``slo_burn`` watchdog rule
(``runtime.history``) pages when BOTH windows burn hot — the classic
multi-window guard: the short window proves it is happening now, the
long window proves it is not a blip.

Hot-path contract: ``ingest_trace`` runs once per TRACE at tail-decide
time (per batch, not per event), is O(spans), allocates one small dict,
and self-times — ``overhead()`` reports cumulative seconds so the bench
can assert attribution costs <2% of step time.
"""

from __future__ import annotations

import time
from array import array
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from sitewhere_tpu.runtime.metrics import MetricsRegistry, RollingQuantile

# the canonical stage axis — waterfall row order and the additive path
STAGES = (
    "ingest", "decode", "inbound", "lane_wait", "permit_wait",
    "flush_assembly", "h2d_stage", "dispatch", "inflight", "resolve",
    "persistence", "rules", "outbound",
)

# rules consumes the persisted-events fork CONCURRENTLY with outbound:
# it shows in the waterfall but never in the additive e2e path
PATH_STAGES = tuple(s for s in STAGES if s != "rules")

# where a stage's [wait, service] pair sits in a ledger entry (slot 0 is
# the trace total)
_ENTRY_AT = {s: 1 + 2 * i for i, s in enumerate(STAGES)}
_ENTRY_ZEROS = bytes(8 * (1 + 2 * len(STAGES)))

# the inference span's contiguous cuts: (stage it ends, the flush
# record's perf_counter stamp that ends it). ``lane_wait`` runs from the
# span's start to the first stamp; ``resolve`` from the last to its end.
_FLUSH_CUTS = (
    ("lane_wait", "t_asked"),
    ("permit_wait", "t_got"),
    ("flush_assembly", "t_assembled"),
    ("h2d_stage", "t_staged"),
    ("dispatch", "t_dispatched"),
    ("inflight", "t_landed"),
)


def stage_vector(
    tr: Any, flushes: Optional[Any] = None
) -> Tuple[Dict[str, List[float]], float]:
    """Flatten one TraceRecord into the additive per-stage vector:
    ``{stage: [queue_wait_ms, service_ms]}`` plus the trace total.
    ``flushes`` maps a ``flush_id`` to its flush record (``.get``); an
    inference span whose flush is not there (no id, evicted, resolved
    unscored) stays whole under ``lane_wait``.
    Multiple spans of one LINEAR stage (sequential sub-batches) sum;
    fork stages (rules/outbound — one sibling span per connector,
    concurrent) keep their slowest sibling, since summing overlapped
    spans would attribute more wall-clock than the trace spent."""
    vec: Dict[str, List[float]] = {}

    def acc(stage: str, wait: float, service: float) -> None:
        cell = vec.get(stage)
        if cell is None:
            vec[stage] = [wait, service]
        else:
            cell[0] += wait
            cell[1] += service

    for s in tr.spans:
        st = s.stage
        wait = max(0.0, s.queue_wait_ms)
        service = max(0.0, s.end_ms - s.start_ms)
        if st == "decode":
            # the decode span's queue wait IS the ingest stage: transport
            # receive → decode start (receiver-queue time)
            acc("ingest", 0.0, wait)
            acc("decode", 0.0, service)
        elif st == "inference":
            rec = None
            if flushes is not None:
                rec = flushes.get(s.annotations.get("flush_id"))
            if rec is None or "t_landed" not in rec:
                acc("lane_wait", wait, service)
                continue
            # the record's stamps are perf_counter seconds; ``ts_ms`` is
            # the wall clock at ``t_dispatched`` — the one anchor that
            # puts them on the span's clock. Each cut is clamped into
            # the span, so the pieces sum to it whatever the clocks did.
            anchor = rec["ts_ms"] - rec["t_dispatched"] * 1e3
            at = s.start_ms
            for stage, stamp in _FLUSH_CUTS:
                cut = min(max(anchor + rec[stamp] * 1e3, at), s.end_ms)
                acc(stage, wait if stage == "lane_wait" else 0.0, cut - at)
                at = cut
            acc("resolve", 0.0, s.end_ms - at)
        elif st in ("inbound", "persistence"):
            acc(st, wait, service)
        elif st in ("rules", "outbound"):
            # fork siblings run concurrently: the trace's cost for the
            # stage is its slowest sibling, not the overlapped sum
            cell = vec.get(st)
            if cell is None or wait + service > cell[0] + cell[1]:
                vec[st] = [wait, service]
        # stages outside the canonical axis (receiver shed markers,
        # command fan-out) fall into the residual on purpose
    return vec, max(0.0, tr.duration_ms)


class _BurnAccount:
    """One tenant's SLO breach accounting: 10 s buckets in a 1 h ring.
    ``note`` is O(1); ``fraction`` sums at most 360 buckets on read."""

    BUCKET_S = 10.0
    __slots__ = ("_ring",)

    def __init__(self) -> None:
        # deque of [bucket_id, total, breached]
        self._ring: deque = deque(maxlen=int(3600 / self.BUCKET_S))

    def note(self, breached: bool, now_s: float) -> None:
        bid = int(now_s / self.BUCKET_S)
        if self._ring and self._ring[-1][0] == bid:
            cell = self._ring[-1]
        else:
            cell = [bid, 0, 0]
            self._ring.append(cell)
        cell[1] += 1
        if breached:
            cell[2] += 1

    def fraction(self, window_s: float, now_s: float) -> Optional[float]:
        """Breach fraction over the trailing window; None when no
        samples landed in it (no traffic ≠ zero breach rate)."""
        lo = int((now_s - window_s) / self.BUCKET_S)
        total = breached = 0
        for bid, t, b in reversed(self._ring):
            if bid <= lo:
                break
            total += t
            breached += b
        if total == 0:
            return None
        return breached / total


class StageLedger:
    """One (tenant, priority) cohort's rolling attribution state: the
    vector window the decomposition reads, plus per-stage and e2e
    RollingQuantile windows for the live gauges."""

    WINDOW = 512
    __slots__ = ("tenant", "priority", "entries", "stage_q", "e2e_q")

    def __init__(self, tenant: str, priority: str) -> None:
        self.tenant = tenant
        self.priority = priority
        # one array('d') a trace: total_ms, then (wait_ms, service_ms) per
        # stage of STAGES. A window of dicts of lists was some fifteen
        # containers a trace for the collector to walk in every full
        # collection — times WINDOW, times every tenant; an array is none
        self.entries: deque = deque(maxlen=self.WINDOW)
        self.stage_q: Dict[str, RollingQuantile] = {}
        self.e2e_q = RollingQuantile(window=256)

    def add(self, vec: Dict[str, List[float]], total_ms: float) -> None:
        entry = array("d", _ENTRY_ZEROS)
        entry[0] = total_ms
        self.entries.append(entry)
        self.e2e_q.add(total_ms)
        for stage, (wait, service) in vec.items():
            at = _ENTRY_AT[stage]
            entry[at] = wait
            entry[at + 1] = service
            q = self.stage_q.get(stage)
            if q is None:
                q = self.stage_q[stage] = RollingQuantile(window=256)
            q.add(wait + service)

    # -- decomposition -----------------------------------------------------
    MIN_DECOMPOSE = 8

    def decompose(self) -> Optional[Dict[str, Any]]:
        """Additive p99 budget: average each stage over the cohort of
        traces RANKED around the p99 — contributions + residual sum to
        the cohort mean exactly, and the cohort mean tracks the p99."""
        n = len(self.entries)
        if n < self.MIN_DECOMPOSE:
            return None
        ranked = sorted(self.entries, key=lambda e: e[0])
        p99_idx = min(n - 1, int(0.99 * n))
        p99 = ranked[p99_idx][0]
        half = max(1, n // 64)
        cohort = ranked[max(0, p99_idx - half):min(n, p99_idx + half + 1)]
        m = len(cohort)
        mean_total = sum(e[0] for e in cohort) / m
        stages: List[Dict[str, Any]] = []
        attributed = 0.0
        for stage in STAGES:
            at = _ENTRY_AT[stage]
            wait = sum(e[at] for e in cohort) / m
            service = sum(e[at + 1] for e in cohort) / m
            tot = wait + service
            if stage in PATH_STAGES:
                attributed += tot
            stages.append({
                "stage": stage,
                "queue_wait_ms": round(wait, 3),
                "service_ms": round(service, 3),
                "total_ms": round(tot, 3),
                "on_path": stage in PATH_STAGES,
                "share": round(tot / mean_total, 4) if mean_total > 0 else 0.0,
            })
        return {
            "n": n,
            "cohort": m,
            "e2e_p99_ms": round(p99, 3),
            "cohort_mean_ms": round(mean_total, 3),
            "stages": stages,
            "residual_ms": round(max(0.0, mean_total - attributed), 3),
        }

    def dominant_stage(self) -> str:
        """The on-path stage owning the largest share of the p99 cohort
        ('' below the decomposition floor)."""
        d = self.decompose()
        if d is None:
            return ""
        best = max(
            (s for s in d["stages"] if s["on_path"]),
            key=lambda s: s["total_ms"],
            default=None,
        )
        return best["stage"] if best and best["total_ms"] > 0 else ""


def dominant_stage_of(tr: Any, flushes: Optional[Any] = None) -> str:
    """One retained trace's dominant stage (critical-path extractor unit):
    the on-path stage with the largest wait+service in ITS OWN vector."""
    vec, _total = stage_vector(tr, flushes)
    best, best_ms = "", 0.0
    for stage in PATH_STAGES:
        cell = vec.get(stage)
        if cell is None:
            continue
        ms = cell[0] + cell[1]
        if ms > best_ms:
            best, best_ms = stage, ms
    return best


class LatencyEngine:
    """The per-instance attribution engine: ledgers keyed (tenant,
    priority), burn accounts keyed tenant, live gauges, and the query
    surface REST serves. Wired by the instance: ``tracer.latency`` feeds
    it, the watchdog reads ``worst_burn``, ``/api/latency`` reads the
    reports."""

    MAX_LEDGERS = 256          # (tenant, priority) cardinality bound
    SLO_TARGET = 0.99          # error budget = 1 - target
    BURN_FAST_S = 300.0        # 5 min page window
    BURN_SLOW_S = 3600.0       # 1 h confirm window

    def __init__(self, metrics: Optional[MetricsRegistry] = None) -> None:
        self.metrics = metrics or MetricsRegistry()
        self._ledgers: "OrderedDict[Tuple[str, str], StageLedger]" = (
            OrderedDict()
        )
        self._burn: Dict[str, _BurnAccount] = {}
        self._slo_ms: Dict[str, float] = {}   # last-seen SLO per tenant
        # tracing bridge, set by the instance (read-only here): the
        # critical-path extractor walks tracer.store's retained ring
        self.tracer = None
        # flush_id → flush record, set by the instance to the scoring
        # service's ``flush_records``: what ``stage_vector`` cuts each
        # inference span by
        self.flushes = None
        # self-timing: the bench's attribution-overhead key reads these
        self.ingest_calls = 0
        self.ingest_secs = 0.0
        m = self.metrics
        m.describe(
            "latency_e2e_p99_ms",
            "rolling end-to-end p99 per tenant and priority class "
            "(latency ledger window)",
        )
        m.describe(
            "latency_stage_p99_ms",
            "rolling per-stage p99 (queue wait + service) per tenant, "
            "priority class and canonical stage",
        )
        m.describe(
            "latency_slo_burn",
            "SLO error-budget burn rate per tenant and window "
            "(1.0 = burning exactly the budget)",
        )
        m.describe(
            "latency_ledger_errors",
            "trace vectors the latency ledger failed to ingest",
        )

    # -- feed (Tracer._decide) --------------------------------------------
    def ingest_trace(self, tr: Any, slo_ms: float) -> None:
        """One deciding trace → ledger vector + burn accounting. Must
        never raise into the tail decision; errors count and drop."""
        t0 = time.perf_counter()
        try:
            priority = getattr(tr, "priority", "") or "measurement"
            key = (tr.tenant, priority)
            led = self._ledgers.get(key)
            if led is None:
                if len(self._ledgers) >= self.MAX_LEDGERS:
                    self._ledgers.popitem(last=False)
                led = self._ledgers[key] = StageLedger(tr.tenant, priority)
            self._ledgers.move_to_end(key)
            vec, total = stage_vector(tr, self.flushes)
            led.add(vec, total)
            self._slo_ms[tr.tenant] = float(slo_ms)
            if priority != "replay":
                # backfill cohorts get attribution but never burn the
                # live SLO budget — replayed history is not a breach
                acct = self._burn.get(tr.tenant)
                if acct is None:
                    acct = self._burn[tr.tenant] = _BurnAccount()
                acct.note(total >= slo_ms, time.time())
        except Exception:  # noqa: BLE001 - attribution must never break
            # the tail decision; the error is counted, not raised
            self.metrics.counter("latency_ledger_errors").inc()
        finally:
            self.ingest_calls += 1
            self.ingest_secs += time.perf_counter() - t0

    def remove_tenant(self, tenant: str) -> None:
        for key in [k for k in self._ledgers if k[0] == tenant]:
            del self._ledgers[key]
        self._burn.pop(tenant, None)
        self._slo_ms.pop(tenant, None)
        self.metrics.drop_labeled(
            families=(
                "latency_e2e_p99_ms", "latency_stage_p99_ms",
                "latency_slo_burn",
            ),
            tenant=tenant,
        )

    # -- burn rates --------------------------------------------------------
    def burn_rates(self, tenant: str) -> Dict[str, Optional[float]]:
        acct = self._burn.get(tenant)
        budget = max(1e-6, 1.0 - self.SLO_TARGET)
        out: Dict[str, Optional[float]] = {"burn_5m": None, "burn_1h": None}
        if acct is None:
            return out
        now = time.time()
        for name, win in (
            ("burn_5m", self.BURN_FAST_S), ("burn_1h", self.BURN_SLOW_S)
        ):
            frac = acct.fraction(win, now)
            out[name] = round(frac / budget, 3) if frac is not None else None
        return out

    def worst_burn(self) -> Optional[Dict[str, Any]]:
        """The hottest tenant by 5 min burn, with its 1 h confirmation,
        dominant stage, and SLO — the slo_burn watchdog rule's read."""
        worst: Optional[Dict[str, Any]] = None
        for tenant in self._burn:
            rates = self.burn_rates(tenant)
            b5 = rates["burn_5m"]
            if b5 is None:
                continue
            if worst is None or b5 > worst["burn_5m"]:
                worst = {
                    "tenant": tenant,
                    "burn_5m": b5,
                    "burn_1h": rates["burn_1h"],
                    "stage": self._dominant_for_tenant(tenant),
                    "slo_ms": self._slo_ms.get(tenant, 0.0),
                }
        return worst

    def _dominant_for_tenant(self, tenant: str) -> str:
        best, best_ms = "", -1.0
        for (t, _p), led in self._ledgers.items():
            if t != tenant:
                continue
            d = led.decompose()
            if d is None:
                continue
            stage = led.dominant_stage()
            if stage:
                ms = next(
                    s["total_ms"] for s in d["stages"] if s["stage"] == stage
                )
                if ms > best_ms:
                    best, best_ms = stage, ms
        return best

    # -- critical-path extractor (tail-retained traces) -------------------
    def breach_cohorts(
        self, tenant: str = "", worst_n: int = 5
    ) -> List[Dict[str, Any]]:
        """SLO-breach cohorts over the retained ring, grouped by
        (tenant, dominant stage), each naming its worst-N traces —
        the 'which traces do I open' list for the current incident."""
        if self.tracer is None:
            return []
        groups: Dict[Tuple[str, str], List[Any]] = {}
        for tr in self.tracer.store.list(tenant=tenant, limit=512,
                                         include_active=False):
            # decision == "slo" covers clean breaches; a forced trace
            # (retry/dlq/error) that ALSO breached keeps its forced
            # reason, so check the duration against the tenant SLO too
            slo = self._slo_ms.get(tr.tenant)
            if tr.decision != "slo" and not (
                slo is not None and tr.duration_ms >= slo
            ):
                continue
            stage = dominant_stage_of(tr, self.flushes) or "unattributed"
            groups.setdefault((tr.tenant, stage), []).append(tr)
        out: List[Dict[str, Any]] = []
        for (t, stage), trs in groups.items():
            trs.sort(key=lambda r: r.duration_ms, reverse=True)
            out.append({
                "tenant": t,
                "stage": stage,
                "count": len(trs),
                "worst": [
                    {
                        "trace_id": r.trace_id,
                        "duration_ms": round(r.duration_ms, 3),
                        # the trace detail always carries .traceEvents
                        # (chrome://tracing / Perfetto)
                        "chrome": f"/api/traces/{r.trace_id}",
                    }
                    for r in trs[:max(1, worst_n)]
                ],
            })
        out.sort(key=lambda c: c["count"], reverse=True)
        return out

    # -- gauges (history tick) --------------------------------------------
    def refresh_gauges(self) -> None:
        m = self.metrics
        for (tenant, priority), led in self._ledgers.items():
            p99 = led.e2e_q.quantile()
            if p99 is not None:
                m.gauge(
                    "latency_e2e_p99_ms", tenant=tenant, priority=priority
                ).set(round(p99, 3))
            for stage, q in led.stage_q.items():
                sp = q.quantile()
                if sp is not None:
                    m.gauge(
                        "latency_stage_p99_ms",
                        tenant=tenant, priority=priority, stage=stage,
                    ).set(round(sp, 3))
        for tenant in self._burn:
            rates = self.burn_rates(tenant)
            for name, win in (("burn_5m", "5m"), ("burn_1h", "1h")):
                v = rates[name]
                if v is not None:
                    m.gauge(
                        "latency_slo_burn", tenant=tenant, window=win
                    ).set(v)

    # -- query surface (REST) ---------------------------------------------
    def overhead(self) -> Dict[str, Any]:
        return {
            "ingest_calls": self.ingest_calls,
            "ingest_secs": round(self.ingest_secs, 6),
            "per_call_us": round(
                self.ingest_secs / self.ingest_calls * 1e6, 3
            ) if self.ingest_calls else 0.0,
        }

    def fleet_report(self) -> Dict[str, Any]:
        """The fleet waterfall: one merged decomposition over every
        ledger window plus the per-(tenant, priority) summaries."""
        merged = StageLedger("", "")
        cohorts: List[Dict[str, Any]] = []
        for (tenant, priority), led in self._ledgers.items():
            merged.entries.extend(led.entries)
            d = led.decompose()
            cohorts.append({
                "tenant": tenant,
                "priority": priority,
                "n": len(led.entries),
                "e2e_p99_ms": (
                    round(led.e2e_q.quantile(), 3)
                    if led.e2e_q.quantile() is not None else None
                ),
                "dominant_stage": led.dominant_stage(),
                "decomposition": d,
            })
        cohorts.sort(key=lambda c: c["e2e_p99_ms"] or 0.0, reverse=True)
        return {
            "stages": list(STAGES),
            "fleet": merged.decompose(),
            "cohorts": cohorts,
            "burn": {t: self.burn_rates(t) for t in sorted(self._burn)},
            "overhead": self.overhead(),
        }

    def tenant_report(self, tenant: str, worst_n: int = 5) -> Dict[str, Any]:
        priorities = {}
        for (t, priority), led in self._ledgers.items():
            if t != tenant:
                continue
            priorities[priority] = {
                "n": len(led.entries),
                "e2e_p99_ms": (
                    round(led.e2e_q.quantile(), 3)
                    if led.e2e_q.quantile() is not None else None
                ),
                "dominant_stage": led.dominant_stage(),
                "decomposition": led.decompose(),
            }
        return {
            "tenant": tenant,
            "slo_ms": self._slo_ms.get(tenant),
            "priorities": priorities,
            "burn": self.burn_rates(tenant),
            "breach_cohorts": self.breach_cohorts(tenant, worst_n=worst_n),
        }

    def snapshot_context(self) -> Dict[str, Any]:
        """Compact context embedded into flight-recorder snapshots: the
        hottest cohorts only — incident evidence, not the full report."""
        out: List[Dict[str, Any]] = []
        for (tenant, priority), led in self._ledgers.items():
            p99 = led.e2e_q.quantile()
            if p99 is None:
                continue
            out.append({
                "tenant": tenant,
                "priority": priority,
                "e2e_p99_ms": round(p99, 3),
                "dominant_stage": led.dominant_stage(),
            })
        out.sort(key=lambda c: c["e2e_p99_ms"], reverse=True)
        return {"cohorts": out[:8]}
