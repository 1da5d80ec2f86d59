"""Metrics: counters, gauges, streaming histograms with p50/p95/p99.

Capability parity with the reference's Prometheus metrics (3.0 per-service
registries: consumer lag, event counts — SURVEY.md §5 [U]; reference mount
empty, see provenance banner). The north-star metrics (events/sec scored,
p99 inference latency, tenants/chip — BASELINE.json:2) are first-class here;
a Prometheus-format scrape endpoint is exposed by ``api.rest``.

Two metric styles share one registry:

- **legacy unlabeled**: ``registry.counter("event_sources.decoded")`` —
  dotted names, exposed under their sanitized name unchanged (existing
  dashboards/tests keep working);
- **labeled families**: ``registry.counter("pipeline_stage_events",
  tenant="t1", stage="inbound")`` — proper Prometheus labels. Labeled
  counters are exposed with the ``_total`` suffix, label values are
  escaped, and every family gets ``# HELP``/``# TYPE`` lines
  (``tools/check_metrics.py`` lints the exposition).
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List, Optional, Tuple

from sitewhere_tpu.runtime.loopledger import LoopLedger


# a device→host materialization that returns faster than this never
# waited on the transfer — the boundary for the d2h_overlapped counters.
# Shared by the scoring reaper (tpu_inference.d2h_overlapped) and the
# media classify readback (media.d2h_overlapped) so their overlap
# fractions stay comparable. Lives here (not parallel/sharded.py) so
# jax-free consumers can import it without paying the jax import.
D2H_OVERLAP_EPS_S = 1e-3

# Published bf16 peak FLOP/s of one chip, keyed by the ``device_kind`` JAX
# reports — the MFU denominator chip_smoke.py looks up. A
# device that is not here is an error, not a default. Sources:
#   "TPU v5 lite": Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16)
PEAK_FLOPS_BF16_BY_KIND: Dict[str, float] = {"TPU v5 lite": 197e12}

# The live ``tpu_mfu_pct{family}`` gauge's denominator on EVERY backend,
# the CPU included: tests/test_flightrec.py reads the gauge > 0 on the CPU
# rig, so the gauge keeps the v5e peak there (a CPU reading is ~0 and
# means nothing). Anything that prints an MFU figure uses
# ``peak_flops_bf16`` instead, which refuses a CPU.
PEAK_FLOPS_BF16 = PEAK_FLOPS_BF16_BY_KIND["TPU v5 lite"]


def peak_flops_bf16(platform: str, device_kind: str) -> Optional[float]:
    """The published bf16 peak for the device JAX reports
    (``jax.devices()[0].platform`` / ``.device_kind``): None on a CPU —
    no MFU is printed there — and ``LookupError`` for an accelerator
    whose kind is not in ``PEAK_FLOPS_BF16_BY_KIND``."""
    if platform == "cpu":
        return None
    try:
        return PEAK_FLOPS_BF16_BY_KIND[device_kind]
    except KeyError:
        raise LookupError(
            f"no published bf16 peak for device_kind {device_kind!r}: add "
            f"it, with its source, to PEAK_FLOPS_BF16_BY_KIND"
        ) from None


# circuit-breaker state → gauge value (runtime.bus.CircuitBreaker publishes
# its transitions through a ``breaker.<name>.state`` gauge using this map,
# so breaker health rides the normal /metrics scrape + snapshot surface)
BREAKER_STATE_VALUES: Dict[str, float] = {
    "closed": 0.0,
    "open": 1.0,
    "half_open": 2.0,
}

LabelKey = Tuple[Tuple[str, str], ...]


class RollingQuantile:
    """Bounded sample window with a cheap cached quantile read.

    The flush supervisor's deadline source: each (family, mesh-slice)
    feeds its dispatch→transfer-landed seconds here, and the deadline
    for the NEXT flush is ``max(floor, x × quantile(0.99))`` — the
    deadline tracks the family's OWN recent latency instead of a global
    constant (docs/ROBUSTNESS.md "Device fault domains"). ``add`` is
    O(1) on the hot path; the sort amortizes over ``refresh_every``
    adds (the p99 of a 128-sample window moves slowly by construction,
    so a slightly stale read is fine — and the floor knob bounds the
    blast radius of any staleness)."""

    __slots__ = ("_buf", "_q", "_cached", "_since_sort", "refresh_every")

    MIN_SAMPLES = 8  # below this the caller's floor rules alone

    def __init__(
        self, window: int = 128, q: float = 0.99, refresh_every: int = 16
    ) -> None:
        from collections import deque

        self._buf = deque(maxlen=max(self.MIN_SAMPLES, int(window)))
        self._q = float(q)
        self._cached: Optional[float] = None
        self._since_sort = 0
        self.refresh_every = max(1, int(refresh_every))

    def add(self, v: float) -> None:
        self._buf.append(float(v))
        self._since_sort += 1
        if self._cached is None or self._since_sort >= self.refresh_every:
            self._recompute()

    def _recompute(self) -> None:
        self._since_sort = 0
        n = len(self._buf)
        if n < self.MIN_SAMPLES:
            self._cached = None
            return
        s = sorted(self._buf)
        self._cached = s[min(n - 1, int(self._q * n))]

    def quantile(self) -> Optional[float]:
        """The cached window quantile, or None under MIN_SAMPLES."""
        return self._cached

    def __len__(self) -> int:
        return len(self._buf)

    def values(self) -> tuple:
        """Window snapshot (oldest → newest) — offline analysis only;
        the hot path reads ``quantile()``."""
        return tuple(self._buf)


def _label_key(labels: Dict[str, str]) -> LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class Counter:
    __slots__ = ("name", "labels", "_v", "_lock")

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None) -> None:
        self.name = name
        self.labels = dict(labels) if labels else None
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self) -> float:
        return self._v


class Gauge:
    __slots__ = ("name", "labels", "value", "_lock")

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None) -> None:
        self.name = name
        self.labels = dict(labels) if labels else None
        self.value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        # synchronized: a read-modify-write user (inc) racing set() from a
        # scrape/collector thread must not lose updates
        with self._lock:
            self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n


def _latency_edges() -> List[float]:
    """Variable-resolution log bucket edges: coarse (ratio 1.25, ±12%)
    below 1 ms and above 1 s, fine (ratio 1.05, ±2.5%) through the
    1 ms–1 s band where every pipeline p99 of interest lives. ~200
    edges total, so bisect record stays O(log n) with zero per-sample
    storage."""
    edges: List[float] = []
    v = 1e-6
    while v < 1e-3 * 0.999:
        edges.append(v)
        v *= 1.25
    v = 1e-3
    while v < 1.0 * 0.999:
        edges.append(v)
        v *= 1.05
    v = 1.0
    while v <= 100.0:
        edges.append(v)
        v *= 1.25
    return edges


class Histogram:
    """Log-bucketed latency histogram with interpolated quantiles.

    Bucket edges come from ``_latency_edges`` (fine resolution in the
    1 ms–1 s band); quantiles interpolate linearly WITHIN the crossing
    bucket instead of returning its upper edge, so p50/p99 don't
    quantize to a fixed grid (round-4 verdict: edge-reporting repeated
    bit-identical p99s across configs at ±12% error).

    Reads (``quantile``/``summary``) copy the bucket state UNDER the
    lock: a scrape racing ``record`` from another thread must never see
    torn counts (a count bumped but ``_n`` not yet, which could push an
    interpolated quantile past ``_max``).
    """

    EDGES = _latency_edges()

    def __init__(
        self, name: str, unit: str = "s",
        labels: Optional[Dict[str, str]] = None,
    ) -> None:
        self.name = name
        self.unit = unit
        self.labels = dict(labels) if labels else None
        self._counts = [0] * (len(self.EDGES) + 1)
        self._sum = 0.0
        self._n = 0
        self._max = 0.0
        self._lock = threading.Lock()

    def _bucket(self, v: float) -> int:
        # bucket i covers (EDGES[i-1], EDGES[i]]; 0 is (-inf, EDGES[0]]
        return bisect.bisect_left(self.EDGES, v)

    def record(self, v: float) -> None:
        b = self._bucket(v)
        with self._lock:
            self._counts[b] += 1
            self._sum += v
            self._n += 1
            if v > self._max:
                self._max = v

    def record_many(self, vs) -> None:
        for v in vs:
            self.record(float(v))

    def reset(self) -> None:
        """Zero all buckets (bench phase boundaries)."""
        with self._lock:
            self._counts = [0] * len(self._counts)
            self._sum = 0.0
            self._n = 0
            self._max = 0.0

    def _state(self) -> Tuple[List[int], float, int, float]:
        """Consistent copy of (counts, sum, n, max) for lock-free math."""
        with self._lock:
            return list(self._counts), self._sum, self._n, self._max

    @property
    def count(self) -> int:
        return self._n

    @property
    def mean(self) -> float:
        with self._lock:
            return self._sum / self._n if self._n else 0.0

    @staticmethod
    def _quantile_from(
        counts: List[int], n: int, mx: float, q: float
    ) -> float:
        if not n:
            return 0.0
        target = q * n
        acc = 0
        for i, c in enumerate(counts):
            if acc + c >= target and c:
                lo = Histogram.EDGES[i - 1] if i > 0 else 0.0
                hi = Histogram.EDGES[i] if i < len(Histogram.EDGES) else mx
                hi = min(hi, mx) if mx else hi
                # linear interpolation within the crossing bucket
                frac = (target - acc) / c
                return min(lo + frac * max(hi - lo, 0.0), mx or hi)
            acc += c
        return mx

    def quantile(self, q: float) -> float:
        counts, _s, n, mx = self._state()
        return self._quantile_from(counts, n, mx, q)

    def summary(self) -> Dict[str, float]:
        # ONE consistent cut for all derived values — three separate
        # quantile() calls could straddle concurrent records
        counts, s, n, mx = self._state()
        return {
            "count": float(n),
            "mean": (s / n) if n else 0.0,
            "p50": self._quantile_from(counts, n, mx, 0.50),
            "p95": self._quantile_from(counts, n, mx, 0.95),
            "p99": self._quantile_from(counts, n, mx, 0.99),
            "max": mx,
        }


class MeterRate:
    """Sliding-window rate meter (events/sec over the last ``window_s``)."""

    def __init__(self, name: str, window_s: float = 10.0) -> None:
        self.name = name
        self.window_s = window_s
        self.labels: Optional[Dict[str, str]] = None
        self._events: List[Tuple[float, float]] = []  # (ts, n)
        self._first_mark: Optional[float] = None
        self._lock = threading.Lock()

    def mark(self, n: float = 1.0) -> None:
        now = time.time()
        with self._lock:
            if self._first_mark is None:
                self._first_mark = now
            self._events.append((now, n))
            cutoff = now - self.window_s
            i = bisect.bisect_left(self._events, (cutoff, -1.0))
            if i:
                del self._events[:i]

    def rate(self) -> float:
        now = time.time()
        with self._lock:
            cutoff = now - self.window_s
            total = sum(n for ts, n in self._events if ts >= cutoff)
            first = self._first_mark
        if first is None:
            return 0.0
        # right after startup the window hasn't filled: dividing by the
        # full window under-reports (1000 events in the first second of a
        # 10 s window is 1000/s, not 100/s). Floor the elapsed divisor so
        # a rate() immediately after the first mark stays finite.
        elapsed = min(self.window_s, max(now - first, 1e-3))
        return total / elapsed


class MfuAccount:
    """Live device-time & MFU attribution for one model family.

    Every resolved scoring flush (or media classify batch) reports the
    FLOPs the device executed (padded plane × analytic per-row flops —
    ``models.common``) and the wall seconds its dispatch was outstanding
    (dispatch → transfer landed). The account feeds three metric
    families:

    - ``tpu_flops_total{family}``          — executed model FLOPs;
    - ``tpu_device_seconds_total{family}`` — dispatch→ready seconds;
    - ``tpu_mfu_pct{family}``              — live gauge: FLOP/s over the
      sliding window ÷ ``peak`` × 100. The window rate reuses MeterRate,
      so the gauge is honest right after startup and decays to 0 when
      the family goes idle (refresh on read via :meth:`refresh`).
    """

    __slots__ = ("family", "peak", "_flops_c", "_secs_c", "_gauge", "_meter")

    # per-DEVICE attribution names (multi-chip serving): the slice-anchored
    # accounts must not share family names with the per-family aggregate —
    # mixing label sets under one name would double-count sum() over the
    # family (docs/OBSERVABILITY.md "Device-labeled metrics")
    DEVICE_NAMES = (
        "tpu_device_flops_total",
        "tpu_device_busy_seconds_total",
        "tpu_mfu_device_pct",
    )

    def __init__(
        self,
        registry: "MetricsRegistry",
        family: str,
        peak: float = PEAK_FLOPS_BF16,
        window_s: float = 10.0,
        flops_name: str = "tpu_flops_total",
        secs_name: str = "tpu_device_seconds_total",
        gauge_name: str = "tpu_mfu_pct",
        **extra_labels: str,
    ) -> None:
        self.family = family
        self.peak = float(peak)
        labels = {"family": family, **extra_labels}
        registry.describe(
            flops_name, "executed model FLOPs "
            "(analytic matmul count x padded plane rows)"
        )
        registry.describe(
            secs_name,
            "wall seconds scoring dispatches were outstanding "
            "(dispatch -> transfer landed)",
        )
        registry.describe(
            gauge_name, "live MFU: windowed FLOP/s / chip peak x 100"
        )
        self._flops_c = registry.counter(flops_name, **labels)
        self._secs_c = registry.counter(secs_name, **labels)
        self._gauge = registry.gauge(gauge_name, **labels)
        key = ".".join([family, *extra_labels.values()])
        self._meter = MeterRate(f"mfu.{key}", window_s=window_s)

    def record(self, flops: float, device_s: float) -> None:
        if flops <= 0 and device_s <= 0:
            return
        self._flops_c.inc(float(flops))
        self._secs_c.inc(max(0.0, float(device_s)))
        self._meter.mark(float(flops))
        self._gauge.set(100.0 * self._meter.rate() / self.peak)

    def refresh(self) -> float:
        """Re-derive the gauge from the current window (scrape-time decay
        for idle families); returns the pct."""
        pct = 100.0 * self._meter.rate() / self.peak
        self._gauge.set(pct)
        return pct


class MetricsRegistry:
    """Named metric registry; one per instance, shared across services."""

    def __init__(self) -> None:
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histos: Dict[str, Histogram] = {}
        self._meters: Dict[str, MeterRate] = {}
        # labeled families: name → {sorted-label-tuple → metric}
        self._labeled: Dict[str, Dict[LabelKey, object]] = {}
        self._kinds: Dict[str, str] = {}  # labeled family → prometheus kind
        self._help: Dict[str, str] = {}
        self._reg_lock = threading.Lock()
        # the event-loop ledger (loop_busy_seconds_total{stage}): lives
        # with the registry because every stage already holds one; it
        # registers nothing until the instance installs it on its loop
        self.loop_ledger = LoopLedger(self)

    def describe(self, name: str, help_text: str) -> None:
        """Attach a ``# HELP`` string to a metric family."""
        self._help[name] = help_text

    def _labeled_child(self, name: str, labels: Dict[str, str], kind: str,
                       factory) -> object:
        fam = self._labeled.get(name)
        if fam is None:
            with self._reg_lock:
                fam = self._labeled.setdefault(name, {})
                self._kinds[name] = kind
        key = _label_key(labels)
        m = fam.get(key)
        if m is None:
            with self._reg_lock:
                m = fam.get(key)
                if m is None:
                    m = fam[key] = factory()
        return m

    def counter(self, name: str, **labels: str) -> Counter:
        if labels:
            return self._labeled_child(
                name, labels, "counter", lambda: Counter(name, labels)
            )
        c = self._counters.get(name)
        if c is None:
            c = self._counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name: str, **labels: str) -> Gauge:
        if labels:
            return self._labeled_child(
                name, labels, "gauge", lambda: Gauge(name, labels)
            )
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges.setdefault(name, Gauge(name))
        return g

    def histogram(self, name: str, unit: str = "s", **labels: str) -> Histogram:
        if labels:
            return self._labeled_child(
                name, labels, "summary",
                lambda: Histogram(name, unit, labels),
            )
        h = self._histos.get(name)
        if h is None:
            h = self._histos.setdefault(name, Histogram(name, unit))
        return h

    def drop_labeled(self, families=None, **labels: str) -> int:
        """Remove every labeled child whose labels include ALL the given
        pairs (tenant teardown: a removed tenant's children must not be
        exported forever — label cardinality is bounded by LIVE tenants).
        ``families`` restricts the sweep to those family names — for
        callers that own only a slice of a tenant's children (e.g. the
        score-health layer on an engine stop) and must not reset other
        subsystems' counters mid-run. Returns the number removed."""
        want = {k: str(v) for k, v in labels.items()}
        removed = 0
        with self._reg_lock:
            items = (
                [(n, f) for n, f in self._labeled.items()
                 if n in set(families)]
                if families is not None
                else list(self._labeled.items())
            )
            for _name, fam in items:
                for key in [
                    k for k in fam
                    if all(dict(k).get(n) == v for n, v in want.items())
                ]:
                    fam.pop(key, None)
                    removed += 1
        return removed

    def meter(self, name: str, window_s: float = 10.0) -> MeterRate:
        m = self._meters.get(name)
        if m is None:
            m = self._meters.setdefault(name, MeterRate(name, window_s))
        return m

    def _snapshot_family(self, name: str, out: Dict[str, object]) -> None:
        """Serialize one family — unlabeled value/summary/rate plus every
        labeled child under its ``name{labels}`` key — into ``out``. The
        single definition snapshot() and snapshot_families() share, so
        the scrape and the metrics-history tick can't diverge."""
        c = self._counters.get(name)
        if c is not None:
            out[name] = c.value
        g = self._gauges.get(name)
        if g is not None:
            out[name] = g.value
        h = self._histos.get(name)
        if h is not None:
            out[name] = h.summary()
        m = self._meters.get(name)
        if m is not None:
            out[name] = m.rate()
        fam = self._labeled.get(name)
        if fam is not None:
            for _key, metric in list(fam.items()):
                k = f"{name}{{{_labels_text(metric.labels)}}}"
                if isinstance(metric, Histogram):
                    out[k] = metric.summary()
                else:
                    out[k] = metric.value

    def snapshot(self) -> Dict[str, object]:
        out: Dict[str, object] = {}
        names = (
            list(self._counters) + list(self._gauges)
            + list(self._histos) + list(self._meters)
            + list(self._labeled)
        )
        for n in dict.fromkeys(names):
            self._snapshot_family(n, out)
        return out

    def snapshot_families(self, names) -> Dict[str, object]:
        """``snapshot()`` restricted to the given family names (exact
        unlabeled keys and labeled families — children expand as usual).
        The metrics-history 1 s tick samples a ~20-family allowlist;
        paying a full-registry summary (every histogram child's
        interpolated quantiles) for it would scale the tick with total
        metric count instead of allowlist size."""
        out: Dict[str, object] = {}
        for n in names:
            self._snapshot_family(n, out)
        return out

    def prometheus_text(self) -> str:
        """Prometheus exposition format for the scrape endpoint.

        Legacy unlabeled metrics keep their historical names (aliases for
        existing dashboards); labeled families follow the conventions —
        ``_total``-suffixed counters, escaped label values, one
        ``# HELP``/``# TYPE`` pair per family.
        """
        lines: List[str] = []
        headed: set = set()

        def head(base: str, kind: str, src_name: str) -> None:
            if base in headed:
                return
            headed.add(base)
            help_text = self._help.get(src_name, f"{src_name} ({kind})")
            lines.append(f"# HELP {base} {_escape_help(help_text)}")
            lines.append(f"# TYPE {base} {kind}")

        # -- legacy unlabeled (names unchanged — alias surface) ----------
        for n, c in list(self._counters.items()):
            base = _sanitize(n)
            head(base, "counter", n)
            lines.append(f"{base} {c.value}")
        for n, g in list(self._gauges.items()):
            base = _sanitize(n)
            head(base, "gauge", n)
            lines.append(f"{base} {g.value}")
        for n, h in list(self._histos.items()):
            base = _sanitize(n)
            head(base, "summary", n)
            s = h.summary()
            for q, label in (("p50", "0.5"), ("p95", "0.95"), ("p99", "0.99")):
                lines.append(f'{base}{{quantile="{label}"}} {s[q]}')
            lines.append(f"{base}_sum {s['mean'] * s['count']}")
            lines.append(f"{base}_count {int(s['count'])}")
        for n, m in list(self._meters.items()):
            base = f"{_sanitize(n)}_rate"
            head(base, "gauge", n)
            lines.append(f"{base} {m.rate()}")

        # -- labeled families (new-style, conformant) --------------------
        # list() copies: a scrape must not race a first-time metric
        # creation on another thread into a dict-changed-size error
        for name, fam in list(self._labeled.items()):
            kind = self._kinds.get(name, "gauge")
            base = _sanitize(name)
            if kind == "counter" and not base.endswith("_total"):
                base += "_total"
            head(base, kind, name)
            for _key, metric in list(fam.items()):
                lbl = _labels_text(metric.labels)
                if isinstance(metric, Histogram):
                    s = metric.summary()
                    for q, ql in (("p50", "0.5"), ("p95", "0.95"),
                                  ("p99", "0.99")):
                        lines.append(
                            f'{base}{{{lbl},quantile="{ql}"}} {s[q]}'
                        )
                    lines.append(f"{base}_sum{{{lbl}}} {s['mean'] * s['count']}")
                    lines.append(f"{base}_count{{{lbl}}} {int(s['count'])}")
                else:
                    lines.append(f"{base}{{{lbl}}} {metric.value}")
        # OpenMetrics-compatible terminator: consumers use it to tell a
        # complete exposition from a truncated one (tools/check_metrics.py
        # lints for it)
        lines.append("# EOF")
        return "\n".join(lines) + "\n"


_ILLEGAL_CHARS = None


def _sanitize(name: str) -> str:
    """Map any string to a legal Prometheus metric name: every character
    outside [a-zA-Z0-9_:] becomes '_' (breaker names carry '[tenant]'
    brackets, stage names carry '.' and '-')."""
    global _ILLEGAL_CHARS
    if _ILLEGAL_CHARS is None:
        import re

        _ILLEGAL_CHARS = re.compile(r"[^a-zA-Z0-9_:]")
    out = _ILLEGAL_CHARS.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _escape_label_value(v: str) -> str:
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _escape_help(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def _labels_text(labels: Optional[Dict[str, str]]) -> str:
    if not labels:
        return ""
    return ",".join(
        f'{_sanitize(k)}="{_escape_label_value(v)}"'
        for k, v in sorted(labels.items())
    )
