"""Device-fault injection: the chaos layer for the TPU fault domain.

PR 1's ``runtime.bus.FaultPlan`` injects *host* faults (dropped/failed
publishes) and proved the at-least-once pipeline; this module is its
DEVICE twin. The hazards it models are the ones a real fleet sees from
a sick chip or a poisoned batch, none of which RAISE at the dispatch
site — they simply never complete, complete late, or complete wrong:

- ``hang_dispatch``   — the dispatched program never finishes: the
  result array never becomes ready and its host materialization blocks
  forever (a wedged device queue / XLA deadlock).
- ``hang_transfer``   — device compute finishes (``is_ready`` True) but
  the d2h copy never crosses the link (stuck DMA).
- ``fail_after_delay``— the result errors out, but only after
  ``delay_s`` of looking in-flight (late XLA runtime error).
- ``corrupt_result``  — the transfer lands, full of NaN garbage
  (bit-flipped HBM, a kernel scribbling past a bound).
- ``slow_chip``       — everything completes, ``delay_s`` late per
  flush (thermal throttling, a contended ICI link) — the "one slow
  chip must not drag healthy slices" scenario.
- ``fail_dispatch``   — the dispatch call itself raises (the classic
  poison batch: data that deterministically crashes the kernel). This
  is the one kind that surfaces at the call site, so the poison-batch
  ejection path (retry once, then DLQ) can be driven per-nth-flush.

Faults select by model family, mesh slice, lane (``serve`` / ``train``
/ ``shadow`` / ``probe`` / ``media`` / ``retry`` — the poison-retry
dispatch carries its own lane so a chaos plan can target the second
strike deterministically), every-nth-matching-flush, and a
first-N budget — composable enough for "hang slice 2's serve lane on
every 3rd flush, twice" in one declaration, mirroring how
``FaultPlan.fail_p`` wired through the bus in PR 1.

Injection is a pure wrapper: the service asks the plan to ``wrap`` a
dispatched device array (or ``wrap_callable`` an executor
materialization), and the returned :class:`FaultyResult` proxy applies
the fault inside ``__array__`` — exactly where the completion reaper's
executor materialization would block on a real wedged device. The
flush supervisor therefore exercises the IDENTICAL code path chaos is
meant to prove (``docs/ROBUSTNESS.md`` "Device fault domains").

Hung proxies block on a plan-wide release event with a bounded safety
timeout; ``clear()`` releases every hung thread (tests and teardown
MUST call it — a worker thread parked in ``__array__`` would otherwise
outlive the test and pin interpreter exit).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

DEVICE_FAULT_KINDS = (
    "hang_dispatch",
    "hang_transfer",
    "fail_after_delay",
    "corrupt_result",
    "slow_chip",
    "fail_dispatch",
)

# a hung proxy never blocks a worker thread longer than this even if a
# buggy test forgets clear() — the interpreter must always be able to
# exit once the pool shuts down
HANG_SAFETY_TIMEOUT_S = 600.0


class InjectedDeviceFault(RuntimeError):
    """Raised by ``fail_dispatch`` / ``fail_after_delay`` injections."""


@dataclass
class DeviceFault:
    """One injectable device fault + its selectors (empty = match all)."""

    kind: str
    families: Tuple[str, ...] = ()
    slices: Tuple[int, ...] = ()
    lanes: Tuple[str, ...] = ()
    nth: int = 1          # fire on every nth MATCHING flush
    first_n: int = 0      # total firing budget (0 = unlimited)
    delay_s: float = 0.05  # fail_after_delay latency / slow_chip stall
    # internal: matching/firing tallies (per-plan bookkeeping)
    seen: int = field(default=0, compare=False)
    fired: int = field(default=0, compare=False)

    def __post_init__(self) -> None:
        if self.kind not in DEVICE_FAULT_KINDS:
            raise ValueError(
                f"kind must be one of {DEVICE_FAULT_KINDS}, got "
                f"{self.kind!r}"
            )

    def selects(self, family: str, sl: int, lane: str) -> bool:
        if self.families and family not in self.families:
            return False
        if self.slices and sl not in self.slices:
            return False
        if self.lanes and lane not in self.lanes:
            return False
        return True


class DeviceFaultPlan:
    """An ordered set of :class:`DeviceFault`\\ s consulted at dispatch.

    Event-loop-threaded like the bus FaultPlan: ``match`` runs at the
    dispatch site; only the *applied* fault behavior (sleep / block /
    raise) runs on worker threads, reading nothing but the fault record
    and the plan-wide release event.
    """

    def __init__(self, *faults: DeviceFault) -> None:
        self.faults = list(faults)
        self._release = threading.Event()
        self.cleared = False
        self.injected = 0   # total faults applied (test assertions)

    # -- selection -------------------------------------------------------
    def match(self, family: str, sl: int, lane: str) -> Optional[DeviceFault]:
        """The fault (if any) this (family, slice, lane) dispatch draws.
        First matching declaration wins; nth/first_n tallies advance per
        fault so independent faults pace independently."""
        if self.cleared:
            return None
        for f in self.faults:
            if f.kind == "fail_dispatch":
                # dispatch-site faults fire ONLY via maybe_raise — a
                # wrap() draw would silently consume their nth/first_n
                # budget on an inert proxy (fail_dispatch has no
                # blocking/corrupting behavior to apply post-dispatch)
                continue
            if not f.selects(family, sl, lane):
                continue
            if f.first_n and f.fired >= f.first_n:
                continue
            f.seen += 1
            if f.nth > 1 and f.seen % f.nth:
                continue
            f.fired += 1
            self.injected += 1
            return f
        return None

    def clear(self) -> None:
        """Drop every fault and release every hung materialization —
        the 'fault cleared / device healed' transition (probation probes
        start landing after this)."""
        self.cleared = True
        self.faults = []
        self._release.set()

    # -- application -----------------------------------------------------
    def wrap(self, result, family: str, sl: int, lane: str):
        """Consult the plan for one dispatched device array; returns the
        array untouched (no fault) or a :class:`FaultyResult` proxy."""
        fault = self.match(family, sl, lane)
        if fault is None:
            return result
        return FaultyResult(result, fault, self)

    def maybe_raise(self, family: str, sl: int, lane: str) -> None:
        """``fail_dispatch`` injection point — call just before the jit
        dispatch; raises :class:`InjectedDeviceFault` when drawn."""
        for f in self.faults:
            if f.kind != "fail_dispatch":
                continue
            if not f.selects(family, sl, lane):
                continue
            if f.first_n and f.fired >= f.first_n:
                continue
            f.seen += 1
            if f.nth > 1 and f.seen % f.nth:
                continue
            f.fired += 1
            self.injected += 1
            raise InjectedDeviceFault(
                f"injected fail_dispatch ({family}@s{sl}/{lane})"
            )

    def wrap_callable(self, fn, family: str, sl: int, lane: str):
        """Fault a worker-thread materialization callable (the media
        classify readback): hang / delay-then-fail / stall apply around
        ``fn``; ``corrupt_result`` has no array to corrupt here and
        passes through."""
        fault = self.match(family, sl, lane)
        if fault is None:
            return fn
        plan = self

        def faulted(*args, **kwargs):
            _apply_blocking(fault, plan)
            return fn(*args, **kwargs)

        return faulted


def _apply_blocking(fault: DeviceFault, plan: DeviceFaultPlan) -> None:
    """The worker-thread half of a fault: block / stall / raise. Hangs
    park on the plan's release event (bounded) so ``clear()`` frees
    them."""
    kind = fault.kind
    if kind in ("hang_dispatch", "hang_transfer"):
        plan._release.wait(HANG_SAFETY_TIMEOUT_S)
        return
    if kind == "fail_after_delay":
        time.sleep(fault.delay_s)
        raise InjectedDeviceFault(
            f"injected fail_after_delay ({fault.delay_s}s)"
        )
    if kind == "slow_chip":
        time.sleep(fault.delay_s)


# ---------------------------------------------------------------------
# host fault domain (ISSUE 16): the HOST twin of the device plan above.
# A host fault never raises at a scoring site — it starves the lease
# control plane (runtime.hostlease) the way a dead/wedged/partitioned
# process starves a real coordinator:
#
# - ``kill9`` / ``sigstop`` — whole-process faults. The in-process plan
#   cannot deliver these to itself; the multi-process chaos harness
#   (tests/test_host_chaos.py) sends the actual signals and the plan
#   records them for selector symmetry only.
# - ``renew_blackhole``  — the lease-renewal frame is silently dropped
#   before it reaches the wire (a one-way partition on the control
#   plane: the host looks alive to itself, dead to the coordinator).
# - ``partition``        — every lease-plane call raises
#   ConnectionError (full netbus partition as the client experiences
#   it; data-plane faults ride the bus FaultPlan, not this one).
# - ``slow_heartbeat``   — each renewal is delayed ``delay_s`` before
#   it is sent (a GC-pausing / overcommitted host whose heartbeats
#   straggle toward the TTL edge).
#
# Faults select by host id and op ("acquire" / "renew"), pace by nth /
# first_n exactly like DeviceFault, and can bound themselves with
# ``duration_s`` (the fault self-heals — the partition that ends).

HOST_FAULT_KINDS = (
    "kill9",
    "sigstop",
    "renew_blackhole",
    "partition",
    "slow_heartbeat",
    # broker fault domain: stall the warm standby's replication tail by
    # delay_s per poll (consulted by netbus.StandbyReplicator with
    # host="standby", op="repl") — the replication-lag gauge must grow
    # visibly instead of the standby silently serving stale state
    "repl_stall",
)


class InjectedHostFault(ConnectionError):
    """Raised by ``partition`` injections on the lease plane."""


@dataclass
class HostFault:
    """One injectable host fault + its selectors (empty = match all)."""

    kind: str
    hosts: Tuple[str, ...] = ()
    ops: Tuple[str, ...] = ()    # "acquire" / "renew" (empty = all)
    nth: int = 1                 # fire on every nth MATCHING call
    first_n: int = 0             # total firing budget (0 = unlimited)
    delay_s: float = 0.05        # slow_heartbeat stall per renewal
    duration_s: float = 0.0      # fault lifetime from first firing (0 = forever)
    # internal: matching/firing tallies (per-plan bookkeeping)
    seen: int = field(default=0, compare=False)
    fired: int = field(default=0, compare=False)
    started: float = field(default=0.0, compare=False)

    def __post_init__(self) -> None:
        if self.kind not in HOST_FAULT_KINDS:
            raise ValueError(
                f"kind must be one of {HOST_FAULT_KINDS}, got {self.kind!r}"
            )

    def selects(self, host: str, op: str) -> bool:
        if self.hosts and host not in self.hosts:
            return False
        if self.ops and op not in self.ops:
            return False
        return True

    def expired(self, now: float) -> bool:
        return bool(
            self.duration_s and self.started
            and now - self.started >= self.duration_s
        )


class HostFaultPlan:
    """An ordered set of :class:`HostFault`\\ s consulted by the lease
    client at each control-plane call. Injectable + clearable exactly
    like :class:`DeviceFaultPlan`: ``match`` at the call site,
    ``clear()`` heals everything, ``injected`` counts applications for
    test assertions."""

    def __init__(self, *faults: HostFault) -> None:
        self.faults = list(faults)
        self.cleared = False
        self.injected = 0

    def add(self, fault: HostFault) -> None:
        """Inject one more fault into a live plan (the chaos harness
        drives this over the host-control topic mid-run). Re-arms a
        previously cleared plan — inject/clear/inject must work."""
        self.cleared = False
        self.faults.append(fault)

    def match(self, host: str, op: str) -> Optional[HostFault]:
        """The fault (if any) this (host, op) control-plane call draws.
        First matching declaration wins; duration-expired faults are
        dropped in place (the partition that healed)."""
        if self.cleared:
            return None
        now = time.monotonic()
        self.faults = [f for f in self.faults if not f.expired(now)]
        for f in self.faults:
            if f.kind in ("kill9", "sigstop"):
                continue  # process-level: the harness delivers signals
            if not f.selects(host, op):
                continue
            if f.first_n and f.fired >= f.first_n:
                continue
            f.seen += 1
            if f.nth > 1 and f.seen % f.nth:
                continue
            if not f.started:
                f.started = now
            f.fired += 1
            self.injected += 1
            return f
        return None

    def clear(self) -> None:
        """Drop every fault — the 'partition healed / host recovered'
        transition (probation heartbeats start landing after this)."""
        self.cleared = True
        self.faults = []


class FaultyResult:
    """Proxy over a dispatched device array applying one fault at the
    points the result path actually touches: ``is_ready`` (the reaper's
    landed() probe), ``copy_to_host_async`` (issued at dispatch), and
    ``__array__`` (the executor materialization)."""

    __slots__ = ("_inner", "_fault", "_plan")

    def __init__(self, inner, fault: DeviceFault, plan: DeviceFaultPlan):
        self._inner = inner
        self._fault = fault
        self._plan = plan

    # -- result-path surface ---------------------------------------------
    def is_ready(self) -> bool:
        if self._fault.kind == "hang_dispatch" and not self._plan.cleared:
            return False  # compute "never finishes"
        try:
            return bool(self._inner.is_ready())
        except Exception:  # noqa: BLE001 - numpy/test doubles
            return True

    def copy_to_host_async(self) -> None:
        if self._fault.kind in ("hang_dispatch", "hang_transfer"):
            return  # the copy "never starts/lands"
        try:
            self._inner.copy_to_host_async()
        except Exception:  # noqa: BLE001 - numpy/test doubles
            pass

    @property
    def nbytes(self) -> int:
        return int(getattr(self._inner, "nbytes", 0))

    @property
    def shape(self):
        return getattr(self._inner, "shape", ())

    def __array__(self, dtype=None, copy=None):
        _apply_blocking(self._fault, self._plan)
        arr = np.asarray(self._inner)
        if self._fault.kind == "corrupt_result" and not self._plan.cleared:
            arr = np.full_like(
                np.asarray(arr, np.float32), np.nan
            ).astype(arr.dtype, copy=False)
        if dtype is not None:
            arr = arr.astype(dtype, copy=False)
        return arr
