"""The event-loop ledger: every busy second of the host loop, by stage.

One event loop carries every host stage of the pipeline (receivers,
decode, inbound, lanes, flush, reaper, resolve, persist, rules,
outbound) and the tracing that watches them. ``host_loop_busy`` says how
full that thread is; this module says with WHAT:

- ``loop_busy_seconds_total{stage,task}`` — the loop thread's busy
  seconds by stage and, within a stage, by the kind of task (the task
  name's prefix; sum over ``task`` for the stage). ``intake``
  (receivers, decode, inbound), ``score`` (lanes, flush, reaper,
  resolve, publish), ``egress`` (persist, rules, outbound) and
  ``observe`` (time inside the tracing itself, moved out of the stage it
  ran under) are SELF time of task steps: a step is one ``coro.send`` —
  from a resume to the next suspension — so a handler that awaits is
  never charged for what ran meanwhile. ``other`` is the rest of the
  thread's CPU seconds (its CPU clock, read when the counter is): the
  steps of tasks with no label — timed too, and published apart as
  ``loop_unlabeled_task_seconds_total`` — the tasks older than the
  ledger (a harness's main task), plain callbacks, the loop's own
  machinery between steps, and the collector's pauses. So the children
  sum to the thread's busy time.
- ``runtime_gc_collections_total{generation}`` and
  ``runtime_gc_pause_seconds{generation}`` — the interpreter's
  collections, from ``gc.callbacks``. The clock steps are timed on
  stops for a pause of the loop thread: it is nobody's stage, so it
  stays in ``other``.

How a step gets its stage: a loop-wide task factory wraps each task's
coroutine in ``_TimedCoro``; the first time its counter is asked for,
the task's name (``SupervisedTask`` and every lifecycle component name
their tasks) is looked up by prefix in ``STAGE_OF_TASK``; a task whose
name says nothing inherits the stage of the task that created it.
``ledger.observe_from`` moves a synchronous stretch of a step to
``observe``: ``StageTimer.observe`` with the span, tail decision, ledger
feed and blackbox record under it; the flush record; the instance's
metrics-history tick.

What it costs: steps are timed on a DUTY CYCLE — one slice of
``LoopLedger.SLICE_S`` in every ``CYCLE``, each slice's seconds scaled
by its cycle's wall time over its own. Outside a slice a wrapped
coroutine's ``send`` IS the coroutine's own (a slot holding the bound
method, so the task calls straight into it) and ``observe_from`` is
never reached; what stays is one wrapper a task.

The steps are timed on ``time.perf_counter()``, the clock the flush
record is on, and both are mirrored into the profiler's trace by
``sw(<stage>)`` annotations at the stage entries, so one traced run puts
the ledger, the flush record and the device's operations on one clock.
Outside a capture an annotation is a flag test.
"""

from __future__ import annotations

import asyncio
import collections.abc
import contextlib
import functools
import gc
import inspect
import time
from threading import get_ident
from time import perf_counter
from typing import Any, Optional

STAGES = ("intake", "score", "egress", "observe", "other")

# task-name prefix → stage ("supervise:" is stripped first). The names
# are the lifecycle components' own (``LifecycleComponent.name``); the
# prefix, less its bracket, is the child's ``task`` label.
STAGE_OF_TASK = (
    ("pump:event-source[", "intake"),
    ("inbound-processing[", "intake"),
    ("device-registration[", "intake"),
    ("tpu-inference-loop", "score"),
    ("tpu-inference-reaper", "score"),
    ("tpu-inference-resolve", "score"),
    ("tpu-inference", "score"),
    ("media-pipeline[", "score"),
    ("event-persistence[", "egress"),
    ("rule-processing[", "egress"),
    ("outbound-connectors[", "egress"),
    ("connector[", "egress"),
    ("device-state[", "egress"),
    ("command-delivery[", "egress"),
)


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, at first use


def _capturing() -> bool:
    """Is a profiler capture running? ``TraceMe``'s own flag: at the
    first call this name is rebound to ``TraceAnnotation.is_enabled``
    (not at import: jax-free consumers import this module)."""
    global _TraceAnnotation, _capturing
    from jax.profiler import TraceAnnotation as _TraceAnnotation

    _capturing = _TraceAnnotation.is_enabled
    return _capturing()


# what ``sw`` hands back while no capture is running
_NO_SPAN = contextlib.nullcontext()


def sw(stage: str, **args: Any):
    """``jax.profiler.TraceAnnotation("sw/<stage>")`` — the program's own
    span on the profiler's clock. Unconditional: whenever a capture is
    running, whoever started it, the span is in it. The flag a
    ``TraceMe`` tests is tested here first, so that outside a capture no
    annotation object is built (three calls into the extension a span,
    on a loop that runs thousands a second)."""
    if not _capturing():
        return _NO_SPAN
    return _TraceAnnotation("sw/" + stage, **args)


async def _under(stage: str, coro):
    with _TraceAnnotation("sw/" + stage):
        return await coro


def spanned(stage: str):
    """Decorator: the whole call — a coroutine function's awaits
    included — runs under ``sw(stage)``: a handler's entry into its
    stage, on the profiler's clock. Outside a capture a coroutine
    function's call returns the function's own coroutine: no frame is
    added to the handler's every step."""
    def deco(fn):
        if inspect.iscoroutinefunction(fn):
            @functools.wraps(fn)
            def wrapped(*a, **kw):
                if not _capturing():
                    return fn(*a, **kw)
                return _under(stage, fn(*a, **kw))

            inspect.markcoroutinefunction(wrapped)
        else:
            @functools.wraps(fn)
            def wrapped(*a, **kw):
                with sw(stage):
                    return fn(*a, **kw)
        return wrapped

    return deco


def task_prefix(name: str) -> Optional[str]:
    """The ``STAGE_OF_TASK`` prefix a task's name falls under."""
    if name.startswith("Task-"):
        return None  # asyncio's default name: says nothing
    if name.startswith("supervise:"):
        name = name[len("supervise:"):]
    for prefix, _stage in STAGE_OF_TASK:
        if name.startswith(prefix):
            return prefix
    return None


def stage_of_task_name(name: str) -> Optional[str]:
    return dict(STAGE_OF_TASK).get(task_prefix(name))


def _timed(call, ledger: "LoopLedger", who: list):
    """``call`` — a coroutine's ``send`` or ``throw`` — as a timed step,
    charged to the counter ``who[0]``. The closure holds no reference
    to the wrapper that carries it, so the two make no cycle."""

    def step(*args):
        counter = who[0] or ledger.counter_of(asyncio.current_task())
        ledger.current = counter
        t0 = perf_counter() - ledger.paused
        try:
            return call(*args)
        finally:
            # Counter._v without its lock: the loop thread is the only
            # writer of these children, and a step must stay cheap
            counter._v += (
                perf_counter() - ledger.paused - t0 - ledger.moved
            )
            ledger.moved = 0.0
            ledger.current = None

    return step


class _TimedCoro:
    """A task's coroutine, as the task sees it. ``send`` and ``throw``
    are slots: outside a sampling slice they hold the wrapped
    coroutine's own bound methods (``_plain``), so a step costs what it
    cost without the ledger; inside one they hold ``_timed`` closures,
    made at the wrapper's first slice and kept — a slice's two edges
    swap what is there and allocate nothing (objects born at every edge
    would each count towards the collector's next full collection).
    ``_who`` is ``[counter the steps are charged to, the task that
    created this one]``, each None until / once the counter is first
    asked for."""

    __slots__ = ("_coro", "send", "throw", "_who", "_plain", "_timed")

    def __init__(self, coro, ledger: "LoopLedger", creator) -> None:
        self._coro = coro
        self._who = [None, creator]
        self._plain = (coro.send, coro.throw)
        self._timed = None
        if ledger.sampling:
            ledger.time(self)
        else:
            self.send, self.throw = self._plain

    def close(self):
        return self._coro.close()

    def __await__(self):
        return self

    def __getattr__(self, name):
        # cr_frame, cr_code, __name__, __qualname__ … for reprs, stack
        # dumps and debuggers
        return getattr(self._coro, name)


collections.abc.Coroutine.register(_TimedCoro)


class _RestCounter:
    """``loop_busy_seconds_total{stage="other"}``: the loop thread's CPU
    seconds since the ledger was installed, less what the labeled
    stages' steps took — computed when read. Where the platform has no
    per-thread CPU clock it is the unlabeled tasks' steps alone."""

    __slots__ = ("name", "labels", "_ledger")

    def __init__(self, name: str, labels: dict, ledger: "LoopLedger") -> None:
        self.name = name
        self.labels = labels
        self._ledger = ledger

    @property
    def value(self) -> float:
        return self._ledger.rest_seconds()


class LoopLedger:
    """Per-registry ledger of the event-loop thread's busy seconds.
    Built with the registry (no metric is registered until ``install``);
    the instance installs it on its loop at start."""

    # the duty cycle: steps are timed during one slice of ``SLICE_S``
    # seconds in every ``CYCLE`` and each slice's seconds are scaled by
    # the cycle's wall time over its own. ``CYCLE = 1`` times every step
    # (tests; a diagnosis that wants every step)
    SLICE_S = 0.1
    CYCLE = 8

    def __init__(self, metrics) -> None:
        self._metrics = metrics
        self.by_task: dict = {}   # STAGE_OF_TASK prefix → its counter
        self.observed = None      # the ``observe`` stage's counter
        self.unlabeled = None     # counter of the unlabeled tasks' steps
        self.sampling = False    # inside a sampling slice
        self.current = None      # counter of the timed step that is running
        self.moved = 0.0         # seconds ``observe`` took out of that step
        self.paused = 0.0        # seconds the collector has stopped the thread
        self.thread_id = 0
        self._loop = None
        self._prev_factory = None
        self._timer = None
        self._cycle_t0 = 0.0     # when the cycle began (perf_counter)
        self._slice_t0 = 0.0     # when its sampling slice began
        self._marks: list = []   # the counters' values at the slice's start
        self._cpu_clock = None   # the loop thread's CPU clock id
        self._cpu0 = 0.0
        self._rest_frozen = 0.0  # ``other`` as it stood at uninstall

    def rest_seconds(self) -> float:
        if self._loop is None:
            return self._rest_frozen
        if self._cpu_clock is None:
            return self.unlabeled.value
        cpu = time.clock_gettime(self._cpu_clock) - self._cpu0
        labeled = sum(c.value for c in self.by_task.values())
        return max(0.0, cpu - labeled - self.observed.value)

    # -- which counter a task's steps are charged to -------------------------
    def counter_of(self, task):
        """The counter of ``task``'s steps: its name's stage, else its
        creator's, else ``unlabeled``. None for a task the ledger did
        not wrap."""
        coro = task.get_coro() if task is not None else None
        if type(coro) is not _TimedCoro:
            return None
        who = coro._who
        if who[0] is None:
            prefix = task_prefix(task.get_name())
            who[0] = (
                self.by_task[prefix] if prefix is not None
                else self.counter_of(who[1]) or self.unlabeled
            )
            who[1] = None  # the creator is not kept alive past this
        return who[0]

    # -- the observe stage ---------------------------------------------------
    def clock(self) -> float:
        """``perf_counter()`` less the collector's pauses of the loop
        thread so far: the clock steps and ``observe`` stretches are
        timed on, so a pause inside one is no part of it."""
        return perf_counter() - self.paused

    def observe_from(self, t0: float) -> None:
        """Move the stretch of the running step since ``t0`` from its
        task's stage to ``observe``: the caller read ``t0`` where the
        tracing's own work began (``ledger.clock()``; on a hot path
        ``ledger.clock() if ledger.current is not None else 0.0`` —
        nothing is read while no step is timed) and calls this where it
        ends. The stretch must be synchronous (an ``await`` inside would
        move what ran meanwhile) and must not contain another."""
        if not t0 or self.current is None or get_ident() != self.thread_id:
            return
        dt = perf_counter() - self.paused - t0
        self.observed._v += dt
        self.moved += dt

    # -- the duty cycle ------------------------------------------------------
    def time(self, wrapper: _TimedCoro) -> None:
        if wrapper._timed is None:
            send, throw = wrapper._plain
            wrapper._timed = (
                _timed(send, self, wrapper._who),
                _timed(throw, self, wrapper._who),
            )
        wrapper.send, wrapper.throw = wrapper._timed

    def _wrappers(self):
        for task in asyncio.all_tasks(self._loop):
            coro = task.get_coro()
            if type(coro) is _TimedCoro:
                yield coro

    def _counters(self):
        return [*self.by_task.values(), self.observed, self.unlabeled]

    def _open_slice(self) -> None:
        self.sampling = True
        self._slice_t0 = perf_counter()
        self._marks = [c._v for c in self._counters()]
        for wrapper in self._wrappers():
            self.time(wrapper)

    def _close_slice(self) -> None:
        """The slice stands for its whole cycle: what each counter
        gained in it is scaled by the cycle's wall time over the
        slice's."""
        now = perf_counter()
        self.sampling = False
        for wrapper in self._wrappers():
            wrapper.send, wrapper.throw = wrapper._plain
        took = now - self._slice_t0
        if took > 0.0:
            scale = (now - self._cycle_t0) / took
            for counter, mark in zip(self._counters(), self._marks):
                counter._v += (counter._v - mark) * (scale - 1.0)
        self._cycle_t0 = now

    def _tick(self) -> None:
        # a plain callback: no task is inside a step while it runs
        if self._loop is None:
            return
        if self.sampling:
            self._close_slice()
            wait = self.SLICE_S * (self.CYCLE - 1)
        else:
            self._open_slice()
            wait = self.SLICE_S
        self._timer = self._loop.call_later(wait, self._tick)

    # -- the task factory ----------------------------------------------------
    def install(self, loop) -> None:
        if self._loop is not None:
            return
        m = self._metrics
        m.describe(
            "loop_busy_seconds_total",
            "the event-loop thread's busy seconds by stage "
            "(intake|score|egress|observe|other) and, within a stage, by "
            "the kind of task: self time of task steps (sampled: one "
            "slice in a cycle, scaled), but for other — the rest of the "
            "thread's CPU seconds; the children sum to the thread's busy "
            "time",
        )
        m.describe(
            "loop_unlabeled_task_seconds_total",
            "self time of the steps of tasks with no stage label — the "
            "part of loop_busy_seconds_total{stage=other} that tasks own",
        )
        for prefix, stage in STAGE_OF_TASK:
            self.by_task[prefix] = m.counter(
                "loop_busy_seconds_total", stage=stage,
                task=prefix.rstrip("["),
            )
        self.observed = m.counter(
            "loop_busy_seconds_total", stage="observe", task=""
        )
        rest = {"stage": "other", "task": ""}
        m._labeled_child(
            "loop_busy_seconds_total", rest, "counter",
            lambda: _RestCounter("loop_busy_seconds_total", rest, self),
        )
        self.unlabeled = m.counter("loop_unlabeled_task_seconds_total")
        self.thread_id = get_ident()
        if hasattr(time, "pthread_getcpuclockid"):
            self._cpu_clock = time.pthread_getcpuclockid(self.thread_id)
            self._cpu0 = time.clock_gettime(self._cpu_clock)
        self._loop = loop
        self._prev_factory = loop.get_task_factory()
        loop.set_task_factory(self._factory)
        # the first slice opens now (start-up is timed whole) and a
        # one-slice cycle never closes it
        self._cycle_t0 = perf_counter()
        self._open_slice()
        if self.CYCLE > 1:
            self._timer = loop.call_later(self.SLICE_S, self._tick)

    def uninstall(self) -> None:
        if self._loop is None:
            return
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self.sampling:
            self._close_slice()
        self._rest_frozen = self.rest_seconds()
        loop, self._loop = self._loop, None
        if loop.get_task_factory() == self._factory:
            loop.set_task_factory(self._prev_factory)
        # a later ledger chained over this one keeps calling the factory:
        # with no loop it passes coroutines through untimed

    def _factory(self, loop, coro, **kwargs):
        if self._loop is not None:
            # the creating task: what an unnamed child inherits from
            coro = _TimedCoro(coro, self, asyncio.current_task(loop))
        prev = self._prev_factory
        if prev is not None:
            return prev(loop, coro, **kwargs)
        return asyncio.Task(coro, loop=loop, **kwargs)


class GcAccount:
    """``runtime_gc_collections_total{generation}`` and
    ``runtime_gc_pause_seconds{generation}`` from ``gc.callbacks``, each
    collection under a ``sw/gc`` annotation. The pauses that make the
    scored tail are the program's own numbers."""

    def __init__(self, metrics) -> None:
        metrics.describe(
            "runtime_gc_collections_total",
            "collections of the interpreter's cycle collector, by "
            "generation",
        )
        metrics.describe(
            "runtime_gc_pause_seconds",
            "how long one collection stopped the interpreter, by "
            "generation",
        )
        self._count = [
            metrics.counter("runtime_gc_collections_total", generation=str(g))
            for g in range(3)
        ]
        self._pause = [
            metrics.histogram("runtime_gc_pause_seconds", generation=str(g))
            for g in range(3)
        ]
        self._ledger = metrics.loop_ledger
        self._t0 = 0.0
        self._span = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = sw("gc", generation=info["generation"])
            self._span.__enter__()
            self._t0 = perf_counter()
            return
        dt = perf_counter() - self._t0
        span, self._span = self._span, None
        if span is None:
            return  # installed mid-collection
        span.__exit__(None, None, None)
        gen = info["generation"]
        self._count[gen].inc()
        self._pause[gen].record(dt)
        ledger = self._ledger
        if get_ident() == ledger.thread_id:
            # the clock of the ledger's steps stops for the pause: it is
            # nobody's stage and stays in ``other``
            ledger.paused += dt

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def uninstall(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
