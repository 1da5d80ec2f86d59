"""The loop thread on the profiler's clock.

(a) Under a real ``jax.profiler`` capture the loop ledger puts every task
step on the loop thread's line as ``sw/loop/<stage>`` (``other`` for a
task with no label), the selector's wait as ``sw/loop/asleep``, and the
program's other ``sw/`` spans nest inside one step; (b) outside a capture
a step is the coroutine's own bound method and the selector's ``select``
the loop's own, and no annotation object is built; (c) ``spanned``
refuses a coroutine function; (e) the benchmark's split of the chip's
idle time by the loop thread's state (``benchmark/metrics/_loop_trace``)
sums to 100, reads the loop thread's line only, and nothing before its
first ``sw/loop/`` event.
"""

import asyncio
import time
from pathlib import Path

import jax
import pytest

from benchmark.metrics import _loop_trace
from sitewhere_tpu.runtime import loopledger
from sitewhere_tpu.runtime.loopledger import spanned, sw
from sitewhere_tpu.runtime.metrics import MetricsRegistry


def _burn(cpu_s: float) -> None:
    t = time.thread_time()
    while time.thread_time() - t < cpu_s:
        pass


async def _until(cond, timeout_s: float = 10.0) -> None:
    t_end = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < t_end, "the ledger's tick never came"
        await asyncio.sleep(0.002)


def _sw_lines(trace_dir: Path) -> list:
    """Each host line's ``sw/`` events: (name, start, end, args)."""
    f = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(f))
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats))
                      for e in line.events if e.name.startswith("sw/")]
            if events:
                out.append(events)
    return out


# --------------------------------------------- (a) steps under a capture
async def test_a_capture_puts_each_step_and_the_sleep_on_the_loop_threads_line(
    tmp_path,
):
    reg = MetricsRegistry()
    ledger = reg.loop_ledger
    ledger.CYCLE, ledger.SLICE_S = 1, 0.01
    ledger.install(asyncio.get_running_loop())

    async def scoring():
        for _ in range(15):
            with sw("flush_assembly"):
                _burn(0.0005)
            t0 = ledger.clock()
            with sw("observe"):
                _burn(0.0002)
            ledger.observe_from(t0)
            await asyncio.sleep(0)

    async def unnamed():
        for _ in range(15):
            _burn(0.0003)
            await asyncio.sleep(0)

    async def sleeper():
        await asyncio.sleep(0.06)

    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            await _until(lambda: ledger.annotating)
            await asyncio.gather(
                asyncio.create_task(scoring(), name="tpu-inference-loop"),
                asyncio.create_task(unnamed()),
            )
            await asyncio.create_task(sleeper(), name="rule-processing[t1]")
        finally:
            jax.profiler.stop_trace()
    finally:
        ledger.uninstall()
    lines = [evs for evs in _sw_lines(tmp_path)
             if any(n.startswith("sw/loop/") for n, *_ in evs)]
    assert len(lines) == 1, "the steps lie on one line: the loop thread's"
    events = lines[0]
    loop = sorted((e for e in events if e[0].startswith("sw/loop/")),
                  key=lambda e: e[1])
    names = {n for n, *_ in loop}
    assert {"sw/loop/score", "sw/loop/other", "sw/loop/egress",
            "sw/loop/asleep"} <= names
    assert {args.get("task") for n, _a, _b, args in loop
            if n == "sw/loop/score"} == {"tpu-inference-loop"}
    # the steps and the sleeps never overlap: one thread, one at a time
    for (_n, _a, end), (_n2, start, _b) in zip(
        [e[:3] for e in loop], [e[:3] for e in loop[1:]]
    ):
        assert start >= end
    # the sleeper's 60 ms between its two steps is the selector's wait
    egress = [e for e in loop if e[0] == "sw/loop/egress"]
    t0, t1 = egress[0][2], egress[-1][1]
    asleep = sum(min(b, t1) - max(a, t0) for n, a, b, _ in loop
                 if n == "sw/loop/asleep" and b > t0 and a < t1)
    assert t1 - t0 >= 50e6
    assert asleep >= 0.7 * (t1 - t0)
    # every other ``sw/`` span nests inside one step
    steps = [e for e in loop if e[0] != "sw/loop/asleep"]
    inner = [e for e in events if not e[0].startswith("sw/loop/")]
    assert {n for n, *_ in inner} >= {"sw/flush_assembly", "sw/observe"}
    for name, a, b, _args in inner:
        assert any(sa <= a and b <= sb for _n, sa, sb, _ in steps), name


# ----------------------------------------------- (b) outside a capture
async def test_outside_a_capture_a_step_and_the_selector_are_the_loops_own(
    tmp_path, monkeypatch,
):
    reg = MetricsRegistry()
    ledger = reg.loop_ledger
    ledger.CYCLE, ledger.SLICE_S = 4, 0.005
    loop = asyncio.get_running_loop()
    sel = loop._selector
    ledger.install(loop)
    stop = asyncio.Event()
    made: list = []

    async def waiter():
        await stop.wait()
        _burn(0.001)

    tasks = [asyncio.create_task(waiter(), name="tpu-inference-loop"),
             asyncio.create_task(waiter())]
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            await _until(lambda: ledger.annotating)
            assert "select" in vars(sel)
        finally:
            jax.profiler.stop_trace()
        await _until(lambda: not ledger.annotating and not ledger.sampling)
        for task in tasks:
            wrapper = task.get_coro()
            assert type(wrapper) is loopledger._TimedCoro
            # the slot holds the coroutine's own bound method: no frame
            # of the ledger's runs in a step
            assert wrapper.send == wrapper._coro.send
            assert wrapper.throw == wrapper._coro.throw
            assert type(wrapper.send).__name__ == "builtin_function_or_method"
        assert "select" not in vars(sel)
        assert sel.select.__func__ is type(sel).select
        real = loopledger._TraceAnnotation

        def counting(*a, **kw):
            made.append(a)
            return real(*a, **kw)

        monkeypatch.setattr(loopledger, "_TraceAnnotation", counting)
        assert sw("flush_assembly") is loopledger._NO_SPAN
        stop.set()
        await asyncio.gather(*tasks)
        await asyncio.sleep(3 * ledger.SLICE_S * ledger.CYCLE)
    finally:
        ledger.uninstall()
    assert made == [], "an annotation was built outside a capture"


# ------------------------------------------- (c) no span across an await
def test_spanned_refuses_a_coroutine_function():
    async def handler():
        await asyncio.sleep(0)

    with pytest.raises(TypeError, match="await"):
        spanned("intake")(handler)

    @spanned("seal")
    def seal(x):
        return x + 1

    assert seal(1) == 2


# -------------------------------------- (e) the benchmark's idle split
# the loop thread's line (ns): asleep, a score step with a dispatch and an
# observe inside, an intake step a collection stopped, an egress step, an
# unlabeled task's step, then nothing from 35 on
LOOP = [
    ("sw/loop/asleep", 0, 10), ("sw/loop/score", 10, 20),
    ("sw/dispatch", 12, 14), ("sw/observe", 15, 17),
    ("sw/loop/intake", 20, 25), ("sw/gc", 21, 22),
    ("sw/loop/egress", 25, 30), ("sw/loop/other", 30, 35),
]
BUSY = [[-8, -2], [5, 6], [38, 39]]   # the chip; idle everywhere else


def test_loop_trace_split_names_every_idle_moment_once():
    shares = _loop_trace.split([LOOP], BUSY, -10.0, 40.0)
    # idle from the first step (0) on: [0,5] [6,38] [39,40] = 38 ns
    want = {"asleep": 9, "score": 8, "observe": 2, "intake": 4,
            "egress": 5, "other": 6 + 4}
    for state, ns in want.items():
        assert shares[state] == pytest.approx(100.0 * ns / 38), state
    assert shares[_loop_trace.NONE] == pytest.approx(100.0 * 4 / 38)
    assert sum(shares[s] for s in _loop_trace.STATES) == pytest.approx(100.0)


def test_loop_trace_reads_the_loop_threads_line_only_and_from_its_first_step():
    alone = _loop_trace.split([LOOP], BUSY, -10.0, 40.0)
    # an executor thread's span (a resolve held across the whole stretch)
    # claims none of the loop's idle time
    other_thread = [("sw/resolve", -10, 40), ("sw/h2d_stage", 1, 30)]
    assert _loop_trace.split([other_thread, LOOP], BUSY, -10.0, 40.0) == alone
    # the idle [-10, -8] and [-2, 0] lies before the first step: unread
    assert sum(alone[s] for s in _loop_trace.STATES) == pytest.approx(100.0)
    # no ``sw/loop/`` event anywhere (a program without the steps): None
    assert _loop_trace.split([other_thread], BUSY, -10.0, 40.0) is None
    assert _loop_trace.split([], BUSY, -10.0, 40.0) is None
    # a busy chip leaves nothing to split
    assert _loop_trace.split([LOOP], [[-10, 40]], -10.0, 40.0) is None


def test_loop_trace_readers_read_nothing_without_a_trace():
    ctx = {"trace": None}
    assert _loop_trace.shares(ctx) is None
    assert _loop_trace.share(ctx, "asleep") is None
