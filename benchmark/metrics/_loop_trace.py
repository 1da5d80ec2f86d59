"""What the loop-thread readers share: the idle time of the fullest chip in
the traced stretch, split by what the program's event-loop thread was
doing. While a profiler capture runs, the program's loop ledger
(``runtime/loopledger.py``) puts every task step of the loop thread under
``sw/loop/<stage>`` (``intake|score|egress|other``), the selector's wait
under ``sw/loop/asleep`` and the tracing's own stretches under
``sw/observe``, on the thread's own line of the host plane and on the
device's clock.

Only that line is read — the host line that carries ``sw/loop/`` events —
so a span on an executor thread claims no loop idle; and only from its
first ``sw/loop/`` event on, since the ledger sees a capture at its next
tick. At each moment the innermost span names the state: ``sw/gc`` is
``other``, ``sw/observe`` is ``observe``, a ``sw/loop/*`` step is its
stage, any other ``sw/`` span is the state of the step around it, and a
moment in no step is ``other`` too (plain callbacks, the loop's own
machinery) — counted apart as ``unowned``. The six shares sum to 100.

Out of the newest profile under ``benchmark/_trace``; every function
returns None where there is nothing to read (no profile, a program
without the spans).
"""

from __future__ import annotations

from pathlib import Path

from benchmark import trace_reduce

TRACE_ROOT = Path(__file__).resolve().parents[1] / "_trace"
STATES = ("asleep", "intake", "score", "egress", "observe", "other")
LOOP = "sw/loop/"
NONE = "unowned"   # a moment in no step: part of ``other``
_CACHE: dict = {}


def _state_of(name: str, around: str) -> str:
    if name == "sw/gc":
        return "other"
    if name == "sw/observe":
        return "observe"
    if name.startswith(LOOP):
        state = name[len(LOOP):]
        return state if state in STATES and state != "observe" else "other"
    return around


def segments(events: list) -> list:
    """``(start, end, state)`` pieces of the loop thread's line, in order
    and without overlap, from its ``sw/`` events ``(name, start, end)``:
    each moment goes to its innermost span (a span is cut at the end of
    the one around it)."""
    out: list = []
    stack: list = []   # (end, state) of the open spans, innermost last
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    at = events[0][1] if events else 0.0

    def close_until(t: float) -> None:
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, state = stack.pop()
            if end > at:
                out.append((at, end, state))
                at = end

    for name, a, b in events:
        close_until(a)
        around = stack[-1][1] if stack else NONE
        if a > at:
            out.append((at, a, around))
            at = a
        if stack:
            b = min(b, stack[-1][0])
        stack.append((b, _state_of(name, around)))
    close_until(float("inf"))
    return out


def idle_gaps(busy: list, t0: float, t1: float) -> list:
    """The gaps of a chip's busy intervals (sorted, merged) in [t0, t1]."""
    gaps, at = [], t0
    for a, b in busy:
        if b <= at:
            continue
        if a > at:
            gaps.append((at, min(a, t1)))
        at = max(at, b)
        if at >= t1:
            break
    if t1 > at:
        gaps.append((at, t1))
    return [(a, b) for a, b in gaps if b > a]


def split(lines: list, busy: list, t_min: float, t_max: float):
    """Shares (%) of the chip's idle time by the loop thread's state, with
    ``unowned`` (the part of ``other`` that no step owns) beside them.
    ``lines``: each host line's ``sw/`` events ``(name, start, end)``;
    ``busy``: the fullest chip's busy intervals. None where no line
    carries a ``sw/loop/`` event or nothing is idle."""
    def steps(events):
        return [e for e in events if e[0].startswith(LOOP)]

    loop = max(lines, key=lambda evs: len(steps(evs)), default=[])
    first = steps(loop)
    if not first:
        return None
    t0 = max(t_min, min(a for _n, a, _b in first))
    seconds = dict.fromkeys((*STATES, NONE), 0.0)
    pieces = segments(loop)
    i = 0
    for g0, g1 in idle_gaps(busy, t0, t_max):
        covered = 0.0
        while i < len(pieces) and pieces[i][1] <= g0:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < g1:
            a, b, state = pieces[j]
            d = min(b, g1) - max(a, g0)
            if d > 0:
                seconds[state] += d
                covered += d
            j += 1
        seconds[NONE] += (g1 - g0) - covered
    total = sum(seconds.values())
    if total <= 0:
        return None
    shares = {k: 100.0 * v / total for k, v in seconds.items()}
    shares["other"] += shares[NONE]
    return shares


def _host_lines(path: str) -> list:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events if e.name.startswith("sw/")]
            if events:
                out.append(events)
    return out


def shares(ctx):
    """The split of the traced stretch's idle time, or None."""
    reduced = ctx["trace"]
    dev = trace_reduce.fullest(reduced) if reduced is not None else None
    if dev is None:
        return None
    files = sorted(TRACE_ROOT.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    key = (str(files[-1]), files[-1].stat().st_mtime)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = split(_host_lines(key[0]), dev["busy"],
                            reduced["t_min"], reduced["t_max"])
    return _CACHE[key]


def share(ctx, state: str):
    """One state's share (%) of the fullest chip's idle time, or None."""
    out = shares(ctx)
    return None if out is None else out[state]
