"""Async-safety analyzer suite (ISSUE 15).

Covers, per the check_hotpath test pattern: a catches-fixture proving
each of check_async's five rules fires, the opt-out and stale-registry
paths for each, the shipped tree's cleanliness, the shared ``astlib``
core (opt-out grammar, call-graph executor hops, parse cache), the
single-sourced ``tools/registries.py`` (every legacy tool reads it),
the CoAP handler-supervision regression (the fire-and-forget fix this
analyzer surfaced), and the ``lint_all`` smoke: every analyzer runs
clean on the shipped tree inside a wall-clock budget.
"""

import asyncio
import importlib.util
import socket
import sys
import textwrap
import time
from pathlib import Path

_TOOLS = Path(__file__).resolve().parent.parent / "tools"


def _load(name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


astlib = _load("astlib")
registries = _load("registries")
check_async = _load("check_async")
lint_all = _load("lint_all")


def _lint(src_root, **over):
    """lint_async over a fixture tree: every registry empty unless the
    test overrides it, every async def a reachability root."""
    kw = dict(
        root_dirs=("*",), blocking_leaves={}, commit_sections={},
        counter_pairs={}, thread_shared={},
    )
    kw.update(over)
    return check_async.lint_async(src_root=src_root, **kw)


def _write(tmp_path, source: str, name: str = "mod.py") -> Path:
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return p


# ---------------------------------------------- rule 1: blocking reach
def test_blocking_catches_direct_indirect_and_honors_executor(tmp_path):
    _write(tmp_path, """\
        import asyncio
        import time

        def helper():
            with open("/tmp/x") as fh:
                return fh.read()

        class S:
            async def direct(self):
                time.sleep(0.1)

            async def indirect(self):
                helper()

            async def hopped(self):
                await asyncio.get_running_loop().run_in_executor(
                    None, helper
                )
        """)
    findings = _lint(tmp_path)
    rules = [f.rule for f in findings]
    assert rules.count("blocking-in-coroutine") == 2, findings
    text = "\n".join(str(f) for f in findings)
    assert "time.sleep" in text
    assert "open() is sync file I/O" in text
    assert "via S.indirect" in text
    # the executor hop is NOT an edge: 'hopped' contributes nothing
    assert "hopped" not in text


def test_blocking_opt_out_reason_and_empty(tmp_path):
    _write(tmp_path, """\
        import time

        class S:
            async def reasoned(self):
                time.sleep(0.1)  # async: ok(chaos-only path, parked rig)

            async def empty(self):
                time.sleep(0.1)  # async: ok()
        """)
    findings = _lint(tmp_path)
    assert len(findings) == 1, findings
    assert "names no reason" in findings[0].msg


def test_blocking_boundary_opt_out_clears_the_chain(tmp_path):
    _write(tmp_path, """\
        import os

        def commit():
            os.fsync(3)

        class S:
            async def cold(self):
                commit()  # async: ok(control-plane cold path)
        """)
    assert _lint(tmp_path) == []


def test_blocking_leaf_registry_fires_and_names_the_leaf(tmp_path):
    _write(tmp_path, """\
        def native_decode(buf):
            return buf

        class S:
            async def hot(self):
                return native_decode(b"x")
        """)
    findings = _lint(
        tmp_path,
        blocking_leaves={"mod.py::native_decode": "ctypes native decode"},
    )
    assert len(findings) == 1, findings
    assert "native_decode" in findings[0].msg
    assert "ctypes native decode" in findings[0].msg


def test_blocking_thread_lock_acquire_and_event_wait(tmp_path):
    _write(tmp_path, """\
        import threading

        _GATE = threading.Event()

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            async def a(self):
                self._lock.acquire()

            async def b(self):
                _GATE.wait()
        """)
    findings = _lint(tmp_path)
    text = "\n".join(f.msg for f in findings)
    assert "threading.Lock.acquire() parks the thread" in text
    assert "threading.Event.wait() parks the thread" in text


# -------------------------------------------- rule 2: lock-across-await
def test_lock_across_await_catches_and_allows_async_lock(tmp_path):
    _write(tmp_path, """\
        import asyncio
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._alock = asyncio.Lock()

            async def bad(self):
                with self._lock:
                    await asyncio.sleep(0)

            async def fine(self):
                async with self._alock:
                    await asyncio.sleep(0)

            async def excused(self):
                with self._lock:
                    await asyncio.sleep(0)  # async: ok(lock uncontended at start)
        """)
    findings = [f for f in _lint(tmp_path) if f.rule == "lock-across-await"]
    assert len(findings) == 1, findings
    assert "bad" in findings[0].qual
    assert "threading.Lock" in findings[0].msg


def test_lock_across_await_sees_past_nested_defs(tmp_path):
    # regression: a lambda/nested def earlier in the with-body must not
    # end the scan — only ITS OWN body is exempt (it runs off-loop)
    _write(tmp_path, """\
        import asyncio
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            async def bad(self):
                with self._lock:
                    cb = lambda: 1
                    def helper():
                        return 2
                    await asyncio.sleep(0)

            async def fine(self):
                with self._lock:
                    cb = lambda: asyncio.sleep(0)
        """)
    findings = [f for f in _lint(tmp_path) if f.rule == "lock-across-await"]
    assert len(findings) == 1, findings
    assert "bad" in findings[0].qual


# --------------------------------------- rule 3: cancellation-atomicity
_COMMIT_SRC = """\
    import asyncio

    class Pump:
        async def run(self, bus, job):
            await bus.publish(job)
            {gap}
            self.persist(job)

        def persist(self, job):
            pass
    """


def test_commit_section_catches_await_between_pair(tmp_path):
    _write(tmp_path, _COMMIT_SRC.format(gap="await asyncio.sleep(0)"))
    sections = {"mod.py": [{
        "function": "Pump.run", "name": "publish→persist",
        "begin": "publish", "end": "persist",
    }]}
    findings = _lint(tmp_path, commit_sections=sections)
    assert len(findings) == 1, findings
    assert findings[0].rule == "cancellation-atomicity"
    assert "publish→persist" in findings[0].msg

    # await-free pair is clean
    _write(tmp_path, _COMMIT_SRC.format(gap="x = 1"))
    assert _lint(tmp_path, commit_sections=sections) == []


def test_commit_section_stale_ops_name_the_missing_symbol(tmp_path):
    _write(tmp_path, _COMMIT_SRC.format(gap="x = 1"))
    findings = _lint(tmp_path, commit_sections={"mod.py": [{
        "function": "Pump.run", "name": "n",
        "begin": "publish", "end": "commit_cursor",
    }]})
    assert len(findings) == 1
    assert findings[0].rule == "stale-registry"
    assert "missing symbol: commit_cursor" in findings[0].msg

    findings = _lint(tmp_path, commit_sections={"mod.py": [{
        "function": "Pump.gone", "name": "n",
        "begin": "publish", "end": "persist",
    }]})
    assert len(findings) == 1
    assert "missing symbol: Pump.gone" in findings[0].msg


def test_counter_pair_requires_finally(tmp_path):
    _write(tmp_path, """\
        class S:
            async def leaky(self):
                self.work()
                self.sem.release()

            async def tight(self):
                try:
                    self.work()
                finally:
                    self.sem.release()

            def work(self):
                pass
        """)
    pairs = {"mod.py": [
        {"function": "S.leaky", "name": "permit", "op": "release",
         "kind": "call"},
        {"function": "S.tight", "name": "permit", "op": "release",
         "kind": "call"},
    ]}
    findings = _lint(tmp_path, counter_pairs=pairs)
    assert len(findings) == 1, findings
    assert "leaky" in findings[0].qual
    assert "outside a finally" in findings[0].msg


def test_counter_pair_augassign_kind(tmp_path):
    _write(tmp_path, """\
        class S:
            def bad(self, n):
                self._inflight -= n

            def good(self, n):
                try:
                    pass
                finally:
                    self._inflight -= n
        """)
    pairs = {"mod.py": [
        {"function": "S.bad", "name": "inflight", "op": "_inflight",
         "kind": "augassign"},
        {"function": "S.good", "name": "inflight", "op": "_inflight",
         "kind": "augassign"},
    ]}
    findings = _lint(tmp_path, counter_pairs=pairs)
    assert len(findings) == 1, findings
    assert "S.bad" == findings[0].qual


# ------------------------------------------- rule 4: unsupervised-task
def test_unsupervised_task_catches_dropped_results(tmp_path):
    _write(tmp_path, """\
        import asyncio

        class S:
            async def dropped(self):
                asyncio.create_task(self.work())

            async def dropped_ensure(self):
                asyncio.ensure_future(self.work())

            async def stored(self):
                self._t = asyncio.create_task(self.work())

            async def awaited(self):
                await asyncio.create_task(self.work())

            async def gathered(self):
                await asyncio.gather(
                    *[asyncio.create_task(self.work()) for _ in range(2)]
                )

            async def excused(self):
                asyncio.create_task(self.work())  # async: ok(daemon probe; dies with the loop by design)

            async def empty_excuse(self):
                asyncio.create_task(self.work())  # async: ok

            async def work(self):
                pass
        """)
    findings = [
        f for f in _lint(tmp_path) if f.rule == "unsupervised-task"
    ]
    assert len(findings) == 3, findings
    msgs = "\n".join(f.msg for f in findings)
    assert msgs.count("fire-and-forget") == 2
    assert "names no supervisor" in msgs


# --------------------------------------- rule 5: cross-thread-mutation
def test_cross_thread_mutation_requires_lock_on_both_sides(tmp_path):
    _write(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                self._m = 0

            def exec_unlocked(self):
                self._n += 1

            def exec_locked(self):
                with self._lock:
                    self._m += 1

            async def loop_side(self):
                self._n = 0
                with self._lock:
                    self._m = 0
        """)
    shared = {"mod.py": [{
        "class": "S",
        "executor_fns": ["S.exec_unlocked", "S.exec_locked"],
        "loop_fns": ["S.loop_side"],
        "locks": ["_lock"],
    }]}
    findings = _lint(tmp_path, thread_shared=shared)
    assert len(findings) == 1, findings
    assert findings[0].rule == "cross-thread-mutation"
    assert "'self._n'" in findings[0].msg
    assert "_m" not in findings[0].msg


def test_cross_thread_stale_function_is_a_finding(tmp_path):
    _write(tmp_path, "class S:\n    pass\n")
    findings = _lint(tmp_path, thread_shared={"mod.py": [{
        "class": "S", "executor_fns": ["S.gone"], "loop_fns": [],
        "locks": [],
    }]})
    assert len(findings) == 1
    assert findings[0].rule == "stale-registry"
    assert "missing symbol: S.gone" in findings[0].msg


# ------------------------------------------------- the shipped tree
def test_check_async_lint_is_clean():
    """The analyzer's tier-1 wiring: zero unsuppressed findings over
    sitewhere_tpu/ (the ISSUE 15 acceptance bar)."""
    assert check_async.lint_async() == []


def test_shipped_opt_outs_carry_reasons():
    """Every '# async: ok' annotation in the tree names its reason —
    the analyzer treats an empty one as a finding, so a clean tree plus
    this grep proves the grammar is used as designed."""
    src = astlib.SRC_ROOT
    hits = []
    for p in src.rglob("*.py"):
        if "__pycache__" in str(p):
            continue
        for lineno, line in enumerate(p.read_text().splitlines(), 1):
            status, reason = astlib.opt_out([line], 1, "async")
            if status != astlib.OPT_OUT_MISSING:
                hits.append((str(p.relative_to(src)), lineno, reason))
    assert hits, "expected at least one deliberate # async: ok(...) site"
    assert all(reason for (_f, _l, reason) in hits), hits


# ------------------------------------------------------- astlib core
def test_opt_out_grammar_statuses():
    lines = [
        "x = 1",
        "x = 1  # async: ok",
        "x = 1  # async: ok()",
        "x = 1  # async: ok(the reaper owns this)",
        "x = 1  # hotpath: ok",
    ]
    assert astlib.opt_out(lines, 1, "async")[0] == astlib.OPT_OUT_MISSING
    assert astlib.opt_out(lines, 2, "async")[0] == astlib.OPT_OUT_EMPTY
    assert astlib.opt_out(lines, 3, "async")[0] == astlib.OPT_OUT_EMPTY
    status, reason = astlib.opt_out(lines, 4, "async")
    assert status == astlib.OPT_OUT_REASON
    assert reason == "the reaper owns this"
    # namespaces are isolated
    assert astlib.opt_out(lines, 5, "async")[0] == astlib.OPT_OUT_MISSING
    assert astlib.opt_out(lines, 5, "hotpath")[0] == astlib.OPT_OUT_EMPTY


def test_call_graph_edges_and_executor_targets(tmp_path):
    _write(tmp_path, """\
        import asyncio

        def leaf():
            pass

        def caller():
            leaf()

        class S:
            async def run(self):
                caller()
                await asyncio.get_running_loop().run_in_executor(
                    None, leaf
                )
        """)
    modules = astlib.walk_package(tmp_path)
    graph = astlib.CallGraph(modules)
    edges = {k: [c for c, _ in v] for k, v in graph.edges.items()}
    assert "mod.py::leaf" in edges["mod.py::caller"]
    assert "mod.py::caller" in edges["mod.py::S.run"]
    # the executor hop is a target, never an edge
    assert "mod.py::leaf" not in edges["mod.py::S.run"]
    assert "mod.py::leaf" in graph.executor_targets
    reachable = {k for k, _ in graph.walk_sync_reachable("mod.py::S.run")}
    assert reachable == {"mod.py::S.run", "mod.py::caller", "mod.py::leaf"}


def test_module_cache_reuses_and_invalidates(tmp_path):
    p = _write(tmp_path, "def f():\n    pass\n")
    a = astlib.get_module(p)
    b = astlib.get_module(p)
    assert a is b, "same (mtime, size) must hit the cache"
    time.sleep(0.01)
    p.write_text("def g():\n    return 1\n")
    c = astlib.get_module(p)
    assert c is not a and "g" in c.functions


def test_stale_registry_helper_names_symbol(tmp_path):
    _write(tmp_path, "def real():\n    pass\n")
    modules = {m.rel: m for m in astlib.walk_package(tmp_path)}
    findings, live = astlib.stale_registry(
        "t", {"mod.py": ["real", "gone"], "absent.py": ["x"]}, modules
    )
    assert [q for _m, q in live] == ["real"]
    text = "\n".join(str(f) for f in findings)
    assert "missing symbol: gone" in text
    assert "absent.py" in text


# ------------------------------------------------ single-sourcing
def test_registries_are_single_sourced():
    """Every legacy tool re-exports THE registries.py object — a
    refactor can't silently orphan one tool's private copy."""
    check_hotpath = _load("check_hotpath")
    check_queues = _load("check_queues")
    check_supervised = _load("check_supervised")
    check_fusion = _load("check_fusion")
    assert check_hotpath.HOT_PATHS is registries.HOT_PATHS
    assert check_queues.REGISTRY is registries.QUEUE_REGISTRY
    assert check_supervised.SUPERVISED_PATHS is registries.SUPERVISED_PATHS
    assert check_fusion.REGISTRY is registries.FUSION_REGISTRY
    assert check_fusion.TRAIN_REGISTRY is registries.TRAIN_REGISTRY
    assert check_fusion.DCT_REGISTRY is registries.DCT_REGISTRY


# --------------------------------------- the CoAP supervision fix
async def test_coap_handler_tasks_are_supervised():
    """Regression for the fire-and-forget check_async surfaced: every
    datagram handler task is tracked, its exception is recorded (not
    silently dropped with the task), and on_stop cancels stragglers."""
    from sitewhere_tpu.comm.coap import (
        NON, POST, OPT_URI_PATH, CoapIngestServer, encode_message,
    )

    gate = asyncio.Event()

    async def submit(tenant, payload, ctx):
        await gate.wait()
        return True

    server = CoapIngestServer(submit, port=0)
    await server.start()
    try:
        msg = encode_message(
            NON, POST, 7, b"", [(OPT_URI_PATH, b"input")], b"{}"
        )
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            sock.sendto(msg, ("127.0.0.1", server.bound_port))
        for _ in range(200):
            if server._handlers:
                break
            await asyncio.sleep(0.01)
        assert len(server._handlers) == 1, "handler task must be tracked"

        # a handler that dies unexpectedly surfaces through the
        # component's error channel instead of vanishing
        async def boom(data, addr, transport):
            raise RuntimeError("handler exploded")

        server._handle = boom
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            sock.sendto(msg, ("127.0.0.1", server.bound_port))
        for _ in range(200):
            if any("handler exploded" in e for e in server.errors):
                break
            await asyncio.sleep(0.01)
        assert any("handler exploded" in e for e in server.errors)
    finally:
        await server.stop()
    assert not server._handlers, "on_stop must cancel in-flight handlers"
    assert gate.is_set() is False  # the parked handler was CANCELLED


# ------------------------------------------------- lint_all smoke
def test_lint_all_fast_suite_clean_within_budget():
    """All pure-AST analyzers run clean on the shipped tree, fast: the
    astlib parse cache keeps the whole fast suite well under the
    tier-1 budget even on the 2-core rig."""
    t0 = time.perf_counter()
    reports = lint_all.run_all(fast=True)
    wall = time.perf_counter() - t0
    by_tool = {r["tool"]: r for r in reports}
    for tool in lint_all.FAST_TOOLS:
        assert by_tool[tool]["status"] == "ok", by_tool[tool]
    for tool in lint_all.SLOW_TOOLS:
        assert by_tool[tool]["status"] == "skipped"
    assert wall < 60.0, f"fast lint suite took {wall:.1f}s"
    # second run rides the astlib parse/graph cache
    t1 = time.perf_counter()
    lint_all.run_all(fast=True)
    assert time.perf_counter() - t1 < wall + 1.0
