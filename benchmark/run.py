"""Run one cell once: build, warm up, pre-fill, measure, check, print.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. The cell's configuration and traffic mix are
found by name from ``BENCHMARK.json``, and through them the builder, the
check, the encoder, the generator kind and the metric readers
(end-to-end and per-layer alike); this file names none of them. The last
line of standard output is the result (see ``benchmark/README.md``).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
GROUP = "benchmark"            # the subscriber's consumer group
DRAIN_TIMEOUT_S = 60.0         # how long a late answer is waited for
TRACE_SECONDS = 3.0            # a traced run traces the window's last part


class Refused(Exception):
    """The run cannot be made here (no chip, a missing file); no result."""


# ------------------------------------------------------------------ cell
def load_cell(workload: str, root: Path = ROOT) -> dict:
    """Everything ``BENCHMARK.json`` and its files say about one cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; have {sorted(cells)}")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic_file = Path(entry["file"]).parents[1] / "traffic" / (
        cell["traffic"] + ".json")
    traffic = json.loads((root / traffic_file).read_text())

    def mine(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": cell["chips"], "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` — how a cell's files name code."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def accelerator(chips: int) -> list:
    """The chips this cell needs, or Refused."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise Refused(
            f"needs {chips} TPU chip(s); JAX reports {len(devices)} "
            f"device(s) on platform {devices[0].platform!r}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    whoever launched the process placed it (JAX_COMPILATION_CACHE_DIR)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------- window
class Run:
    """What the generator kind drives and the subscriber fills."""

    def __init__(self, system, traffic, seconds: float, recorder) -> None:
        self.system, self.traffic, self.seconds = system, traffic, seconds
        self.broker = system.broker
        self.timed = traffic.timed
        self.sent_at = np.full(self.timed.count, np.nan)  # s from t0
        self.t0 = 0.0
        self.seen = recorder        # the check's record of the scored topics
        self.alerts: set = set()    # (tenant token, device token)
        self._rows_seen = 0
        self._rows_at_open = 0
        self.lag_samples: list = []
        # the interpreter's collections inside the window, by generation:
        # how many, their pauses together and the longest (seconds)
        self.gc_count = [0, 0, 0]
        self.gc_pause_s = [0.0, 0.0, 0.0]
        self.gc_pause_max_s = [0.0, 0.0, 0.0]
        self._gc_t = 0.0
        self.loop_cpu_s = 0.0       # CPU seconds of the event-loop thread
        self._cpu0 = 0.0
        self._tasks: list = []

    def delivered_rows(self) -> int:
        """Rows that have left the last stage, over all tenants."""
        return sum(self.system.outbound_rows(t) for t in self.system.tenants)

    async def _watch(self, idx: int, topic: str) -> None:
        consume, add, clock = self.system.bus.consume, self.seen.add, time.perf_counter
        while True:
            items = await consume(topic, GROUP, 1024)
            now = clock()
            for it in items:
                self._rows_seen += add(now, idx, it)

    async def _sample_lag(self) -> None:
        """Every 100 ms: the deepest consumer lag (in batches) per kind
        of topic — the last word of its name — over all tenants."""
        lags = self.system.bus.lags
        while True:
            worst: dict = {}
            for topic, st in lags().items():
                kind = topic.rsplit(".", 1)[-1]
                for group, lag in st["groups"].items():
                    key = f"{kind}<{group.split('[')[0]}"
                    if group != GROUP and lag > worst.get(key, 0):
                        worst[key] = lag
            self.lag_samples.append((time.perf_counter(), worst))
            await asyncio.sleep(0.1)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
            return
        gen, dt = info["generation"], time.perf_counter() - self._gc_t
        self.gc_count[gen] += 1
        self.gc_pause_s[gen] += dt
        if dt > self.gc_pause_max_s[gen]:
            self.gc_pause_max_s[gen] = dt

    def subscribe(self) -> None:
        async def on_alert(topic: str, _payload: bytes) -> None:
            parts = topic.split("/")
            self.alerts.add((parts[1], parts[3]))

        self.broker.subscribe("sitewhere/+/output/+/alert", on_alert)
        for i, tok in enumerate(self.system.tenants):
            topic = self.system.scored_topic(tok)
            self.system.bus.subscribe(topic, GROUP)
            self._tasks.append(asyncio.create_task(self._watch(i, topic)))

    def start_window(self) -> None:
        self._rows_at_open = self._rows_seen
        self.seen.open_window()
        self._tasks.append(asyncio.create_task(self._sample_lag()))
        gc.callbacks.append(self._on_gc)
        self._cpu0 = time.thread_time()
        self.t0 = time.perf_counter()

    def close_window(self) -> float:
        """The window's last moment, on the clock ``t0`` is on."""
        t_close = time.perf_counter()
        self.loop_cpu_s = time.thread_time() - self._cpu0
        gc.callbacks.remove(self._on_gc)
        return t_close

    async def wait_rows(self, rows: int, timeout_s: float, what: str) -> bool:
        t_end = time.monotonic() + timeout_s
        while self._rows_seen < rows:
            if time.monotonic() > t_end:
                print(f"benchmark: gave up after {timeout_s}s waiting for "
                      f"{what}: {self._rows_seen} of {rows} rows seen",
                      file=sys.stderr)
                return False
            await asyncio.sleep(0.005)
        return True

    async def prefill(self, diagnose) -> None:
        """Fill the windows of the traffic file's pre-fill streams before
        the clock starts: each round is offered at the traffic file's
        pre-fill rate (unpaced, the program's admission deadline expires
        rows that queue for half a second), the next leaves when the last
        is scored, and the window opens on an empty pipeline: the last
        stage has delivered them all."""
        rows = 0
        rate = self.traffic.params["prefill"]["rate_ev_s"]
        for msgs in self.traffic.prefill:
            gap = msgs.samples / rate
            t0 = time.perf_counter()
            for m in range(msgs.count):
                await self.broker.publish(msgs.topics[m], msgs.payloads[m])
                if m % 64 == 63:
                    await asyncio.sleep(
                        max(0.0, t0 + m * gap - time.perf_counter()))
            rows += msgs.count * msgs.samples
            if not await self.wait_rows(rows, 120.0, "pre-fill"):
                diagnose()
                raise RuntimeError("pre-fill was not scored")
        t_end = time.monotonic() + 60.0
        while self.delivered_rows() < rows:
            if time.monotonic() > t_end:
                raise RuntimeError("pre-fill was not delivered by the last stage")
            await asyncio.sleep(0.01)

    async def stop(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        self._tasks = []


def snapshot(metrics) -> dict:
    """Counter values and histogram (count, sum)s, by exposition key."""
    out = {}
    for key, v in metrics.snapshot().items():
        if isinstance(v, dict) and "count" in v:
            out[key] = (v["count"], v["count"] * v.get("mean", 0.0))
        elif isinstance(v, (int, float)):
            out[key] = float(v)
    return out


class Span:
    """Counters before and after a stretch of the window."""

    def __init__(self, before: dict, after: dict, seconds: float) -> None:
        self.before, self.after, self.seconds = before, after, seconds

    def _keys(self, family: str) -> list:
        return [k for k in self.after
                if k == family or k.startswith(family + "{")]

    def count(self, family: str) -> float:
        """Counter delta, summed over a labeled family's children."""
        return sum(self.after[k] - self.before.get(k, 0.0)
                   for k in self._keys(family)
                   if not isinstance(self.after[k], tuple))

    def children(self, family: str) -> dict:
        return {k: self.after[k] - self.before.get(k, 0.0)
                for k in self._keys(family)
                if not isinstance(self.after[k], tuple)}

    def hist(self, family: str) -> tuple:
        """(observations, their sum) of a histogram over the stretch."""
        n = s = 0.0
        for k in self._keys(family):
            if isinstance(self.after[k], tuple):
                b = self.before.get(k, (0.0, 0.0))
                n += self.after[k][0] - b[0]
                s += self.after[k][1] - b[1]
        return n, s


# ----------------------------------------------------------------- trace
def wrap_spans(targets: list) -> None:
    """Put a profiler annotation around each (module, class, method,
    name) of the configuration's ``spans``: the benchmark's own spans
    around the calls into each layer, on the trace's clock. Only a traced
    run does this."""
    import functools
    import inspect

    import jax

    for mod_name, cls_name, method, name in targets:
        try:
            cls = getattr(importlib.import_module(mod_name), cls_name)
            fn = getattr(cls, method)
        except (ImportError, AttributeError) as exc:
            # a later program may have reshaped this call: the trace then
            # lacks this one span, and the run goes on
            print(f"note span bench/{name} not placed: {exc}", file=sys.stderr)
            continue
        label = f"bench/{name}"
        if inspect.iscoroutinefunction(fn):
            @functools.wraps(fn)
            async def wrapped(*a, _fn=fn, _label=label, **kw):
                with jax.profiler.TraceAnnotation(_label):
                    return await _fn(*a, **kw)
        else:
            @functools.wraps(fn)
            def wrapped(*a, _fn=fn, _label=label, **kw):
                with jax.profiler.TraceAnnotation(_label):
                    return _fn(*a, **kw)
        if isinstance(inspect.getattr_static(cls, method), staticmethod):
            wrapped = staticmethod(wrapped)
        setattr(cls, method, wrapped)


def start_trace(directory: Path) -> None:
    import shutil

    import jax

    shutil.rmtree(directory, ignore_errors=True)
    directory.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(directory), profiler_options=opts)


# ------------------------------------------------------------------- run
def device_info(devices: list) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def tracked_objects() -> int:
    """Objects the interpreter's cycle collector has to walk."""
    return len(gc.get_objects())


async def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
                   devices: list, t_process: float = None,
                   sabotage=None, control: bool = False,
                   drain_timeout_s: float = DRAIN_TIMEOUT_S) -> dict:
    """One run of one cell on ``devices``; returns the result object.
    ``sabotage(system, run)`` is the self-test's hook for breaking the
    timed path underneath, and ``control`` puts the control (the
    reference one precision down) in the program's place before the
    comparison; the benchmark's own runs use neither."""
    from benchmark import trace_reduce

    t_process = T_PROCESS if t_process is None else t_process
    config, params = cell["config"], cell["traffic"]
    builder = module("builders", config["builder"])
    check = module("checks", config["check"])
    encoder = module("encoders", params["encoder"])
    kind = module("generators", params["kind"])
    # a profile is taken only of a TPU: a CPU run (the self-test) reads
    # the counters and prints no device metric
    profile = trace and devices[0].platform == "tpu"
    if profile:
        wrap_spans(config["spans"])
    system = await builder.build(config, seed, devices)
    run = None
    try:
        heap_program = tracked_objects()
        t_gen = time.perf_counter()
        traffic = encoder.build(params, config, seed, seconds, kind.plan)
        system.info["traffic_s"] = time.perf_counter() - t_gen
        run = Run(system, traffic, seconds, check.recorder(traffic))
        # what the harness itself adds to the collector's work
        system.info["heap_objects_harness"] = tracked_objects() - heap_program
        run.subscribe()
        if sabotage is not None:
            sabotage(system, run)
        t_pre = time.perf_counter()
        await run.prefill(lambda: check.diagnose(system, config))
        system.info["prefill_s"] = time.perf_counter() - t_pre
        gc.collect()
        system.info["heap_objects_open"] = tracked_objects()
        before = snapshot(system.metrics)
        run.start_window()
        setup_s = run.t0 - t_process
        traced = None
        trace_dir = ROOT / "benchmark" / "_trace" / cell["name"]

        async def tracer() -> None:
            nonlocal traced
            length = min(TRACE_SECONDS, seconds / 2)
            await asyncio.sleep(seconds - length)
            b = snapshot(system.metrics)
            t_a = time.perf_counter()
            if profile:
                start_trace(trace_dir)
            await asyncio.sleep(max(0.0, run.t0 + seconds - time.perf_counter()))
            t_b = time.perf_counter()
            a = snapshot(system.metrics)
            if profile:
                import jax

                await asyncio.get_running_loop().run_in_executor(
                    None, jax.profiler.stop_trace)
            traced = Span(b, a, t_b - t_a)

        trace_task = asyncio.create_task(tracer()) if trace else None
        await kind.drive(run)
        await asyncio.sleep(max(0.0, run.t0 + seconds - time.perf_counter()))
        t_close = run.close_window()
        window = Span(before, snapshot(system.metrics), t_close - run.t0)
        if trace_task is not None:
            await trace_task
        sent = ~np.isnan(run.sent_at)
        attempted = int(sent.sum()) * run.timed.samples
        drained = await run.wait_rows(
            run._rows_at_open + attempted, drain_timeout_s,
            "the window's events")
        t_giveup = time.perf_counter()
        # counted only now: listing a million objects stops the loop for
        # some tens of ms, and until here answers were still being timed
        system.info["heap_objects_close"] = tracked_objects()
        device = device_info(devices)
        facts = await check.collect(system, run, window, attempted, drained,
                                    config)
    finally:
        if run is not None:
            await run.stop()
        await system.stop()
    del system
    gc.collect()
    verdict = check.judge(facts, run, builder.reference(config, seed), seed)
    program_correct, program_checks = verdict["correct"], verdict["checks"]
    if control:
        verdict = check.judge(
            facts, run, builder.reference(config, seed), seed,
            stand_in=builder.reference(config, seed, control=True))
    lat = check.latencies_ms(run, t_giveup)
    result = {
        "correct": verdict["correct"], "attempted": attempted,
        "failed": verdict["failed"], "metrics": {}, "device": device,
    }
    reduced = (trace_reduce.reduce(trace_dir, [d.id for d in devices])
               if profile else None)
    ctx = {
        "window": window, "traced": traced, "trace": reduced,
        "run": run, "config": config, "traffic": params,
        "device": device, "chips": len(devices), "latencies_ms": lat,
        "seconds": seconds, "setup_s": setup_s,
    }
    for m in cell["per_layer" if trace else "end_to_end"]:
        value = module("metrics", m["name"]).read(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {
                "value": float(value), "unit": m["unit"]}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s_mean"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
        (trace_dir / "planes.json").write_text(
            json.dumps(reduced["planes"], indent=1))
    info = {k: round(v, 3) if isinstance(v, float) else v
            for k, v in facts["info"].items()}
    if len(lat):
        # a backlog that grows shows as a later half slower than the first
        half = len(lat) // 2
        info["p50_first_half_ms"] = float(np.median(lat[:half]))
        info["p50_second_half_ms"] = float(np.median(lat[half:]))
    # every consumer group's deepest lag (batches) over the window's first
    # and last three samples (300 ms): what benchmark.sweep's rule reads
    inside = [w for t, w in run.lag_samples if run.t0 <= t <= run.t0 + seconds]
    for key, part in (("lag_at_open", inside[:3]), ("lag_at_close", inside[-3:])):
        deepest = info[key] = {}
        for sample in part:
            for group, lag in sample.items():
                if lag > deepest.get(group, 0):
                    deepest[group] = lag
    info["gc"] = {"collections": run.gc_count,
                  "pause_ms": [round(1000 * x, 1) for x in run.gc_pause_s],
                  "pause_max_ms": [round(1000 * x, 1)
                                   for x in run.gc_pause_max_s]}
    info["loop_cpu_s"] = round(run.loop_cpu_s, 3)
    result["info"] = info
    if control:
        result["program_correct"] = program_correct
        result["program_checks"] = program_checks
    result["checks"] = verdict["checks"]
    for line in verdict["notes"]:
        print(f"note {line}", file=sys.stderr)
    for name, (value, limit) in verdict["checks"].items():
        print(f"check {name} = {value} (limit {limit})", file=sys.stderr)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        cell = load_cell(args.workload)
        devices = accelerator(cell["chips"])
    except (Refused, OSError, KeyError, ValueError) as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        sys.exit(2)
    enable_compile_cache()
    result = asyncio.run(run_cell(
        cell, args.seed, args.seconds, bool(args.trace), devices))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
