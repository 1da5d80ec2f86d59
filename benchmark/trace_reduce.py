"""From a profiler trace to numbers: device busy time, time per device
operation and per compiled program, and the idle gaps by what the host
was doing. Reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
nothing but JAX.

Device planes are named ``/device:TPU:<id>``; their ``XLA Ops`` line
holds one event per executed operation (busy = the union of those
intervals) and ``XLA Modules`` one per executed program. Host planes hold
the benchmark's own ``bench/<layer>`` annotations (``run.wrap_spans``),
on the same clock.
"""

from __future__ import annotations

from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench/"


def _merge(intervals: list) -> list:
    """Union of [start, end) intervals, sorted."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def reduce(trace_dir: Path, device_ids: list) -> dict:
    import jax

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices: dict = {}
    spans: list = []
    t_min, t_max = float("inf"), 0.0
    planes = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            lines.setdefault(line.name, []).extend(events)
            for _n, a, b in events:
                t_min, t_max = min(t_min, a), max(t_max, b)
        planes.append({"plane": plane.name,
                       "lines": {k: len(v) for k, v in lines.items()}})
        if plane.name.startswith("/device:TPU:"):
            dev_id = int(plane.name.rsplit(":", 1)[1].split()[0])
            if dev_id not in device_ids:
                continue
            ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
            by_op: dict = {}
            for name, a, b in ops:
                by_op[name] = by_op.get(name, 0.0) + (b - a) / 1e9
            by_module: dict = {}
            for name, a, b in lines.get(MODULES_LINE, []):
                n, s = by_module.get(name, (0, 0.0))
                by_module[name] = (n + 1, s + (b - a) / 1e9)
            busy = _merge([[a, b] for _n, a, b in ops])
            devices[dev_id] = {
                "busy": busy,
                "busy_s": sum(b - a for a, b in busy) / 1e9,
                "ops": by_op, "modules": by_module,
            }
        else:
            for events in lines.values():
                spans += [(n[len(SPAN_PREFIX):], a, b) for n, a, b in events
                          if n.startswith(SPAN_PREFIX)]
    window_s = max(0.0, (t_max - t_min) / 1e9) if devices else 0.0
    busy = [d["busy_s"] for d in devices.values()]
    return {
        "devices": devices, "spans": spans, "window_s": window_s,
        "t_min": t_min, "t_max": t_max,
        "busy_s_mean": sum(busy) / len(busy) if busy else 0.0,
        "planes": planes,
    }


def fullest(reduced: dict):
    """The busiest chip's entry, or None."""
    devs = reduced["devices"]
    return max(devs.values(), key=lambda d: d["busy_s"]) if devs else None


def module_time(reduced: dict, program: str) -> tuple:
    """(executions, device seconds) of the programs whose name contains
    ``program``, over all chips."""
    n = s = 0.0
    for dev in reduced["devices"].values():
        for name, (k, sec) in dev["modules"].items():
            if program in name:
                n += k
                s += sec
    return n, s


def idle_by_host_span(reduced: dict) -> dict:
    """Idle seconds of the fullest chip, by the innermost benchmark span
    the host was in during each gap (``host_other`` where in none)."""
    dev = fullest(reduced)
    if dev is None:
        return {}
    gaps, at = [], reduced["t_min"]
    for a, b in dev["busy"]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if reduced["t_max"] > at:
        gaps.append((at, reduced["t_max"]))
    import numpy as np

    spans = sorted(reduced["spans"], key=lambda s: s[1])
    starts = np.asarray([s[1] for s in spans], np.float64)
    ends = np.asarray([s[2] for s in spans], np.float64)
    longest = float((ends - starts).max()) if spans else 0.0
    out: dict = {}
    # the longest gaps carry nearly all the idle time; the rest is lumped
    gaps.sort(key=lambda g: g[0] - g[1])
    for g0, g1 in gaps[2000:]:
        out["short_gaps"] = out.get("short_gaps", 0.0) + (g1 - g0) / 1e9
    for g0, g1 in gaps[:2000]:
        lo = int(np.searchsorted(starts, g0 - longest, "left"))
        hi = int(np.searchsorted(starts, g1, "left"))
        covered = [(ends[i] - starts[i], spans[i][0],
                    max(starts[i], g0), min(ends[i], g1))
                   for i in range(lo, hi) if ends[i] > g0]
        # innermost first: the shortest span claims its overlap
        claimed: list = []
        for _len, name, a, b in sorted(covered):
            free = b - a - sum(max(0.0, min(b, d) - max(a, c))
                               for c, d in claimed)
            if free > 0:
                out[name] = out.get(name, 0.0) + free / 1e9
                claimed = _merge(claimed + [[a, b]])
        rest = (g1 - g0) - sum(d - c for c, d in claimed)
        if rest > 0:
            out["host_other"] = out.get("host_other", 0.0) + rest / 1e9
    return out


def breakdown(reduced: dict) -> dict:
    ops: dict = {}
    for dev in reduced["devices"].values():
        for name, s in dev["ops"].items():
            ops[name] = ops.get(name, 0.0) + s
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle_by_host_span(reduced).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}
