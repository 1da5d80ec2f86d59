"""What the paged stream-state readers share: device time of the
one-step program's operations by layer, told by shapes only those layers
carry (a TPU profile's device events carry the HLO text and no scope):
the grouped products by their kernels' names, window attention by its
rings' flattened store, full attention by its pool. The counters, the
step count and the cost functions are ``_stream_trace``'s. Every function
returns None where there is nothing to read."""

from __future__ import annotations

import bisect
import math

from benchmark import peaks, trace_reduce
from benchmark.metrics._stream_trace import (  # noqa: F401 - shared
    TRACE_ROOT,
    costs,
    counter,
    steps,
)

_CACHE: dict = {}


def _signatures(config: dict) -> list:
    """(layer, substrings) in order of precedence; a KeyError where the
    configuration has no paged store."""
    model = config["model"]
    page = model["page_size"]
    shape = f",{page},{2 * model['num_key_value_heads']},{model['head_dim']}]"
    ring = math.ceil((model["sliding_window"] - 1 + model["chunk_size"])
                     / page)
    return [
        ("gated_moe", ["%gmm", "ragged-dot"]),
        ("full_attn", [f"[{model['pool_pages']}{shape}"]),
        ("swa", [f"[{config['max_streams'] * ring}{shape}"]),
    ]


def _layer_seconds(ctx) -> dict:
    files = sorted(TRACE_ROOT.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {}
    key = (str(files[-1]), files[-1].stat().st_mtime)
    if key in _CACHE:
        return _CACHE[key]
    import jax

    program = ctx["config"]["programs"]["step"]
    signatures = _signatures(ctx["config"])
    out: dict = {}
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if not {trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE} <= set(lines):
            continue
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines[trace_reduce.MODULES_LINE].events
                      if program in e.name)
        starts = [a for a, _b in runs]
        for e in lines[trace_reduce.OPS_LINE].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= runs[i][1]:
                continue  # another program's operation
            for layer, marks in signatures:
                if any(m in e.name for m in marks):
                    out[layer] = out.get(layer, 0.0) + e.duration_ns / 1e9
                    break
    _CACHE.clear()
    _CACHE[key] = out
    return out


def layer_seconds(ctx, layer: str):
    """Device seconds of one layer's operations inside the one-step
    program over the traced stretch, or None."""
    if ctx["trace"] is None or "model" not in ctx["config"]:
        return None
    try:
        return _layer_seconds(ctx).get(layer)
    except KeyError:  # a configuration without a paged store
        return None


def layer_ms(ctx, layer: str):
    """Mean device ms a step spends in one layer's operations, or None."""
    seconds, n = layer_seconds(ctx, layer), steps(ctx)
    return 1000.0 * seconds / n if seconds and n else None


def roofline_pct(ctx, layers: tuple, flops: float, nbytes: float):
    """The least time the chip could take for that work over the device
    time of ``layers``' operations, in per cent, or None."""
    seconds = [layer_seconds(ctx, layer) for layer in layers]
    if not all(seconds):
        return None
    peak = peaks.peaks_for(ctx["device"]["kind"])
    least = max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * least / sum(seconds)
