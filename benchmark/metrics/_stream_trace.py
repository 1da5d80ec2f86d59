"""What the stream-state readers share: device time of the one-step
program's operations by layer, out of the newest profile under
``benchmark/_trace``; the step's own counters over the traced stretch. Every function returns None where there is nothing to
read (no profile, a program without the scopes or the counters)."""

from __future__ import annotations

import importlib
from pathlib import Path

from benchmark import peaks, trace_reduce

TRACE_ROOT = Path(__file__).resolve().parents[1] / "_trace"
_CACHE: dict = {}


def _signatures(model: dict) -> list:
    """(layer, substrings) in order of precedence. A TPU profile's device
    events carry the HLO instruction's text and no scope, so a layer's
    operations are told by the shapes only they carry: the grouped
    product's kernel by its name (``gmm``, or ``ragged-dot``), the mixer by its state, its input
    projection and its convolution width, attention by its ring."""
    heads, hd, n = (model["mamba_num_heads"], model["mamba_head_dim"],
                    model["ssm_state_size"])
    d_in = heads * hd
    conv = d_in + 2 * model["n_groups"] * n
    ctx = model["context_positions"]
    return [
        ("moe", ["%gmm", "ragged-dot"]),
        ("ssm", [f"{heads},{hd},{n}]", f",{d_in + conv + heads}]",
                 f",{(model['conv_kernel'] - 1) * conv}]", f",{conv}]"]),
        ("attn", [f",{ctx},", f",{ctx}]", f",{ctx + 1}]"]),
    ]


def _layer_seconds(ctx) -> dict:
    """Device seconds of the step program's operations, by layer."""
    files = sorted(TRACE_ROOT.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {}
    key = (str(files[-1]), files[-1].stat().st_mtime)
    if key in _CACHE:
        return _CACHE[key]
    import bisect

    import jax

    program = ctx["config"]["programs"]["step"]
    signatures = _signatures(ctx["config"]["model"])
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


def steps(ctx) -> float:
    """Executions of the one-step program in the traced stretch."""
    if ctx["trace"] is None:
        return 0.0
    n, _s = trace_reduce.module_time(
        ctx["trace"], ctx["config"]["programs"]["step"])
    return n


def layer_seconds(ctx, layer: str):
    """Device seconds of one layer's operations inside the one-step
    program over the traced stretch, or None."""
    if ctx["trace"] is None or "model" not in ctx["config"]:
        return None
    try:
        return _layer_seconds(ctx).get(layer)
    except KeyError:  # a configuration without these sizes
        return None


def layer_ms(ctx, layer: str):
    """Mean device ms a step spends in one layer's operations, or None."""
    seconds, n = layer_seconds(ctx, layer), steps(ctx)
    return 1000.0 * seconds / n if seconds and n else None


def counter(ctx, name: str, span: str = "window") -> float:
    span = ctx[span]
    return span.count(f"tpu_inference.stream_{name}") if span else 0.0


def costs(ctx):
    """The configuration's model block and its family's cost functions."""
    model = ctx["config"]["model"]
    return model, importlib.import_module(
        f"benchmark.costs.{model['family']}")


def roofline_pct(ctx, scope: str, flops: float, nbytes: float):
    """The least time the chip could take for that work (the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s) over the device
    time under ``scope``, in per cent."""
    peak = peaks.peaks_for(ctx["device"]["kind"])
    least = max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * least / layer_seconds(ctx, scope)
