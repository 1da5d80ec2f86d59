"""Jitted step, as a kernel: the least time the chip could take for the
VALID rows of the traced flushes (the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s) over the device time the step program took."""
from benchmark import metrics, peaks, trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    cfg, traced = ctx["config"], ctx["traced"]
    n, seconds = trace_reduce.module_time(ctx["trace"], cfg["programs"]["step"])
    rows = traced.count("tpu_inference.flush_rows")
    flushes = traced.count("tpu_inference.flushes")
    if not n or not seconds or not rows or not flushes:
        return None
    slots = min(cfg["mesh"]["slots_per_shard"], rows / flushes)
    flops, nbytes = metrics.step_cost(ctx, rows, flushes, slots)
    peak = peaks.peaks_for(ctx["device"]["kind"])
    least = max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * least / seconds
