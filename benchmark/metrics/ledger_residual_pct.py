"""Whole path: the share of the mean due->scored time that no named
interval of the program's ledger accounts for — mean due->scored, less the
generator's mean send lateness, less the means of `pipeline.intake`,
`tpu_inference.lane_wait`, `.acquire_wait`, `.flush_assembly`,
`.h2d_stage`, `.dispatch`, `.inflight` and `.resolve`, over mean
due->scored."""
import numpy as np

PARTS = ("pipeline.intake", "tpu_inference.lane_wait",
         "tpu_inference.acquire_wait", "tpu_inference.flush_assembly",
         "tpu_inference.h2d_stage", "tpu_inference.dispatch",
         "tpu_inference.inflight", "tpu_inference.resolve")


def read(ctx):
    lat, run, w = ctx["latencies_ms"], ctx["run"], ctx["window"]
    if lat is None or not len(lat):
        return None
    named = 0.0
    for part in PARTS:
        n, total = w.hist(part)
        if not n:
            return None     # a program without the ledger's intervals
        named += 1000.0 * total / n
    sent = ~np.isnan(run.sent_at)
    late = float(np.mean(run.sent_at[sent] * 1000.0 - run.timed.due_ms[sent]))
    mean = float(np.mean(lat))
    return 100.0 * (mean - late - named) / mean if mean > 0 else None
