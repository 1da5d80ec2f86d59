"""One reader per metric, end-to-end and per-layer alike:
``read(ctx) -> float | None``.

``ctx`` (a dict the runner fills): ``window`` and ``traced`` — counters
before/after the whole window and the traced part of it (``run.Span``:
``count``, ``hist``, ``children``, ``seconds``; ``traced`` is None in an
untraced run);
``trace`` — the reduced profiler trace (``trace_reduce.reduce``), None
without a TPU; ``run`` — the runner's own samples (``run.Run``: send times, lag
samples, the interpreter's collections, the event loop's CPU seconds);
``config``, ``traffic`` — the cell's files; ``device`` — as in the result
line; ``chips``; ``latencies_ms`` — due->scored per timed event;
``seconds``, ``setup_s``. A reader that finds nothing to read returns
None and the metric is left out of the line.
"""

import importlib

import numpy as np


def step_cost(ctx, valid_rows: float, flushes: float,
              slots_used_per_flush: float) -> tuple:
    """(FLOPs, bytes) the flushes need, by the cost functions of the
    configuration's model family (``costs/<family>.py``)."""
    cfg = ctx["config"]
    family = importlib.import_module(
        f"benchmark.costs.{cfg['model']['family']}")
    return family.step_cost(cfg["model"], cfg["wire"], valid_rows, flushes,
                            slots_used_per_flush)


def latency_percentile(ctx, q: float):
    """The q-th percentile of due->scored over every timed event."""
    lat = ctx["latencies_ms"]
    return float(np.percentile(lat, q)) if lat is not None and len(lat) else None
