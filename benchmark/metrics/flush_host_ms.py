"""Lanes -> staging -> h2d: host ms per flush spent assembling the
staging buffers, issuing the h2d copy and dispatching the step."""


def read(ctx):
    w = ctx["window"]
    flushes = w.count("tpu_inference.flushes")
    if not flushes:
        return None
    total = sum(w.hist(f"tpu_inference.{part}")[1]
                for part in ("flush_assembly", "h2d_stage", "dispatch"))
    return 1000.0 * total / flushes
