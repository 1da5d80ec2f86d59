"""Device queue: flushes already in flight on the slice when a flush was
dispatched, averaged over the window's flushes
(`tpu_inference.inflight_depth_sum` / `tpu_inference.flushes`)."""


def read(ctx):
    w = ctx["window"]
    if "tpu_inference.inflight_depth_sum" not in w.after:
        return None
    flushes = w.count("tpu_inference.flushes")
    if not flushes:
        return None
    return w.count("tpu_inference.inflight_depth_sum") / flushes
