"""Device queue: mean ms a flush had the device to itself, read on the
host — landing minus the later of its own dispatch and the previous landing
on the slice (`tpu_inference.service`, per flush; never overlaps). The
host-side twin of the trace's `step_device_ms`."""


def read(ctx):
    n, total = ctx["window"].hist("tpu_inference.service")
    return 1000.0 * total / n if n else None
