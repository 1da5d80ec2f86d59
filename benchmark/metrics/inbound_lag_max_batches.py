"""Intake + inbound: the deepest consumer lag (in batches) seen on any
tenant's inbound-events topic, polled every 100 ms over the window."""


def read(ctx):
    run = ctx["run"]
    inside = [max([0, *(v for k, v in lags.items()
                        if k.startswith("inbound-events<"))])
              for t, lags in run.lag_samples
              if run.t0 <= t <= run.t0 + run.seconds]
    return float(max(inside)) if inside else None
