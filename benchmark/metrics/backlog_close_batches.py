"""Host event loop: the deepest backlog the window built — the consumer
lag (in batches) of any stage's group on any tenant's topic over the
window's last three samples (300 ms), less what the same group held as
the window opened (the pipeline is empty then; a topic that is only ever
read at start-up holds a constant that is no backlog). Below the knee it
stays at a few batches; past it the slowest stage (today
``outbound-connectors``) falls behind and this grows with the window,
until overload control starts to expire rows at 512."""


def read(ctx):
    run = ctx["run"]
    inside = [lags for t, lags in run.lag_samples
              if run.t0 <= t <= run.t0 + run.seconds]
    if len(inside) < 4:
        return None
    at_open = inside[0]
    return float(max([0, *(v - at_open.get(k, 0)
                           for lags in inside[-3:] for k, v in lags.items())]))
