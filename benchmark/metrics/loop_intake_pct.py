"""Host event loop: the share of the window the loop's thread spent in
task steps of stage `intake` (receivers, decode, inbound) —
`loop_busy_seconds_total{stage="intake"}` over the window's seconds."""


def share(ctx, stage: str):
    """The stage's children of `loop_busy_seconds_total` (one a kind of
    task), summed, over the window, in percent."""
    w = ctx["window"]
    mine = [seconds for key, seconds in
            w.children("loop_busy_seconds_total").items()
            if f'stage="{stage}"' in key]
    return 100.0 * sum(mine) / w.seconds if mine else None


def read(ctx):
    return share(ctx, "intake")
