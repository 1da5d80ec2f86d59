"""Host event loop: the longest stop of the interpreter for one
collection (of any generation; the longest is a full one whenever a full
one falls inside the window) — timed between the collector's own start
and stop callbacks. The tail of due->scored is made of these stops."""


def read(ctx):
    run = ctx["run"]
    if not sum(run.gc_count):
        return None
    return 1000.0 * max(run.gc_pause_max_s)
