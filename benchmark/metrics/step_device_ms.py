"""Jitted step: mean device time of one execution of the step program,
from the trace."""
from benchmark import trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    n, seconds = trace_reduce.module_time(
        ctx["trace"], ctx["config"]["programs"]["step"])
    return 1000.0 * seconds / n if n else None
