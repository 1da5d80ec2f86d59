"""End to end: process start to the first timed event."""


def read(ctx):
    return ctx["setup_s"]
