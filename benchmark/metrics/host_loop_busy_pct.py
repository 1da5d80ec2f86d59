"""Host event loop: the share of the window in which the one thread that
carries every host stage (generator, decode, inbound, lanes, reaper,
persist, rules, outbound) was on the CPU — its thread CPU seconds over
the window's seconds. The offered rate over this share is the rate at
which that thread would be full: the host's capacity, read from the
window itself."""


def read(ctx):
    run = ctx["run"]
    if not run.loop_cpu_s:
        return None
    return 100.0 * run.loop_cpu_s / ctx["window"].seconds
