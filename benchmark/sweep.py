"""Tools for the PR that defines or re-tunes the benchmark — not part of
a run: sweep one traffic parameter over a cell (to find the knee), or
read the control beside the program on several seeds (to set the
configuration's ``limits``). One process, one cell, several windows.

    python3 -m benchmark.sweep --workload lstm-ad-32t.live --param rate_ev_s \\
        --values 500,600,700,800,900,1000 --seeds 1,2 --seconds 60 --control 1
    python3 -m benchmark.sweep --workload lstm-ad-32t.live \\
        --seeds 1,2,3 --seconds 5 --control 1

The first is the knee sweep ``PERF.md`` section 4 records: every value on
every seed, and with each window the verdict of ``sustained`` below — the
one rule by which a rate is or is not sustained (``sweep_windows.json``
holds the windows its constants were set on, each with its verdict). The
knee is the highest rate that every seed sustains. With ``--control 1``
every window also prints the control's checks beside the program's.
``--config key=value`` runs every window with that key of the
configuration file replaced (a state size to try before it is written
into the file); ``--trace 1`` prints the per-layer metrics of traced
windows in place of the end-to-end ones.

Where the swept parameter is ``rate_ev_s`` and the configuration
publishes a report interval, the registered fleet follows the rate as the
configuration's ``reduced_why`` says it does: ``devices_per_tenant`` =
rate x interval / tenants, rounded up.

Prints one JSON line per window: the value, the seed, ``sustained`` and
the reasons where it is not, ``correct`` (the program's), the end-to-end
metrics, the event loop's busy share, set-up phases, the first- and
second-half latency medians, every group's lag at the open and the close,
every check, and the accounting notes.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import gc
import io
import json
import math
import time
from contextlib import redirect_stderr

# A consumer group may close this many batches deeper than it opened. One
# tenant's topic takes at most one batch a flush (20-41 flushes/s), and a
# full collection stops the loop for up to 0.65 s, so a sustained window
# can close 13-20 batches deep on the topic it stopped on; a group that
# falls behind grows by more than a batch a second for the whole window.
LAG_SLACK_BATCHES = 32
# The second half's median latency may lie this far BELOW the first
# half's: a median that FALLS by halves is the window opening on a slow
# host (first half 1.5-2.6 x the second, 1 window in 16-24 at the seat,
# PERF.md section 2) or, on the flush policy before PR 35, flipping
# between a device-queue mode and a host-bound one (204 -> 135 ms at 800
# ev/s, PERF.md section 4) -- neither says the rate was kept up with.
HALF_MEDIAN_FALL = 1.05
# ... and this far ABOVE it. Since PR 35 the median reads one step plus
# the host loop's turn, and the turn lengthens with the heap all through a
# window at EVERY rate: the fourteen 60 s windows of sweep_windows.json at
# 500-650 ev/s (PR 35's tree and PR 37's), every one correct and none more
# than 2 batches behind, rise 1.01-1.098 x by halves (the highest: 650 ev/s,
# 45.0 -> 49.3 ms, loop 90% busy). The four at 700 ev/s rise 1.100, 1.105,
# 1.140 and 1.206 x with the loop 90-93% busy and a median a sixth over
# 650's: past the bend, where a per cent of host time is a tenth of the
# wait. The line stands between the two groups and has no room to spare
# (1.098 against 1.100), so a rate is judged on both seeds and by its busy
# share beside (PERF.md section 4). A backlog that no group's lag shows is
# far past it: 0.2% over capacity adds 30 ms to the first half's median and
# 90 to the second's.
HALF_MEDIAN_RISE = 1.10


def sustained(correct: bool, failed: int, info: dict) -> tuple:
    """(verdict, reasons it is false) for one window, from its result
    line: ``correct``, ``failed`` and ``info`` as ``run.run_cell`` gives
    them. Sustained means: correct, nothing failed, no consumer group
    (the scoring consumer's and every later stage's alike) closing more
    than ``LAG_SLACK_BATCHES`` deeper than it opened, and a second-half
    median no more than ``HALF_MEDIAN_RISE`` times the first half's and no
    less than it over ``HALF_MEDIAN_FALL``."""
    reasons = []
    if not correct:
        reasons.append("not correct")
    if failed:
        reasons.append(f"{failed} events failed")
    opened = info.get("lag_at_open", {})
    for group, lag in sorted(info.get("lag_at_close", {}).items()):
        if lag - opened.get(group, 0) > LAG_SLACK_BATCHES:
            reasons.append(
                f"{group} closes {lag} batches behind (opened at "
                f"{opened.get(group, 0)}; slack {LAG_SLACK_BATCHES})")
    first = info.get("p50_first_half_ms")
    second = info.get("p50_second_half_ms")
    if first is None or second is None:
        reasons.append("no latencies")
    elif second > HALF_MEDIAN_RISE * first:
        reasons.append(
            f"p50 by halves {first:.1f} -> {second:.1f} ms "
            f"(rises by more than {HALF_MEDIAN_RISE} x)")
    elif second < first / HALF_MEDIAN_FALL:
        reasons.append(
            f"p50 by halves {first:.1f} -> {second:.1f} ms "
            f"(falls by more than {HALF_MEDIAN_FALL} x)")
    return not reasons, reasons


def fleet_per_tenant(rate_ev_s: float, interval_s: float, tenants: int) -> int:
    """The devices a tenant registers where every device reports once an
    interval and the tenants share the offered rate: Little's law, rounded
    up (``benchmark/file_cases.py`` holds every cell's files to it)."""
    return math.ceil(rate_ev_s * interval_s / tenants)


def with_value(cell: dict, param: str, value: float) -> dict:
    """The cell with its traffic's ``param`` set to ``value`` (and, for a
    rate, the fleet that rate implies at the published interval)."""
    cell = copy.deepcopy(cell)
    cell["traffic"][param] = value
    config = cell["config"]
    interval = config.get("published", {}).get("report_interval_s")
    if param == "rate_ev_s" and interval:
        config["devices_per_tenant"] = fleet_per_tenant(
            value, interval, config["tenants"])
    return cell


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--param")
    ap.add_argument("--values", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--config", action="append", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args()
    # not at the top: the rule above is read by plain file checks and by
    # tier-1 tests that want neither numpy nor JAX
    from benchmark import run

    cell = run.load_cell(args.workload)
    for pair in args.config:
        key, value = pair.split("=", 1)
        cell["config"][key] = json.loads(value)
    devices = run.accelerator(cell["chips"])
    run.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    values = [float(v) for v in args.values.split(",") if v] or [None]
    for value in values:
        this = cell if value is None else with_value(cell, args.param, value)
        for seed in seeds:
            # the last window's state is cyclic garbage by now, and two
            # stores beside a third's transient copy do not fit the chip
            gc.collect()
            err = io.StringIO()
            with redirect_stderr(err):
                res = asyncio.run(run.run_cell(
                    this, seed, args.seconds, bool(args.trace), devices,
                    t_process=time.perf_counter(),
                    control=bool(args.control), drain_timeout_s=30.0))
            notes = [ln for ln in err.getvalue().splitlines()
                     if ln.startswith(("note", "benchmark:"))]
            info = res["info"]
            correct = res.get("program_correct", res["correct"])
            verdict, reasons = sustained(correct, res["failed"], info)
            print(json.dumps({
                args.param or "seed": value if value is not None else seed,
                "seed": seed, "sustained": verdict, "reasons": reasons,
                "correct": correct, "control_correct": (
                    res["correct"] if args.control else None),
                "attempted": res["attempted"], "failed": res["failed"],
                "metrics": {k: round(v["value"], 3)
                            for k, v in res["metrics"].items()},
                "host_loop_busy_pct": round(
                    100.0 * info["loop_cpu_s"] / args.seconds, 2),
                "info": info, "checks": res["checks"],
                "program_checks": res.get("program_checks"),
                "device": res["device"],
                "notes": notes[:8],
            }), flush=True)


if __name__ == "__main__":
    main()
