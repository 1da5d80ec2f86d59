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
one rule by which a rate is or is not sustained. The knee is the highest
rate that every seed sustains. With ``--control 1`` every window also
prints the control's checks beside the program's. ``--config key=value``
runs every window with that key of the configuration file replaced (a
state size to try before it is written into the file); ``--trace 1``
prints the per-layer metrics of traced windows in place of the end-to-end
ones.

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

from benchmark import run

# A consumer group may close this many batches deeper than it opened. One
# tenant's topic takes at most one batch a flush (20-37 flushes/s), and a
# full collection stops the loop for up to 0.65 s, so a sustained window
# can close 13-20 batches deep on the topic it stopped on; a group that
# falls behind grows by more than a batch a second for the whole window.
LAG_SLACK_BATCHES = 32
# The second half's median latency may differ from the first half's by
# this, either way: a median that RISES by halves is a backlog growing, and
# one that FALLS is the window flipping between the device-queue mode and
# the host-bound one (near the knee the same tree reads ~200 ms in one and
# ~100-135 ms in the other, PERF.md section 4) -- no seat for a cell either.
HALF_MEDIAN_RATIO = 1.05


def sustained(correct: bool, failed: int, info: dict) -> tuple:
    """(verdict, reasons it is false) for one window, from its result
    line: ``correct``, ``failed`` and ``info`` as ``run.run_cell`` gives
    them. Sustained means: correct, nothing failed, no consumer group
    (the scoring consumer's and every later stage's alike) closing more
    than ``LAG_SLACK_BATCHES`` deeper than it opened, and a second-half
    median within ``HALF_MEDIAN_RATIO`` of the first half's, above it or
    below."""
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
    elif not first / HALF_MEDIAN_RATIO <= second <= HALF_MEDIAN_RATIO * first:
        reasons.append(
            f"p50 by halves {first:.1f} -> {second:.1f} ms "
            f"(not within {HALF_MEDIAN_RATIO} x either way)")
    return not reasons, reasons


def with_value(cell: dict, param: str, value: float) -> dict:
    """The cell with its traffic's ``param`` set to ``value`` (and, for a
    rate, the fleet that rate implies at the published interval)."""
    cell = copy.deepcopy(cell)
    cell["traffic"][param] = value
    config = cell["config"]
    interval = config.get("published", {}).get("report_interval_s")
    if param == "rate_ev_s" and interval:
        config["devices_per_tenant"] = math.ceil(
            value * interval / config["tenants"])
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
