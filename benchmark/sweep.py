"""Tools for the PR that defines or re-tunes the benchmark — not part of
a run: sweep one traffic parameter over a cell (to find the knee), or
read the control beside the program on several seeds (to set the
configuration's ``limits``). One process, one cell, several short windows.

    python3 -m benchmark.sweep --workload lstm-ad-32t.live --param rate_ev_s \\
        --values 700,850,1000,1150,1300 --seeds 1,2,3,4,5 --seconds 20 --control 1
    python3 -m benchmark.sweep --workload lstm-ad-32t.live \\
        --seeds 1,2,3 --seconds 5 --control 1

The first is the knee sweep ``PERF.md`` section 4 records (a rate is
sustained while ``info.lag_at_close`` stays at a few batches and the
second half's median latency does not exceed the first's); with
``--control 1`` every window also prints the control's checks beside the
program's.

Prints one JSON line per window: the value or seed, ``correct``, the
end-to-end metrics, set-up phases, the first/second-half latency medians
(a growing backlog), every check, and the accounting notes.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import io
import json
import time
from contextlib import redirect_stderr

from benchmark import run


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--param")
    ap.add_argument("--values", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    devices = run.accelerator(cell["chips"])
    run.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    values = [float(v) for v in args.values.split(",") if v] or [None]
    for i, value in enumerate(values):
        for seed in seeds if value is None else [seeds[i % len(seeds)]]:
            this = copy.deepcopy(cell)
            if value is not None:
                this["traffic"][args.param] = value
            err = io.StringIO()
            with redirect_stderr(err):
                res = asyncio.run(run.run_cell(
                    this, seed, args.seconds, False, devices,
                    t_process=time.perf_counter(),
                    control=bool(args.control), drain_timeout_s=30.0))
            notes = [ln for ln in err.getvalue().splitlines()
                     if ln.startswith(("note", "benchmark:"))]
            print(json.dumps({
                args.param or "seed": value if value is not None else seed,
                "seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "metrics": {k: round(v["value"], 3)
                            for k, v in res["metrics"].items()},
                "info": res["info"], "checks": res["checks"],
                "program_checks": res.get("program_checks"),
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "notes": notes[:8],
            }), flush=True)


if __name__ == "__main__":
    main()
