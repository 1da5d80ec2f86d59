#!/usr/bin/env python
"""Run every analyzer in ``tools/`` as one suite: one table, one JSON
findings document, one exit code.

The six analyzers (docs/STATIC_ANALYSIS.md has the full catalog):

===============  ====================================================
check_async      five async-safety rules over the package call graph
check_hotpath    zero-copy allocation discipline on registered hot paths
check_queues     bounded-queue depth/shed observability registry
check_supervised deadline supervision on device awaits
check_fusion     fused-kernel lowering invariants (jaxpr traces)
check_metrics    Prometheus exposition conformance (live scrape)
===============  ====================================================

Modes:

- ``python tools/lint_all.py`` — the full suite. check_fusion traces
  jaxprs (imports jax) and check_metrics boots a small instance; both
  take seconds-to-minutes on the CPU rig.
- ``python tools/lint_all.py --fast`` — the pure-AST/regex analyzers
  only (async, hotpath, queues, supervised): ~1 s cold (the package
  parse + call-graph build), sub-second once the shared ``astlib``
  parse cache is warm; this is what tier-1 runs.
- ``--json PATH`` — machine-readable findings (``-`` = stdout).

Exit code: 1 iff any non-skipped analyzer produced findings (or
crashed — an analyzer that cannot run is a failure, not a skip).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable, Dict, List

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)

import astlib  # noqa: E402

REPO_ROOT = str(astlib.REPO_ROOT)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

FAST_TOOLS = ("check_async", "check_hotpath", "check_queues",
              "check_supervised")
SLOW_TOOLS = ("check_fusion", "check_metrics")


def _findings_async() -> List[dict]:
    import check_async

    return [f.to_json() for f in check_async.lint_async()]


def _findings_hotpath() -> List[dict]:
    import check_hotpath

    return [
        {"tool": "check_hotpath", "msg": f} for f in
        check_hotpath.lint_hotpaths()
    ]


def _findings_queues() -> List[dict]:
    import check_queues

    return [
        {"tool": "check_queues", "msg": f} for f in
        check_queues.lint_queues()
    ]


def _findings_supervised() -> List[dict]:
    import check_supervised

    return [
        {"tool": "check_supervised", "msg": f} for f in
        check_supervised.lint_supervised()
    ]


def _findings_fusion() -> List[dict]:
    import check_fusion

    out = (
        check_fusion.lint_fusion()
        + check_fusion.lint_train_fusion()
        + check_fusion.lint_dct()
    )
    return [{"tool": "check_fusion", "msg": f} for f in out]


def _findings_metrics() -> List[dict]:
    import asyncio

    import check_metrics

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    text = asyncio.run(check_metrics._scrape_live())
    return [
        {"tool": "check_metrics", "msg": f} for f in
        check_metrics.lint_exposition(text)
    ]


_RUNNERS: Dict[str, Callable[[], List[dict]]] = {
    "check_async": _findings_async,
    "check_hotpath": _findings_hotpath,
    "check_queues": _findings_queues,
    "check_supervised": _findings_supervised,
    "check_fusion": _findings_fusion,
    "check_metrics": _findings_metrics,
}


def run_all(fast: bool = False) -> List[Dict]:
    """Run the suite; returns one report row per analyzer:
    ``{"tool", "status": "ok"|"fail"|"error"|"skipped", "findings",
    "wall_s", "note"}``. ``fast`` limits to the pure-AST analyzers
    (the tier-1 configuration)."""
    reports: List[Dict] = []
    for tool in (*FAST_TOOLS, *SLOW_TOOLS):
        if fast and tool in SLOW_TOOLS:
            reports.append({
                "tool": tool, "status": "skipped", "findings": [],
                "wall_s": 0.0,
                "note": "slow analyzer (use the full suite)",
            })
            continue
        t0 = time.perf_counter()
        try:
            findings = _RUNNERS[tool]()
            status = "ok" if not findings else "fail"
            note = ""
        except Exception as exc:  # noqa: BLE001 - an analyzer that
            # cannot run must fail the suite visibly, not vanish
            findings = []
            status = "error"
            note = repr(exc)
        reports.append({
            "tool": tool, "status": status, "findings": findings,
            "wall_s": round(time.perf_counter() - t0, 3), "note": note,
        })
    return reports


def format_table(reports: List[Dict]) -> str:
    header = f"{'analyzer':18} {'status':8} {'findings':>8} {'wall_s':>8}  note"
    out = [header, "-" * len(header)]
    for r in reports:
        out.append(
            f"{r['tool']:18} {r['status']:8} {len(r['findings']):>8} "
            f"{r['wall_s']:>8.2f}  {r['note']}"
        )
    return "\n".join(out)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="run every tools/check_* analyzer as one suite"
    )
    ap.add_argument("--fast", action="store_true",
                    help="pure-AST analyzers only (tier-1 configuration)")
    ap.add_argument("--json", default="",
                    help="write findings JSON to PATH ('-' = stdout)")
    args = ap.parse_args(argv)

    reports = run_all(fast=args.fast)
    print(format_table(reports), file=sys.stderr)
    for r in reports:
        for f in r["findings"]:
            print(f"{r['tool']}: {f['msg']}", file=sys.stderr)
    doc = {
        "suite": "lint_all",
        "fast": bool(args.fast),
        "reports": reports,
        "total_wall_s": round(sum(r["wall_s"] for r in reports), 3),
        "failed": [
            r["tool"] for r in reports if r["status"] in ("fail", "error")
        ],
    }
    if args.json == "-":
        print(json.dumps(doc, indent=2))
    elif args.json:
        with open(args.json, "w") as fh:
            json.dump(doc, fh, indent=2)
    n_findings = sum(len(r["findings"]) for r in reports)
    print(
        f"lint_all: {len(reports)} analyzer(s), "
        f"{sum(1 for r in reports if r['status'] == 'skipped')} skipped, "
        f"{n_findings} finding(s), {doc['total_wall_s']:.2f}s"
    )
    return 1 if doc["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
